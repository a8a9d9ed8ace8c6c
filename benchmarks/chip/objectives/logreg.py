"""L2-regularised logistic regression over a LIBSVM-like binary dataset.

What a configuration of this kind needs from the benchmark:

- `generate`: the dataset, made on the device in one jitted call from the
  seed, at the configuration's published n, p and nonzeros per row, dense
  float32 as the system serves it. The statistics follow the repository's
  synthetic LIBSVM sets: |N(0, 1)| values with a sign skewed by +0.3, rows
  normalised to unit length, labels from a hidden separator with 8% of
  them flipped.
- `program`: the system's objective over that data.
- `reference` (in `logreg_reference`): the plain reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from objectives import logreg_reference

reference = logreg_reference.run_request


def seed_key(seed: int):
    """A PRNG key from a seed of any size (a run's seed may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _distinct_columns(key, n: int, p: int, nnz: int):
    """(n, nnz) distinct column indices per row, uniform over the subsets
    of size nnz (Floyd's sampling: step t draws j in [0, p - nnz + t] and
    takes p - nnz + t instead where j is already held)."""
    def step(held, inp):
        t, k = inp
        top = p - nnz + t
        j = jax.random.randint(k, (n,), 0, top + 1)
        pick = jnp.where(jnp.any(held == j[:, None], axis=1), top, j)
        return held.at[:, t].set(pick), None

    held = jnp.full((n, nnz), -1, jnp.int32)
    held, _ = jax.lax.scan(step, held, (jnp.arange(nnz),
                                        jax.random.split(key, nnz)))
    return held


@functools.partial(jax.jit, static_argnames=("n", "p", "nnz"))
def _generate(key, *, n: int, p: int, nnz: int):
    k_cols, k_val, k_sign, k_sep, k_flip = jax.random.split(key, 5)
    cols = _distinct_columns(k_cols, n, p, nnz)
    vals = (jnp.abs(jax.random.normal(k_val, (n, nnz)))
            * jnp.sign(jax.random.normal(k_sign, (n, nnz)) + 0.3))
    vals = vals / jnp.maximum(
        jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True)), 1e-8)
    X = jnp.zeros((n, p), jnp.float32).at[
        jnp.arange(n)[:, None], cols].set(vals)
    separator = jax.random.normal(k_sep, (p,)) / jnp.sqrt(p)
    y = jnp.sign(jnp.sum(vals * separator[cols], axis=1) + 1e-12)
    y = jnp.where(jax.random.uniform(k_flip, (n,)) < 0.08, -y, y)
    return X, jnp.where(y == 0, 1.0, y).astype(jnp.float32)


def generate(cfg: dict, seed: int):
    """(X, y) on the default device; ``cfg`` gives n, p, nnz_per_row."""
    return _generate(seed_key(seed), n=int(cfg["n"]), p=int(cfg["p"]),
                     nnz=int(cfg["nnz_per_row"]))


def program(cfg: dict, data):
    from repro.core import LogisticRegression
    X, y = data
    return LogisticRegression(X, y, l2_reg=float(cfg["l2"]))
