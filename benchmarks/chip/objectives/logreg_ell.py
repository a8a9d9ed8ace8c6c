"""L2-regularised logistic regression over sparse rows in ELL layout: the
kind of a configuration too wide to hold dense (news20: p = 1,355,191).

What a configuration of this kind needs from the benchmark:

- `generate`: the dataset, made on the device in one jitted call from the
  seed, at the configuration's n, p and nonzeros per row, as ELL rows
  (`logreg_ell_reference.EllRows`: column ids and float32 values, p
  carried with them) and labels. No (n, p) array is ever built. The
  columns, values and labels are drawn as `logreg` draws them, from the
  same keys, so the rows are the nonzeros of `logreg`'s X for the same
  configuration and seed.
- `program`: the system's ELL objective over that data.
- `reference` (in `logreg_ell_reference`): the plain reference.
- `SCOPES`: the names the system's objective gives its device work, read
  by `chipbench.objective_scopes`. They are written out here, not
  imported, so that a program that names none of them can be read too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from objectives import logreg_ell_reference
from objectives.logreg import _distinct_columns, seed_key
from objectives.logreg_ell_reference import EllRows

reference = logreg_ell_reference.run_request

ELL_GRAD = "ell_grad"            # the per-sample gradient's gather, scatter
ELL_SNAPSHOT = "ell_snapshot"    # the scatter-add snapshot gradient
SCOPES = (ELL_GRAD, ELL_SNAPSHOT)


@functools.partial(jax.jit, static_argnames=("n", "p", "nnz"))
def _generate(key, *, n: int, p: int, nnz: int):
    k_cols, k_val, k_sign, k_sep, k_flip = jax.random.split(key, 5)
    cols = _distinct_columns(k_cols, n, p, nnz)
    vals = (jnp.abs(jax.random.normal(k_val, (n, nnz)))
            * jnp.sign(jax.random.normal(k_sign, (n, nnz)) + 0.3))
    vals = vals / jnp.maximum(
        jnp.sqrt(jnp.sum(vals * vals, axis=1, keepdims=True)), 1e-8)
    separator = jax.random.normal(k_sep, (p,)) / jnp.sqrt(p)
    y = jnp.sign(jnp.sum(vals * separator[cols], axis=1) + 1e-12)
    y = jnp.where(jax.random.uniform(k_flip, (n,)) < 0.08, -y, y)
    return cols, vals, jnp.where(y == 0, 1.0, y).astype(jnp.float32)


def generate(cfg: dict, seed: int):
    """(rows, y) on the default device; ``cfg`` gives n, p, nnz_per_row."""
    p = int(cfg["p"])
    cols, vals, y = _generate(seed_key(seed), n=int(cfg["n"]), p=p,
                              nnz=int(cfg["nnz_per_row"]))
    return EllRows(cols, vals, p), y


def program(cfg: dict, data):
    from repro.core import EllLogisticRegression
    rows, y = data
    return EllLogisticRegression(rows.cols, rows.vals, y, rows.p,
                                 l2_reg=float(cfg["l2"]))
