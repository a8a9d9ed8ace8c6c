"""Plain reference for the served requests of the ELL logistic-regression
cells: `logreg_reference` over sparse rows, which are never densified.

The rows are an `EllRows`: for each sample its column ids and values,
padded to a fixed number of nonzeros per row, with p carried as static
data so that the rows alone give the width of w. The request's random
stream, the ring of the last tau + 1 iterates and the three readers are
those of `logreg_reference`; the iterates differ from the system's only
by rounding, and the same code run in bfloat16 is the control. It imports
nothing of the system under test.

Departures from `logreg_reference`, each forced by the width
(p = 1,355,191 for news20):

- margins gather each row's nonzeros from w and sum them (`_HI` on the
  row products); the loss and the snapshot gradient run over blocks of
  `BLOCK` rows in a `lax.scan`, the gradient as a scatter-add of each
  block's scaled rows into p;
- a per-sample gradient is l2 * w with the scaled row added at its
  columns;
- the unlock reader picks each coordinate's slot by a one-hot sum over
  the ring's slots (one term is not zero, so the sum is exact) rather
  than by a per-coordinate gather, which a TPU runs far slower at this
  width.

    f(w) = mean_i log(1 + exp(-y_i x_i.w)) + (l2 / 2) ||w||^2
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from objectives.logreg_reference import resolve

_HI = jax.lax.Precision.HIGHEST
BLOCK = 512


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["cols", "vals"], meta_fields=["p"])
@dataclasses.dataclass(frozen=True)
class EllRows:
    """n sparse rows of width p: ``cols`` (n, nnz) int32 column ids and
    ``vals`` (n, nnz) values; a padding slot holds value 0."""
    cols: jax.Array
    vals: jax.Array
    p: int


def _blocks(rows: EllRows, y):
    """(cols, vals, y) in blocks of `BLOCK` rows, blocks leading; the rows
    that pad the last block have y = 0 and no nonzero, so they add
    nothing."""
    pad = -y.shape[0] % BLOCK

    def split(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(-1, BLOCK, *a.shape[1:])
    return split(rows.cols), split(rows.vals), split(y)


def _margins(c, v, y, w):
    return y * jnp.einsum("rk,rk->r", v, w[c], precision=_HI)


def loss(rows, y, l2, w):
    def block(acc, blk):
        c, v, yb = blk
        t = jnp.logaddexp(0.0, -_margins(c, v, yb, w))
        return acc + jnp.sum(jnp.where(yb != 0, t, 0.0)), None

    total, _ = jax.lax.scan(block, jnp.zeros((), w.dtype), _blocks(rows, y))
    return total / y.shape[0] + 0.5 * l2 * jnp.sum(w * w)


def full_grad(rows, y, l2, w):
    def block(g, blk):
        c, v, yb = blk
        r = -yb * jax.nn.sigmoid(-_margins(c, v, yb, w))
        return g.at[c].add(r[:, None] * v), None

    g, _ = jax.lax.scan(block, jnp.zeros_like(w), _blocks(rows, y))
    return g / y.shape[0] + l2 * w


def sample_grad(c, v, yi, l2, w):
    r = -yi * jax.nn.sigmoid(-yi * jnp.sum(v * w[c]))
    return (l2 * w).at[c].add(r * v)


def _read(scheme, ring, a, m, key):
    """The iterate a reader sees at update m whose oldest age is a."""
    slots, dim = ring.shape
    if scheme == "consistent":
        return ring[a % slots]
    if scheme == "inconsistent":
        newer = ring[jnp.minimum(a + 1, m) % slots]
        return jnp.where(jax.random.bernoulli(key, 0.5, (dim,)),
                         ring[a % slots], newer)
    if scheme == "unlock":
        span = (m - a + 1).astype(jnp.float32)
        ages = a + jnp.floor(jax.random.uniform(key, (dim,))
                             * span).astype(jnp.int32)
        hit = (ages % slots)[None, :] == jnp.arange(slots)[:, None]
        return jnp.sum(jnp.where(hit, ring, 0), axis=0)
    raise ValueError(f"unknown scheme {scheme!r}")


@functools.partial(jax.jit, static_argnames=(
    "algo", "scheme", "total", "tau", "drop_prob", "option"))
def _epoch(rows, y, l2, w, key, step, *, algo, scheme, total, tau,
           drop_prob, option):
    n, dim = y.shape[0], rows.p
    k_idx, _, k_scan = jax.random.split(key, 3)
    idx = jax.random.randint(k_idx, (total,), 0, n)
    steps = jnp.arange(total)
    delays = jnp.minimum(steps, tau)         # p equal-speed threads
    mu = full_grad(rows, y, l2, w) if algo != "hogwild" else None
    ring = jnp.tile(w[None, :], (tau + 1, 1))

    def body(carry, inp):
        u, ring, acc = carry
        m, i, d, k = inp
        k_read, k_drop = jax.random.split(k)
        a = jnp.maximum(m - d, 0)
        seen = _read(scheme, ring, a, m, k_read)
        c, v = rows.cols[i], rows.vals[i]
        g = sample_grad(c, v, y[i], l2, seen)
        if algo != "hogwild":
            g = g - sample_grad(c, v, y[i], l2, w) + mu
        if scheme == "unlock" and drop_prob > 0:
            # a racing write loses a random share of the coordinates
            g = g * jax.random.bernoulli(k_drop, 1.0 - drop_prob,
                                         (dim,)).astype(g.dtype)
        u_next = (u - step * g).astype(u.dtype)
        ring = ring.at[(m + 1) % (tau + 1)].set(u_next)
        return (u_next, ring, acc + u_next), None

    (u, _, acc), _ = jax.lax.scan(body, (w, ring, jnp.zeros_like(w)),
                                  (steps, idx, delays,
                                   jax.random.split(k_scan, total)))
    if algo == "hogwild" or option == 1:
        return u
    return (acc / total).astype(w.dtype)


_loss = jax.jit(loss)


def run_request(rows: EllRows, y, l2: float, row: dict, epochs: int,
                drop_prob: float, dtype=jnp.float32):
    """(final w, [loss after each epoch, from epoch 0]) of one request row,
    computed in ``dtype``: float32, as the configuration states, for the
    reference; bfloat16 for the control."""
    total, tau, scheme = resolve(row, y.shape[0])
    rows = EllRows(rows.cols, rows.vals.astype(dtype), rows.p)
    y = y.astype(dtype)
    l2 = jnp.asarray(l2, dtype)
    w = jnp.zeros((rows.p,), dtype)
    key = jax.random.PRNGKey(int(row["seed"]))
    step = jnp.float32(row["step_size"])
    decay = jnp.float32(row.get("decay", 0.9))
    losses = [float(_loss(rows, y, l2, w))]
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        w = _epoch(rows, y, l2, w, sub, step.astype(dtype), algo=row["algo"],
                   scheme=scheme, total=total, tau=tau,
                   drop_prob=float(drop_prob),
                   option=int(row.get("option", 2)))
        losses.append(float(_loss(rows, y, l2, w)))
        step = step * decay if row["algo"] == "hogwild" else step
    return jax.device_get(w).astype("float32"), losses
