"""Plain reference for the served requests of the logistic-regression cells.

One request row is one configuration of AsySVRG, Hogwild! or serial SVRG
(arXiv:1508.05711, Algorithm 1 and §5) run for a number of epochs from
w = 0 over the generated data. This module computes the same iterates in
straightforward JAX: one Python branch per read scheme, a ring of the last
tau + 1 iterates, a `lax.scan` over the inner steps and full-precision
matrix-vector products for the snapshot gradient and the loss. It imports
nothing of the system under test, and takes from it nothing but the data
that the benchmark generated.

The random stream follows the request's seed exactly as the paper's delay
model draws it in this repository's convention: per epoch one key split
into (sample indices, delays, per-step keys); per step one key split into
(read, write-drop). So the reference's iterates differ from the system's
only by rounding, and the same code run in bfloat16 is the control.

    f(w) = mean_i log(1 + exp(-y_i x_i.w)) + (l2 / 2) ||w||^2
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def resolve(row: dict, n: int):
    """(inner steps per epoch, tau, scheme) of one request row, by the
    paper's §5.1 settings: AsySVRG runs M = 2n / p updates per thread
    (M~ = pM in all) with tau = p - 1 unless given; serial SVRG is the
    tau = 0 case with consistent reads; Hogwild! runs n / p updates per
    thread in each epoch."""
    algo = row["algo"]
    threads = max(1, int(row.get("num_threads", 8)))
    tau = int(row.get("tau", 0))
    if algo == "svrg":
        total = int(row.get("inner_steps", 0)) or 2 * n
        return total, 0, "consistent"
    if algo == "hogwild":
        total = max(1, n // threads) * threads
        tau = threads - 1 if tau < 0 else tau
    elif algo == "asysvrg":
        per_thread = int(row.get("inner_steps", 0)) or (2 * n) // threads
        total = threads * per_thread
        tau = tau or threads - 1
    else:
        raise ValueError(f"unknown algo {algo!r}")
    return total, max(0, min(tau, total - 1)), row["scheme"]


def loss(X, y, l2, w):
    margins = y * jnp.dot(X, w, precision=_HI)
    return (jnp.mean(jnp.logaddexp(0.0, -margins))
            + 0.5 * l2 * jnp.sum(w * w))


def full_grad(X, y, l2, w):
    n = X.shape[0]
    r = -y * jax.nn.sigmoid(-y * jnp.dot(X, w, precision=_HI))
    return jnp.dot(r, X, precision=_HI) / n + l2 * w


def sample_grad(x, yi, l2, w):
    return -yi * jax.nn.sigmoid(-yi * jnp.sum(x * w)) * x + l2 * w


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
        return ring[ages % slots, jnp.arange(dim)]
    raise ValueError(f"unknown scheme {scheme!r}")


@functools.partial(jax.jit, static_argnames=(
    "algo", "scheme", "total", "tau", "drop_prob", "option"))
def _epoch(X, y, l2, w, key, step, *, algo, scheme, total, tau, drop_prob,
           option):
    n, dim = X.shape
    k_idx, _, k_scan = jax.random.split(key, 3)
    idx = jax.random.randint(k_idx, (total,), 0, n)
    steps = jnp.arange(total)
    delays = jnp.minimum(steps, tau)         # p equal-speed threads
    mu = full_grad(X, y, l2, w) if algo != "hogwild" else None
    ring = jnp.tile(w[None, :], (tau + 1, 1))

    def body(carry, inp):
        u, ring, acc = carry
        m, i, d, k = inp
        k_read, k_drop = jax.random.split(k)
        a = jnp.maximum(m - d, 0)
        seen = _read(scheme, ring, a, m, k_read)
        v = sample_grad(X[i], y[i], l2, seen)
        if algo != "hogwild":
            v = v - sample_grad(X[i], y[i], l2, w) + mu
        if scheme == "unlock" and drop_prob > 0:
            # a racing write loses a random share of the coordinates
            v = v * jax.random.bernoulli(k_drop, 1.0 - drop_prob,
                                         (dim,)).astype(v.dtype)
        u_next = (u - step * v).astype(u.dtype)
        ring = ring.at[(m + 1) % (tau + 1)].set(u_next)
        return (u_next, ring, acc + u_next), None

    (u, _, acc), _ = jax.lax.scan(body, (w, ring, jnp.zeros_like(w)),
                                  (steps, idx, delays,
                                   jax.random.split(k_scan, total)))
    if algo == "hogwild" or option == 1:
        return u
    return (acc / total).astype(w.dtype)


_loss = jax.jit(loss)


def run_request(X, y, l2: float, row: dict, epochs: int, drop_prob: float,
                dtype=jnp.float32):
    """(final w, [loss after each epoch, from epoch 0]) of one request row,
    computed in ``dtype``: float32, as the configuration states, for the
    reference; bfloat16 for the control."""
    n, dim = X.shape
    total, tau, scheme = resolve(row, n)
    X = X.astype(dtype)
    y = y.astype(dtype)
    l2 = jnp.asarray(l2, dtype)
    w = jnp.zeros((dim,), dtype)
    key = jax.random.PRNGKey(int(row["seed"]))
    step = jnp.float32(row["step_size"])
    decay = jnp.float32(row.get("decay", 0.9))
    losses = [float(_loss(X, y, l2, w))]
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        w = _epoch(X, y, l2, w, sub, step.astype(dtype), algo=row["algo"],
                   scheme=scheme, total=total, tau=tau,
                   drop_prob=float(drop_prob),
                   option=int(row.get("option", 2)))
        losses.append(float(_loss(X, y, l2, w)))
        step = step * decay if row["algo"] == "hogwild" else step
    return jax.device_get(w).astype("float32"), losses
