"""Hogwild! (Recht et al. 2011) — the paper's baseline, same delay engine.

Plain asynchronous SGD: v_m = ∇f_{i_m}(û_m) with NO control variate. Run
under the same bounded-delay read semantics so the comparison against
AsySVRG isolates exactly the paper's contribution (variance reduction under
asynchrony). Experiment settings follow the paper §5.1: each epoch runs n/p
iterations per thread (1 effective pass), constant step γ decayed by 0.9
per epoch ("These settings are the same as those in the experiments in
Hogwild!").

Like `repro.core.asysvrg`, the epoch body (`_hogwild_epoch_core`) is written
to be `vmap`-able over a batch of (seed, scheme, step, τ, delay-kind, decay)
configurations: scheme/delay dispatch is data (`by_scheme` /
`_delay_schedule_core`), every reduction is vmap-bitwise-stable, and the
per-epoch γ ← decay·γ schedule is threaded through the `lax.scan` carry of
`_hogwild_epochs_core` so the whole multi-epoch run — decay included — is
ONE compiled program. `repro.core.sweep` vmaps that program over a config
grid; `run_hogwild` here drives the identical program for a single config,
which is what makes the sweep rows bit-identical to this sequential driver
on XLA:CPU (tests/test_sweep_hogwild.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.asysvrg import (
    AsyRunResult,
    DELAY_IDS,
    INNER_STEP_SCOPE,
    LOSS_SCOPE,
    SAMPLE_GRAD_SCOPE,
    SCHEME_IDS,
    _delay_schedule_core,
    by_scheme,
    drop_writes,
    read_dispatch,
)
from repro.core.objective import Objective


def _resolve_hogwild_steps(n: int, num_threads: int, tau: int):
    """(p, total = (n // p)·p, clamped τ) — the ONE place this arithmetic
    lives; `run_hogwild`'s update bookkeeping and the sweep engine both
    derive from it, so the two can never drift."""
    p_threads = max(1, num_threads)
    total = max(1, n // p_threads) * p_threads          # n/p per thread
    tau = (p_threads - 1) if tau < 0 else tau
    tau = max(0, min(tau, total - 1))
    return p_threads, total, tau


def _hogwild_epoch_core(obj: Objective, data, w, key, gamma, tau, scheme_id,
                        delay_id, *, total: int, buf_len: int,
                        drop_prob: float):
    """One Hogwild! epoch (total async updates), vmap-able over configs.

    ``obj``/``data`` follow the same protocol split as
    `asysvrg._epoch_core`: pure methods + static config from ``obj``, every
    numeric input in ``data``, params as the objective's FLAT vector.

    Dynamic (batchable): w, key, gamma, tau, scheme_id, delay_id.
    Static (shared by the batch): total, buf_len ≥ max τ + 1, drop_prob.
    """
    n = obj.num_samples(data)
    dim = w.shape[0]
    k_idx, k_delay, k_scan = jax.random.split(key, 3)
    idx = jax.random.randint(k_idx, (total,), 0, n)
    delays = _delay_schedule_core(delay_id, total, tau, k_delay)
    buffer = jnp.tile(w[None, :], (buf_len, 1))         # slot m%(τ+1) = u_m
    keys = jax.random.split(k_scan, total)
    ms = jnp.arange(total)

    def inner_loop(scheme: int):
        def body(carry, inp):
            with jax.named_scope(INNER_STEP_SCOPE):
                u, buffer = carry
                m, i, d, k = inp
                k_read, k_drop = jax.random.split(k)
                a = jnp.maximum(m - d, 0)
                u_read = read_dispatch(scheme, buffer, tau, a, m, k_read, dim)
                with jax.named_scope(SAMPLE_GRAD_SCOPE):
                    v = obj.flat_sample_grad(data, i, u_read)
                # unlock write-write race: drop a random coordinate fraction
                v, = drop_writes(scheme, k_drop, drop_prob, (v,))
                u_next = u - gamma * v
                buffer = buffer.at[jnp.mod(m + 1, tau + 1)].set(u_next)
                return (u_next, buffer), None

        (u_last, _), _ = jax.lax.scan(body, (w, buffer),
                                      (ms, idx, delays, keys))
        return u_last

    u_last = by_scheme(scheme_id, inner_loop)
    return u_last


def _hogwild_epochs_core(obj: Objective, data, w0, key, gamma0, decay, tau,
                         scheme_id, delay_id, *, epochs: int, total: int,
                         buf_len: int, drop_prob: float, row_epochs=None):
    """`epochs` Hogwild! epochs as one `lax.scan`, γ ← decay·γ in the carry.

    Returns (w_final, losses[epochs+1]) with the fixed-order loss recorded
    after every epoch (index 0 = loss at w0) — the decay schedule and the
    history both live INSIDE the compiled program, so a vmap over configs
    batches them too.

    ``row_epochs`` (a dynamic, batchable scalar; default = the static
    ``epochs`` bound) is this config's own epoch budget: once the epoch
    index reaches it the row FREEZES — carry passthrough (w, γ) and masked
    loss writes (the last live loss is re-emitted) — so a sweep row with a
    shorter budget is bit-identical to an independent shorter run while
    scanning to the group's shared static bound.
    """
    with jax.named_scope(LOSS_SCOPE):
        loss0 = obj.flat_loss(data, w0)
    bound = jnp.int32(epochs) if row_epochs is None else row_epochs

    def step(carry, e):
        w, key, gamma, loss_prev = carry
        key, sub = jax.random.split(key)
        active = e < bound
        w_new = _hogwild_epoch_core(
            obj, data, w, sub, gamma, tau, scheme_id, delay_id,
            total=total, buf_len=buf_len, drop_prob=drop_prob)
        w_next = jnp.where(active, w_new, w)
        gamma_next = jnp.where(active, gamma * decay, gamma)
        with jax.named_scope(LOSS_SCOPE):
            loss_w = obj.flat_loss(data, w_next)
        loss_next = jnp.where(active, loss_w, loss_prev)
        return (w_next, key, gamma_next, loss_next), loss_next

    (w_fin, _, _, _), losses = jax.lax.scan(
        step, (w0, key, gamma0, loss0), jnp.arange(epochs))
    return w_fin, jnp.concatenate([loss0[None], losses])


def hogwild_epoch(obj: Objective, w, key, step_size: float,
                  num_threads: int, tau: int = -1, scheme: str = "unlock",
                  drop_prob: float = 0.02, delay_kind: str = "fixed"):
    """One Hogwild! epoch (public single-config wrapper over the core)."""
    if scheme not in SCHEME_IDS:
        raise ValueError(f"unknown scheme {scheme!r}")
    if delay_kind not in DELAY_IDS:
        raise ValueError(f"unknown delay schedule {delay_kind!r}")
    _, total, tau = _resolve_hogwild_steps(obj.n, num_threads, tau)
    delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[delay_kind]
    return _hogwild_epoch_core(
        obj, obj.data_args(), obj.as_flat(w), key,
        jnp.float32(step_size), jnp.int32(tau),
        jnp.int32(SCHEME_IDS[scheme]), jnp.int32(delay_id),
        total=total, buf_len=tau + 1, drop_prob=drop_prob)


def run_hogwild(obj: Objective, epochs: int, step_size: float,
                num_threads: int = 8, decay: float = 0.9,
                scheme: str = "unlock", tau: int = -1, seed: int = 0,
                w0=None, delay_kind: str = "fixed",
                drop_prob: float = 0.02) -> AsyRunResult:
    """Multi-epoch driver (one configuration, ONE jit for the whole run).

    The γ-decay schedule and the per-epoch loss history are computed inside
    the compiled epochs-scan (`_hogwild_epochs_core`), so a `run_sweep` over
    Hogwild! configs reproduces this driver bit-identically from a single
    batched compilation. `total_updates` derives from the same
    `total = (n // p)·p` expression the epoch core scans over.
    """
    if scheme not in SCHEME_IDS:
        raise ValueError(f"unknown scheme {scheme!r}")
    if delay_kind not in DELAY_IDS:
        raise ValueError(f"unknown delay schedule {delay_kind!r}")
    w = obj.init_flat() if w0 is None else obj.as_flat(w0)
    key = jax.random.PRNGKey(seed)
    _, total, tau = _resolve_hogwild_steps(obj.n, num_threads, tau)
    delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[delay_kind]
    data = obj.data_args()

    runner = jax.jit(lambda w0_, k, g0, d: _hogwild_epochs_core(  # repro-lint: ignore[RL002] sequential reference driver: single-shot jit per call, capture is intentional; the cached-runner path (service/cache) passes data as arguments
        obj, data, w0_, k, g0, d,
        jnp.int32(tau), jnp.int32(SCHEME_IDS[scheme]), jnp.int32(delay_id),
        epochs=epochs, total=total, buf_len=tau + 1, drop_prob=drop_prob))
    w_fin, losses = runner(w, key, jnp.float32(step_size),
                           jnp.float32(decay))

    return AsyRunResult(
        w=w_fin,
        history=tuple(float(v) for v in losses),
        effective_passes=tuple(float(e) for e in range(epochs + 1)),
        total_updates=epochs * total)               # same total as the scan
