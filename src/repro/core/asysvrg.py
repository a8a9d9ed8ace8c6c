"""AsySVRG — the paper's contribution, as an exact delay-simulation engine.

The paper's convergence analysis (§4) models the asynchronous execution as a
SERIAL sequence of updates  u_{m+1} = u_m − η v_m  where the gradient inside
v_m was evaluated at a stale view of u whose age lag is bounded by τ. We
implement precisely that semantics as a `lax.scan`, which makes the algorithm
bit-reproducible on any hardware while preserving every property the theory
depends on:

  * consistent reading (§4.1):  v_m = p_{k(m), i_m}; the read is one whole
    buffered iterate u_{k(m)}, with m − k(m) ≤ τ.
  * inconsistent reading (§4.2, Eq. 10):  û_m = P_{g1} u_{a(m)} + P_{g2}
    u_{a(m)+1} — a per-coordinate mixture of two ADJACENT ages.
  * unlock (§5.2):  per-coordinate ages mixed over the whole window
    [a(m), m] AND a write-race model that drops a random fraction of an
    update's coordinates (the paper gives no theory for unlock; this models
    exactly the races removing the locks admits).

The ring buffer holds the last τ+1 iterates; delays come from a pluggable
schedule ("fixed" models p equal-speed threads in round-robin — Assumption 3 —
where a gradient applied at m was read τ = p−1 updates earlier; "uniform"
models speed jitter).

The epoch body (`_epoch_core`) is written to be `vmap`-able over a batch of
(seed, scheme, step-size, τ, delay-kind) configurations — that is what
`repro.core.sweep` compiles into ONE jitted grid run (and, via the `algo`
axis, the same engine also serves serial-SVRG rows as the τ=0 degenerate
case; `repro.core.hogwild` reuses the dispatch-as-data pieces for the
baseline). Two design rules make the batched run BIT-IDENTICAL to the
sequential driver here:

  1. scheme / delay-kind dispatch is data (``lax.switch`` / ``where``),
     not Python control flow, so a config batch shares one trace. The
     scheme is taken once per epoch (`by_scheme`): one branch per scheme
     holds the whole inner loop with that scheme's reader and, for unlock
     alone, the drop mask (the sweep passes a group's one scheme
     unbatched, so the switch stays a branch);
  2. every reduction is either elementwise, a row-reduce over a trailing
     axis, or a fixed-order `lax.scan` accumulation (see
     objective.loss_fixed_order) — the shapes XLA:CPU reduces identically
     with and without a leading batch axis. Plain `X @ w` / `jnp.mean`
     change summation order under vmap and break bitwise equality.

The inner-loop update u − η(g − g0 + gf) routes through the fused
`kernels/svrg_update` op (4 reads + 1 write at peak HBM bandwidth on TPU;
bit-identical jnp reference on other backends).

On p-thread hardware the schemes differ in THROUGHPUT (lock cost), not in
per-update semantics; the benchmark layer (benchmarks/table2_schemes.py)
carries the measured-cost throughput model, while this engine carries the
convergence behaviour. Together they reproduce Tables 2–3 and Figure 1.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import SVRGConfig
from repro.core.objective import LogisticRegression, Objective
from repro.kernels.svrg_update import ops as svrg_update_ops

SCHEME_IDS = {"consistent": 0, "inconsistent": 1, "unlock": 2}
DELAY_IDS = {"zero": 0, "fixed": 1, "uniform": 2}
_UNLOCK = SCHEME_IDS["unlock"]

# Names of the epoch cores' device work. `jax.named_scope` writes them into
# each op's metadata (the `tf_op` path of a device trace) and nowhere else:
# no op, no host call, no result bit changes. A reader matches whole
# components of that path.
SNAPSHOT_SCOPE = "snapshot"         # the full-gradient pass at w_t
INNER_STEP_SCOPE = "inner_step"     # one inner update (the scan's body)
READ_SCOPE = "read"                 # read_dispatch, all scheme branches
SAMPLE_GRAD_SCOPE = "sample_grad"   # one per-sample gradient
DROP_MASK_SCOPE = "drop_mask"       # the unlock write-drop mask
LOSS_SCOPE = "loss"                 # one fixed-order loss scan
# each reader branch under its scheme's name, in SCHEME_IDS order
READER_SCOPES = tuple(f"read_{s}" for s in SCHEME_IDS)
SCOPES = (SNAPSHOT_SCOPE, INNER_STEP_SCOPE, READ_SCOPE, *READER_SCOPES,
          SAMPLE_GRAD_SCOPE, DROP_MASK_SCOPE, svrg_update_ops.SCOPE,
          LOSS_SCOPE)


class AsyRunResult(NamedTuple):
    w: jnp.ndarray
    history: tuple          # objective value after each epoch (incl. epoch 0)
    effective_passes: tuple # cumulative effective passes at each history point
    total_updates: int


def _delay_schedule_core(delay_id, num_updates: int, tau, key) -> jnp.ndarray:
    """Numeric-dispatch delay schedule: 0 ≤ d_m ≤ min(m, τ).

    ``delay_id`` and ``tau`` may be traced scalars (the sweep batches over
    them); ``num_updates`` is static. All three kinds are computed from the
    same key and selected elementwise, so the choice is data, not control
    flow — and τ=0 collapses every kind to the zero schedule.
    """
    m = jnp.arange(num_updates)
    cap = jnp.minimum(m, tau).astype(jnp.int32)
    u = jax.random.uniform(key, (num_updates,))
    uniform = jnp.floor(u * (cap + 1)).astype(jnp.int32)
    zero = jnp.zeros((num_updates,), jnp.int32)
    return jnp.where(delay_id == DELAY_IDS["zero"], zero,
                     jnp.where(delay_id == DELAY_IDS["fixed"], cap, uniform))


def make_delay_schedule(kind: str, num_updates: int, tau: int, key,
                        p: int = 1) -> jnp.ndarray:
    """Delays d_m with 0 ≤ d_m ≤ min(m, τ).

    "fixed":    d_m = min(m, τ)  — p equal-speed round-robin threads
                (thread that applies update m read the iterate τ updates ago).
    "uniform":  d_m ~ U{0..min(m, τ)} — jittered thread speeds.
    "zero":     d_m = 0 — degenerates to sequential SVRG.
    """
    if kind not in DELAY_IDS:
        raise ValueError(f"unknown delay schedule {kind!r}")
    delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[kind]
    return _delay_schedule_core(delay_id, num_updates, tau, key)


def _read_consistent(buffer, slot_of, a, m, key, dim):
    """Locked read: one whole iterate of age a."""
    del m, key, dim
    return buffer[slot_of(a)]


def _read_inconsistent(buffer, slot_of, a, m, key, dim):
    """Eq. 10: coordinates mix ages a and a+1 (a+1 capped at m)."""
    ua = buffer[slot_of(a)]
    ub = buffer[slot_of(jnp.minimum(a + 1, m))]
    mask = jax.random.bernoulli(key, 0.5, (dim,))
    return jnp.where(mask, ua, ub)


def _read_unlock(buffer, slot_of, a, m, key, dim):
    """Lock-free read: every coordinate gets an independent age in [a, m]."""
    span = (m - a + 1).astype(jnp.float32)
    ages = a + jnp.floor(jax.random.uniform(key, (dim,)) * span).astype(jnp.int32)
    slots = slot_of(ages)
    # Every slot is below τ + 1 ≤ the buffer's static length, so a select
    # over its rows returns exactly the bits of the per-coordinate gather
    # `buffer[slots, arange(dim)]`, which a TPU runs far slower.
    return jax.lax.select_n(slots, *buffer)


_READERS = {
    "consistent": _read_consistent,
    "inconsistent": _read_inconsistent,
    "unlock": _read_unlock,
}
# readers by scheme id, in SCHEME_IDS order
_READER_LIST = (_read_consistent, _read_inconsistent, _read_unlock)


def by_scheme(scheme_id, run):
    """``run(scheme)`` for the scheme ``scheme_id`` names: a `lax.switch`
    over one trace of ``run`` per scheme, each with its scheme a Python
    int, so a branch holds only its own scheme's work.

    Where ``scheme_id`` is one scalar for the program (`run_asysvrg`,
    `run_hogwild`, and a sweep group, whose key holds its scheme) the
    device runs one branch; a vmap that batches ``scheme_id`` turns the
    switch into a select that runs every branch, with the same bits.
    """
    return jax.lax.switch(
        scheme_id, [lambda s=s: run(s) for s in range(len(_READER_LIST))])


def read_dispatch(scheme: int, buffer, tau, a, m, key, dim: int):
    """Read u under reading scheme ``scheme``, an id fixed at trace time
    (a `by_scheme` branch's).

    ``tau`` may be traced; ``dim`` is static. The ring-buffer slot
    arithmetic uses the DYNAMIC τ, so a buffer padded to any length ≥ τ+1
    reads identically. A buffer of exactly one slot (τ = 0 and one
    thread: `run_asysvrg` at τ = 0, or a sweep row with
    ``num_threads=1``; `_row_buf_len` pads other τ = 0 rows to their
    thread count) holds the only iterate any scheme can read, so it is
    returned with no reader. That keeps such a sweep row bit-identical to
    `run_asysvrg` on XLA:CPU, whose fused code otherwise differs between
    the two programs by an ulp.
    """
    with jax.named_scope(READ_SCOPE):
        if buffer.shape[0] == 1:
            return buffer[0]
        buf_len = tau + 1

        def slot_of(age):
            return jnp.mod(age, buf_len)

        with jax.named_scope(READER_SCOPES[scheme]):
            return _READER_LIST[scheme](buffer, slot_of, a, m, key, dim)


def drop_writes(scheme: int, key, drop_prob: float, grads):
    """The unlock write-write race: for ``scheme`` unlock (an id fixed at
    trace time), ``grads`` times one Bernoulli(1 − ``drop_prob``) 0/1 mask
    of their width; for the other schemes ``grads`` unmasked.

    The mask is drawn once and kept: left to itself, XLA on a TPU fuses
    the p-wide draw into each masked product and draws it once per
    product.
    """
    grads = tuple(grads)
    if scheme != _UNLOCK or drop_prob <= 0:
        return grads
    with jax.named_scope(DROP_MASK_SCOPE):
        keep = jax.lax.optimization_barrier(jax.random.bernoulli(
            key, 1.0 - drop_prob, grads[0].shape).astype(grads[0].dtype))
        return tuple(x * keep for x in grads)


def _epoch_core(obj: Objective, data, w, key, eta, tau, scheme_id, delay_id,
                *, total: int, buf_len: int, option: int, drop_prob: float):
    """One outer iteration of Algorithm 1, vmap-able over configurations.

    ``obj`` is any `repro.core.objective.Objective`; only its PURE methods
    (and static config) are used — ``data`` (the `obj.data_args()` tuple)
    carries every numeric input, so this function can close over ``obj``
    inside a cached runner and still serve other same-static-key instances'
    data. ``w`` is the objective's FLAT param vector (pytree objectives
    cross through `repro.utils.tree`'s bit-exact ravel): the delay ring
    buffer, the reader coordinate masks and the fused-kernel update below
    all work on that one vector, unchanged from the logreg-only engine.

    Dynamic (batchable): w, key, eta, tau, scheme_id, delay_id.
    Static (shared by the batch): total = M̃ = pM, buf_len ≥ max τ + 1,
    option, drop_prob.
    """
    n = obj.num_samples(data)
    dim = w.shape[0]
    k_idx, k_delay, k_scan = jax.random.split(key, 3)
    with jax.named_scope(SNAPSHOT_SCOPE):
        mu = obj.flat_full_grad(data, w)                # parallel snapshot pass
    u0 = w
    idx = jax.random.randint(k_idx, (total,), 0, n)
    delays = _delay_schedule_core(delay_id, total, tau, k_delay)

    buffer = jnp.tile(u0[None, :], (buf_len, 1))        # slot m%(τ+1) = u_m
    keys = jax.random.split(k_scan, total)
    ms = jnp.arange(total)

    def inner_loop(scheme: int):
        def body(carry, inp):
            with jax.named_scope(INNER_STEP_SCOPE):
                u, buffer, acc = carry
                m, i, d, k = inp
                k_read, k_drop = jax.random.split(k)
                a = jnp.maximum(m - d, 0)
                u_read = read_dispatch(scheme, buffer, tau, a, m, k_read, dim)
                # A fusion boundary (m >= 0 always holds): it keeps the read
                # out of the gradient's fusion, as a per-step switch on the
                # scheme did, so XLA:CPU gives a row the same gradient bits
                # at every group width (an optimization_barrier does not).
                u_read = jax.lax.cond(m >= 0, lambda x: x, lambda x: x * 0,
                                      u_read)
                with jax.named_scope(SAMPLE_GRAD_SCOPE):
                    g = obj.flat_sample_grad(data, i, u_read)
                    g0 = obj.flat_sample_grad(data, i, u0)
                # unlock write-write race: drop a random coordinate
                # fraction. Masking the three inputs with the same 0/1 mask
                # equals masking v = g − g0 + gf (exact for 0/1 factors),
                # which keeps the update expressible as the fused kernel's
                # 4-read form.
                g, g0, gf = drop_writes(scheme, k_drop, drop_prob, (g, g0, mu))
                u_next = svrg_update_ops.apply_leaf(u, g, g0, gf, eta)
                buffer = buffer.at[jnp.mod(m + 1, tau + 1)].set(u_next)
                return (u_next, buffer, acc + u_next), None

        (u_last, _, acc), _ = jax.lax.scan(
            body, (u0, buffer, jnp.zeros_like(u0)), (ms, idx, delays, keys))
        return u_last, acc

    u_last, acc = by_scheme(scheme_id, inner_loop)
    return u_last if option == 1 else acc / total


def _asysvrg_epochs_core(obj: Objective, data, w0, key, eta, tau, scheme_id,
                         delay_id, *, epochs: int, total: int, buf_len: int,
                         option: int, drop_prob: float, row_epochs=None):
    """``epochs`` outer AsySVRG iterations as one `lax.scan`, with the
    fixed-order loss recorded after every epoch (index 0 = loss at w0).

    The multi-epoch mirror of `_hogwild_epochs_core`: ``row_epochs`` (a
    dynamic, batchable scalar; default = the static ``epochs`` bound) is
    this config's own budget — past it the row FREEZES (carry passthrough +
    masked loss writes re-emitting the last live loss), so a sweep row with
    a shorter budget is bit-identical to an independent shorter run.

    This is the ONE definition of the per-row epochs scan: the sweep
    engine's vmap path batches it (`repro.core.sweep._asysvrg_group_fn`)
    and the fused Pallas megakernel runs it per grid row
    (`repro.kernels.sweep_epoch`) — both paths execute literally this
    function, which is what makes them bit-identical on XLA:CPU.
    """
    with jax.named_scope(LOSS_SCOPE):
        loss0 = obj.flat_loss(data, w0)
    bound = jnp.int32(epochs) if row_epochs is None else row_epochs

    def step(carry, e):
        w, key, loss_prev = carry
        key, sub = jax.random.split(key)
        active = e < bound
        w_new = _epoch_core(
            obj, data, w, sub, eta, tau, scheme_id, delay_id,
            total=total, buf_len=buf_len, option=option,
            drop_prob=drop_prob)
        # frozen rows: carry passthrough + masked loss write (the last
        # live loss is re-emitted), so a row with a shorter budget is
        # bit-identical to an independent shorter run
        w_next = jnp.where(active, w_new, w)
        with jax.named_scope(LOSS_SCOPE):
            loss_w = obj.flat_loss(data, w_next)
        loss_next = jnp.where(active, loss_w, loss_prev)
        return (w_next, key, loss_next), loss_next

    (w_fin, _, _), losses = jax.lax.scan(
        step, (w0, key, loss0), jnp.arange(epochs))
    return w_fin, jnp.concatenate([loss0[None], losses])


def _resolve_steps(obj: Objective, cfg: SVRGConfig):
    """(p, M, M̃=pM, clamped τ) from the config — paper §5.1 defaults."""
    p_threads = max(1, cfg.num_threads)
    M = cfg.inner_steps or (2 * obj.n) // p_threads
    total = p_threads * M                               # M̃ = pM
    tau = cfg.tau if cfg.tau else (p_threads - 1)
    tau = max(0, min(tau, total - 1)) if total > 1 else 0
    return p_threads, M, total, tau


def asysvrg_epoch(obj: Objective, w, key, cfg: SVRGConfig,
                  delay_kind: str = "fixed", drop_prob: float = 0.02):
    """One outer iteration of Algorithm 1 under the chosen reading scheme.

    ``w`` may be the objective's param pytree or its flat vector; the
    return matches the flat form. Returns w_{t+1} per cfg.option (1 = final
    iterate, 2 = inner average).
    """
    if cfg.scheme not in SCHEME_IDS:
        raise ValueError(f"unknown scheme {cfg.scheme!r}")
    if delay_kind not in DELAY_IDS:
        raise ValueError(f"unknown delay schedule {delay_kind!r}")
    _, _, total, tau = _resolve_steps(obj, cfg)
    delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[delay_kind]
    return _epoch_core(
        obj, obj.data_args(), obj.as_flat(w), key,
        jnp.float32(cfg.step_size), jnp.int32(tau),
        jnp.int32(SCHEME_IDS[cfg.scheme]), jnp.int32(delay_id),
        total=total, buf_len=tau + 1, option=cfg.option, drop_prob=drop_prob)


def run_asysvrg(obj: Objective, epochs: int, cfg: SVRGConfig,
                seed: int = 0, w0=None, delay_kind: str = "fixed",
                drop_prob: float = 0.02) -> AsyRunResult:
    """Multi-epoch driver (one configuration, one jit per call).

    Effective-pass accounting follows §5.1: each epoch visits the dataset 3x
    (1 full-gradient pass + 2n inner visits when M̃ = 2n). The history is
    recorded with the fixed-order loss so `repro.core.sweep` reproduces it
    bit-identically from a single batched compilation. `AsyRunResult.w` is
    the FLAT iterate; pytree objectives unravel it via
    ``obj.unravel_params``.
    """
    w = obj.init_flat() if w0 is None else obj.as_flat(w0)
    key = jax.random.PRNGKey(seed)

    _, _, total, _ = _resolve_steps(obj, cfg)
    # §5.1 accounting: one inner update visits ONE instance; with M̃ = 2n the
    # epoch visits the dataset 3x (1 snapshot pass + 2n inner visits)
    passes_per_epoch = 1.0 + total / obj.n

    data = obj.data_args()
    epoch_fn = jax.jit(lambda w, k: asysvrg_epoch(
        obj, w, k, cfg, delay_kind=delay_kind, drop_prob=drop_prob))
    loss_fn = jax.jit(lambda w: obj.flat_loss(data, w))  # repro-lint: ignore[RL002] sequential reference driver: one obj per process, capture is intentional; the cached-runner path (service/cache) passes data as arguments

    history = [float(loss_fn(w))]
    passes = [0.0]
    for e in range(epochs):
        key, sub = jax.random.split(key)
        w = epoch_fn(w, sub)
        history.append(float(loss_fn(w)))
        passes.append(passes[-1] + passes_per_epoch)
    return AsyRunResult(w=w, history=tuple(history),
                        effective_passes=tuple(passes),
                        total_updates=epochs * total)


def parallel_full_grad(obj: LogisticRegression, w, p_threads: int):
    """The paper's partitioned snapshot pass: thread a computes φ_a over its
    disjoint shard; the sum of partitions equals n·∇f(w) (up to the L2 term).
    Used by tests to verify the partitioned pass is exact."""
    n = obj.n
    base = n // p_threads
    sizes = [base + (1 if a < n % p_threads else 0) for a in range(p_threads)]
    parts = []
    lo = 0
    for sz in sizes:
        parts.append(obj.partial_full_grad(w, lo, sz))
        lo += sz
    return sum(parts) / n + obj.l2 * w
