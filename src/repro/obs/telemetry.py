"""Algorithm telemetry: realized staleness + update-magnitude series.

The paper's convergence guarantee is parameterized by the delay bound τ,
but what convergence actually responds to is the REALIZED staleness of
each read (Lian et al., 1506.08272): a row configured at τ=7 whose
uniform schedule mostly drew d_m <= 2 behaves like a much smaller τ. An
opt-in ``SweepSpec.telemetry`` flag surfaces that per row, WITHOUT
touching the compiled program:

  * The engines draw every delay d_m inside the jitted scan from a key
    chain that is a pure function of the row's seed — per epoch
    ``key, sub = split(key)``, then ``k_idx, k_delay, k_scan =
    split(sub, 3)`` and ``delays = _delay_schedule_core(delay_id, total,
    τ, k_delay)`` (identical in `core/asysvrg.py` and `core/hogwild.py`).
    JAX PRNG is deterministic eager-vs-jit, so replaying that chain HERE,
    outside any jit, reproduces the exact delays the compiled scan used —
    recomputation, not instrumentation.
  * Update-norm and loss-delta series come from arrays the engine already
    returns (``final_w``, ``histories``).

Both make telemetry trace-safe and bit-safe by construction: nothing is
added to, reordered in, or read out of the jitted group fn, so results
with the flag on are bit-identical to the pinned engine outputs
(asserted in tests/test_obs.py against runs with the flag off, and the
pre-refactor pin stays green). repro-lint RL006 enforces the
construction: no obs/timing calls can enter a ``*_core`` scope.

Computed only for rows that set the flag (a host-side replay costs
O(epochs · M̃) numpy work per row); un-flagged rows carry zeros and
``rows[c] == False``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.asysvrg import _delay_schedule_core


class SweepTelemetry(NamedTuple):
    """Row-aligned telemetry series (all [C] or [C, max_epochs]).

    ``rows`` marks which rows were computed (``SweepSpec.telemetry``);
    every series is zero where ``rows`` is False. Staleness entries are
    the realized delays d_m the row's reads executed with; per-epoch
    entries past a row's own budget are zero (the row was frozen)."""
    rows: np.ndarray                 # [C] bool: telemetry computed?
    staleness_mean: np.ndarray       # [C] mean d_m over the row's run
    staleness_var: np.ndarray        # [C] variance of d_m
    staleness_max: np.ndarray        # [C] max realized d_m (<= τ always)
    staleness_per_epoch: np.ndarray  # [C, max_epochs] per-epoch mean d_m
    update_norm: np.ndarray          # [C] ||w_final - w0||_2
    loss_delta: np.ndarray           # [C, max_epochs] loss[e+1] - loss[e]
    loss_delta_var: np.ndarray       # [C] variance of live loss deltas


def realized_delays(seed: int, delay_id: int, tau: int, total: int,
                    epochs: int) -> np.ndarray:
    """[epochs, total] — the exact delay schedule the compiled scan drew.

    Replays the engines' key-split chain from ``PRNGKey(seed)`` (shared
    verbatim by the asysvrg and hogwild epoch cores)."""
    key = jax.random.PRNGKey(seed)
    delay_id_ = np.int32(delay_id)
    tau_ = np.int32(tau)
    out = np.empty((epochs, total), np.int32)
    for e in range(epochs):
        key, sub = jax.random.split(key)
        _, k_delay, _ = jax.random.split(sub, 3)
        out[e] = np.asarray(
            _delay_schedule_core(delay_id_, total, tau_, k_delay))
    return out


def compute(specs: Sequence, resolved: Sequence, histories: np.ndarray,
            final_w: np.ndarray, w_init) -> Optional["SweepTelemetry"]:
    """Telemetry for every flagged row of one assembled result (None when
    no row set the flag). ``specs``/``resolved`` are the row-aligned
    normalized specs and `_Resolved` entries; ``histories`` has the
    result's [C, max_epochs+1] width; ``w_init`` is the flat start
    iterate every row shares."""
    flags = np.asarray([bool(getattr(s, "telemetry", False))
                        for s in specs])
    if not flags.any():
        return None
    C, width = histories.shape
    max_epochs = width - 1
    w0 = np.asarray(w_init, np.float64)

    stale_mean = np.zeros(C, np.float64)
    stale_var = np.zeros(C, np.float64)
    stale_max = np.zeros(C, np.int64)
    stale_epoch = np.zeros((C, max_epochs), np.float64)
    update_norm = np.zeros(C, np.float64)
    loss_delta = np.zeros((C, max_epochs), np.float64)
    loss_delta_var = np.zeros(C, np.float64)

    hist64 = np.asarray(histories, np.float64)
    for c in np.flatnonzero(flags):
        r = resolved[c]
        epochs = min(int(r.epochs), max_epochs)
        delays = realized_delays(specs[c].seed, r.delay_id, r.tau,
                                 r.total, epochs)
        flat = delays.reshape(-1).astype(np.float64)
        stale_mean[c] = flat.mean() if flat.size else 0.0
        stale_var[c] = flat.var() if flat.size else 0.0
        stale_max[c] = int(delays.max()) if delays.size else 0
        stale_epoch[c, :epochs] = delays.mean(axis=1)
        update_norm[c] = float(np.linalg.norm(
            np.asarray(final_w[c], np.float64) - w0))
        deltas = hist64[c, 1:epochs + 1] - hist64[c, :epochs]
        loss_delta[c, :epochs] = deltas
        loss_delta_var[c] = deltas.var() if deltas.size else 0.0

    return SweepTelemetry(rows=flags, staleness_mean=stale_mean,
                          staleness_var=stale_var, staleness_max=stale_max,
                          staleness_per_epoch=stale_epoch,
                          update_norm=update_norm, loss_delta=loss_delta,
                          loss_delta_var=loss_delta_var)


def to_dict(tel: "SweepTelemetry") -> dict:
    """JSON-safe wire form (nested lists of Python scalars — exact, like
    the rest of the result payload)."""
    return {name: np.asarray(getattr(tel, name)).tolist()
            for name in SweepTelemetry._fields}


_DTYPES = {"rows": np.bool_, "staleness_max": np.int64}


def from_dict(payload: dict) -> "SweepTelemetry":
    return SweepTelemetry(**{
        name: np.asarray(payload[name], _DTYPES.get(name, np.float64))
        for name in SweepTelemetry._fields})
