"""Multi-algorithm, device-sharded sweep engine: the experiment grid in ONE
jit per group — per pod, not per host.

The paper's tables and figures are *comparisons* — AsySVRG vs Hogwild! vs
serial SVRG over (reading scheme × thread count × step size × seed × τ).
The benchmark layer used to run each cell as its own `run_*` call — one
trace, one compile, and epochs × Python dispatches PER CELL. This module
turns the grid into data: every configuration becomes a row of scalar
arrays (seed, algo, scheme-id, step-size, τ, delay-id, decay, epochs), the
epoch body is `vmap`-ed over that row axis, and a `lax.scan` drives the
epochs — so N×compile becomes 1×compile and the entire grid advances in
lockstep through one XLA program.

Two axes make the engine paper-scale:

**Config-batch sharding.** When a mesh with a ``data`` axis is active —
passed as ``run_sweep(..., mesh=...)`` or installed ambiently via
``repro.sharding.context.mesh_context`` (the launcher's mesh, see
`repro.launch.mesh.make_sweep_mesh` / `make_production_mesh`) — each
group's row axis is padded to a multiple of the ``data``-axis size and
dispatched through ``shard_map``: every device runs the identical vmapped
program over its row shard, with NO cross-row collectives, so an N-config
grid is one jit per group per *pod* instead of per host. Padding rows
replicate row 0 and are dropped on reassembly. Without a mesh (or with a
1-device ``data`` axis) the unsharded single-device path runs unchanged.

**Masked per-row epochs.** ``SweepSpec.epochs`` (0 = inherit `run_sweep`'s
``epochs`` argument) lets rows of ONE call run different epoch budgets: the
group scans to its members' max and finished rows are frozen — the carry
passes through unchanged and the loss write is masked (the last live loss
is carried forward), so a row with ``epochs=E`` is bit-identical to an
independent E-epoch run. This is what folds Fig. 1's paired budgets
(AsySVRG E vs Hogwild! 3E, equal effective passes) into a single
`run_sweep` call.

The `algo` axis selects the epoch engine per row:

  * ``"asysvrg"`` — Algorithm 1 via `asysvrg._epoch_core` (the paper's
    contribution: SVRG control variate under bounded-delay reads);
  * ``"hogwild"`` — the baseline via `hogwild._hogwild_epochs_core`, same
    bounded-delay read semantics, no control variate, with the per-epoch
    γ ← decay·γ schedule threaded through the scan carry so decay lives
    inside the compiled program;
  * ``"svrg"``    — serial SVRG routed through the SAME asysvrg path as the
    zero-delay degenerate case (τ=0, zero delay schedule, consistent reads
    — "If τ=0, AsySVRG degenerates to the sequential version of SVRG").
    svrg specs are NORMALIZED on entry: contradictory ``tau != 0`` raises,
    and ``scheme``/``delay_kind`` are rewritten to the values that execute,
    so `SweepResult.row()` never reports a scheme that never ran.

Bit-exactness contract: per-config loss histories and final iterates are
BIT-IDENTICAL to sequential `run_asysvrg` / `run_hogwild` calls with the
same specs (tests/test_sweep.py, tests/test_sweep_hogwild.py), and the
sharded dispatch is bit-identical per row to the unsharded path
(tests/test_sweep_sharded.py, under forced multi-device CPU). The contract
holds because both epoch cores and every objective's loss only use reductions
whose bits survive vmap batching (see repro.core.objective) — and because
each row's arithmetic is device-local under `shard_map` (no cross-row
collectives). It is CALIBRATED AGAINST XLA:CPU reduction behaviour and must
be re-validated per backend before the sharded path is trusted on TPU/GPU.

Grouping: specs are grouped by the STATIC dims of their compiled program —
(engine, M̃, option, buf_len) — compiled once per group, and rows reassemble
in input order. ``buf_len`` (the delay ring-buffer length) is pinned PER
ROW at resolve time from the row's own (τ, num_threads): adding an
unrelated high-τ row to a sweep can therefore never change another row's
compiled program shape (it lands in its own group). Rows that should share
a group share a thread count, which the paper's grids do; the ring-buffer
slot arithmetic uses the dynamic τ, so buf_len only affects shapes, never
bits. A grid over schemes / seeds / steps / τ / delay-kinds / epochs at one
thread count is one group per algo.

**Persistent compiled runners.** The group bodies (`_asysvrg_group_fn` /
`_hogwild_group_fn`) close over the objective's PURE methods + static
config only — the ``obj.data_args()`` tuple and the per-row ``w0`` enter
as runtime arguments — and every dispatch goes through the module-level
runner cache in `repro.service.cache`, keyed on (engine, M̃, option,
buf_len, epochs-bound, drop_prob, mesh fingerprint, objective static key,
data shapes/dtypes). A repeated same-shape `run_sweep` therefore reuses the
previous call's jitted runner and compiles NOTHING (tests/test_service.py
counts traces to prove it), and the `repro.service` scheduler coalesces
many clients' specs through the same runners.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import os

from repro.config import SVRGConfig
from repro.core.asysvrg import (
    DELAY_IDS,
    SCHEME_IDS,
    _asysvrg_epochs_core,
    _resolve_steps,
)
from repro.core.hogwild import _hogwild_epochs_core, _resolve_hogwild_steps
from repro.core.objective import Objective, get_objective, params_from_flat
from repro.kernels.dispatch import fused_sweep_mode
from repro.obs.trace import tracer as _tracer
from repro.sharding.context import current_mesh

ALGOS = ("asysvrg", "hogwild", "svrg")
# svrg rows run on the asysvrg engine (τ=0 degenerate case), so two engines
_ENGINE_ASYSVRG = "asysvrg"
_ENGINE_HOGWILD = "hogwild"
_DATA_AXIS = "data"

# engine modes: how a group's epoch scan executes. "vmap" batches the
# per-row epochs cores with jax.vmap (per-update XLA op dispatch); "fused"
# maps the row axis onto a Pallas grid and runs the whole (group × epoch)
# scan as ONE megakernel launch (repro.kernels.sweep_epoch) — under the
# Pallas interpreter, where it is BIT-IDENTICAL to the vmap path
# (tests/test_kernel_sweep.py). On a TPU backend "fused" is refused at plan
# time (`repro.kernels.dispatch.FUSED_TPU_BLOCKERS`). "" on a spec inherits
# the process default: $REPRO_SWEEP_ENGINE, else "vmap".
ENGINE_MODES = ("vmap", "fused")
_ENGINE_MODE_ENV = "REPRO_SWEEP_ENGINE"


def default_engine_mode() -> str:
    """The process-wide engine mode specs with ``engine_mode=""`` resolve
    to: ``$REPRO_SWEEP_ENGINE`` when set (validated), else "vmap" — the
    fused megakernel is opt-in per spec or per process."""
    mode = os.environ.get(_ENGINE_MODE_ENV, "").strip().lower()
    if mode and mode not in ENGINE_MODES:
        raise ValueError(
            f"{_ENGINE_MODE_ENV}={mode!r} — expected one of {ENGINE_MODES}")
    return mode or "vmap"


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One grid cell: the knobs Tables 2–3 / Fig. 1 vary.

    ``algo`` picks the epoch engine ("asysvrg" / "hogwild" / "svrg").
    τ conventions follow each algorithm's sequential driver:
      * asysvrg: ``tau=0`` means "derive τ = p−1" (SVRGConfig convention);
        ``num_threads``/``inner_steps`` fix M̃ = pM exactly as SVRGConfig.
      * hogwild: ``tau=-1`` derives τ = p−1 and ``tau=0`` is genuinely zero
        delay (`run_hogwild` convention); M̃ = (n // p)·p.
      * svrg: τ MUST be 0 (anything else raises — svrg is the zero-delay
        degenerate case) and reads execute consistent with zero delays;
        M̃ = ``inner_steps`` or 2n (`run_svrg` convention).
    ``decay`` is the per-epoch γ ← decay·γ factor (hogwild only).
    ``epochs`` is this row's outer-epoch budget; 0 inherits `run_sweep`'s
    ``epochs`` argument. Rows of one call may disagree (masked epochs).
    ``objective`` optionally names a REGISTERED objective
    (`repro.core.objective.register_objective`) — the wire-addressable form
    the HTTP tier uses; "" means "the objective the call passes in". All
    rows of one plan must resolve to ONE objective (the result arrays are
    rectangular in its flat dim); submit separate requests to sweep several
    objectives — the service scheduler keeps them in distinct groups via
    the objective fingerprint in the group key.
    ``engine_mode`` picks how the row's group executes: "vmap" (the
    batched-XLA path) or "fused" (the Pallas sweep-epoch megakernel,
    `repro.kernels.sweep_epoch`); "" inherits `default_engine_mode()`.
    The mode joins the group key, so fused and vmap rows never share a
    compiled runner — and their results are bit-identical in interpret
    mode, so flipping the flag never changes a row's numbers on CPU.
    ``telemetry`` opts the row into `repro.obs.telemetry` series
    (realized staleness, update norms) on its `SweepResult`. It is pure
    reporting computed OUTSIDE the jitted group fn from already-returned
    arrays, deliberately absent from the group key: flipping it can never
    retrace, regroup, or change a single bit of the numeric outputs.
    """
    seed: int = 0
    scheme: str = "inconsistent"
    step_size: float = 0.1
    tau: int = 0
    delay_kind: str = "fixed"
    num_threads: int = 8
    inner_steps: int = 0
    option: int = 2
    algo: str = "asysvrg"
    decay: float = 0.9
    epochs: int = 0
    objective: str = ""
    engine_mode: str = ""
    telemetry: bool = False

    def to_config(self) -> SVRGConfig:
        return SVRGConfig(scheme=self.scheme, step_size=self.step_size,
                          num_threads=self.num_threads, tau=self.tau,
                          inner_steps=self.inner_steps, option=self.option)


class SweepResult(NamedTuple):
    """Row-aligned sweep outputs.

    ``specs`` are the NORMALIZED specs describing what executed (derived τ
    substituted, svrg scheme/delay rewritten, per-row epochs made explicit).
    ``histories``/``effective_passes`` have the GLOBAL max-epochs width;
    rows with a shorter budget are frozen past their own epoch count — use
    :meth:`curve` for a row trimmed to its own budget.
    ``telemetry`` (a `repro.obs.telemetry.SweepTelemetry`, None unless a
    spec opted in) carries realized-staleness / update-norm series derived
    from the arrays above — extra reporting, never extra engine outputs.
    ``diverged_rows`` (None unless a watchdog ran and flagged something)
    holds, per row, -1 for healthy or the last trusted epoch for a row the
    `repro.obs.watchdog` detected diverging; under ``cancel_row`` that is
    also the epoch the row was frozen at (``epochs_per_row`` reflects it).
    """
    specs: Tuple[SweepSpec, ...]
    histories: np.ndarray         # [C, max_epochs+1] loss after each epoch
    effective_passes: np.ndarray  # [C, max_epochs+1] cumulative eff. passes
    final_w: np.ndarray           # [C, flat_dim] FLAT final iterates
    total_updates: np.ndarray     # [C] updates applied over all row epochs
    epochs_per_row: np.ndarray    # [C] each row's executed epoch budget
    param_shapes: Tuple = ()      # objective's ((path, shape, dtype), ...)
    telemetry: Optional[object] = None  # SweepTelemetry when a row opted in
    diverged_rows: Optional[np.ndarray] = None  # [C] -1 or last trusted epoch

    def curve(self, c: int) -> Tuple[np.ndarray, np.ndarray]:
        """(effective_passes, loss history) trimmed to row c's own budget."""
        e = int(self.epochs_per_row[c])
        return self.effective_passes[c, :e + 1], self.histories[c, :e + 1]

    def final_params(self, c: int):
        """Row c's final iterate in the objective's PYTREE form, rebuilt
        bit-exactly from the flat row via the recorded ``param_shapes``
        (flat-vector objectives get the row back unchanged)."""
        if not self.param_shapes:
            return self.final_w[c]
        return params_from_flat(self.final_w[c], self.param_shapes)

    def row(self, c: int) -> Dict:
        """One config as a flat record (for CSV-ish reporting)."""
        s = self.specs[c]
        passes, hist = self.curve(c)
        return {**dataclasses.asdict(s),
                "history": hist,
                "effective_passes": passes,
                "total_updates": int(self.total_updates[c])}


def make_grid(schemes: Sequence[str] = ("consistent", "inconsistent", "unlock"),
              seeds: Sequence[int] = (0,),
              step_sizes: Sequence[float] = (0.1,),
              taus: Sequence[int] = (0,),
              delay_kinds: Sequence[str] = ("fixed",),
              num_threads: int = 8,
              inner_steps: int = 0,
              option: int = 2,
              algo: str = "asysvrg",
              decay: float = 0.9,
              epochs: int = 0,
              objective: str = "") -> List[SweepSpec]:
    """Cartesian grid over the paper's experiment axes, outermost-first.

    The ``taus`` axis uses ONE convention for every algo: 0 means "derive
    τ = p−1". For hogwild rows that is translated to the driver's ``-1``
    sentinel, so the default grid is a real asynchronous baseline, not the
    zero-delay degenerate one (build `SweepSpec(algo="hogwild", tau=0)`
    directly for genuinely zero delay).
    """
    if algo == "hogwild":
        taus = [-1 if t == 0 else t for t in taus]
    return [
        SweepSpec(seed=seed, scheme=scheme, step_size=step, tau=tau,
                  delay_kind=kind, num_threads=num_threads,
                  inner_steps=inner_steps, option=option, algo=algo,
                  decay=decay, epochs=epochs, objective=objective)
        for scheme in schemes
        for seed in seeds
        for step in step_sizes
        for tau in taus
        for kind in delay_kinds
    ]


class _Resolved(NamedTuple):
    engine: str          # "asysvrg" | "hogwild" (svrg routes to asysvrg)
    total: int           # M̃, the static inner-scan bound
    tau: int
    scheme_id: int
    delay_id: int
    option: int          # 0 for hogwild (engine has no option switch)
    passes_per_epoch: float  # repro-lint: ignore[RL004] derived from engine+total+n (all keyed); pass-count accounting only, never shapes the compiled program
    buf_len: int         # ring-buffer length, pinned per-row (see _resolve)
    epochs: int          # this row's outer-epoch budget
    fused: bool = False  # True = Pallas megakernel, False = vmap path


def _row_buf_len(tau: int, num_threads: int, total: int) -> int:
    """Ring-buffer length from the ROW's own fields (never the group's).

    ≥ τ+1 (correctness) and padded up to the thread count so a grid varying
    τ at one thread count still shares one compiled shape — while adding an
    unrelated high-τ row cannot change this row's buffer (it gets its own
    group). Dynamic-τ slot arithmetic makes any length ≥ τ+1 read
    bit-identically (tests/test_sweep.py), so this only moves shapes.
    """
    return min(max(tau + 1, max(1, num_threads)), max(1, total))


def _normalize_spec(spec: SweepSpec) -> SweepSpec:
    """Entry normalization: reject contradictions, rewrite svrg to what runs.

    svrg rows execute consistent reads with a zero delay schedule at τ=0 —
    a spec recording anything else would make `SweepResult.row()` report a
    scheme that never ran. τ≠0 on svrg is a contradiction (svrg IS the τ=0
    degenerate case) and raises; scheme/delay_kind (dataclass defaults are
    asysvrg-flavoured) are rewritten silently.
    """
    if spec.algo not in ALGOS:
        raise ValueError(f"unknown algo {spec.algo!r}")
    if spec.scheme not in SCHEME_IDS:
        raise ValueError(f"unknown scheme {spec.scheme!r}")
    if spec.delay_kind not in DELAY_IDS:
        raise ValueError(f"unknown delay schedule {spec.delay_kind!r}")
    if spec.epochs < 0:
        raise ValueError(f"epochs must be >= 0 (0 = inherit), got {spec.epochs}")
    if spec.engine_mode and spec.engine_mode not in ENGINE_MODES:
        raise ValueError(
            f"unknown engine_mode {spec.engine_mode!r} "
            f"(expected one of {ENGINE_MODES}, or '' to inherit)")
    if spec.algo == "svrg":
        if spec.tau != 0:
            raise ValueError(
                f"algo='svrg' is the τ=0 degenerate case; tau={spec.tau} "
                "contradicts it — use algo='asysvrg' for τ>0")
        return dataclasses.replace(spec, scheme="consistent",
                                   delay_kind="zero")
    return spec


def _resolve(obj: Objective, spec: SweepSpec,
             default_epochs: int) -> _Resolved:
    """Per-spec resolution, delegating to each algorithm's own arithmetic.

    Raises (rather than letting a negative M̃ surface as a cryptic
    trace-time shape error) for non-positive resolved totals — this is the
    validation the service relies on to reject a bad spec at submit time.
    A fused row on a TPU backend raises here too (`fused_sweep_mode`).
    """
    epochs = spec.epochs or default_epochs
    if epochs < 1:
        raise ValueError(f"resolved epochs must be >= 1, got {epochs}")
    fused = (spec.engine_mode or default_engine_mode()) == "fused"
    if fused:
        fused_sweep_mode()

    if spec.algo == "hogwild":
        _, total, tau = _resolve_hogwild_steps(obj.n, spec.num_threads,
                                               spec.tau)
        delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[spec.delay_kind]
        res = _Resolved(_ENGINE_HOGWILD, total, tau,
                        SCHEME_IDS[spec.scheme], delay_id, 0, 1.0,
                        _row_buf_len(tau, spec.num_threads, total), epochs,
                        fused)
    elif spec.algo == "svrg":
        # the zero-delay degenerate case on the asysvrg engine (paper §3)
        total = spec.inner_steps or 2 * obj.n
        res = _Resolved(_ENGINE_ASYSVRG, total, 0,
                        SCHEME_IDS["consistent"], DELAY_IDS["zero"],
                        spec.option, 1.0 + total / obj.n,
                        _row_buf_len(0, spec.num_threads, total), epochs,
                        fused)
    else:
        _, _, total, tau = _resolve_steps(obj, spec.to_config())
        delay_id = DELAY_IDS["zero"] if tau == 0 else DELAY_IDS[spec.delay_kind]
        res = _Resolved(_ENGINE_ASYSVRG, total, tau, SCHEME_IDS[spec.scheme],
                        delay_id, spec.option, 1.0 + total / obj.n,
                        _row_buf_len(tau, spec.num_threads, total), epochs,
                        fused)
    if res.total < 1:
        raise ValueError(
            f"resolved inner-step count M̃ must be >= 1, got {res.total} "
            f"(inner_steps={spec.inner_steps}) for {spec}")
    return res


def _executed_spec(spec: SweepSpec, r: _Resolved) -> SweepSpec:
    """Rewrite convention sentinels to resolved values: the spec a
    `SweepResult` carries describes exactly what executed (derived τ made
    explicit, zero-delay collapse reflected, per-row epochs pinned)."""
    delay = "zero" if r.delay_id == DELAY_IDS["zero"] else spec.delay_kind
    return dataclasses.replace(spec, tau=r.tau, delay_kind=delay,
                               epochs=r.epochs,
                               engine_mode="fused" if r.fused else "vmap")


# (objective fingerprint, engine, M̃, option, buf_len, fused) — the
# fingerprint covers the objective's static config AND data bytes, so the
# service scheduler can pool rows from different requests without ever
# coalescing two objectives (or two datasets) into one compiled dispatch.
# ``fused`` (the resolved engine mode) sits LAST so key_[0] stays the
# objective fingerprint everywhere the scheduler peeks at it.
_GroupKey = Tuple[int, str, int, int, int, bool]


class SweepPlan(NamedTuple):
    """Static execution plan: what compiles together, with which bounds."""
    specs: Tuple[SweepSpec, ...]          # normalized, executed-semantics
    resolved: Tuple[_Resolved, ...]
    groups: Dict[_GroupKey, List[int]]    # group key -> member row indices
    objective: Objective                  # the ONE objective every row runs

    def group_epochs(self, key: _GroupKey) -> int:
        """A group's static scan bound: max member epoch budget."""
        return max(self.resolved[c].epochs for c in self.groups[key])


def _resolve_objective(obj: Optional[Objective],
                       specs: Sequence[SweepSpec]) -> Objective:
    """The plan's single objective: named specs resolve via the registry,
    "" means the caller's ``obj``; mixing objectives in one plan raises
    (results are rectangular in ONE flat dim — submit separate sweeps)."""
    names = {s.objective for s in specs}
    resolved: Dict[str, Objective] = {}
    for name in sorted(names - {""}):
        resolved[name] = get_objective(name)
    if "" in names:
        if obj is None:
            raise ValueError(
                "specs with objective='' need an explicit objective argument")
        resolved[""] = obj
    fps = {o.fingerprint() for o in resolved.values()}
    if len(fps) > 1:
        raise ValueError(
            f"one sweep, one objective: specs name {sorted(names)} which "
            "resolve to different objectives — submit separate sweeps")
    return next(iter(resolved.values()))


def plan_sweep(obj: Optional[Objective], epochs: int,
               specs: Sequence[SweepSpec]) -> SweepPlan:
    """Normalize + resolve specs and group them by compiled-program shape.

    Exposed for tests and capacity planning: the group keys are the static
    dims (objective fingerprint, engine, M̃, option, buf_len, fused), all
    pinned per-row, so a row's key never depends on which other rows share
    the sweep. ``obj`` may be None when every spec names a registered
    objective.
    """
    specs = tuple(_normalize_spec(s) for s in specs)
    if not specs:
        raise ValueError("empty sweep")
    obj = _resolve_objective(obj, specs)
    ofp = obj.fingerprint()
    resolved = tuple(_resolve(obj, s, epochs) for s in specs)
    specs = tuple(_executed_spec(s, r) for s, r in zip(specs, resolved))
    groups: Dict[_GroupKey, List[int]] = {}
    for c, r in enumerate(resolved):
        groups.setdefault(
            (ofp, r.engine, r.total, r.option, r.buf_len, r.fused),
            []).append(c)
    return SweepPlan(specs=specs, resolved=resolved, groups=groups,
                     objective=obj)


def _active_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """The mesh whose `data` axis shards the config-row axis, if any.

    Explicit ``mesh=`` wins; otherwise the ambient `mesh_context` mesh
    (repro.sharding.context) is picked up, so a launcher that installed the
    production mesh shards its sweeps with no call-site changes. A mesh
    without a >1-sized ``data`` axis degrades to the unsharded path.
    """
    if mesh is None:
        mesh = current_mesh()
    if mesh is None or _DATA_AXIS not in mesh.axis_names:
        return None
    if int(mesh.shape[_DATA_AXIS]) <= 1:
        return None
    return mesh


def _pad_rows(args: Tuple[jnp.ndarray, ...], pad: int):
    """Pad each row-leading array by replicating row 0 (a valid config —
    padding rows compute real, discarded work)."""
    if pad == 0:
        return args
    return tuple(jnp.concatenate([a] + [a[:1]] * pad, axis=0) for a in args)


# row-leading runtime arguments per engine (after the objective data args)
_NUM_ROW_ARGS = {_ENGINE_ASYSVRG: 7, _ENGINE_HOGWILD: 8}


def _asysvrg_group_fn(obj: Objective, num_data: int, epochs: int, total: int,
                      buf_len: int, option: int, drop_prob: float):
    """vmap(per-config masked epochs-scan) for one asysvrg/svrg group.

    Closes over the objective's PURE methods + static config ONLY — the
    data tuple (``obj.data_args()``-shaped, ``num_data`` leading arguments)
    and every per-row array are runtime arguments — so the returned
    function can live in the persistent runner cache (repro.service.cache)
    and any same-``runner_static_key`` objective's data reuses one compiled
    program.
    """

    def group(*all_args):
        data = all_args[:num_data]
        keys, etas, taus, scheme_ids, delay_ids, row_epochs, w0_rows = \
            all_args[num_data:]

        def per_config(key, eta, tau, scheme_id, delay_id, row_epochs, w0):
            return _asysvrg_epochs_core(
                obj, data, w0, key, eta, tau, scheme_id, delay_id,
                epochs=epochs, total=total, buf_len=buf_len, option=option,
                drop_prob=drop_prob, row_epochs=row_epochs)

        return jax.vmap(per_config)(keys, etas, taus, scheme_ids, delay_ids,
                                    row_epochs, w0_rows)

    return group


def _hogwild_group_fn(obj: Objective, num_data: int, epochs: int, total: int,
                      buf_len: int, drop_prob: float):
    """vmap(multi-epoch Hogwild! scan, γ-decay in the carry); pure methods +
    statics only — data and row arrays enter at call time (see
    `_asysvrg_group_fn`)."""

    def group(*all_args):
        data = all_args[:num_data]
        (keys, gammas, decays, taus, scheme_ids, delay_ids, row_epochs,
         w0_rows) = all_args[num_data:]

        def per_config(key, gamma0, decay, tau, scheme_id, delay_id,
                       row_epochs, w0):
            return _hogwild_epochs_core(
                obj, data, w0, key, gamma0, decay, tau, scheme_id, delay_id,
                epochs=epochs, total=total, buf_len=buf_len,
                drop_prob=drop_prob, row_epochs=row_epochs)

        return jax.vmap(per_config)(keys, gammas, decays, taus, scheme_ids,
                                    delay_ids, row_epochs, w0_rows)

    return group


def _group_fn(engine: str, *, obj: Objective, num_data: int, epochs: int,
              total: int, buf_len: int, option: int, drop_prob: float,
              fused: bool = False):
    """(unjitted group body, row-arg count) for the runner cache.

    ``fused=True`` swaps the vmap batching for the Pallas sweep-epoch
    megakernel (repro.kernels.sweep_epoch) — same calling convention, same
    per-row epochs-scan functions, so in interpret mode the two bodies are
    bit-identical.
    """
    if fused:
        from repro.kernels.sweep_epoch import fused_group_fn
        return (fused_group_fn(obj, num_data, engine=engine, epochs=epochs,
                               total=total, buf_len=buf_len, option=option,
                               drop_prob=drop_prob,
                               interpret=fused_sweep_mode() == "interpret"),
                _NUM_ROW_ARGS[engine])
    if engine == _ENGINE_HOGWILD:
        return (_hogwild_group_fn(obj, num_data, epochs, total, buf_len,
                                  drop_prob),
                _NUM_ROW_ARGS[engine])
    return (_asysvrg_group_fn(obj, num_data, epochs, total, buf_len, option,
                              drop_prob),
            _NUM_ROW_ARGS[engine])


def _shard_group_fn(fn, mesh: Mesh, num_data: int, num_row: int):
    """shard_map the group body: the objective's data args replicate, every
    row-leading input/output shards over `data`.

    Each device runs the identical program over its row shard and NO
    collective crosses rows, which is why sharded rows stay bit-identical
    to the unsharded path. (`check_vma=False`: mesh axes other than `data`
    — e.g. `model` in the production mesh — replicate the rows redundantly,
    which is deterministic and harmless.)
    """
    spec = P(_DATA_AXIS)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(),) * num_data + (spec,) * num_row,
                         out_specs=(spec, spec),
                         check_vma=False)


def _accumulate_passes(ppe: Sequence[float], epochs_per_row: np.ndarray,
                       max_epochs: int) -> np.ndarray:
    """[C, max_epochs+1] cumulative effective passes, vectorized.

    ``np.cumsum``'s running float64 sum is the same left-to-right addition
    order as the sequential drivers' ``acc += passes_per_epoch`` loop, and
    frozen rows add 0.0 — bitwise a no-op for the non-negative partial sums
    here — so this replaces the old O(C·E) Python loop bit-identically.
    """
    ppe_col = np.asarray(ppe, np.float64)[:, None]
    live = np.arange(max_epochs)[None, :] < np.asarray(epochs_per_row)[:, None]
    out = np.zeros((len(epochs_per_row), max_epochs + 1), np.float64)
    out[:, 1:] = np.cumsum(np.where(live, ppe_col, 0.0), axis=1)
    return out


def _write_row_history(dst_row: np.ndarray, hist_row: np.ndarray,
                       group_epochs: int) -> None:
    """Demux ONE row's group-width history into a destination row of any
    width — the single definition of the freeze/trim rule every dispatch
    path (run_sweep, the service scheduler, checkpointed jobs) shares.

    Beyond a row's own budget every entry is the frozen last live loss, so
    trimming (destination narrower than the group scan) and re-emitting
    the tail (destination wider) are both bit-exact.
    """
    width = dst_row.shape[0]
    if width <= group_epochs + 1:
        dst_row[:] = hist_row[:width]
    else:
        dst_row[:group_epochs + 1] = hist_row
        dst_row[group_epochs + 1:] = hist_row[-1]


def _row_args(engine: str, specs: Sequence[SweepSpec],
              resolved: Sequence[_Resolved], members: Sequence[int],
              w_init) -> Tuple[jnp.ndarray, ...]:
    """A group runner's row-leading arguments for ``members``, in calling
    order (after the objective's data args; `_NUM_ROW_ARGS` of them)."""
    keys = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray([specs[c].seed for c in members]))
    etas = jnp.asarray([specs[c].step_size for c in members], jnp.float32)
    taus_a = jnp.asarray([resolved[c].tau for c in members], jnp.int32)
    scheme_ids = jnp.asarray([resolved[c].scheme_id for c in members],
                             jnp.int32)
    delay_ids = jnp.asarray([resolved[c].delay_id for c in members],
                            jnp.int32)
    row_epochs = jnp.asarray([resolved[c].epochs for c in members],
                             jnp.int32)
    w0_rows = jnp.tile(w_init[None, :], (len(members), 1))

    if engine == _ENGINE_HOGWILD:
        decays = jnp.asarray([specs[c].decay for c in members], jnp.float32)
        return (keys, etas, decays, taus_a, scheme_ids, delay_ids,
                row_epochs, w0_rows)
    return (keys, etas, taus_a, scheme_ids, delay_ids, row_epochs, w0_rows)


def _dispatch_group(obj: Objective, specs: Sequence[SweepSpec],
                    resolved: Sequence[_Resolved], members: Sequence[int],
                    key_: _GroupKey, group_epochs: int, w_init,
                    drop_prob: float, mesh: Optional[Mesh]):
    """Run ONE (objective, engine, M̃, option, buf_len) group through the
    persistent runner cache; returns (histories [rows, group_epochs+1],
    final_w [rows, flat_dim]) as numpy, padding rows already sliced off.

    ``specs``/``resolved`` are row-aligned sequences indexed by ``members``
    — `run_sweep` passes a single plan's rows, the service scheduler a
    coalesced multi-request batch. The runner comes from
    `repro.service.cache` (imported lazily; the service layer builds on
    this module), so every caller shares one compiled program per key.
    """
    from repro.service.cache import get_group_runner

    _, engine, total, option, buf_len, fused = key_
    args = _row_args(engine, specs, resolved, members, w_init)
    runner = get_group_runner(engine, group_epochs=group_epochs, total=total,
                              option=option, buf_len=buf_len,
                              drop_prob=drop_prob, mesh=mesh, obj=obj,
                              fused=fused)
    if mesh is not None:
        # pad the row axis to a multiple of the data-axis size; padded rows
        # replicate row 0 and are sliced off below
        args = _pad_rows(args, -len(members) % int(mesh.shape[_DATA_AXIS]))
    # the execute span brackets the runner CALL (dispatch + any trace-time
    # compile), never code inside the jit — RL006 enforces that boundary.
    # With tracing on it also waits for the device, so it ends when the
    # outputs are ready, and its `fetch` child times the copies to the
    # host; off, the call returns at the enqueue and `np.asarray` waits.
    # Tag construction is gated so the tracer-off warm path pays only the
    # enabled check; compiled=True lands via cache._counted's annotate.
    tr = _tracer()
    tags = {}
    if tr.enabled:
        from repro.kernels.dispatch import mode_tags
        tags = dict(engine=engine, rows=len(members), total=int(total),
                    group_epochs=int(group_epochs), **mode_tags(fused))
    with tr.span_active("execute", **tags):
        w_fin, hist = runner(*obj.data_args(), *args)
        if tr.enabled:
            jax.block_until_ready((w_fin, hist))
        with tr.span_active("fetch"):
            return (np.asarray(hist)[:len(members)],
                    np.asarray(w_fin)[:len(members)])


def group_label(key_: _GroupKey) -> str:
    """Human-readable label for one compiled group (progress ids)."""
    _, engine, total, option, buf_len, fused = key_
    return (f"{engine}-{'fused' if fused else 'vmap'}-M{int(total)}"
            f"-opt{option}-buf{int(buf_len)}")


def _assemble_result(specs: Tuple[SweepSpec, ...],
                     resolved: Sequence[_Resolved], histories: np.ndarray,
                     final_w: np.ndarray,
                     param_shapes: Tuple = (), w_init=None,
                     diverged: Optional[Dict[int, int]] = None) -> SweepResult:
    """Derive the accounting rows (passes, totals, epoch budgets) from the
    resolved specs and build the `SweepResult` — the ONE definition all
    dispatch paths (run_sweep, service demux, checkpointed jobs) share, so
    accounting can never diverge between them.

    ``w_init`` (the flat start iterate) enables the opt-in telemetry
    attachment: rows with ``SweepSpec.telemetry`` get realized-staleness /
    update-norm series DERIVED from the already-final arrays here — after
    every engine output is fixed, so the flag cannot perturb results.

    ``diverged`` (flat row -> last trusted epoch, from the watchdog)
    becomes the optional ``diverged_rows`` marker array; callers passing
    it hand in ``resolved`` rows whose epoch budgets already reflect any
    ``cancel_row`` truncation, so the accounting below follows for free."""
    epochs_per_row = np.asarray([r.epochs for r in resolved], np.int64)
    passes = _accumulate_passes([r.passes_per_epoch for r in resolved],
                                epochs_per_row, histories.shape[1] - 1)
    total_updates = epochs_per_row * np.asarray(
        [r.total for r in resolved], np.int64)
    telemetry = None
    if w_init is not None and any(s.telemetry for s in specs):
        # lazy: repro.obs.telemetry imports back into repro.core
        from repro.obs import telemetry as _telemetry
        telemetry = _telemetry.compute(specs, resolved, histories, final_w,
                                       w_init)
    diverged_rows = None
    if diverged:
        diverged_rows = np.full(len(specs), -1, np.int64)
        for c, e in diverged.items():
            diverged_rows[c] = e
    return SweepResult(specs=specs, histories=histories,
                       effective_passes=passes, final_w=final_w,
                       total_updates=total_updates,
                       epochs_per_row=epochs_per_row,
                       param_shapes=param_shapes, telemetry=telemetry,
                       diverged_rows=diverged_rows)


def run_sweep(obj: Optional[Objective], epochs: int,
              specs: Sequence[SweepSpec], *, w0=None,
              drop_prob: float = 0.02,
              mesh: Optional[Mesh] = None) -> SweepResult:
    """Run every spec for its epoch budget in one compiled program per
    (objective, engine, M̃, option, buf_len) group, row-sharded across the
    mesh `data` axis when one is active (explicit ``mesh=`` or the ambient
    `repro.sharding.context` mesh). Histories/final iterates are
    bit-identical to per-spec `run_asysvrg` / `run_hogwild` calls — sharded
    or not (XLA:CPU-calibrated; re-validate per backend).

    ``obj`` is any `repro.core.objective.Objective` (or None when every
    spec names a registered one); pytree objectives run on their FLAT
    vector and `SweepResult.final_params` rebuilds the tree bit-exactly.
    Runners are fetched from the persistent cache in `repro.service.cache`:
    a repeated sweep with the same static group dims and data shapes
    compiles nothing."""
    plan = plan_sweep(obj, epochs, specs)
    specs, resolved, obj = plan.specs, plan.resolved, plan.objective
    w_init = obj.init_flat() if w0 is None else obj.as_flat(w0)
    mesh = _active_mesh(mesh)

    C = len(specs)
    max_epochs = max(r.epochs for r in resolved)
    histories = np.zeros((C, max_epochs + 1), np.float32)
    final_w = np.zeros((C, obj.flat_dim), np.float32)

    for key_, members in plan.groups.items():
        group_epochs = plan.group_epochs(key_)
        hist, w_fin = _dispatch_group(obj, specs, resolved, members, key_,
                                      group_epochs, w_init, drop_prob, mesh)
        for row, c in enumerate(members):
            _write_row_history(histories[c], hist[row], group_epochs)
            final_w[c] = w_fin[row]

    return _assemble_result(specs, resolved, histories, final_w,
                            param_shapes=obj.param_shapes(), w_init=w_init)
