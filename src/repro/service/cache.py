"""Persistent compiled-runner cache — the ROADMAP "sweep-group runner
cache" item, closed.

Before this module, every `run_sweep` call rebuilt its jitted group runners
from fresh closures: the closure captured `X`/`y` and a new function object
per call, which defeats JAX's jit cache, so a service re-running the same
grid paid full XLA recompilation per call — the regime the paper's
"compute cost per effective pass" framing targets. The group bodies now
close over hashable statics only (`repro.core.sweep._group_fn`; data and
row arrays enter as runtime arguments) and THIS module owns the one place
they are jitted: a module-level dict keyed on everything that determines
the compiled program —

    (engine, M̃, option, buf_len, epochs-bound, drop_prob,
     mesh fingerprint, objective static key, data shapes + dtypes)

A repeated same-shape sweep — direct `run_sweep` or through the
`repro.service.api.SweepService` — fetches the SAME jitted callable and
compiles nothing. Compiles are counted by a wrapper that increments a
counter at TRACE time (the Python body only runs when jit traces), which is
version-independent and exactly counts (re)compilations; hit/miss counters
cover the cache itself. `tests/test_service.py` pins the regression: a
second same-shape sweep performs zero new traces.

The cache is process-global on purpose — many logical clients / services
in one process (the multi-tenant sweep server) share compiled programs —
and LRU-BOUNDED (`set_cache_limit`, default 64 runners) so tenants rotating
through shapes cannot grow the executable set without bound. `clear_cache()`
exists for tests and for dropping device buffers referenced by cached
executables.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.core import sweep as _sweep
from repro.kernels.dispatch import fused_sweep_mode, kernel_mode
from repro.obs import ledger as _ledger
from repro.obs.trace import tracer as _tracer
from repro.sharding.context import mesh_fingerprint


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Snapshot of the runner cache counters (monotonic since process start
    or the last `clear_cache(reset_stats=True)`)."""
    hits: int = 0
    misses: int = 0
    compiles: int = 0

    @property
    def hit_rate(self) -> float:
        lookups = self.hits + self.misses
        return self.hits / lookups if lookups else 0.0

    def since(self, base: "CacheStats") -> "CacheStats":
        """Counter deltas relative to an earlier snapshot."""
        return CacheStats(hits=self.hits - base.hits,
                          misses=self.misses - base.misses,
                          compiles=self.compiles - base.compiles)


class _Counters:
    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compiles = 0

    def snapshot(self) -> CacheStats:
        return CacheStats(hits=self.hits, misses=self.misses,
                          compiles=self.compiles)


# Per-lookup scoped attribution: a caller (one `SweepService` dispatch
# window) installs a private _Counters sink on ITS thread; every lookup —
# and every trace-time compile, which happens while the runner is called
# on the same thread — credits the sink in addition to the globals. Unlike
# the old absorb-the-global-delta-around-a-window scheme, two services
# flushing CONCURRENTLY cannot pollute each other's counters: each thread
# only feeds its own sink.
_TLS = threading.local()


@contextlib.contextmanager
def scoped_counters(sink: _Counters):
    """Credit this thread's cache lookups/compiles to ``sink`` (nests:
    the previous sink is restored on exit; only the innermost one counts)."""
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = sink
    try:
        yield sink
    finally:
        _TLS.sink = prev


@contextlib.contextmanager
def uncounted_trace():
    """Suspend compile counting on this thread: a re-trace forced for
    bookkeeping (the ledger's one-time AOT ``cost_analysis`` of an
    already-compiled runner) is not a user-visible (re)compile, and must
    not perturb the exact-compile-count contracts (`tests/test_service.py`,
    the obs-smoke 0-recompiles gate)."""
    prev = getattr(_TLS, "uncounted", False)
    _TLS.uncounted = True
    try:
        yield
    finally:
        _TLS.uncounted = prev


def _credit(field: str) -> None:
    """Bump one counter on the globals and the thread's scoped sink (if
    any). Caller holds _LOCK; the sink is thread-private so the same lock
    suffices."""
    setattr(_COUNTERS, field, getattr(_COUNTERS, field) + 1)
    sink = getattr(_TLS, "sink", None)
    if sink is not None:
        setattr(sink, field, getattr(sink, field) + 1)


_LOCK = threading.Lock()
_RUNNERS: "OrderedDict[tuple, object]" = OrderedDict()
_COUNTERS = _Counters()
# LRU bound: a long-lived multi-tenant service must not accumulate XLA
# executables forever as tenants rotate through shapes. 64 runners is an
# order of magnitude above any one workload's live set (a grid is a few
# groups; a tenant fleet a few dozen); callers holding an evicted runner
# keep using it — eviction only drops the SHARED reference.
_MAX_RUNNERS = 64

_RunnerKey = Tuple  # (engine, M̃, option, buf_len, epochs, drop_prob,
#                     mesh fingerprint, objective static key,
#                     per-data-leaf (shape, dtype), (body, kernel mode))


def _kernel_mode_key(fused: bool) -> Tuple[str, str]:
    """The cache-key facet for the engine body and its RESOLVED kernel
    lowering: ("vmap", the `svrg_update` mode the inner update traces
    with) or ("fused", the megakernel mode). Resolving at key time means
    flipping ``REPRO_KERNEL_MODE`` mid-process can never serve a runner
    built for the other lowering."""
    if fused:
        return ("fused", fused_sweep_mode())
    return ("vmap", kernel_mode())


def runner_key(engine: str, *, group_epochs: int, total: int, option: int,
               buf_len: int, drop_prob: float, mesh: Optional[Mesh],
               obj, fused: bool = False) -> _RunnerKey:
    """Everything that determines the compiled program. The objective's data
    enters the runner as arguments, so only its SHAPES/DTYPES are keyed
    (plus `obj.runner_static_key()`, the static config its pure methods
    close over) — two tenants sweeping same-shape datasets of one objective
    class share one compiled program."""
    data_sig = tuple((tuple(a.shape), str(jax.numpy.asarray(a).dtype))
                     for a in obj.data_args())
    return (engine, int(total), int(option), int(buf_len), int(group_epochs),
            float(drop_prob), mesh_fingerprint(mesh),
            obj.runner_static_key(), data_sig, _kernel_mode_key(fused))


def _counted(fn):
    """Increment the compile counter at trace time: the wrapper body runs
    exactly once per jit (re)trace, never on a cached execution. Tracing
    happens when the cached runner is CALLED (no lock held), so taking
    _LOCK here cannot deadlock with `get_group_runner`."""
    def traced(*args):
        if getattr(_TLS, "uncounted", False):
            return fn(*args)
        with _LOCK:
            _credit("compiles")
        # trace-time host Python on the dispatching thread: the open
        # dispatch/execute span group (if any) gets the attribution; the
        # tracer's lock is a leaf, so holding no cache lock here matters
        _tracer().annotate(compiled=True)
        # same-thread hook: lets the performance ledger attribute the
        # wall time of the dispatch in flight to compilation
        _ledger.note_compile()
        return fn(*args)
    return traced


def get_group_runner(engine: str, *, group_epochs: int, total: int,
                     option: int, buf_len: int, drop_prob: float,
                     mesh: Optional[Mesh], obj, fused: bool = False):
    """The jitted runner for one (engine, M̃, option, buf_len, …) group,
    built at most once per key. ``fused=True`` keys and builds the Pallas
    sweep-epoch megakernel body instead of the vmap body.

    The returned callable takes ``(*obj.data_args(), *row_args)`` with
    every row array row-leading; under a mesh it is shard_mapped over the
    `data` axis (data args replicated) before jitting — see
    `repro.core.sweep._shard_group_fn` for the bit-exactness argument. The
    body closes over ``obj``'s pure methods, but the key carries only its
    `runner_static_key()` — any same-key instance's data can run through a
    runner another instance built.
    """
    key = runner_key(engine, group_epochs=group_epochs, total=total,
                     option=option, buf_len=buf_len, drop_prob=drop_prob,
                     mesh=mesh, obj=obj, fused=fused)
    num_data = len(obj.data_args())
    with _LOCK:
        runner = _RUNNERS.get(key)
        if runner is not None:
            _credit("hits")
            _tracer().annotate(cache="hit")
            _RUNNERS.move_to_end(key)            # LRU touch
            return runner
        _credit("misses")
        _tracer().annotate(cache="miss")
        fn, num_row = _sweep._group_fn(engine, obj=obj, num_data=num_data,
                                       epochs=group_epochs,
                                       total=total, buf_len=buf_len,
                                       option=option, drop_prob=drop_prob,
                                       fused=fused)
        if mesh is not None:
            fn = _sweep._shard_group_fn(fn, mesh, num_data, num_row)
        runner = jax.jit(_counted(fn))
        _RUNNERS[key] = runner
        while len(_RUNNERS) > _MAX_RUNNERS:
            _RUNNERS.popitem(last=False)         # evict least recently used
        return runner


def cache_stats() -> CacheStats:
    """Current hit/miss/compile counters (a frozen snapshot)."""
    with _LOCK:
        return CacheStats(hits=_COUNTERS.hits, misses=_COUNTERS.misses,
                          compiles=_COUNTERS.compiles)


def cache_size() -> int:
    with _LOCK:
        return len(_RUNNERS)


def clear_cache(reset_stats: bool = True) -> None:
    """Drop every cached runner (tests; or to release executables)."""
    with _LOCK:
        _RUNNERS.clear()
        if reset_stats:
            _COUNTERS.hits = _COUNTERS.misses = _COUNTERS.compiles = 0


def set_cache_limit(max_runners: int) -> int:
    """Set the LRU bound on cached runners; returns the previous bound.
    Deployments with many concurrent shapes raise it; tests shrink it."""
    global _MAX_RUNNERS
    if max_runners < 1:
        raise ValueError(f"cache limit must be >= 1, got {max_runners}")
    with _LOCK:
        prev, _MAX_RUNNERS = _MAX_RUNNERS, max_runners
        while len(_RUNNERS) > _MAX_RUNNERS:
            _RUNNERS.popitem(last=False)
    return prev
