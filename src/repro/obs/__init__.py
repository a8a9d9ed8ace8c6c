"""repro.obs — observability for the sweep stack.

Four stdlib-only pieces plus two numeric ones:

  * `repro.obs.trace` — the request-lifecycle flight recorder: bounded
    ring buffer of monotonic-clock span trees, one trace id per request,
    threaded submit -> plan -> coalesce -> pad -> dispatch -> execute ->
    demux -> result. Served at ``GET /trace``.
  * `repro.obs.metrics` — cumulative histograms (flush/request latency,
    rows-per-flush, pad-factor) the service records on every flush.
  * `repro.obs.prometheus` — text-exposition rendering of the existing
    ``/stats`` snapshot dict + the histograms, served at ``GET /metrics``.
  * `repro.obs.progress` — bounded live-progress bus: per-slice loss
    events published from ``run_job`` slice boundaries and completed
    flushes, consumed via ``GET /watch`` with cursor-based resume.
  * `repro.obs.telemetry` — opt-in per-row realized-staleness and
    update-norm series, recomputed OUTSIDE the jitted group fn from
    already-returned arrays (imports jax; import it explicitly, never
    from this package root, so the tracer stays importable in the
    stdlib-only repro-lint lane).
  * `repro.obs.watchdog` — divergence watchdog (imports numpy; import it
    explicitly for the same reason as telemetry).

House rule (repro-lint RL006): none of these APIs may be called inside a
``*_core`` jitted scope or a ``kernels/**/kernel.py`` module —
observability brackets compiled programs, it never runs inside them.
"""
from repro.obs.metrics import Histogram, ServiceHistograms
from repro.obs.progress import (
    ProgressBus,
    ProgressEvent,
    disable_progress,
    enable_progress,
    progress_bus,
    progress_enabled,
)
from repro.obs.trace import (
    Span,
    Tracer,
    disable_tracing,
    enable_tracing,
    tracer,
)

__all__ = [
    "Histogram",
    "ServiceHistograms",
    "ProgressBus",
    "ProgressEvent",
    "Span",
    "Tracer",
    "disable_progress",
    "disable_tracing",
    "enable_progress",
    "enable_tracing",
    "progress_bus",
    "progress_enabled",
    "tracer",
]
