"""RL006 — observability brackets compiled programs, never enters them.

The obs contract (repro.obs): tracing spans wrap runner *calls*, metrics
observe on the host after dispatch, and telemetry is recomputed from
already-returned arrays. A timing or tracing call INSIDE a jitted scope
is broken either way it lands: as a traced no-op it silently measures
nothing (host Python runs once, at trace time, so the "span" would time
the trace, not the execution), and anything that does escape to the host
(callbacks) perturbs the compiled program the cache key cannot see —
which is exactly how "telemetry changed my bits" bugs are born.

Flagged inside any function named ``*_core`` (the house convention for
jit-traced numeric bodies, nested functions included) and anywhere in a
``kernels/**/kernel.py`` module:

  * wall-clock reads: ``time.monotonic`` / ``perf_counter`` / ``time`` /
    ``process_time`` / ``thread_time`` (+ ``_ns`` variants);
  * the tracer API: ``tracer()``, ``enable_tracing``, ``disable_tracing``
    and any ``.span`` / ``.span_all`` / ``.span_active`` / ``.annotate``
    / ``.new_trace`` / ``.record_error`` method call;
  * histogram recording: any ``.observe(...)`` call;
  * the live-progress bus: ``progress_bus`` / ``ProgressBus`` /
    ``enable_progress`` / ``disable_progress`` and ``.publish`` /
    ``.watch`` method calls;
  * the divergence watchdog: ``Watchdog`` / ``enforce_group`` /
    ``first_bad_epoch`` (host-side numpy inspection by contract);
  * any reference into ``repro.obs`` (aliased module access included).

``jax.named_scope`` is the one instrument allowed there: it names the
ops traced inside it in their metadata (a device trace's ``tf_op``
path) and adds no op and no host call, so it cannot change the program.

Fix: move the measurement to the call site that dispatches the jitted
function (see `repro.core.sweep._dispatch_group` for the pattern), or
recompute the quantity outside jit like `repro.obs.telemetry` does.
"""
from __future__ import annotations

import ast
from pathlib import PurePath
from typing import List

from repro.analysis.astutil import FUNC_NODES, call_name, dotted_name
from repro.analysis.diagnostics import Diagnostic

_TIMING_CALLS = {
    f"time.{fn}{suffix}"
    for fn in ("monotonic", "perf_counter", "time", "process_time",
               "thread_time")
    for suffix in ("", "_ns")
}
_TRACER_CALLS = {"tracer", "enable_tracing", "disable_tracing"}
# live-obs entry points: progress bus and watchdog — both host-side by
# contract, so any call inside a jitted scope is a bug
_PROGRESS_CALLS = {"progress_bus", "ProgressBus", "enable_progress",
                   "disable_progress"}
_WATCHDOG_CALLS = {"Watchdog", "enforce_group", "first_bad_epoch"}
_OBS_METHODS = {"span", "span_all", "span_active", "annotate", "new_trace",
                "record_error", "observe", "publish", "watch"}


def _kernel_module(path: str) -> bool:
    p = PurePath(path)
    return p.name == "kernel.py" and "kernels" in p.parts


def _why(node: ast.Call) -> str:
    """Non-empty reason when this call is an obs/timing escape."""
    name = call_name(node) or ""
    if name in _TIMING_CALLS:
        return f"wall-clock read `{name}(...)`"
    last = name.rsplit(".", 1)[-1]
    if last in _TRACER_CALLS:
        return f"tracer API call `{name}(...)`"
    if last in _PROGRESS_CALLS:
        return f"progress-bus call `{name}(...)`"
    if last in _WATCHDOG_CALLS:
        return f"watchdog call `{name}(...)`"
    if "." in name and last in _OBS_METHODS:
        return f"obs recording call `{name}(...)`"
    return ""


def _scan(path: str, scope: ast.AST, where: str,
          out: List[Diagnostic], seen: set) -> None:
    for node in ast.walk(scope):
        why = ""
        if isinstance(node, ast.Call):
            why = _why(node)
        elif isinstance(node, ast.Attribute):
            name = dotted_name(node) or ""
            if name.startswith("repro.obs") or name.startswith("obs."):
                why = f"reference into repro.obs (`{name}`)"
        if why and (node.lineno, why) not in seen:
            seen.add((node.lineno, why))
            out.append(Diagnostic(
                path, node.lineno, "RL006",
                f"{why} inside {where} — observability must bracket the "
                "compiled program, not run inside it (time/record at the "
                "dispatch site, or recompute outside jit like "
                "repro.obs.telemetry)"))


def check(path: str, tree: ast.AST, source: str) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    seen: set = set()
    if _kernel_module(path):
        _scan(path, tree, "a Pallas kernel module", out, seen)
    for node in ast.walk(tree):
        if isinstance(node, FUNC_NODES) and node.name.endswith("_core"):
            _scan(path, node, f"jitted scope `{node.name}`", out, seen)
    return out
