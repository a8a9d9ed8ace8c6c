"""Validate every BENCH_*.json artifact before CI uploads it.

The bench jobs are self-gating two ways: benchmarks with a correctness
component (kernel_sweep parity, service_throughput warm-compile count)
raise inside ``main()``, and THIS checker catches the quieter failure mode
— a benchmark that "succeeded" but wrote an artifact downstream tooling
cannot consume. Every ``BENCH_*.json`` in the scanned directory must

  * parse as strict JSON (the writer turns inf/nan into strings; a raw
    ``Infinity`` literal here means someone bypassed
    `benchmarks.artifacts.write_bench_json`),
  * be a non-empty JSON object, and
  * carry the required keys registered below for its benchmark name —
    the stable schema downstream perf-trajectory tooling keys on.

Exit status is the gate: 0 all valid, 1 any violation (listed on stderr),
2 when no artifacts were found but some were expected (``--expect``).

Usage:  python -m benchmarks.check_artifacts [DIR] [--expect name ...]
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Tuple

# BENCH name -> top-level keys every artifact of that name must carry.
# Names absent here get only the parse/object checks (new benchmarks work
# out of the box; add their schema once a consumer depends on it).
REQUIRED_KEYS: Dict[str, Tuple[str, ...]] = {
    "kernel_sweep": ("backend", "fused_mode", "shapes"),
    "service_throughput": ("cold_s", "warm_s", "warm_cold_ratio",
                           "coalesced_speedup"),
    "server_latency": (),
    "table2_schemes": (),
    "table3_vs_hogwild": (),
    "frontier_stability": (),
    "nonconvex_frontier": (),
    "fig1_convergence": (),
    # obs-smoke lane: warm tracer-on vs tracer-off serving rounds plus the
    # traced HTTP smoke (span chain + Prometheus scrape) and per-feature
    # warm deltas (tracer/histograms/progress/telemetry)
    "obs_overhead": ("tracer_off_s", "tracer_on_s", "overhead_frac",
                     "http_smoke", "features"),
    # obs-smoke lane: live-progress stream + divergence watchdog over one
    # multi-group run_job
    "progress_ledger": ("progress", "watchdog"),
    # written by `python -m repro.analysis --json-out` in the repro-lint
    # CI lane; diagnostics must be [] for the lane to pass, but the
    # artifact records suppression counts for trend tooling either way
    "repro_lint": ("files", "diagnostics", "suppressions", "rules"),
}

# kernel_sweep is additionally checked per shape: these are the keys the
# roofline-vs-measured comparison needs (acceptance criterion: timings AND
# predicted intensity for >= 2 group shapes).
_KERNEL_SHAPE_KEYS = ("label", "rows", "inner_steps", "epochs", "vmap_s",
                      "fused_s", "measured_speedup", "parity", "roofline")


def _check_kernel_sweep(payload: dict) -> List[str]:
    errs = []
    shapes = payload.get("shapes")
    if not isinstance(shapes, list) or len(shapes) < 2:
        return [f"shapes: expected a list of >= 2 group shapes, "
                f"got {shapes!r:.80}"]
    for i, s in enumerate(shapes):
        missing = [k for k in _KERNEL_SHAPE_KEYS
                   if not isinstance(s, dict) or k not in s]
        if missing:
            errs.append(f"shapes[{i}]: missing keys {missing}")
        elif "intensity_headroom" not in s["roofline"]:
            errs.append(f"shapes[{i}].roofline: missing intensity_headroom")
    return errs


def check_file(path: str) -> List[str]:
    """All schema violations for one artifact (empty list = valid)."""
    name = os.path.basename(path)[len("BENCH_"):-len(".json")]
    try:
        with open(path) as fh:
            payload = json.load(fh, parse_constant=lambda c: (_ for _ in ())
                                .throw(ValueError(f"non-strict JSON: {c}")))
    except (ValueError, OSError) as e:
        return [f"unparseable: {e}"]
    if not isinstance(payload, dict) or not payload:
        return ["top level must be a non-empty JSON object"]
    errs = [f"missing required key {k!r}"
            for k in REQUIRED_KEYS.get(name, ()) if k not in payload]
    if name == "kernel_sweep" and not errs:
        errs += _check_kernel_sweep(payload)
    return errs


def main(argv: List[str]) -> int:
    # everything after --expect is a benchmark NAME, not the scan dir
    # (the old `not a.startswith("--")` filter misread the first expected
    # name as the positional directory)
    args = list(argv)
    expected: List[str] = []
    if "--expect" in args:
        i = args.index("--expect")
        expected = args[i + 1:]
        args = args[:i]
    directory = args[0] if args else os.environ.get("BENCH_DIR", ".")
    try:
        entries = os.listdir(directory)
    except OSError as e:
        print(f"FAIL cannot scan {directory}: {e}", file=sys.stderr)
        entries = []
    paths = sorted(p for p in entries
                   if p.startswith("BENCH_") and p.endswith(".json"))
    failures = 0
    for p in paths:
        errs = check_file(os.path.join(directory, p))
        if errs:
            failures += 1
            for e in errs:
                print(f"FAIL {p}: {e}", file=sys.stderr)
        else:
            print(f"ok   {p}")
    missing = [n for n in expected if f"BENCH_{n}.json" not in paths]
    for n in missing:
        print(f"FAIL expected artifact BENCH_{n}.json not found in "
              f"{directory}", file=sys.stderr)
    if not paths and expected:
        return 2
    return 1 if failures or missing else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
