"""Observability tax: the flight recorder must be free when off and
near-free when on.

Two phases, both on a warm runner cache (the regime servers live in):

  * HTTP SMOKE — tracing enabled, a real `SweepServer` with the flush
    daemon, two tenants submit over the wire. Asserts the full span chain
    (submit → plan → coalesce → pad → dispatch → execute → demux) is
    retrievable from ``/trace`` by the ``trace_id`` the submit response
    echoes, and that ``/metrics`` scrapes as Prometheus 0.0.4 text with
    the four service histograms populated.
  * OVERHEAD — alternating tracer-off / tracer-on rounds through the
    in-process `SweepService` (same specs, same widths, zero compiles),
    min-of-rounds wall time per mode. Acceptance: warm tracer-on overhead
    ``(on - off) / off <= 5%``. The disabled path is a single bool check,
    and the enabled path only brackets host-side stages — neither may show
    up against the compiled program's runtime.
  * FEATURES — per-feature attribution on one all-off baseline service:
    each round flips exactly one of tracer / histograms / progress /
    telemetry on and prices its warm delta against the all-off round.
    Acceptance: the live-progress bus (the PR-10 feature that recomputes
    per-row losses and publishes slice events) stays ``<= 5%`` over the
    all-off baseline with zero recompiles — enabling it must never reach
    a group key.

Writes ``BENCH_obs_overhead.json`` (keys: ``tracer_off_s``,
``tracer_on_s``, ``overhead_frac``, ``http_smoke``, ``features``);
``--quick`` is the CI `obs-smoke` configuration.
"""
from __future__ import annotations

import json
import re
import sys
import time
import urllib.request

from benchmarks.artifacts import write_bench_json
from repro.core import LogisticRegression, SweepSpec
from repro.data.libsvm import make_synthetic_libsvm
from repro.obs.progress import disable_progress, enable_progress
from repro.obs.trace import disable_tracing, enable_tracing
from repro.server import FlushPolicy, SweepClient, SweepServer
from repro.service import SweepService, cache_stats

ACCEPT_OVERHEAD_FRAC = 0.05
ROWS_PER_REQUEST = 4
# the switchable obs features, each priced in isolation against all-off
# ("telemetry" rides the SweepSpec flag, the others are process/service
# toggles — see _set_features)
FEATURES = ("tracer", "histograms", "progress", "telemetry")

# every line of a 0.0.4 text exposition: comment, blank, or sample
_PROM_LINE = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})?\s[^\s]+)$")

# span names every traced HTTP request must produce (pad appears because
# the daemon installs a WidthRegistry; execute carries the engine tags)
_EXPECTED_SPANS = {"submit", "plan", "coalesce", "pad", "dispatch",
                   "execute", "fetch", "demux", "encode"}


def _specs(base_seed: int, rows: int = ROWS_PER_REQUEST,
           telemetry: bool = False):
    return [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=base_seed + c,
                      telemetry=telemetry)
            for c in range(rows)]


def _submit_raw(url: str, specs, tenant: str) -> dict:
    """POST /submit and keep the whole response body — the stock client
    returns only request_id, but the smoke needs the echoed trace_id."""
    from repro.server.http import spec_to_dict
    body = {"specs": [spec_to_dict(s) for s in specs], "tenant": tenant}
    req = urllib.request.Request(
        url + "/submit", data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        payload = json.loads(resp.read().decode())
        payload["x_trace_id"] = resp.headers.get("X-Trace-Id", "")
    return payload


def http_smoke(obj, epochs: int) -> dict:
    """Traced end-to-end pass over the wire; returns what it verified."""
    enable_tracing()
    try:
        svc = SweepService(obj, epochs=epochs)
        policy = FlushPolicy(max_rows=2 * ROWS_PER_REQUEST, max_delay_ms=20)
        with SweepServer(svc, policy=policy) as server:
            client = SweepClient(server.url, poll_s=5.0)
            subs = [_submit_raw(server.url, _specs(100 * (t + 1)), f"t{t}")
                    for t in range(2)]
            for sub in subs:
                client.result(sub["request_id"], timeout=600)

            span_names = set()
            for sub in subs:
                tid = sub["trace_id"]
                if sub["x_trace_id"] != tid:
                    raise AssertionError(
                        f"X-Trace-Id header {sub['x_trace_id']!r} != body "
                        f"trace_id {tid!r}")
                tree = client.trace(tid)
                names = {s["name"] for s in tree["spans"]}
                missing = _EXPECTED_SPANS - names
                if missing:
                    raise AssertionError(
                        f"trace {tid} missing spans {sorted(missing)} "
                        f"(got {sorted(names)})")
                span_names |= names

            text = client.metrics()
            bad = [ln for ln in text.splitlines()
                   if ln and not _PROM_LINE.match(ln)]
            if bad:
                raise AssertionError(f"non-Prometheus lines: {bad[:3]}")
            for hist in ("repro_flush_latency_seconds",
                         "repro_request_latency_seconds",
                         "repro_rows_per_flush", "repro_pad_factor"):
                if f"{hist}_count" not in text:
                    raise AssertionError(f"histogram {hist} not exposed")
        return {"requests": len(subs), "spans": sorted(span_names),
                "metrics_lines": len(text.splitlines()), "ok": True}
    finally:
        disable_tracing(clear=True)


def _round(svc, base_seed: int, submits: int,
           telemetry: bool = False) -> float:
    """One warm closed-loop round: N submits, one flush, all results."""
    t0 = time.perf_counter()
    rids = [svc.submit(_specs(base_seed + 1000 * i, telemetry=telemetry))
            for i in range(submits)]
    svc.flush()
    for rid in rids:
        svc.result(rid)
    return time.perf_counter() - t0


def measure_overhead(obj, epochs: int, rounds: int, submits: int) -> dict:
    """Alternate tracer-off / tracer-on rounds on one warm service; the
    interleave cancels drift (thermal, GC) that back-to-back blocks bake
    into whichever mode runs second."""
    svc = SweepService(obj, epochs=epochs, max_results=4 * submits)
    _round(svc, base_seed=1, submits=submits)            # compile + warm
    base = cache_stats()

    off, on = [], []
    for r in range(rounds):
        disable_tracing(clear=True)
        off.append(_round(svc, 10_000 + 97 * r, submits))
        enable_tracing()
        try:
            on.append(_round(svc, 20_000 + 97 * r, submits))
        finally:
            disable_tracing(clear=True)

    compiles = cache_stats().since(base).compiles
    if compiles:
        raise AssertionError(
            f"measured rounds recompiled ({compiles} traces) — the "
            "telemetry/tracing flags must never reach the group key")
    tracer_off_s, tracer_on_s = min(off), min(on)
    return {
        "rounds": rounds, "submits_per_round": submits,
        "rows_per_round": submits * ROWS_PER_REQUEST,
        "tracer_off_s": tracer_off_s,
        "tracer_on_s": tracer_on_s,
        "off_rounds_s": off, "on_rounds_s": on,
        "overhead_frac": (tracer_on_s - tracer_off_s) / tracer_off_s,
        "compiles_measured": compiles,
    }


def _set_features(svc, enabled: frozenset) -> None:
    """Flip the process/service obs toggles to exactly ``enabled``
    ("telemetry" is per-spec, handled by the round itself)."""
    if "tracer" in enabled:
        enable_tracing()
    else:
        disable_tracing(clear=True)
    if "progress" in enabled:
        enable_progress()
    else:
        disable_progress(clear=True)
    svc.histograms.enabled = "histograms" in enabled


def measure_features(obj, epochs: int, rounds: int, submits: int) -> dict:
    """Per-feature warm deltas: one all-off baseline round per iteration,
    then one round per feature with exactly that feature on, interleaved
    so drift hits every mode equally. Min-of-rounds throughout."""
    svc = SweepService(obj, epochs=epochs, max_results=4 * submits)
    _set_features(svc, frozenset())
    _round(svc, base_seed=1, submits=submits)            # compile + warm
    _round(svc, base_seed=1, submits=submits, telemetry=True)  # warm too
    base = cache_stats()

    baseline = []
    rounds_by_feature = {f: [] for f in FEATURES}
    try:
        for r in range(rounds):
            _set_features(svc, frozenset())
            baseline.append(_round(svc, 30_000 + 971 * r, submits))
            for i, feat in enumerate(FEATURES):
                _set_features(svc, frozenset((feat,)))
                rounds_by_feature[feat].append(_round(
                    svc, 40_000 + 971 * r + 7 * i, submits,
                    telemetry=(feat == "telemetry")))
    finally:
        _set_features(svc, frozenset())
        svc.histograms.enabled = True        # restore the service default

    compiles = cache_stats().since(base).compiles
    if compiles:
        raise AssertionError(
            f"feature rounds recompiled ({compiles} traces) — obs toggles "
            "must never reach a group key")
    base_s = min(baseline)
    features = {
        feat: {
            "round_s": min(series),
            "delta_frac": (min(series) - base_s) / base_s,
        }
        for feat, series in rounds_by_feature.items()
    }
    progress_frac = features["progress"]["delta_frac"]
    if progress_frac > ACCEPT_OVERHEAD_FRAC:
        raise AssertionError(
            f"progress-bus warm rounds {progress_frac * 100:.1f}% slower "
            f"than all-off (acceptance: <= "
            f"{ACCEPT_OVERHEAD_FRAC * 100:.0f}%)")
    return {"baseline_s": base_s, "baseline_rounds_s": baseline,
            "compiles_measured": compiles, **features}


def run(quick: bool = False):
    ds = make_synthetic_libsvm("real-sim", seed=11,
                               scale=0.002 if quick else 0.01)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    epochs = 1 if quick else 2
    rounds = 3 if quick else 6
    submits = 2 if quick else 4

    smoke = http_smoke(obj, epochs)
    bench = measure_overhead(obj, epochs, rounds, submits)
    features = measure_features(obj, epochs, rounds, submits)

    out = {"dataset": "real-sim", "epochs": epochs, "http_smoke": smoke,
           "features": features}
    out.update(bench)
    # acceptance: the flight recorder may not tax the warm serving path
    # by more than 5% — its spans bracket host-side stages only
    if out["overhead_frac"] > ACCEPT_OVERHEAD_FRAC:
        raise AssertionError(
            f"tracer-on warm rounds {out['overhead_frac'] * 100:.1f}% "
            f"slower than tracer-off (acceptance: <= "
            f"{ACCEPT_OVERHEAD_FRAC * 100:.0f}%)")
    return out


def main(quick: bool = True):
    out = run(quick=quick)
    write_bench_json("obs_overhead", out)
    print("name,us_per_call,derived")
    print(f"obs_tracer_off,{out['tracer_off_s'] * 1e6:.0f},"
          f"min_of_{out['rounds']}_rounds")
    print(f"obs_tracer_on,{out['tracer_on_s'] * 1e6:.0f},"
          f"overhead_frac={out['overhead_frac']:.4f};"
          f"compiles={out['compiles_measured']}")
    print(f"obs_http_smoke,0,spans={'+'.join(out['http_smoke']['spans'])};"
          f"metrics_lines={out['http_smoke']['metrics_lines']}")
    for feat in FEATURES:
        entry = out["features"][feat]
        print(f"obs_feature_{feat},{entry['round_s'] * 1e6:.0f},"
              f"delta_frac={entry['delta_frac']:.4f}")


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
