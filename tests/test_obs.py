"""Observability suite: flight recorder, Prometheus exposition, telemetry.

Pins the PR's acceptance contracts:

  * TRACE COMPLETENESS — every HTTP request's full span tree (submit →
    plan → coalesce → pad → dispatch → execute → demux → result) is
    retrievable at ``GET /trace?id=...`` using the ``X-Trace-Id`` the
    submit response echoed, with cache hit/miss + engine-mode attribution
    on the dispatch spans.
  * EXPOSITION VALIDITY — ``GET /metrics`` parses as Prometheus text
    format 0.0.4 and the histogram series keep the cumulative-bucket
    invariants (``le="+Inf"`` == ``_count``, buckets non-decreasing).
  * BIT-SAFETY — results with ``SweepSpec.telemetry`` on are bit-identical
    to runs with it off (telemetry is recomputed OUTSIDE jit; the flag is
    deliberately absent from the group key, so on/off share one compiled
    program), and the staleness series match the engines' delay schedule
    in closed form.
  * LIVENESS — ``/healthz`` turns 503 once the flush daemon's heartbeat
    stalls (wedged dispatch) or its thread dies, and recovers to 200.
"""
import dataclasses
import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core import LogisticRegression, SweepSpec, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.obs import Histogram, ServiceHistograms, Tracer
from repro.obs import prometheus as obs_prometheus
from repro.obs import telemetry as obs_telemetry
from repro.obs.trace import disable_tracing, enable_tracing, tracer
from repro.server import FlushPolicy, SweepClient, SweepServer
from repro.server.http import result_from_dict, result_to_dict
from repro.service import SweepService


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _specs(seeds, **over):
    base = dict(scheme="inconsistent", step_size=0.5, tau=3, num_threads=4,
                inner_steps=25)
    base.update(over)
    return [SweepSpec(seed=s, **base) for s in seeds]


# ------------------------------------------------------------------ tracer
def test_tracer_disabled_is_total_noop():
    tr = Tracer()
    assert tr.new_trace() == ""
    with tr.span("", "submit"):
        with tr.span_active("execute"):
            tr.annotate(cache="hit")
    tr.record_error("", RuntimeError("boom"))
    assert tr.recent() == []
    assert tr.get("") is None
    assert tr.last_error() is None


def test_tracer_span_tree_parenting_and_tags():
    tr = Tracer()
    tr.enable()
    tid = tr.new_trace()
    with tr.span(tid, "submit", rows=2):
        with tr.span(tid, "plan", parent_name="submit"):
            pass
    # a later phase can name a CLOSED parent (the flush path does)
    with tr.span_all([tid, "", "t-unknown"], "coalesce",
                     parent_name="submit"):
        # layers that never see trace ids attach to the open group
        with tr.span_active("execute", mode="vmap"):
            tr.annotate(cache="hit")
    dump = tr.get(tid)
    by_name = {s["name"]: s for s in dump["spans"]}
    assert set(by_name) == {"submit", "plan", "coalesce", "execute"}
    assert by_name["submit"]["parent_id"] is None
    assert by_name["plan"]["parent_id"] == by_name["submit"]["span_id"]
    assert by_name["coalesce"]["parent_id"] == by_name["submit"]["span_id"]
    assert by_name["execute"]["parent_id"] == by_name["coalesce"]["span_id"]
    assert by_name["execute"]["tags"] == {"mode": "vmap", "cache": "hit"}
    assert all(s["duration_ms"] is not None for s in dump["spans"])
    assert json.loads(json.dumps(dump)) == dump          # JSON-safe


def test_tracer_bounds_and_last_error_survive_eviction():
    tr = Tracer(max_traces=2, max_spans=3)
    tr.enable()
    t1 = tr.new_trace()
    with tr.span(t1, "submit"):
        pass
    tr.record_error(t1, RuntimeError("boom"))
    t2, t3 = tr.new_trace(), tr.new_trace()
    assert tr.get(t1) is None                 # evicted by the ring buffer
    err = tr.last_error()
    assert err["trace_id"] == t1 and "boom" in err["error"]
    assert [s["name"] for s in err["spans"]] == ["submit", "error"]
    with tr.span(t2, "a"), tr.span(t2, "b"), tr.span(t2, "c"):
        pass
    with tr.span(t2, "d"):                    # over max_spans: dropped
        pass
    assert [s["name"] for s in tr.get(t2)["spans"]] == ["a", "b", "c"]
    assert [r["trace_id"] for r in tr.recent()] == [t3, t2]
    tr.disable(clear=True)
    assert tr.recent() == [] and tr.last_error() is None


def test_service_records_complete_span_chain(obj):
    enable_tracing()
    try:
        svc = SweepService(obj, epochs=2)
        rid = svc.submit(_specs([1, 2]), tenant="team-a")
        svc.flush()
        svc.result(rid)
        tid = svc.trace_id(rid)
        assert tid
        dump = tracer().get(tid)
        names = [s["name"] for s in dump["spans"]]
        # no width policy on a bare service -> no pad span
        assert set(names) == {"submit", "plan", "coalesce", "dispatch",
                              "execute", "fetch", "demux", "result"}
        by_name = {s["name"]: s for s in dump["spans"]}
        assert (by_name["fetch"]["parent_id"]
                == by_name["execute"]["span_id"])
        assert by_name["submit"]["tags"]["tenant"] == "team-a"
        assert by_name["submit"]["tags"]["request_id"] == rid
        assert by_name["dispatch"]["tags"]["cache"] in ("hit", "miss")
        assert by_name["execute"]["tags"]["engine_mode"] in ("vmap", "fused")
        # one flush latency + one request latency + rows + pad factor
        for h in svc.histograms.as_dict().values():
            assert h.snapshot()[2] == 1
    finally:
        disable_tracing(clear=True)


def test_untraced_service_mints_no_ids(obj):
    svc = SweepService(obj, epochs=1)
    rid = svc.submit(_specs([3]))
    svc.flush()
    svc.result(rid)
    assert svc.trace_id(rid) == ""


# -------------------------------------------------------------- histograms
def test_histogram_cumulative_bucket_semantics():
    h = Histogram((0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    cumulative, total, count = h.snapshot()
    assert cumulative == [(0.1, 1), (1.0, 2)]
    assert count == 3
    assert total == pytest.approx(5.55)


_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"\})? '
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|NaN|[+-]Inf)$")
_PROM_TYPE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* histogram$")


def _assert_parses_as_prometheus(text):
    assert text.endswith("\n")
    lines = text.rstrip("\n").split("\n")
    for line in lines:
        if line.startswith("#"):
            assert _PROM_TYPE.match(line), line
        else:
            assert _PROM_LINE.match(line), line
    return lines


def test_prometheus_render_gauges_labels_and_histograms():
    snapshot = {
        "service": {"flushes": 3, "cache_hit_rate": 0.5, "note": "skip-me"},
        "tenants": {"team-a": {"rows_submitted": 128}},
        "daemon": {"last_error": None, "running": True},
    }
    hists = ServiceHistograms()
    hists.flush_latency_seconds.observe(0.004)
    hists.flush_latency_seconds.observe(12.0)
    text = obs_prometheus.render(snapshot, histograms=hists.as_dict())
    lines = _assert_parses_as_prometheus(text)
    assert "repro_service_flushes 3" in lines
    assert "repro_service_cache_hit_rate 0.5" in lines
    assert 'repro_tenants_rows_submitted{tenant="team-a"} 128' in lines
    assert "repro_daemon_running 1" in lines
    assert not any("skip-me" in ln or "note" in ln for ln in lines)
    assert 'repro_flush_latency_seconds_bucket{le="+Inf"} 2' in lines
    assert "repro_flush_latency_seconds_count 2" in lines
    # cumulative buckets are non-decreasing in bound order
    buckets = [int(ln.split()[-1]) for ln in lines
               if ln.startswith("repro_flush_latency_seconds_bucket")]
    assert buckets == sorted(buckets)


# --------------------------------------------------------------- telemetry
def test_fixed_delay_staleness_matches_closed_form(obj):
    """delay_kind="fixed" draws delay d_m = min(m, τ) deterministically, so
    the realized-staleness series has a closed form independent of the
    replay code under test."""
    tau, total, epochs = 3, 100, 3
    specs = _specs([1], delay_kind="fixed", telemetry=True)
    res = run_sweep(obj, epochs, specs)
    tel = res.telemetry
    expect = np.minimum(np.arange(total), tau).astype(np.float64)
    assert tel.rows.tolist() == [True]
    assert tel.staleness_max[0] == tau
    assert tel.staleness_mean[0] == pytest.approx(expect.mean())
    assert tel.staleness_var[0] == pytest.approx(expect.var())
    np.testing.assert_allclose(tel.staleness_per_epoch[0],
                               np.full(epochs, expect.mean()))
    # update-norm and loss-delta come from the returned arrays directly
    w0 = obj.init_flat()
    assert tel.update_norm[0] == pytest.approx(float(np.linalg.norm(
        np.asarray(res.final_w[0], np.float64) - np.asarray(w0, np.float64))))
    hist64 = np.asarray(res.histories[0], np.float64)
    np.testing.assert_allclose(tel.loss_delta[0], hist64[1:] - hist64[:-1])


def test_zero_and_uniform_delay_staleness_properties(obj):
    specs = [SweepSpec(algo="svrg", step_size=0.5, num_threads=1,
                       inner_steps=30, seed=2, telemetry=True),
             SweepSpec(scheme="inconsistent", step_size=0.5, tau=5,
                       num_threads=4, inner_steps=25, seed=3,
                       delay_kind="uniform", telemetry=True),
             SweepSpec(scheme="inconsistent", step_size=0.5, tau=5,
                       num_threads=4, inner_steps=25, seed=4)]
    res = run_sweep(obj, 2, specs)
    tel = res.telemetry
    assert tel.rows.tolist() == [True, True, False]
    # svrg has no stale reads: the whole staleness series is zero
    assert tel.staleness_max[0] == 0 and tel.staleness_mean[0] == 0.0
    # uniform draws are bounded by τ and not degenerate
    assert 0 < tel.staleness_mean[1] < 5
    assert 0 < tel.staleness_max[1] <= 5
    # un-flagged rows carry zeros everywhere
    assert tel.staleness_mean[2] == 0.0 and tel.update_norm[2] == 0.0
    assert not tel.loss_delta[2].any()
    # the replay is deterministic: same seed, same series
    again = run_sweep(obj, 2, specs).telemetry
    for name in tel._fields:
        np.testing.assert_array_equal(getattr(tel, name),
                                      getattr(again, name))


def test_telemetry_flag_never_changes_bits(obj):
    """Acceptance: telemetry on/off is bit-identical — the flag is not in
    the group key and the compiled program never sees it."""
    specs_on = _specs([5, 6], delay_kind="uniform", telemetry=True)
    specs_off = [dataclasses.replace(s, telemetry=False) for s in specs_on]
    on, off = run_sweep(obj, 3, specs_on), run_sweep(obj, 3, specs_off)
    np.testing.assert_array_equal(on.histories, off.histories)
    np.testing.assert_array_equal(on.final_w, off.final_w)
    np.testing.assert_array_equal(on.effective_passes, off.effective_passes)
    np.testing.assert_array_equal(on.total_updates, off.total_updates)
    assert off.telemetry is None and on.telemetry is not None


def test_telemetry_round_trips_through_wire_codec(obj):
    res = run_sweep(obj, 2, _specs([7], delay_kind="fixed", telemetry=True))
    payload = json.loads(json.dumps(result_to_dict(0, res)))
    back = result_from_dict(payload)
    for name in res.telemetry._fields:
        got, want = getattr(back.telemetry, name), getattr(res.telemetry,
                                                           name)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # absent telemetry stays absent
    plain = run_sweep(obj, 2, _specs([7], delay_kind="fixed"))
    assert result_from_dict(
        json.loads(json.dumps(result_to_dict(0, plain)))).telemetry is None


# ------------------------------------------------------------------- HTTP
@pytest.fixture()
def traced_server(obj):
    enable_tracing()
    svc = SweepService(obj, epochs=1, max_results=8)
    server = SweepServer(svc, policy=FlushPolicy(max_rows=64,
                                                 max_delay_ms=20)).start()
    try:
        yield svc, server, SweepClient(server.url, poll_s=5.0)
    finally:
        server.stop()
        disable_tracing(clear=True)


def test_http_request_has_complete_retrievable_span_tree(traced_server, obj):
    svc, server, client = traced_server
    body = json.dumps({
        "specs": [dataclasses.asdict(s) for s in _specs([1, 2])],
        "tenant": "team-a"}).encode()
    req = urllib.request.Request(server.url + "/submit", data=body,
                                 method="POST",
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
        header_tid = resp.getheader("X-Trace-Id")
    assert payload["trace_id"] == header_tid and header_tid
    client.result(payload["request_id"], timeout=30)
    dump = client.trace(header_tid)
    names = {s["name"] for s in dump["spans"]}
    # the daemon installs a width policy, so the pad phase appears too
    assert names == {"submit", "plan", "coalesce", "pad", "dispatch",
                     "execute", "fetch", "demux", "result", "encode"}
    recent = client.trace()
    assert recent["enabled"] is True
    assert header_tid in [t["trace_id"] for t in recent["recent"]]
    with pytest.raises(Exception):          # unknown id -> 404
        client.trace("t-nope")


def test_http_result_has_fetch_under_execute_and_encode_after_result(
        traced_server, obj):
    """The device wait and the host copy of a dispatch are separate spans
    (`fetch` under `execute`), and the HTTP tier's answer is its own span
    (`encode`, under `submit`) after the last `result` wait."""
    svc, server, client = traced_server
    rid = client.submit(_specs([4]), tenant="team-c")
    client.result(rid, timeout=30)
    spans = client.trace(svc.trace_id(rid))["spans"]
    by_id = {s["span_id"]: s for s in spans}
    fetch = [s for s in spans if s["name"] == "fetch"]
    assert fetch and all(by_id[s["parent_id"]]["name"] == "execute"
                         for s in fetch)
    execute = by_id[fetch[0]["parent_id"]]
    assert execute["start_s"] <= fetch[0]["start_s"]
    encode = [s for s in spans if s["name"] == "encode"]
    assert len(encode) == 1
    assert by_id[encode[0]["parent_id"]]["name"] == "submit"
    last_result = max((s for s in spans if s["name"] == "result"),
                      key=lambda s: s["start_s"])
    assert encode[0]["start_s"] >= (last_result["start_s"]
                                    + last_result["duration_ms"] / 1000.0)


def test_http_metrics_endpoint_is_valid_prometheus(traced_server, obj):
    svc, server, client = traced_server
    rid = client.submit(_specs([3]), tenant="team-b")
    client.result(rid, timeout=30)
    req = urllib.request.Request(server.url + "/metrics")
    with urllib.request.urlopen(req, timeout=10) as resp:
        ctype = resp.getheader("Content-Type")
        text = resp.read().decode()
    assert "version=0.0.4" in ctype
    lines = _assert_parses_as_prometheus(text)
    joined = "\n".join(lines)
    assert "repro_service_flushes " in joined
    assert "repro_queue_depth_requests " in joined
    assert 'repro_tenants_rows_completed{tenant="team-b"} 1' in lines
    assert "repro_daemon_heartbeat_age_s " in joined
    # histogram invariant: +Inf bucket equals _count, per series
    for name in ("repro_flush_latency_seconds", "repro_request_latency_seconds",
                 "repro_rows_per_flush", "repro_pad_factor"):
        inf = [ln for ln in lines if ln.startswith(f'{name}_bucket{{le="+Inf"}}')]
        count = [ln for ln in lines if ln.startswith(f"{name}_count")]
        assert len(inf) == 1 and len(count) == 1
        assert inf[0].split()[-1] == count[0].split()[-1]
    _assert_parses_as_prometheus(client.metrics())


def test_healthz_reports_stalled_daemon(obj):
    """/healthz flips to 503 while the flush thread is wedged inside a
    dispatch (heartbeat older than the policy bound) and recovers after."""
    svc = SweepService(obj, epochs=1)
    release = threading.Event()
    real_flush = svc.flush

    def wedged_flush(selector=None):
        release.wait(timeout=10.0)
        return real_flush(selector)

    server = SweepServer(svc, policy=FlushPolicy(
        max_rows=1, max_delay_ms=5, heartbeat_stall_s=0.4)).start()
    client = SweepClient(server.url, poll_s=2.0)
    try:
        assert client.healthz()["status"] == "ok"
        svc.flush = wedged_flush
        client.submit(_specs([9]))            # size trigger -> wedged flush
        deadline = time.monotonic() + 5.0
        status, payload = 200, {}
        while time.monotonic() < deadline:
            try:
                payload = client.healthz()
                status = 200
            except Exception as e:            # ServerError carries payload
                status, payload = e.status, e.payload
                break
            time.sleep(0.05)
        assert status == 503, payload
        assert payload["status"] == "stalled"
        assert payload["heartbeat_age_s"] > 0.4
        release.set()
        svc.flush = real_flush
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            try:
                assert client.healthz()["status"] == "ok"
                break
            except Exception:
                time.sleep(0.05)
        else:
            pytest.fail("healthz never recovered after the wedge released")
    finally:
        release.set()
        svc.flush = real_flush
        server.stop()


def test_healthz_reports_dead_daemon_thread(obj):
    svc = SweepService(obj, epochs=1)
    server = SweepServer(svc, policy=FlushPolicy(max_delay_ms=10)).start()
    client = SweepClient(server.url)
    try:
        assert client.healthz()["daemon_running"] is True
        # kill the flush thread out from under the server: liveness, not
        # just construction, must back daemon_running
        server.daemon._stop.set()
        server.daemon._wake.set()
        server.daemon._thread.join(5.0)
        try:
            payload = client.healthz()
            status = 200
        except Exception as e:
            status, payload = e.status, e.payload
        assert status == 503 and payload["status"] == "stalled"
        assert payload["daemon_running"] is False
    finally:
        server.stop()               # joins the already-dead flush thread
