"""HTTP front-end suite: the serving loop over a real socket.

An in-process `SweepServer` (ThreadingHTTPServer + flush daemon) driven by
`SweepClient` over loopback: submit → deadline-triggered flush → result.
Pins the acceptance contracts — HTTP-served results BIT-IDENTICAL to
in-process `run_sweep` for every tenant, 0 compiles on a warm same-shape
request, and the error mapping (400 bad spec, 404 unknown id, 410
evicted, 504 pending)."""
import dataclasses
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import LogisticRegression, SweepSpec, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.server import FairShare, FlushPolicy, SweepClient, SweepServer
from repro.server.http import result_from_dict, result_to_dict
from repro.service import ResultEvictedError, SweepService, cache_stats


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


@pytest.fixture()
def served(obj):
    """A started server (fast deadline flush) + client; stops after."""
    svc = SweepService(obj, epochs=1, max_results=8)
    server = SweepServer(svc, policy=FlushPolicy(max_rows=64,
                                                 max_delay_ms=25),
                         fairness=FairShare(quantum_rows=16)).start()
    try:
        yield svc, server, SweepClient(server.url, poll_s=5.0)
    finally:
        server.stop()


def _specs(seeds):
    return [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=s)
            for s in seeds]


def _assert_same(got, want):
    np.testing.assert_array_equal(got.histories, want.histories)
    np.testing.assert_array_equal(got.final_w, want.final_w)
    np.testing.assert_array_equal(got.effective_passes,
                                  want.effective_passes)
    np.testing.assert_array_equal(got.total_updates, want.total_updates)
    np.testing.assert_array_equal(got.epochs_per_row, want.epochs_per_row)
    assert got.specs == want.specs


# ------------------------------------------------------------ acceptance
def test_http_served_results_bit_identical_multi_tenant(served, obj):
    """Three tenants over HTTP; the daemon's deadline policy flushes once;
    each tenant's result is bit-identical to in-process run_sweep — and a
    second same-shape request costs 0 compiles (warm path)."""
    svc, server, client = served
    tenants = {"team-a": _specs([0, 1]),
               "team-b": _specs([2]),
               "team-c": [SweepSpec(algo="svrg", step_size=0.5,
                                    num_threads=1, inner_steps=30, seed=4)]}
    rids = {name: client.submit(specs, tenant=name, priority=i)
            for i, (name, specs) in enumerate(tenants.items())}
    for name, specs in tenants.items():
        _assert_same(client.result(rids[name], timeout=180),
                     run_sweep(obj, 1, specs))
    stats = svc.stats()
    assert stats.requests_completed == 3
    assert stats.rows_coalesced >= 3          # a+b shared a compiled group

    base = cache_stats()
    rid = client.submit(_specs([7, 8]), tenant="team-a")
    _assert_same(client.result(rid, timeout=180),
                 run_sweep(obj, 1, _specs([7, 8])))
    assert cache_stats().since(base).compiles == 0, \
        "warm same-shape HTTP request recompiled"


def test_healthz_stats_and_flush_endpoints(served):
    svc, server, client = served
    health = client.healthz()
    assert health["status"] == "ok" and health["daemon_running"]
    rid = client.submit(_specs([10]))
    done = client.flush()                     # operator escape hatch
    assert rid in done
    stats = client.stats()
    assert stats["service"]["requests_completed"] >= 1
    assert stats["queue"]["depth_requests"] == 0
    assert stats["tenants"]["default"]["rows_submitted"] == 1
    assert {"count", "p50_ms", "p95_ms", "max_ms"} <= \
        set(stats["flush_latency"])
    assert "daemon" in stats and "fairness" in stats


def test_error_mapping(served):
    svc, server, client = served
    with pytest.raises(KeyError):
        client.result(10_000, timeout=5)      # never existed: 404
    with pytest.raises(ValueError):
        client.submit([])                     # empty: 400
    with pytest.raises(ValueError):           # unknown field: 400
        client._call("POST", "/submit",
                     {"specs": [{"algo": "asysvrg", "nope": 1}]})
    with pytest.raises(ValueError):           # invalid spec: 400
        client.submit([SweepSpec(scheme="bogus")])
    # evicted: overflow the FIFO bound (max_results=8) then ask again
    rid0 = client.submit(_specs([20]))
    client.result(rid0, timeout=180)
    for i in range(8):
        client.sweep(_specs([21 + i]), timeout=180)
    with pytest.raises(ResultEvictedError):
        client.result(rid0, timeout=5)        # 410, typed error
    # pending: a quiet queue under an hour-long deadline never flushes
    server.daemon.policy = dataclasses.replace(server.daemon.policy,
                                               max_delay_ms=3_600_000)
    rid = client.submit(_specs([40]))
    with pytest.raises(TimeoutError):
        client.result(rid, timeout=1.0)       # 504 pending -> client timeout
    server.daemon.policy = dataclasses.replace(server.daemon.policy,
                                               max_delay_ms=25)


def test_http_refuses_a_spec_naming_engine_mode(served, obj):
    """A spec that names a field SweepSpec does not have (here the removed
    ``engine_mode``, which older clients send) gets a 400 naming it, and
    the server goes on serving."""
    svc, server, client = served
    spec = {**dataclasses.asdict(_specs([50])[0]), "engine_mode": "vmap"}
    req = urllib.request.Request(
        server.url + "/submit", method="POST",
        data=json.dumps({"specs": [spec]}).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req)
    assert err.value.code == 400
    assert "engine_mode" in err.value.read().decode()
    assert client.healthz()["status"] == "ok"
    _assert_same(client.sweep(_specs([51]), timeout=180),
                 run_sweep(obj, 1, _specs([51])))


def test_unknown_route_404(served):
    svc, server, client = served
    with urllib.request.urlopen(server.url + "/healthz") as resp:
        assert resp.status == 200
    try:
        urllib.request.urlopen(server.url + "/nope")
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_wire_codec_round_trips_bits(obj):
    """result -> JSON -> result is bitwise lossless (float32 histories,
    float64 passes, int64 counters) — the property HTTP bit-identity
    rests on."""
    res = run_sweep(obj, 1, _specs([0]))
    payload = json.loads(json.dumps(result_to_dict(7, res)))
    back = result_from_dict(payload)
    _assert_same(back, res)
    assert back.histories.dtype == np.float32
    assert back.effective_passes.dtype == np.float64
    assert back.total_updates.dtype == np.int64
    # diverged_rows: None round-trips as None, arrays as int64; payloads
    # from pre-watchdog servers (no key at all) decode too
    assert back.diverged_rows is None
    marked = res._replace(diverged_rows=np.asarray([2, -1], np.int64))
    wire = json.loads(json.dumps(result_to_dict(8, marked)))
    assert wire["diverged_rows"] == [2, -1]
    decoded = result_from_dict(wire)
    assert decoded.diverged_rows.dtype == np.int64
    np.testing.assert_array_equal(decoded.diverged_rows, [2, -1])
    del wire["diverged_rows"]
    assert result_from_dict(wire).diverged_rows is None


def test_submit_ticket_trace_id_round_trips(obj):
    """The satellite contract: ``submit`` surfaces the echoed X-Trace-Id
    (as ``SubmitTicket.trace_id``, still an int for old callers), the id
    resolves against ``/trace``, and ``result``/``watch`` accept it back
    as an outgoing correlation header without changing behavior."""
    from repro.obs.trace import disable_tracing, enable_tracing
    svc = SweepService(obj, epochs=1, max_results=8)
    enable_tracing()
    try:
        server = SweepServer(svc, policy=FlushPolicy(max_rows=64,
                                                     max_delay_ms=25)).start()
        try:
            client = SweepClient(server.url, poll_s=5.0)
            rid = client.submit(_specs([0, 1]), tenant="team-a")
            assert isinstance(rid, int)           # old call sites keep working
            assert rid.trace_id and rid.trace_id == svc.trace_id(rid)
            # the ticket's trace id is the SAME id /trace serves the span
            # tree under — the whole point of echoing it
            res = client.result(rid, timeout=180, trace_id=rid.trace_id)
            _assert_same(res, run_sweep(obj, 1, _specs([0, 1])))
            tree = client.trace(rid.trace_id)
            assert {"submit", "dispatch"} <= {s["name"] for s in tree["spans"]}
            # watch() takes the same correlation header; with the bus off
            # it answers instantly with no events and enabled=False
            got = client.watch(cursor=0, timeout_s=0.0,
                               trace_id=rid.trace_id)
            assert got["events"] == [] and got["enabled"] is False
        finally:
            server.stop()
    finally:
        disable_tracing(clear=True)
