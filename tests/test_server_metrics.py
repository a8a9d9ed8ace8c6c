"""`repro.server.metrics.snapshot` contract: one JSON-safe dict, always.

The snapshot backs three consumers with different parsers — ``/stats``
(json.dumps), ``/metrics`` (the Prometheus walker, which float()s every
leaf) and operator scripts — so the contract is structural: every
configuration (± daemon, ± fairness) serializes with the stock JSON
encoder, the top-level sections are stable, and NO numpy scalar ever
leaks into a leaf (np.float64 survives json.dumps by accident of
subclassing, np.int64 raises, and both break strict consumers — the walk
below rejects every non-builtin type).
"""
import json

import pytest

from repro.core import LogisticRegression, SweepSpec
from repro.data.libsvm import make_synthetic_libsvm
from repro.server import FairShare, FlushPolicy, ServeDaemon, snapshot
from repro.service import SweepService

_BUILTIN_LEAVES = (str, bool, int, float, type(None))


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _specs(seeds):
    return [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=s)
            for s in seeds]


def _worked_service(obj):
    """A service with real accounting: latencies, tenants, cache counters."""
    svc = SweepService(obj, epochs=1)
    for tenant, seed in (("team-a", 1), ("team-b", 2)):
        svc.submit(_specs([seed]), tenant=tenant)
    svc.flush()
    svc.submit(_specs([3]))                     # leave the queue non-empty
    return svc


def _assert_builtin_tree(node, path="$"):
    """Reject numpy scalars (and any other non-builtin) at every leaf.
    ``type() in`` on purpose: np.float64 IS-A float, np.bool_ is not a
    bool — isinstance would wave the first through."""
    if isinstance(node, dict):
        for key, child in node.items():
            assert type(key) is str, f"non-str key {key!r} at {path}"
            _assert_builtin_tree(child, f"{path}.{key}")
    elif isinstance(node, (list, tuple)):
        for i, child in enumerate(node):
            _assert_builtin_tree(child, f"{path}[{i}]")
    else:
        assert type(node) in _BUILTIN_LEAVES, \
            f"non-builtin leaf {type(node).__name__} at {path}: {node!r}"


def test_snapshot_service_only_round_trips_and_has_all_sections(obj):
    svc = _worked_service(obj)
    snap = snapshot(svc)
    assert set(snap) == {"service", "queue", "tenants", "flush_latency",
                         "request_latency", "runner_cache"}
    _assert_builtin_tree(snap)
    assert json.loads(json.dumps(snap)) == snap
    assert snap["service"]["flushes"] == 1
    assert snap["queue"]["depth_requests"] == 1
    assert snap["queue"]["oldest_age_ms"] > 0
    assert set(snap["tenants"]) == {"team-a", "team-b", "default"}
    assert snap["tenants"]["team-a"] == {"rows_submitted": 1,
                                         "rows_completed": 1}
    assert snap["flush_latency"]["count"] == 1
    assert snap["flush_latency"]["p95_ms"] >= 0.0
    assert snap["request_latency"]["count"] == 2


def test_snapshot_with_daemon_and_fairness_blocks(obj):
    svc = _worked_service(obj)
    fairness = FairShare(quantum_rows=16)
    daemon = ServeDaemon(svc, FlushPolicy(max_delay_ms=10),
                         fairness=fairness)
    with daemon:
        snap = snapshot(svc, daemon, fairness)
        assert set(snap) == {"service", "queue", "tenants", "flush_latency",
                             "request_latency", "runner_cache", "daemon",
                             "fairness"}
        _assert_builtin_tree(snap)
        assert json.loads(json.dumps(snap)) == snap
        assert snap["daemon"]["running"] is True
        assert snap["daemon"]["heartbeat_age_s"] >= 0.0
        assert snap["daemon"]["policy"]["heartbeat_stall_s"] == 30.0
        assert snap["fairness"]["quantum_rows"] == 16
    # after stop(): still JSON-safe, and liveness reads False/stale
    snap = snapshot(svc, daemon, fairness)
    _assert_builtin_tree(snap)
    assert snap["daemon"]["running"] is False


def test_snapshot_leaves_survive_the_prometheus_walker(obj):
    """The /metrics renderer float()s every numeric leaf it keeps; the
    snapshot must never hand it something that changes value under
    float() (i.e. only real numbers, bools, strings, None)."""
    from repro.obs.prometheus import render
    svc = _worked_service(obj)
    text = render(snapshot(svc), histograms=svc.histograms.as_dict())
    assert text.endswith("\n") and "repro_service_rows_submitted" in text


def test_prometheus_escapes_malicious_tenant_labels(obj):
    """Regression pin for the 0.0.4 label-escaping rules: a tenant name
    carrying backslashes, quotes and newlines must come out as ONE valid
    sample line with ``\\\\``, ``\\"`` and ``\\n`` escapes — an unescaped
    quote ends the label value early and an unescaped newline injects a
    whole forged sample into the scrape."""
    from repro.obs.prometheus import render
    evil = 'team"a\\b\nrepro_forged_metric 1'
    svc = SweepService(obj, epochs=1)
    svc.submit(_specs([1]), tenant=evil)
    svc.flush()
    text = render(snapshot(svc))
    expected = 'tenant="team\\"a\\\\b\\nrepro_forged_metric 1"'
    assert expected in text
    # no forged series: the newline never reached the exposition raw
    assert not any(ln.startswith("repro_forged_metric")
                   for ln in text.splitlines())
    # every line still parses as 0.0.4 (comment/blank/sample)
    import re
    prom_line = re.compile(
        r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})?\s[^\s]+)$")
    bad = [ln for ln in text.splitlines() if ln and not prom_line.match(ln)]
    assert not bad, bad
