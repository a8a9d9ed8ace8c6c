"""Background flush daemon suite: policy triggers, stable widths, job
time-slicing, drain semantics, and the concurrent-tenancy stress test.

Everything here runs WITHOUT a client ever calling ``flush()`` — the point
of the serving tier is that coalesced dispatch happens asynchronously on
size/deadline policy, and every result is still bit-identical to a
standalone ``run_sweep`` of that tenant's specs.
"""
import dataclasses
import sys
import threading

import numpy as np
import pytest

from repro.core import LogisticRegression, SweepSpec, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.server import FairShare, FlushPolicy, ServeDaemon, WidthRegistry
from repro.service import SweepService, cache_stats, clear_cache


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _specs(seeds, tau=3, threads=4, steps=25):
    return [SweepSpec(scheme="inconsistent", step_size=0.5, tau=tau,
                      num_threads=threads, inner_steps=steps, seed=s)
            for s in seeds]


def _assert_same(got, want):
    np.testing.assert_array_equal(got.histories, want.histories)
    np.testing.assert_array_equal(got.final_w, want.final_w)
    np.testing.assert_array_equal(got.effective_passes,
                                  want.effective_passes)
    assert got.specs == want.specs


# ------------------------------------------------------------- policy knobs
def test_flush_policy_validation():
    with pytest.raises(ValueError):
        FlushPolicy(max_rows=0)
    with pytest.raises(ValueError):
        FlushPolicy(max_delay_ms=-1)
    with pytest.raises(ValueError):
        FlushPolicy(max_pad_factor=0.5)
    with pytest.raises(ValueError):
        FlushPolicy(job_groups_per_slice=0)


def test_deadline_triggered_flush(obj):
    """A lone small request on a quiet server is dispatched by the DEADLINE
    trigger — no client flush, no size threshold reached."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=1000, max_delay_ms=30))
    with daemon:
        rid = svc.submit(_specs([0, 1]))
        res = svc.wait_result(rid, timeout=120)
    _assert_same(res, run_sweep(obj, 1, _specs([0, 1])))
    assert daemon.stats.deadline_flushes >= 1
    assert daemon.stats.size_flushes == 0


def test_size_triggered_flush_coalesces_tenants(obj):
    """Enough rows queued fires the SIZE trigger before the (long)
    deadline, and the flush coalesces the tenants' compatible rows."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=4, max_delay_ms=60_000))
    with daemon:
        rid_a = svc.submit(_specs([2, 3]), tenant="a")
        rid_b = svc.submit(_specs([4, 5]), tenant="b")
        res_a = svc.wait_result(rid_a, timeout=120)
        res_b = svc.wait_result(rid_b, timeout=120)
    _assert_same(res_a, run_sweep(obj, 1, _specs([2, 3])))
    _assert_same(res_b, run_sweep(obj, 1, _specs([4, 5])))
    assert daemon.stats.size_flushes >= 1
    stats = svc.stats()
    assert stats.flushes == 1 and stats.rows_coalesced == 4


def test_stable_widths_keep_warm_path_at_zero_compiles(obj):
    """The width registry pads a smaller same-shape batch up to the width
    already compiled, so the warm path performs 0 new traces; without it
    the narrower batch would retrace (control asserted too)."""
    clear_cache()
    svc = SweepService(obj, epochs=1, width_policy=WidthRegistry())
    svc.submit(_specs([0, 1, 2]))
    svc.flush()                               # natural width 3: compiles
    base = cache_stats()
    rid = svc.submit(_specs([7, 8]))          # width 2 -> padded to 3
    svc.flush()
    assert cache_stats().since(base).compiles == 0
    assert svc.stats().rows_padded == 1
    _assert_same(svc.result(rid), run_sweep(obj, 1, _specs([7, 8])))

    # control: the same drift WITHOUT the registry retraces once
    clear_cache()
    svc2 = SweepService(obj, epochs=1)
    svc2.sweep(_specs([0, 1, 2]))
    base = cache_stats()
    svc2.sweep(_specs([7, 8]))
    assert cache_stats().since(base).compiles >= 1


def test_stable_widths_carry_across_schemes(obj):
    """Groups that differ only in their scheme run one compiled program,
    so a width one scheme compiled serves the next: 0 new traces."""
    clear_cache()
    svc = SweepService(obj, epochs=1, width_policy=WidthRegistry())
    svc.submit(_specs([0, 1, 2]))             # inconsistent, width 3
    svc.flush()
    base = cache_stats()
    unlock = [dataclasses.replace(s, scheme="unlock") for s in _specs([7, 8])]
    rid = svc.submit(unlock)                  # width 2 -> padded to 3
    svc.flush()
    assert cache_stats().since(base).compiles == 0
    assert svc.stats().rows_padded == 1
    _assert_same(svc.result(rid), run_sweep(obj, 1, unlock))


def test_width_registry_bounds_padding_waste():
    reg = WidthRegistry(max_pad_factor=2.0)
    key = ("asysvrg", 100, 2, 4)
    assert reg((*key,), 1, 8) == 8            # new width: recorded
    assert reg((*key,), 1, 5) == 8            # pad 5 -> 8: within 2x
    assert reg((*key,), 1, 3) == 3            # 8 > 2*3: record 3 instead
    assert reg((*key,), 1, 4) == 8            # 8 == 2*4: exactly at bound
    assert reg((*key,), 1, 2) == 3            # smallest admissible wins
    assert sorted(reg.known_widths((*key,), 1)) == [3, 8]
    assert reg(("other",), 5, 8) == 8         # keys don't bleed


def test_job_time_slicing_interleaves_with_queue(obj):
    """A giant multi-group job runs a slice at a time via
    run_job(max_groups=1) while small requests keep flushing in between —
    the queue is never starved, and the job result is bit-identical to one
    uninterrupted run_sweep."""
    svc = SweepService(obj, epochs=1)
    job_specs = (_specs([1]) +                # three distinct group shapes
                 _specs([2], tau=2, threads=3, steps=20) +
                 [SweepSpec(algo="hogwild", scheme="consistent",
                            step_size=0.5, tau=2, num_threads=3, seed=3)])
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=1000, max_delay_ms=10,
                                          job_groups_per_slice=1))
    with daemon:
        handle = daemon.submit_job(job_specs)
        rid = svc.submit(_specs([9, 10]))     # rides between job slices
        res_req = svc.wait_result(rid, timeout=120)
        res_job = handle.result(timeout=240)
    assert handle.slices == 3                 # one slice per compiled group
    assert daemon.stats.job_slices == 3
    assert daemon.stats.jobs_completed == 1
    _assert_same(res_job, run_sweep(obj, 1, job_specs))
    _assert_same(res_req, run_sweep(obj, 1, _specs([9, 10])))


def test_stop_drains_queue_and_jobs(obj):
    """stop(drain=True) flushes what is still queued and finishes every
    job, so shutdown loses nothing."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=1000,
                                          max_delay_ms=60_000))
    daemon.start()
    rid = svc.submit(_specs([11]))
    handle = daemon.submit_job(_specs([12]))
    daemon.stop(drain=True)
    _assert_same(svc.result(rid), run_sweep(obj, 1, _specs([11])))
    _assert_same(handle.result(timeout=0), run_sweep(obj, 1, _specs([12])))
    assert svc.pending() == 0 and daemon.jobs_pending() == 0


def test_fair_share_slices_successive_flushes(obj):
    """With a FairShare selector, one deadline tick drains the queue in
    successive bounded slices (the daemon loops until the selector leaves
    nothing), and every request still completes bit-identically."""
    svc = SweepService(obj, epochs=1)
    fair = FairShare(quantum_rows=2, max_rows_per_flush=2)
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=1000, max_delay_ms=20),
                         fairness=fair)
    with daemon:
        rids = {svc.submit(_specs([20 + i]), tenant=f"t{i}"): [20 + i]
                for i in range(5)}
        for rid, seeds in rids.items():
            _assert_same(svc.wait_result(rid, timeout=120),
                         run_sweep(obj, 1, _specs(seeds)))
    assert svc.stats().flushes >= 3           # 5 rows through 2-row slices


# --------------------------------------------------- concurrent tenancy
def test_concurrent_tenancy_stress(obj):
    """N tenant threads submit + await against ONE service under the
    background daemon: no lost requests, no duplicate ids, every result
    bit-identical to a standalone run_sweep of that tenant's specs."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc, FlushPolicy(max_rows=6, max_delay_ms=25))
    n_threads, rounds = 8, 2
    results, errors = {}, []
    ids = []
    id_lock = threading.Lock()

    def tenant(t):
        try:
            for r in range(rounds):
                seeds = [1000 * t + 10 * r, 1000 * t + 10 * r + 1]
                rid = svc.submit(_specs(seeds), tenant=f"tenant-{t}")
                with id_lock:
                    ids.append(rid)
                res = svc.wait_result(rid, timeout=180)
                results[(t, r)] = (seeds, res)
        except Exception as e:                 # pragma: no cover
            errors.append(e)

    with daemon:
        threads = [threading.Thread(target=tenant, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors
    assert len(ids) == len(set(ids)) == n_threads * rounds
    assert len(results) == n_threads * rounds          # nothing lost
    for (t, r), (seeds, res) in results.items():
        _assert_same(res, run_sweep(obj, 1, _specs(seeds)))
    per_tenant = svc.tenant_rows()
    assert len(per_tenant) == n_threads
    assert all(v == (2 * rounds, 2 * rounds) for v in per_tenant.values())


# --------------------------------------------- stats locking (RL003 fixes)
def test_flush_now_counter_survives_thread_races(obj):
    """forced_flushes is bumped under _lock: N threads hammering
    flush_now() concurrently must account every call exactly (the
    pre-lock ``self.stats.forced_flushes += 1`` was a lost-update race
    between HTTP handler threads and the drain path)."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc)          # not started: queue stays empty,
    n_threads, calls = 8, 50           # flush_now() exercises only the
    old = sys.getswitchinterval()      # counter + the (empty) dispatch
    sys.setswitchinterval(1e-6)        # force frequent preemption
    try:
        threads = [threading.Thread(
            target=lambda: [daemon.flush_now() for _ in range(calls)])
            for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        sys.setswitchinterval(old)
    assert daemon.stats_snapshot().forced_flushes == n_threads * calls


def test_stats_snapshot_is_a_decoupled_copy(obj):
    """stats_snapshot() hands out a frozen-in-time COPY — later daemon
    activity must not mutate an exporter's already-taken snapshot."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc)
    before = daemon.stats_snapshot()
    daemon.flush_now()
    assert before.forced_flushes == 0
    assert before is not daemon.stats
    assert daemon.stats_snapshot().forced_flushes == 1


def test_flush_error_surfaces_through_locked_snapshots(obj, monkeypatch):
    """A poisoned dispatch lands in flush_errors/last_error under _lock,
    is visible through the snapshot accessors (what metrics.snapshot now
    reads), and a later healthy flush clears it."""
    svc = SweepService(obj, epochs=1)
    daemon = ServeDaemon(svc)
    boom = RuntimeError("poisoned dispatch")

    def failing_flush(selector=None):
        raise boom

    monkeypatch.setattr(svc, "flush", failing_flush)
    assert daemon.flush_now() == []
    assert daemon.last_error_snapshot() is boom
    snap = daemon.stats_snapshot()
    assert snap.flush_errors == 1 and snap.forced_flushes == 1

    from repro.server.metrics import snapshot
    exported = snapshot(svc, daemon=daemon)
    assert "poisoned dispatch" in exported["daemon"]["last_error"]
    assert exported["daemon"]["flush_errors"] == 1

    monkeypatch.undo()                 # healthy flush clears the error
    daemon.flush_now()
    assert daemon.last_error_snapshot() is None
    assert snapshot(svc, daemon=daemon)["daemon"]["last_error"] is None
