"""Sweep service suite: persistent runner cache + request coalescing.

Pins the three contracts the `repro.service` subsystem introduces:

  * COMPILE-COUNTER REGRESSION — a second same-shape sweep (direct
    `run_sweep` or through `SweepService`) performs ZERO new compiles: the
    group bodies close over hashable statics only, so the module-level
    runner cache hands back the previous call's jitted program. The counter
    increments at trace time, so it exactly counts (re)compilations.
  * COALESCING BIT-IDENTITY — rows from many requests merged into shared
    compiled groups demux back bit-identical to standalone `run_sweep`
    calls, for all three algos and mixed per-row epoch budgets.
  * CHECKPOINT-RESUME — a preempted `run_job` resumes from the newest
    checkpoint, re-runs only unfinished groups, and the final result is
    bit-identical to one `run_sweep` call.
"""
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.core import LogisticRegression, SweepSpec, run_sweep
from repro.core.asysvrg import SCHEME_IDS
from repro.data.libsvm import make_synthetic_libsvm
from repro.service import (
    ResultEvictedError,
    SweepService,
    cache_size,
    cache_stats,
    clear_cache,
    coalesce,
)
from repro.service.cache import runner_key


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _grid_a():
    return [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=s)
            for s in range(2)]


def _grid_mixed():
    """All three algos + mixed per-row epoch budgets in one request."""
    return [SweepSpec(scheme="unlock", step_size=0.25, tau=3, num_threads=4,
                      inner_steps=25, seed=7, epochs=1),
            SweepSpec(scheme="consistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=8, epochs=3),
            SweepSpec(algo="hogwild", scheme="consistent", step_size=0.5,
                      tau=2, num_threads=3, seed=1),
            SweepSpec(algo="svrg", step_size=0.5, num_threads=1,
                      inner_steps=30, seed=2)]


def _assert_same(got, want):
    np.testing.assert_array_equal(got.histories, want.histories)
    np.testing.assert_array_equal(got.final_w, want.final_w)
    np.testing.assert_array_equal(got.effective_passes,
                                  want.effective_passes)
    np.testing.assert_array_equal(got.total_updates, want.total_updates)
    np.testing.assert_array_equal(got.epochs_per_row, want.epochs_per_row)
    assert got.specs == want.specs


# --------------------------------------------------------------- cache layer
def test_second_same_shape_sweep_compiles_nothing(obj):
    """Acceptance: repeated `run_sweep` with the same static group dims and
    data shapes performs zero new traces — the ROADMAP runner-cache item."""
    specs = _grid_a()
    clear_cache()
    first = run_sweep(obj, 2, specs)
    cold = cache_stats()
    assert cold.misses >= 1 and cold.compiles >= 1
    second = run_sweep(obj, 2, specs)
    warm = cache_stats().since(cold)
    assert warm.compiles == 0, "second same-shape sweep recompiled"
    assert warm.misses == 0 and warm.hits >= 1
    _assert_same(second, first)


def test_service_second_sweep_compiles_nothing(obj):
    """The acceptance criterion through the service front-end."""
    svc = SweepService(obj, epochs=2)
    svc.sweep(_grid_a())
    base = cache_stats()
    svc.sweep(_grid_a())
    assert cache_stats().since(base).compiles == 0
    stats = svc.stats()
    assert stats.flushes == 2 and stats.cache_hit_rate > 0


def test_cache_keys_separate_static_dims(obj):
    """Different epochs-bound / drop_prob / objective key different
    runners; identical dims (even via a different Mesh-less path) share."""
    k = dict(group_epochs=2, total=100, option=2, buf_len=4,
             drop_prob=0.02, mesh=None, obj=obj)
    base = runner_key("asysvrg", **k)
    assert runner_key("asysvrg", **k) == base
    assert runner_key("hogwild", **k) != base
    assert runner_key("asysvrg", **{**k, "group_epochs": 3}) != base
    assert runner_key("asysvrg", **{**k, "drop_prob": 0.0}) != base
    assert runner_key("asysvrg", **{**k, "buf_len": 8}) != base
    # same static key, same data shapes, DIFFERENT instance: shares a runner
    obj2 = LogisticRegression(obj.X, obj.y, l2_reg=obj.l2)
    assert runner_key("asysvrg", **{**k, "obj": obj2}) == base


def test_vmap_runner_keyed_on_kernel_mode(obj, monkeypatch):
    """Flipping $REPRO_KERNEL_MODE mid-process re-keys the vmap runner: a
    runner traced with the jnp reference update is never served once the
    `svrg_update` mode resolves to the Pallas kernel body, and back."""
    from repro.kernels.dispatch import KERNEL_MODE_ENV
    from repro.service.cache import get_group_runner
    k = dict(group_epochs=2, total=100, option=2, buf_len=4,
             drop_prob=0.02, mesh=None, obj=obj)
    monkeypatch.setenv(KERNEL_MODE_ENV, "reference")
    reference = get_group_runner("asysvrg", **k)
    monkeypatch.setenv(KERNEL_MODE_ENV, "interpret")
    interpret = get_group_runner("asysvrg", **k)
    assert interpret is not reference
    assert runner_key("asysvrg", **k)[-1] == ("vmap", "interpret")
    monkeypatch.setenv(KERNEL_MODE_ENV, "reference")
    assert get_group_runner("asysvrg", **k) is reference


def test_clear_cache_resets(obj):
    run_sweep(obj, 1, _grid_a()[:1])
    assert cache_size() >= 1
    clear_cache()
    assert cache_size() == 0
    assert cache_stats().misses == 0


# ----------------------------------------------------------- scheduler layer
def test_coalesce_merges_compatible_rows_across_requests(obj):
    """Rows with equal static group dims and scheme pool into ONE group
    across requests; incompatible rows (different M̃) stay separate, and
    so does a row of another scheme."""
    svc = SweepService(obj, epochs=2)
    svc.submit(_grid_a())                      # M̃ = 4*25, inconsistent
    svc.submit([SweepSpec(scheme="inconsistent", step_size=1.0, tau=3,
                          num_threads=4, inner_steps=25, seed=9)])
    svc.submit([SweepSpec(scheme="unlock", step_size=1.0, tau=2,
                          num_threads=3, inner_steps=20, seed=9)])  # M̃ = 60
    svc.submit([SweepSpec(scheme="unlock", step_size=1.0, tau=3,
                          num_threads=4, inner_steps=25, seed=9)])
    batch = coalesce(obj, tuple(svc._pending))
    sizes = sorted(len(m) for m in batch.groups.values())
    assert sizes == [1, 1, 3]                  # 2+1 merged, 2 alone
    unlock_m100 = [m for k, m in batch.groups.items()
                   if k.total == 100 and k.scheme == "unlock"]
    assert unlock_m100 == [[4]]                # its own group
    svc.flush()
    assert svc.stats().rows_coalesced == 3
    assert svc.stats().groups_merged == 1


def test_scheme_sequence_runs_one_runner_as_one_group_per_scheme(obj):
    """A tuner's consistent / inconsistent / unlock requests, one after
    another: three groups, each of one scheme, through one compiled
    runner (the scheme is no part of the runner key)."""
    clear_cache()
    svc = SweepService(obj, epochs=1)
    for k, scheme in enumerate(SCHEME_IDS):
        svc.sweep([SweepSpec(scheme=scheme, step_size=0.5, tau=3,
                             num_threads=4, inner_steps=25, seed=k)])
    stats = svc.stats()
    assert (stats.cache_misses, stats.cache_hits, stats.compiles) == (1, 2, 1)
    assert stats.groups_dispatched == 3
    assert stats.groups_by_scheme == {s: 1 for s in SCHEME_IDS}


def test_multi_request_coalescing_bit_identical(obj):
    """Acceptance: every request's demuxed result equals a standalone
    `run_sweep` of that request — all three algos, mixed per-row epochs,
    different per-request default budgets, one flush."""
    svc = SweepService(obj, epochs=2)
    reqs = {svc.submit(_grid_a()): (_grid_a(), 2),
            svc.submit(_grid_mixed()): (_grid_mixed(), 2),
            svc.submit(_grid_a()[:1], epochs=3): (_grid_a()[:1], 3)}
    done = svc.flush()
    assert sorted(done) == sorted(reqs)
    for rid, (specs, epochs) in reqs.items():
        _assert_same(svc.result(rid), run_sweep(obj, epochs, specs))
    assert svc.stats().rows_coalesced > 0


def test_result_flushes_implicitly_and_unknown_id_raises(obj):
    svc = SweepService(obj, epochs=1)
    rid = svc.submit(_grid_a()[:1])
    assert svc.pending() == 1
    res = svc.result(rid)                      # implicit flush
    assert svc.pending() == 0
    _assert_same(res, run_sweep(obj, 1, _grid_a()[:1]))
    with pytest.raises(KeyError):
        svc.result(10_000)


def test_empty_submissions_rejected(obj):
    svc = SweepService(obj)
    with pytest.raises(ValueError):
        svc.submit([])
    assert svc.flush() == []                   # nothing pending is a no-op


def test_invalid_spec_rejected_at_submit_not_flush(obj):
    """A bad spec raises to ITS client at submit time and can never wedge
    a shared flush: the other tenant's request still completes."""
    svc = SweepService(obj, epochs=1)
    rid = svc.submit(_grid_a()[:1])
    with pytest.raises(ValueError):
        svc.submit([SweepSpec(algo="svrg", tau=3)])      # svrg is τ=0
    with pytest.raises(ValueError):
        svc.submit([SweepSpec(scheme="nope")])
    with pytest.raises(ValueError):
        svc.submit(_grid_a()[:1], epochs=0)              # resolves to 0
    with pytest.raises(ValueError):                      # resolves M̃ < 1,
        svc.submit([SweepSpec(algo="svrg", num_threads=1,  # would only blow
                              inner_steps=-1)])          # up at trace time
    assert svc.pending() == 1                  # queue not poisoned
    _assert_same(svc.result(rid), run_sweep(obj, 1, _grid_a()[:1]))


def test_results_retention_bound_and_discard(obj):
    """Completed results are FIFO-bounded (a long-lived server must not
    hold every tenant's histories forever) and releasable via discard."""
    svc = SweepService(obj, epochs=1, max_results=2)
    rids = [svc.submit(_grid_a()[:1]) for _ in range(3)]
    svc.flush()
    with pytest.raises(KeyError):              # oldest evicted
        svc.result(rids[0])
    _assert_same(svc.result(rids[2]), run_sweep(obj, 1, _grid_a()[:1]))
    svc.discard(rids[2])
    with pytest.raises(KeyError):
        svc.result(rids[2])
    svc.discard(rids[2])                       # idempotent


def test_eviction_never_drops_actively_awaited_result(obj):
    """One wide flush completing more requests than ``max_results`` must
    not evict a result whose consumer is already parked in wait_result —
    eviction skips watched ids and drops an unwatched one instead."""
    import threading
    import time

    svc = SweepService(obj, epochs=1, max_results=1)
    r1 = svc.submit(_grid_a()[:1])
    r2 = svc.submit(_grid_a()[1:2])
    got = {}
    waiter = threading.Thread(
        target=lambda: got.update(res=svc.wait_result(r1, timeout=120)))
    waiter.start()
    for _ in range(500):                       # let the waiter park
        if r1 in svc._watched:
            break
        time.sleep(0.01)
    assert r1 in svc._watched
    svc.flush()                                # completes BOTH requests
    waiter.join()
    _assert_same(got["res"], run_sweep(obj, 1, _grid_a()[:1]))
    with pytest.raises(ResultEvictedError):    # the unwatched one paid
        svc.result(r2)


def test_tenant_accounting_is_bounded(obj):
    """Tenant tags are arbitrary client strings: the per-tenant row map is
    FIFO-bounded so tag-churning clients can't grow the service."""
    svc = SweepService(obj, epochs=1, max_tenants=2)
    for t in ("a", "b", "c"):
        svc.submit(_grid_a()[:1], tenant=t)
    rows = svc.tenant_rows()
    assert len(rows) == 2 and "a" not in rows


def test_evicted_ids_distinguished_from_unknown(obj):
    """An id whose result fell off the `max_results` FIFO raises the typed
    `ResultEvictedError` (naming the bound, so a client of a busy server
    knows to re-submit or raise the bound); an id that NEVER existed stays
    a bare KeyError. `wait_result` mirrors the distinction."""
    svc = SweepService(obj, epochs=1, max_results=1)
    old = svc.submit(_grid_a()[:1])
    svc.flush()
    newer = svc.submit(_grid_a()[:1])
    svc.flush()                                # evicts `old`
    with pytest.raises(ResultEvictedError, match="max_results=1"):
        svc.result(old)
    with pytest.raises(ResultEvictedError):
        svc.wait_result(old, timeout=0.1)
    # a phantom id is NOT reported as evicted
    with pytest.raises(KeyError) as ei:
        svc.result(10_000)
    assert not isinstance(ei.value, ResultEvictedError)
    with pytest.raises(KeyError) as ei:
        svc.wait_result(10_000, timeout=0.1)
    assert not isinstance(ei.value, ResultEvictedError)
    svc.result(newer)                          # the live one still serves


def test_flush_selector_must_partition_queue(obj):
    """A selector that drops or duplicates a request is a lost-request bug
    waiting to happen; flush() rejects it and keeps the queue intact."""
    svc = SweepService(obj, epochs=1)
    rid = svc.submit(_grid_a()[:1])
    with pytest.raises(ValueError, match="partition"):
        svc.flush(lambda pending: ((), ()))            # dropped
    with pytest.raises(ValueError, match="partition"):
        svc.flush(lambda pending: (pending, pending))  # duplicated
    assert svc.pending() == 1                  # queue untouched
    _assert_same(svc.result(rid), run_sweep(obj, 1, _grid_a()[:1]))


def test_concurrent_services_cache_attribution_exact(obj):
    """Cache counters are credited at the LOOKUP site through a thread-
    scoped sink: a WARM service flushing concurrently with a COLD one
    (new compiled shape) must report 0 compiles of its own, even though
    the process-global counters moved under it. The old window-absorption
    accounting raced exactly here."""
    import threading

    clear_cache()
    warm_specs = _grid_a()
    run_sweep(obj, 2, warm_specs)              # pre-compile the warm shape
    svc_warm = SweepService(obj, epochs=2)
    svc_cold = SweepService(obj, epochs=5)     # new epochs-bound: compiles
    svc_warm.submit(warm_specs)
    svc_cold.submit(warm_specs)
    barrier = threading.Barrier(2)
    errs = []

    def flush(svc):
        try:
            barrier.wait()                     # force the windows to overlap
            svc.flush()
        except Exception as e:                 # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=flush, args=(s,))
               for s in (svc_warm, svc_cold)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    warm, cold = svc_warm.stats(), svc_cold.stats()
    assert warm.compiles == 0, \
        "warm service charged for a concurrent service's compile"
    assert cold.compiles >= 1
    assert warm.cache_hits >= 1 and warm.cache_misses == 0
    # the per-service sinks jointly account for the global movement
    total = cache_stats()
    assert warm.compiles + cold.compiles <= total.compiles


def test_concurrent_submits_mint_unique_ids(obj):
    """submit() from many tenant threads never duplicates request ids or
    drops a queued request."""
    import threading

    svc = SweepService(obj, epochs=1)
    ids, errs = [], []

    def client():
        try:
            ids.append(svc.submit(_grid_a()[:1]))
        except Exception as e:                 # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=client) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(set(ids)) == 16
    assert svc.pending() == 16


def test_result_waits_for_inflight_flush(obj):
    """result() called while ANOTHER thread's flush has the request in
    flight blocks until the result lands instead of raising KeyError."""
    import threading

    svc = SweepService(obj, epochs=1)
    rid = svc.submit(_grid_a()[:1])
    flusher = threading.Thread(target=svc.flush)
    flusher.start()
    try:
        res = svc.result(rid)                  # races the flush window
    finally:
        flusher.join()
    _assert_same(res, run_sweep(obj, 1, _grid_a()[:1]))


def test_cache_lru_bound(obj):
    """The runner cache is LRU-bounded: distinct keys beyond the limit
    evict the least recently used entry instead of growing forever."""
    from repro.service import set_cache_limit

    clear_cache()
    prev = set_cache_limit(2)
    try:
        for e in (1, 2, 3):                    # 3 distinct epoch-bound keys
            run_sweep(obj, e, _grid_a()[:1])
        assert cache_size() == 2
        base = cache_stats()
        run_sweep(obj, 3, _grid_a()[:1])       # most recent: still cached
        assert cache_stats().since(base).compiles == 0
        run_sweep(obj, 1, _grid_a()[:1])       # evicted: rebuilt + retraced
        assert cache_stats().since(base).misses == 1
    finally:
        set_cache_limit(prev)
        clear_cache()


# ------------------------------------------------------- checkpointed jobs
def test_run_job_checkpoint_resume_bit_identical(obj, tmp_path):
    """A job preempted after each group (max_groups=1) resumes to the same
    bits as one uninterrupted `run_sweep`; finished groups never re-run."""
    specs = _grid_mixed()
    svc = SweepService(obj, epochs=2)
    calls = 0
    res, done = None, False
    while not done:
        res, done = svc.run_job(specs, checkpointer=Checkpointer(str(tmp_path)),
                                max_groups=1)
        calls += 1
        assert calls < 20
    assert calls >= 3                          # >=3 groups -> real resumes
    _assert_same(res, run_sweep(obj, 2, specs))


def test_run_job_rejects_foreign_checkpoint(obj, tmp_path):
    svc = SweepService(obj, epochs=1)
    ckpt = Checkpointer(str(tmp_path))
    _, done = svc.run_job(_grid_a()[:1], checkpointer=ckpt, max_groups=1)
    with pytest.raises(ValueError, match="different job"):
        svc.run_job(_grid_mixed(), checkpointer=Checkpointer(str(tmp_path)))


def test_run_job_rejects_different_w0_resume(obj, tmp_path):
    """The job fingerprint pins the numeric inputs too: a resume from a
    different initial iterate must not blend with checkpointed groups."""
    specs = _grid_a()[:1] + [SweepSpec(algo="svrg", step_size=0.5,
                                       num_threads=1, inner_steps=30)]
    svc = SweepService(obj, epochs=1)
    _, done = svc.run_job(specs, checkpointer=Checkpointer(str(tmp_path)),
                          max_groups=1)
    assert not done
    svc_b = SweepService(obj, epochs=1, w0=np.full(obj.p, 0.1, np.float32))
    with pytest.raises(ValueError, match="different job"):
        svc_b.run_job(specs, checkpointer=Checkpointer(str(tmp_path)))
