"""Live observability suite: progress streaming and the watchdog.

The PR-10 contracts, bottom-up:

  * `ProgressBus` — bounded, cursor-resumable, watch_id-filtered, and a
    blocked ``watch`` wakes on publish (unit tests, no jax).
  * `Watchdog` — divergence detection on host-side numpy histories at
    slice/flush boundaries; ``cancel_row`` freezes the offender via the
    per-row epoch mask while every SURVIVOR stays bit-identical to a
    watchdog-off run (vmap and fused engines; the sharded variant lives
    in tests/test_sweep_sharded.py); ``cancel_job`` raises `JobDiverged`
    from ``run_job`` but degrades to ``cancel_row`` inside a coalesced
    multi-tenant flush.
  * Progress events — per-slice/-flush loss series equal the final
    `SweepResult` histories bit-for-bit, and watchdog truncations
    persist across checkpoint-resume.
  * End-to-end acceptance: a multi-slice job submitted over HTTP,
    streamed live via ``GET /watch`` while it runs.

``step_size=1e30`` is the forced-divergence vehicle throughout: on this
logistic objective it NaNs the loss at epoch 1, and step_size is not in
the group key, so the poisoned row shares a compiled group with healthy
rows.
"""
import threading
import time

import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.core import LogisticRegression, SweepSpec, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.obs.progress import (ProgressBus, disable_progress,
                                enable_progress, progress_bus)
from repro.obs.watchdog import (JobDiverged, Watchdog, enforce_group,
                                first_bad_epoch)
from repro.service import SweepService, cache_stats

BAD_STEP = 1e30       # NaNs the logistic loss on epoch 1, reliably


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with the live-obs toggle off and the
    process-global bus empty (it is a process singleton)."""
    disable_progress(clear=True)
    yield
    disable_progress(clear=True)


def _specs(seeds, step_size=0.5, inner_steps=25):
    return [SweepSpec(scheme="inconsistent", step_size=step_size, tau=3,
                      num_threads=4, inner_steps=inner_steps, seed=s)
            for s in seeds]


# --------------------------------------------------------------- ProgressBus
def test_progress_bus_cursor_resume_and_filter():
    bus = ProgressBus()
    for i in range(5):
        bus.publish(kind="slice", watch_id=f"job-{i % 2}", slice_index=i)
    all_events, cursor = bus.watch(cursor=0)
    assert [e.slice_index for e in all_events] == [0, 1, 2, 3, 4]
    assert cursor == all_events[-1].seq == 5
    # resume: nothing new past the cursor, cursor stays put
    again, cursor2 = bus.watch(cursor=cursor)
    assert again == [] and cursor2 == cursor
    # filter: only job-1's events, cursor advances to ITS last seq so a
    # filtered consumer never re-reads interleaved foreign events
    ours, c1 = bus.watch(cursor=0, watch_id="job-1")
    assert [e.slice_index for e in ours] == [1, 3]
    assert c1 == ours[-1].seq
    bus.publish(kind="done", watch_id="job-1")
    more, _ = bus.watch(cursor=c1, watch_id="job-1")
    assert [e.kind for e in more] == ["done"]


def test_progress_bus_is_bounded():
    bus = ProgressBus(maxlen=4)
    for i in range(10):
        bus.publish(kind="slice", watch_id="j", slice_index=i)
    events, cursor = bus.watch(cursor=0)
    # only the newest maxlen retained; seq stays globally monotone
    assert [e.slice_index for e in events] == [6, 7, 8, 9]
    assert cursor == 10 and bus.latest_seq() == 10


def test_progress_bus_watch_blocks_until_publish():
    bus = ProgressBus()
    got = {}

    def consumer():
        got["events"], got["cursor"] = bus.watch(cursor=0, watch_id="j",
                                                 timeout=10.0)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    bus.publish(kind="slice", watch_id="other")   # filtered out: keeps waiting
    bus.publish(kind="slice", watch_id="j")
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert [e.watch_id for e in got["events"]] == ["j"]
    # and an empty timeout-expiry returns immediately with cursor unchanged
    events, cursor = bus.watch(cursor=got["cursor"], timeout=0.0)
    assert events == [] and cursor == got["cursor"]


def test_progress_event_round_trips_json():
    import json
    bus = ProgressBus()
    ev = bus.publish(kind="slice", watch_id="job-1", tenant="t",
                     group="asysvrg-vmap-M100-opt2-buf4", slice_index=2,
                     slices_total=3, rows=(4, 5),
                     losses=((0.5, 0.25), (0.5, 0.125)),
                     loss_deltas=((-0.25,), (-0.375,)), diverged=(5,),
                     wall_s=0.125, trace_id="t01")
    back = json.loads(json.dumps(ev.to_dict()))
    assert back["kind"] == "slice" and back["rows"] == [4, 5]
    assert back["losses"][1] == [0.5, 0.125] and back["diverged"] == [5]


# ------------------------------------------------------------------ Watchdog
def test_first_bad_epoch_scan():
    nan_at_2 = np.asarray([1.0, 0.5, np.nan, 0.1], np.float32)
    assert first_bad_epoch(nan_at_2, epochs=3, explosion_ratio=1e3) == 2
    # entries past the row's own budget are frozen re-emits: not inspected
    assert first_bad_epoch(nan_at_2, epochs=1, explosion_ratio=1e3) is None
    assert first_bad_epoch(np.asarray([1.0, np.inf]), 1, 1e3) == 1
    # explosion without NaN: |loss| > ratio * |loss[0]|
    assert first_bad_epoch(np.asarray([1.0, 2.0, 5000.0]), 2, 1e3) == 2
    assert first_bad_epoch(np.asarray([1.0, 0.5, 0.25]), 2, 1e3) is None
    # epoch 0 (the initial loss) is trusted by construction
    assert first_bad_epoch(np.asarray([np.nan, 1.0]), 1, 1e3) is None
    assert first_bad_epoch(np.asarray([1.0]), 0, 1e3) is None


def test_watchdog_validation_and_tenant_policy():
    with pytest.raises(ValueError, match="unknown watchdog policy"):
        Watchdog(policy="explode")
    with pytest.raises(ValueError, match="unknown watchdog policy"):
        Watchdog(tenant_policies={"t": "bogus"})
    with pytest.raises(ValueError, match="explosion_ratio"):
        Watchdog(explosion_ratio=0.0)
    wd = Watchdog(policy="record", tenant_policies={"strict": "cancel_job"})
    assert wd.policy_for("strict") == "cancel_job"
    assert wd.policy_for("anyone-else") == "record"


@pytest.mark.parametrize("engine_mode", ["vmap", "fused"])
def test_flush_cancel_row_survivors_bit_identical(obj, engine_mode):
    """THE bit-identity contract: one poisoned row in a shared compiled
    group gets cancelled (frozen at w0 — its first bad epoch is 1), and
    every surviving row's history AND final iterate are bit-identical to
    a watchdog-off `run_sweep` of the same specs. The freeze re-dispatch
    rides the per-row epoch mask, so it must not compile anything."""
    import dataclasses
    good = [dataclasses.replace(s, engine_mode=engine_mode)
            for s in _specs([0, 1, 2])]
    bad = dataclasses.replace(_specs([99], step_size=BAD_STEP)[0],
                              engine_mode=engine_mode)
    specs = [good[0], bad, good[1], good[2]]

    svc = SweepService(obj, epochs=3, watchdog=Watchdog(policy="cancel_row"))
    rid = svc.submit(specs)
    svc.flush()                                   # compiles once
    base = cache_stats()
    rid2 = svc.submit(specs)
    svc.flush()                                   # warm flush + warm freeze
    assert cache_stats().since(base).compiles == 0, \
        "watchdog re-dispatch recompiled — epochs must stay a runtime array"
    got = svc.result(rid2)
    svc.result(rid)

    np.testing.assert_array_equal(got.diverged_rows, [-1, 0, -1, -1])
    assert got.epochs_per_row.tolist() == [3, 0, 3, 3]
    # cancelled row: frozen at w0 — every entry the initial loss, finite
    assert np.isfinite(got.histories[1]).all()
    assert np.all(got.histories[1] == got.histories[1, 0])

    ref = run_sweep(obj, 3, good)                 # watchdog-off reference
    for row, ref_row in zip((0, 2, 3), (0, 1, 2)):
        np.testing.assert_array_equal(got.histories[row],
                                      ref.histories[ref_row])
        np.testing.assert_array_equal(got.final_w[row],
                                      ref.final_w[ref_row])
    assert svc.stats().rows_diverged >= 1


def test_record_policy_marks_without_touching_outputs(obj):
    """``record`` flags the row in ``diverged_rows`` but keeps all
    outputs — the whole result stays bit-identical to watchdog-off."""
    specs = _specs([0, 1]) + _specs([99], step_size=BAD_STEP)
    svc = SweepService(obj, epochs=2, watchdog=Watchdog(policy="record"))
    rid = svc.submit(specs)
    svc.flush()
    got = svc.result(rid)
    ref = run_sweep(obj, 2, specs)
    np.testing.assert_array_equal(got.histories, ref.histories)
    np.testing.assert_array_equal(got.final_w, ref.final_w)
    assert got.epochs_per_row.tolist() == [2, 2, 2]   # nothing truncated
    np.testing.assert_array_equal(got.diverged_rows, [-1, -1, 0])


def test_cancel_job_raises_from_run_job_but_degrades_in_flush(obj, tmp_path):
    """``cancel_job`` is a job-scoped verdict: `run_job` raises
    `JobDiverged`, but a coalesced flush (multi-tenant by construction)
    degrades it to ``cancel_row`` so one tenant cannot cancel another."""
    specs = _specs([0]) + _specs([99], step_size=BAD_STEP)
    svc = SweepService(obj, epochs=2, watchdog=Watchdog(policy="cancel_job"))
    with pytest.raises(JobDiverged) as exc:
        svc.run_job(specs, 2, checkpointer=Checkpointer(str(tmp_path)))
    assert exc.value.rows == {1: 0}

    rid = svc.submit(specs)
    svc.flush()                                   # must NOT raise
    got = svc.result(rid)
    np.testing.assert_array_equal(got.diverged_rows, [-1, 0])
    np.testing.assert_array_equal(got.histories[0],
                                  run_sweep(obj, 2, _specs([0])).histories[0])


def test_enforce_group_respects_pad_duplicates():
    """Width-stabilizing pad rows past ``real`` re-run some real spec and
    may well diverge with it; they are demuxed away, so the watchdog must
    not inspect them (a pad row must never trigger a freeze)."""
    hist = np.asarray([[1.0, 0.5], [1.0, np.nan]], np.float32)
    w = np.zeros((2, 3), np.float32)

    class _Row:
        epochs = 1
    calls = []
    out = enforce_group(Watchdog(policy="cancel_row"), hist, w,
                        members=[0, 0], resolved=[_Row()], real=1,
                        tenant_of=lambda c: "t",
                        redispatch=lambda amended: calls.append(amended))
    assert out[2] == {} and out[3] == {} and calls == []


# ----------------------------------------------------------- progress events
def test_flush_events_match_result_histories(obj):
    specs = _specs([0, 1, 2])
    svc = SweepService(obj, epochs=2)
    enable_progress()
    bus = progress_bus()
    cursor = bus.latest_seq()                     # ignore prior traffic
    rid = svc.submit(specs, tenant="team-a")
    svc.flush()
    res = svc.result(rid)
    events, _ = bus.watch(cursor=cursor, watch_id=f"req-{rid}")
    assert [e.kind for e in events] == ["flush"]
    ev = events[0]
    assert ev.tenant == "team-a" and ev.rows == (0, 1, 2)
    for row in ev.rows:
        streamed = np.asarray(ev.losses[row], np.float32)
        np.testing.assert_array_equal(streamed, res.histories[row])
        np.testing.assert_array_equal(
            np.asarray(ev.loss_deltas[row], np.float32),
            np.diff(res.histories[row]).astype(np.float32))


def test_publishing_is_off_by_default(obj):
    svc = SweepService(obj, epochs=1)
    bus = progress_bus()
    before = bus.latest_seq()
    rid = svc.submit(_specs([5]))
    svc.flush()
    svc.result(rid)
    assert bus.latest_seq() == before


def test_run_job_slice_events_and_watchdog_resume(obj, tmp_path):
    """run_job publishes one ``slice`` event per dispatched group (losses
    == the checkpointed, watchdog-amended histories) plus ``done``; and a
    PREEMPTED job resumed by a fresh service keeps its frozen rows — the
    truncation is checkpoint state, not service memory."""
    specs = (_specs([0, 1]) + _specs([99], step_size=BAD_STEP)
             + _specs([7], inner_steps=50))      # 2 compiled groups
    ckpt = Checkpointer(str(tmp_path))
    enable_progress()
    bus = progress_bus()
    cursor = bus.latest_seq()

    svc = SweepService(obj, epochs=2, watchdog=Watchdog(policy="cancel_row"))
    res, done = svc.run_job(specs, 2, checkpointer=ckpt, max_groups=1,
                            progress_id="job-test")
    assert res is None and not done               # preempted after slice 1

    # a NEW service (fresh process stand-in) finishes from the checkpoint
    svc2 = SweepService(obj, epochs=2,
                        watchdog=Watchdog(policy="cancel_row"))
    res, done = svc2.run_job(specs, 2, checkpointer=ckpt,
                             progress_id="job-test")
    assert done
    np.testing.assert_array_equal(res.diverged_rows, [-1, -1, 0, -1])
    assert res.epochs_per_row.tolist() == [2, 2, 0, 2]

    events, _ = bus.watch(cursor=cursor, watch_id="job-test")
    kinds = [e.kind for e in events]
    assert kinds == ["slice", "slice", "done"]
    assert events[0].slices_total == events[1].slices_total == 2
    assert {events[0].slice_index, events[1].slice_index} == {0, 1}
    seen = {}
    for ev in events[:2]:
        for row, losses in zip(ev.rows, ev.losses):
            seen[row] = losses
    assert set(seen) == {0, 1, 2, 3}
    for row, losses in seen.items():
        budget = int(res.epochs_per_row[row])
        np.testing.assert_array_equal(np.asarray(losses, np.float32),
                                      res.histories[row, :budget + 1])
    assert events[0].diverged == (2,) or events[1].diverged == (2,)

    # the survivors match a watchdog-off run of the healthy specs
    ref = run_sweep(obj, 2, _specs([0, 1]) + _specs([7], inner_steps=50))
    for row, ref_row in ((0, 0), (1, 1), (3, 2)):
        np.testing.assert_array_equal(res.histories[row],
                                      ref.histories[ref_row])
        np.testing.assert_array_equal(res.final_w[row],
                                      ref.final_w[ref_row])


# ------------------------------------------------------- end-to-end over HTTP
def test_live_watch_job_over_http_acceptance(obj):
    """The acceptance path: a multi-slice job submitted over HTTP with a
    poisoned row, streamed via ``GET /watch?id=job-N`` WHILE it runs.
    Asserts (a) a slice event arrives before the job completes, (b) every
    streamed loss equals the final result's histories bit-for-bit,
    (c) the watchdog cancels exactly the poisoned row while survivors
    stay bit-identical to a watchdog-off in-process run.

    Both groups use inner_steps no other test shares (21, 61) so each
    slice pays a cold compile: after slice 1 streams, slice 2 is still
    seconds away in XLA — a guaranteed window to observe the job live."""
    from repro.server import FlushPolicy, SweepClient, SweepServer

    good = _specs([0, 1], inner_steps=21) + _specs([7], inner_steps=61)
    specs = (good[:2] + _specs([99], step_size=BAD_STEP, inner_steps=21)
             + good[2:])
    svc = SweepService(obj, epochs=2, watchdog=Watchdog(policy="cancel_row"))
    enable_progress()
    with SweepServer(svc, policy=FlushPolicy(max_delay_ms=10)) as server:
        client = SweepClient(server.url, poll_s=5.0)
        job = client.submit_job(specs, 2, tenant="team-a")
        watch_id = job["watch_id"]
        assert watch_id == f"job-{job['job_id']}"

        events, cursor, pending_after_first_slice = [], 0, False
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            got = client.watch(watch_id, cursor=cursor, timeout_s=0.25)
            assert got["enabled"] is True
            cursor = got["cursor"]
            events.extend(got["events"])
            done_seen = any(e["kind"] == "done" for e in events)
            if events and not done_seen and not pending_after_first_slice:
                # (a) live: the first slice streamed while the job still
                # had the second group to compile and dispatch
                with pytest.raises(TimeoutError):
                    client.job_result(job["job_id"], timeout=0.05)
                pending_after_first_slice = True
            if done_seen:
                break
        res = client.job_result(job["job_id"], timeout=300)

    kinds = [e["kind"] for e in events]
    assert pending_after_first_slice and kinds[-1] == "done"
    assert kinds.count("slice") == 2              # one per compiled group
    assert all(e["tenant"] == "team-a" for e in events)

    # (b) streamed losses == final histories, bit for bit
    seen = {}
    for e in events:
        for row, losses in zip(e["rows"], e["losses"]):
            seen[row] = losses
    assert set(seen) == {0, 1, 2, 3}
    for row, losses in seen.items():
        budget = int(res.epochs_per_row[row])
        np.testing.assert_array_equal(np.asarray(losses, np.float32),
                                      res.histories[row, :budget + 1])

    # (c) the poisoned row was cancelled; survivors bit-identical to the
    # watchdog-off in-process reference
    np.testing.assert_array_equal(res.diverged_rows, [-1, -1, 0, -1])
    assert res.epochs_per_row.tolist() == [2, 2, 0, 2]
    assert np.isfinite(res.histories[2]).all()
    ref = run_sweep(obj, 2, good)
    for row, ref_row in ((0, 0), (1, 1), (3, 2)):
        np.testing.assert_array_equal(res.histories[row],
                                      ref.histories[ref_row])
        np.testing.assert_array_equal(res.final_w[row],
                                      ref.final_w[ref_row])
