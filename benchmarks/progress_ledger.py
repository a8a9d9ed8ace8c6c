"""Live-observability smoke: progress streaming + the divergence watchdog.

Runs one multi-group job through ``SweepService.run_job`` with the live
progress bus and the divergence watchdog on, and writes the schema-gated
``BENCH_progress_ledger.json``:

  * ``progress`` — what the live stream delivered: slice events BEFORE
    the job finished, and per-row event losses that match the final
    `SweepResult` histories bit-for-bit (checked here, hard failure).
  * ``watchdog`` — one deliberately diverging row (``step_size=1e30``
    NaNs on epoch 1) cancelled by ``cancel_row`` while every survivor
    stays bit-identical; the artifact records the cancelled count.

Two groups come from two ``inner_steps`` values (the group key includes
the per-epoch update count), so the job takes more than one slice.
"""
from __future__ import annotations

import sys
import tempfile

import numpy as np

from benchmarks.artifacts import write_bench_json
from repro.checkpoint import Checkpointer
from repro.core import LogisticRegression, SweepSpec
from repro.data.libsvm import make_synthetic_libsvm
from repro.obs.progress import disable_progress, enable_progress, \
    progress_bus
from repro.obs.watchdog import Watchdog
from repro.service import SweepService

WATCH_ID = "bench-progress-ledger"


def _specs(rows_per_group: int):
    """Two compiled groups (inner_steps 23 vs 46) plus one row that
    diverges immediately — same group as the first, so the watchdog's re-dispatch
    is a cache hit, not a new compile."""
    good = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                      num_threads=4, inner_steps=steps, seed=7 * c + steps)
            for steps in (23, 46) for c in range(rows_per_group)]
    bad = [SweepSpec(scheme="inconsistent", step_size=1e30, tau=3,
                     num_threads=4, inner_steps=23, seed=999)]
    return good + bad


def run(quick: bool = False) -> dict:
    ds = make_synthetic_libsvm("real-sim", seed=11,
                               scale=0.002 if quick else 0.01)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    epochs = 2 if quick else 3
    specs = _specs(rows_per_group=2 if quick else 4)

    svc = SweepService(obj, epochs=epochs,
                       watchdog=Watchdog(policy="cancel_row"))
    enable_progress()
    bus = progress_bus()
    bus.clear()
    try:
        events = []
        cursor = 0
        with tempfile.TemporaryDirectory() as spool:
            ckpt = Checkpointer(spool)
            done = False
            while not done:
                # one group per slice: every boundary publishes an event
                res, done = svc.run_job(specs, epochs, checkpointer=ckpt,
                                        max_groups=1,
                                        progress_id=WATCH_ID)
                got, cursor = bus.watch(cursor=cursor, watch_id=WATCH_ID,
                                        timeout=0.0)
                events.extend(got)
                if not done and not any(e.kind == "slice" for e in events):
                    raise AssertionError(
                        "no slice event arrived before job completion — "
                        "the live stream is not live")

        kinds = [e.kind for e in events]
        if kinds.count("done") != 1 or "slice" not in kinds:
            raise AssertionError(f"unexpected event stream {kinds}")

        # the stream must be exact, not approximate: per-row losses in the
        # final slice events == the result histories, bit for bit
        last_loss = {}
        for e in events:
            for row, losses in zip(e.rows, e.losses):
                last_loss[row] = losses
        for row, losses in last_loss.items():
            budget = int(res.epochs_per_row[row])
            want = res.histories[row, :budget + 1]
            got = np.asarray(losses, np.float32)
            if not np.array_equal(got, want):
                raise AssertionError(
                    f"row {row}: streamed losses diverge from the final "
                    f"histories ({got} vs {want})")

        diverged = np.flatnonzero(res.diverged_rows >= 0)
        if diverged.tolist() != [len(specs) - 1]:
            raise AssertionError(
                f"watchdog should cancel exactly the step_size=1e30 row, "
                f"got diverged rows {diverged.tolist()}")

        return {
            "dataset": "real-sim", "epochs": epochs, "rows": len(specs),
            "progress": {
                "watch_id": WATCH_ID,
                "events": len(events),
                "slice_events": kinds.count("slice"),
                "losses_bit_exact": True,
            },
            "watchdog": {
                "policy": "cancel_row",
                "diverged_rows": diverged.tolist(),
                "survivors": int(len(specs) - len(diverged)),
            },
        }
    finally:
        disable_progress(clear=True)


def main(quick: bool = True):
    out = run(quick=quick)
    write_bench_json("progress_ledger", out)
    print("name,us_per_call,derived")
    print(f"progress_events,0,slices={out['progress']['slice_events']};"
          f"diverged={out['watchdog']['diverged_rows']}")


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
