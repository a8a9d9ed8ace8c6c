"""Fused Pallas sweep-epoch megakernel vs the vmap engine, self-gating.

For each group shape the SAME sweep runs twice — ``engine_mode="vmap"``
(the XLA-batched scan) and ``engine_mode="fused"`` (one Pallas launch per
group, rows on the grid) — and the benchmark ASSERTS the results match
before recording a single timing: in interpret mode the fused path must be
BIT-EXACT to the vmap path (the two bodies execute the same per-row
epochs-scan functions), so any drift is a correctness regression and this
benchmark fails the CI job rather than logging a delta. On a real
accelerator (compiled Mosaic lowering) the gate relaxes to allclose.

The artifact pairs measured times with the roofline-predicted intensity
headroom (`repro.launch.roofline.sweep_epoch_roofline`). Even under the
Pallas INTERPRETER on XLA:CPU the fused path wins (~2-3x on the CI
shapes): the grid loop executes one row's whole epochs-scan at a time, so
the working set is a single row's carry instead of the vmap path's
batched [rows, buf_len+2, d] carry streaming through memory every update
— a scaled-down preview of the VMEM-residency argument. The full
predicted headroom (~13x intensity) is what the compiled TPU path banks;
the real-accelerator revalidation item checks the prediction.

Writes ``BENCH_kernel_sweep.json`` (uploaded by the CI ``kernels-interpret``
job as ``bench-json-kernels``). ``--quick`` shrinks shapes for CI;
``--interpret`` pins ``REPRO_KERNEL_MODE=interpret`` so the run is
reproducible off-CI regardless of backend.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

from benchmarks.artifacts import write_bench_json
from repro.core import LogisticRegression, SweepSpec, plan_sweep, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.kernels.dispatch import KERNEL_MODE_ENV, fused_sweep_mode
from repro.launch.roofline import sweep_epoch_roofline

_SCHEMES = ("consistent", "inconsistent", "unlock")


def _group_shapes(quick: bool):
    """(label, rows, inner_steps, epochs) — ≥2 shapes per run: one wide
    (many config rows, the service-coalescing regime) and one deep (few
    rows, long inner scans, the single-tenant convergence regime)."""
    if quick:
        return [("wide", 8, 20, 2), ("deep", 3, 60, 3)]
    return [("wide", 16, 100, 3), ("deep", 4, 400, 4)]


def _specs(rows: int, inner_steps: int, engine_mode: str):
    return [SweepSpec(scheme=_SCHEMES[c % 3], step_size=0.1, tau=2,
                      num_threads=4, inner_steps=inner_steps, seed=c,
                      engine_mode=engine_mode)
            for c in range(rows)]


def _time(fn, reps: int) -> float:
    fn()                                   # warm: compile + cache the runner
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def run(quick: bool = False):
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    mode = fused_sweep_mode()
    reps = 2 if quick else 3
    shapes = []
    for label, rows, inner, epochs in _group_shapes(quick):
        vmap_specs = _specs(rows, inner, "vmap")
        fused_specs = _specs(rows, inner, "fused")
        plan = plan_sweep(obj, epochs, fused_specs)
        # one group per scheme, all of one compiled shape
        key_, *_ = plan.groups
        total, buf_len = key_.total, key_.buf_len
        assert all(k.fused for k in plan.groups), \
            "fused specs must plan onto the fused group key"

        r_vmap = run_sweep(obj, epochs, vmap_specs)
        r_fused = run_sweep(obj, epochs, fused_specs)
        # ---- the gate: parity BEFORE any timing is recorded -------------
        if mode == "interpret":
            np.testing.assert_array_equal(
                r_fused.histories, r_vmap.histories,
                err_msg=f"[{label}] fused histories diverged from vmap "
                        "(interpret mode must be bit-exact)")
            np.testing.assert_array_equal(
                r_fused.final_w, r_vmap.final_w,
                err_msg=f"[{label}] fused final iterates diverged from vmap")
        else:
            np.testing.assert_allclose(r_fused.histories, r_vmap.histories,
                                       rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(r_fused.final_w, r_vmap.final_w,
                                       rtol=1e-5, atol=1e-6)

        vmap_s = _time(lambda: run_sweep(obj, epochs, vmap_specs), reps)
        fused_s = _time(lambda: run_sweep(obj, epochs, fused_specs), reps)
        roof = sweep_epoch_roofline(rows=rows, dim=obj.flat_dim, total=total,
                                    epochs=epochs, buf_len=buf_len)
        shapes.append({
            "label": label, "rows": rows, "inner_steps": total,
            "epochs": epochs, "dim": obj.flat_dim, "buf_len": buf_len,
            "vmap_s": vmap_s, "fused_s": fused_s,
            "measured_speedup": vmap_s / fused_s,
            "parity": "bit-exact" if mode == "interpret" else "allclose",
            "roofline": roof,
        })
    return {
        "backend": jax.default_backend(),
        "fused_mode": mode,
        "shapes": shapes,
    }


def main(quick: bool = True, interpret: bool = False):
    if interpret:
        os.environ[KERNEL_MODE_ENV] = "interpret"
    out = run(quick=quick)
    write_bench_json("kernel_sweep", out)
    print("name,us_per_call,derived")
    for s in out["shapes"]:
        tag = f"kernel_sweep_{s['label']}_{s['rows']}x{s['inner_steps']}"
        print(f"{tag}_vmap,{s['vmap_s'] * 1e6:.1f},parity={s['parity']}")
        print(f"{tag}_fused,{s['fused_s'] * 1e6:.1f},"
              f"mode={out['fused_mode']};"
              f"measured_speedup={s['measured_speedup']:.3f};"
              f"roofline_headroom="
              f"{s['roofline']['intensity_headroom']:.1f};"
              f"roofline_speedup="
              f"{s['roofline']['predicted_speedup']:.2f}")


if __name__ == "__main__":
    main(quick="--quick" in sys.argv, interpret="--interpret" in sys.argv)
