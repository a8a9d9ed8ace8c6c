"""Sweep service demo: multi-tenant coalescing + checkpoint-resumable jobs.

Three scenes on one objective (the paper's logistic-regression workload):

  1. WARM CACHE — the same grid swept twice; the second call fetches its
     compiled runners from the persistent cache (repro.service.cache) and
     compiles nothing.
  2. COALESCING — three logical clients submit compatible grids; one
     `flush` merges their rows into shared compiled groups and each client
     gets back exactly what a standalone `run_sweep` of its own specs
     would return (bit-identical — asserted below).
  3. CHECKPOINT-RESUME — a long sweep job dispatched group by group
     through `repro.checkpoint.Checkpointer`, preempted after every group
     (``max_groups=1``) and resumed until done; the assembled result is
     again bit-identical to one uninterrupted `run_sweep`.

    PYTHONPATH=src python examples/sweep_service.py
"""
import tempfile

import numpy as np

from repro.checkpoint import Checkpointer
from repro.core import (LogisticRegression, SweepSpec, make_grid, run_sweep,
                        svrg_sweep_spec)
from repro.data.libsvm import make_synthetic_libsvm
from repro.service import SweepService, cache_stats, clear_cache
from repro.utils.compile_cache import enable_compile_cache


def main():
    ds = make_synthetic_libsvm("rcv1", scale=0.03)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    print(f"dataset rcv1-like: n={obj.n} p={obj.p}\n")

    # ---- 1. warm cache: second same-shape sweep compiles nothing --------
    clear_cache()
    grid = make_grid(seeds=(0, 1), step_sizes=(1.0,), taus=(9,),
                     num_threads=10)
    run_sweep(obj, 3, grid)
    cold = cache_stats()
    run_sweep(obj, 3, grid)
    warm = cache_stats().since(cold)
    print(f"cold sweep: {cold.compiles} compiles; "
          f"repeat: {warm.compiles} compiles, {warm.hits} cache hits\n")

    # ---- 2. three tenants, one coalesced dispatch -----------------------
    svc = SweepService(obj, epochs=3)
    rid_a = svc.submit(make_grid(schemes=("inconsistent",), seeds=(3, 4),
                                 step_sizes=(1.0, 2.0), taus=(9,),
                                 num_threads=10))
    specs_b = [SweepSpec(scheme="inconsistent", step_size=1.0, tau=9,
                         num_threads=10, seed=5),
               svrg_sweep_spec(step_size=1.0)]
    rid_b = svc.submit(specs_b)
    rid_c = svc.submit([SweepSpec(algo="hogwild", scheme="unlock",
                                  step_size=1.0, tau=9, num_threads=10,
                                  epochs=9)])
    svc.flush()
    stats = svc.stats()
    print(f"3 requests, {stats.rows_submitted} rows -> "
          f"{stats.groups_dispatched} dispatched groups "
          f"({stats.rows_coalesced} rows coalesced across requests, "
          f"cache hit rate {stats.cache_hit_rate:.0%})")
    for rid, name in ((rid_a, "tenant A"), (rid_b, "tenant B"),
                      (rid_c, "tenant C")):
        res = svc.result(rid)
        gaps = ", ".join(f"{res.curve(c)[1][-1]:.4f}"
                         for c in range(len(res.specs)))
        print(f"  {name}: final losses [{gaps}]")

    # each tenant's demuxed result == its own standalone run_sweep
    res_b = svc.result(rid_b)
    base_b = run_sweep(obj, 3, specs_b)
    np.testing.assert_array_equal(res_b.histories, base_b.histories)
    print("  demuxed results bit-identical to standalone run_sweep\n")

    # ---- 3. checkpoint-resumable job ------------------------------------
    job_specs = grid + [svrg_sweep_spec(step_size=1.0),
                        SweepSpec(algo="hogwild", scheme="inconsistent",
                                  step_size=1.0, tau=9, num_threads=10)]
    with tempfile.TemporaryDirectory() as ckpt_dir:
        calls, done, res = 0, False, None
        while not done:
            # a fresh Checkpointer each call simulates process restarts
            res, done = svc.run_job(job_specs, epochs=3,
                                    checkpointer=Checkpointer(ckpt_dir),
                                    max_groups=1)
            calls += 1
        base = run_sweep(obj, 3, job_specs)
        np.testing.assert_array_equal(res.histories, base.histories)
        print(f"job of {len(job_specs)} rows survived {calls - 1} "
              "preemptions; resumed result bit-identical to one "
              "uninterrupted run_sweep")


if __name__ == "__main__":
    enable_compile_cache()
    main()
