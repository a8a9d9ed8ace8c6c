"""Async sweep serving demo: flush daemon + 3 tenants with mixed
priorities over HTTP, plus a time-sliced giant job.

Four scenes on one server (the paper's logistic-regression workload):

  1. BOOT — `SweepServer` = service + background flush daemon (size /
     deadline `FlushPolicy`, stable batch widths) + stdlib HTTP listener,
     with a `FairShare` admission policy: an *interactive* tenant in a
     high priority class, a weight-2 *batch* tenant, and a weight-1
     *bulk* tenant.
  2. ASYNC SERVING — the three tenants submit concurrently over HTTP and
     just wait on their results: nobody calls flush(); the daemon's
     deadline fires once and serves everyone from ONE coalesced dispatch,
     each result bit-identical to a standalone `run_sweep` (asserted).
  3. WARM PATH — a second wave of same-shape probes: the runner cache +
     width registry serve it with ZERO new compiles.
  4. GIANT JOB — bulk's 3-group grid runs group-by-group through the
     checkpointed ``run_job(max_groups=1)`` lane while interactive's
     small requests keep landing in between (time-slicing: the giant
     cannot starve the queue).

    PYTHONPATH=src python examples/serve_sweeps.py
"""
import threading

import numpy as np

from repro.core import LogisticRegression, SweepSpec, make_grid, run_sweep
from repro.data.libsvm import make_synthetic_libsvm
from repro.server import (FairShare, FlushPolicy, SweepClient, SweepServer,
                          snapshot)
from repro.service import SweepService, cache_stats, clear_cache
from repro.utils.compile_cache import enable_compile_cache


def main():
    ds = make_synthetic_libsvm("rcv1", scale=0.03)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    clear_cache()

    # ---- 1. boot: service + daemon + HTTP listener ----------------------
    fair = FairShare(quantum_rows=8, max_rows_per_flush=32)
    fair.set_tenant("interactive", priority=1)       # drains strictly first
    fair.set_tenant("batch", weight=2.0)             # 2x bulk's fair share
    fair.set_tenant("bulk", weight=1.0)
    svc = SweepService(obj, epochs=3)
    server = SweepServer(svc, policy=FlushPolicy(max_rows=24,
                                                 max_delay_ms=30),
                         fairness=fair).start()
    print(f"serving sweeps on {server.url} "
          f"(deadline 30ms, fair-share quanta {fair.quantum_rows} rows)\n")

    # ---- 2. three tenants submit concurrently; the daemon flushes -------
    grids = {
        "interactive": make_grid(schemes=("inconsistent",), seeds=(1,),
                                 step_sizes=(1.0,), taus=(9,),
                                 num_threads=10),
        "batch": make_grid(schemes=("unlock", "consistent"), seeds=(2, 3),
                           step_sizes=(1.0,), taus=(9,), num_threads=10),
        "bulk": make_grid(schemes=("consistent",), seeds=(4,),
                          step_sizes=(0.5, 1.0), taus=(9,),
                          num_threads=10),
    }
    results = {}

    def tenant(name, specs):
        client = SweepClient(server.url)
        rid = client.submit(specs, tenant=name,
                            priority=1 if name == "interactive" else 0)
        results[name] = client.result(rid, timeout=600)

    threads = [threading.Thread(target=tenant, args=item)
               for item in grids.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = svc.stats()
    print(f"3 tenants, {stats.rows_submitted} rows -> {stats.flushes} "
          f"daemon flush(es), {stats.rows_coalesced} rows coalesced "
          "across tenants; nobody called flush()")
    for name, specs in grids.items():
        np.testing.assert_array_equal(results[name].histories,
                                      run_sweep(obj, 3, specs).histories)
    print("every tenant's HTTP result bit-identical to its own "
          "run_sweep\n")

    # ---- 3. warm path: a second wave costs zero compiles ----------------
    base = cache_stats()
    client = SweepClient(server.url)
    rid = client.submit(make_grid(schemes=("inconsistent",), seeds=(9,),
                                  step_sizes=(2.0,), taus=(9,),
                                  num_threads=10), tenant="interactive",
                        priority=1)
    client.result(rid, timeout=600)
    print(f"warm same-shape probe: {cache_stats().since(base).compiles} "
          "new compiles (runner cache + stable widths)\n")

    # ---- 4. giant job time-sliced between flushes -----------------------
    giant = (make_grid(schemes=("unlock",), seeds=(5, 6), step_sizes=(1.0,),
                       taus=(9,), num_threads=10)
             + [SweepSpec(algo="svrg", step_size=1.0, num_threads=1),
                SweepSpec(algo="hogwild", scheme="unlock", step_size=1.0,
                          tau=9, num_threads=10)])
    handle = server.daemon.submit_job(giant, tenant="bulk")
    rid = client.submit(grids["interactive"], tenant="interactive",
                        priority=1)
    client.result(rid, timeout=600)          # lands between job slices
    res = handle.result(timeout=600)
    np.testing.assert_array_equal(res.histories,
                                  run_sweep(obj, 3, giant).histories)
    print(f"bulk's {len(giant)}-row job ran in {handle.slices} "
          "checkpointed slices while interactive kept being served; "
          "job result bit-identical to one run_sweep")

    snap = snapshot(svc, server.daemon, fair)
    print(f"\nmetrics: flush p50/p95 "
          f"{snap['flush_latency']['p50_ms']:.0f}/"
          f"{snap['flush_latency']['p95_ms']:.0f} ms, request p50/p95 "
          f"{snap['request_latency']['p50_ms']:.0f}/"
          f"{snap['request_latency']['p95_ms']:.0f} ms, per-tenant rows "
          f"{ {t: v['rows_completed'] for t, v in snap['tenants'].items()} }")
    server.stop()


if __name__ == "__main__":
    enable_compile_cache()
    main()
