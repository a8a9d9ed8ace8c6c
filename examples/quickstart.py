"""Quickstart: AsySVRG on the paper's own workload (logistic regression).

Reproduces the core claim in ~30 seconds on CPU: AsySVRG (all three reading
schemes) converges linearly and beats Hogwild! per effective pass. EVERY
scenario here runs in ONE `run_sweep` call on the multi-algorithm sweep
engine (repro.core.sweep): the three AsySVRG schemes, the serial-SVRG
baseline (``algo="svrg"``, the τ=0 degenerate case on the same engine), AND
the Hogwild! baseline (``algo="hogwild"``, γ-decay inside the compiled
scan) — the Hogwild! row carries its own 3× per-row ``epochs`` budget (1
pass/epoch vs AsySVRG's ~3) via the masked-epoch axis, so equal effective
passes no longer need a second call. Adding a scenario is one more
SweepSpec row — no new compiles, no new driver code. On a multi-device
host, pass ``mesh=make_sweep_mesh()`` to shard the rows across devices.

Serving sweeps: re-running grids is as cheap as running them — every
dispatch goes through the persistent compiled-runner cache
(`repro.service.cache`), so a second same-shape sweep compiles nothing —
and the serving tier (`repro.server`) makes the whole thing a deployable
HTTP service: clients submit over the wire and a background flush daemon
coalesces tenants' specs into shared compiled dispatches on a deadline
policy, nobody ever calling flush() (see the "serving sweeps" section
below; examples/serve_sweeps.py is the full multi-tenant demo with
priorities and a time-sliced giant job, examples/sweep_service.py the
in-process + checkpoint-resume one).

Fused kernel path: every group can also run as ONE Pallas megakernel
launch (`engine_mode="fused"` per spec, or ``REPRO_SWEEP_ENGINE=fused``
process-wide) with the config rows mapped onto the kernel grid — bit-exact
to the default vmap engine in interpret mode; see the "fused kernel path"
section below for when it profits and how to read the benchmark.

Bring your own objective: the engine is not married to logistic regression.
Subclass `repro.core.Objective` with three math methods (fixed-order loss,
stable full gradient, stable per-sample gradient — see the class docstring
for the bitwise-stability rules) and every layer above works unchanged:
sweeps, the runner cache, coalescing, checkpoint-resume and the HTTP tier.
The last section below onboards a ridge-regression objective in ~25 lines;
examples/nonconvex_sweep.py does the same for an MLP language model
(pytree params) and a nonconvex clipped-penalty logistic through the
sweep service.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (LogisticRegression, Objective, SweepSpec, make_grid,
                        run_sweep, svrg_sweep_spec)
from repro.core.objective import _fixed_order_sum
from repro.data.libsvm import make_synthetic_libsvm
from repro.server import FlushPolicy, SweepClient, SweepServer
from repro.service import SweepService, cache_stats
from repro.utils.compile_cache import enable_compile_cache


class Ridge(Objective):
    """Least squares + l2 — a complete bring-your-own objective.

    The whole protocol: hand the engine your data (`data_args`), an initial
    parameter pytree (`init_params`, here a bare vector), and the three math
    methods. Reduces stay elementwise/trailing-axis or fixed-order
    (`_fixed_order_sum`, lax.scan) so every engine bit-exactness guarantee
    — coalescing, sharding, wire round-trips — holds for free.
    """

    def __init__(self, X, y, l2: float = 1e-3):
        self.X, self.y, self.l2 = jnp.asarray(X), jnp.asarray(y), float(l2)
        self.n, self.p = self.X.shape

    def data_args(self):
        return (self.X, self.y, jnp.float32(self.l2))

    def init_params(self):
        return jnp.zeros(self.p)

    def static_key(self):
        return ()

    def loss_fixed_order(self, data, w):
        X, y, l2 = data
        r = jnp.sum(X * w, axis=-1) - y          # stable row-wise matvec
        return (_fixed_order_sum(0.5 * r * r) / X.shape[0]
                + 0.5 * l2 * _fixed_order_sum(w * w))

    def full_grad_stable(self, data, w):
        X, y, l2 = data
        r = jnp.sum(X * w, axis=-1) - y
        return jnp.sum(r[:, None] * X, axis=0) / X.shape[0] + l2 * w

    def sample_grad_stable(self, data, i, w):
        X, y, l2 = data
        return (jnp.sum(X[i] * w) - y[i]) * X[i] + l2 * w


def main():
    ds = make_synthetic_libsvm("rcv1", scale=0.05)
    obj = LogisticRegression(ds.X, ds.y, l2_reg=1e-3)
    _, f_star = obj.optimum(max_iter=3000)
    print(f"dataset rcv1-like: n={obj.n} p={obj.p}  f*={f_star:.6f}\n")

    # AsySVRG × 3 schemes + serial SVRG + pass-matched Hogwild!, one call:
    # 6 epochs × ~3 passes for the SVRG family, 18 × 1 for Hogwild!
    specs = make_grid(schemes=("consistent", "inconsistent", "unlock"),
                      seeds=(0,), step_sizes=(2.0,), taus=(9,),
                      num_threads=10)
    specs += [svrg_sweep_spec(step_size=2.0)]
    specs += [SweepSpec(algo="hogwild", scheme="unlock", step_size=2.0,
                        num_threads=10, tau=9, epochs=18)]
    res = run_sweep(obj, 6, specs)

    print(f"{'method':28s} {'passes':>7s} {'final gap':>12s}")
    for c, spec in enumerate(res.specs):
        name = {"svrg": "SVRG-serial",
                "hogwild": f"Hogwild!-{spec.scheme}"}.get(
                    spec.algo, f"AsySVRG-{spec.scheme}")
        passes, hist = res.curve(c)
        gap = hist[-1] - f_star
        print(f"{name:28s} {passes[-1]:7.0f} {gap:12.3e}")

    print("\nAsySVRG reaches a much smaller gap at EQUAL effective passes —")
    print("the paper's Figure 1 (right) in one table, from one compile-set.")

    # ---- fused kernel path: the SAME grid as one Pallas megakernel
    # launch per group — rows on the kernel grid, the whole multi-epoch
    # scan inside one launch so the iterate/snapshot/anchor state stays
    # kernel-resident instead of streaming through memory every update.
    # Flip it per spec (engine_mode="fused") or process-wide with
    # REPRO_SWEEP_ENGINE=fused; off TPU it runs under the Pallas
    # interpreter, BIT-EXACT to the vmap engine (asserted here). It
    # profits when groups are wide or scans deep (the memory-bound
    # regime): `python -m benchmarks.kernel_sweep` records measured
    # vmap-vs-fused times next to the roofline-predicted intensity
    # headroom (repro.launch.roofline.sweep_epoch_roofline) per shape.
    import dataclasses

    import numpy as np
    fused = run_sweep(obj, 6, [dataclasses.replace(s, engine_mode="fused")
                               for s in specs])
    assert np.array_equal(fused.histories, res.histories)
    print("\nfused megakernel path: same grid, one launch per group, "
          "bit-exact to the vmap engine")

    # ---- serving sweeps: the same shapes again, served over HTTP. Two
    # tenants submit to a SweepServer and simply wait: the background
    # flush daemon's 25ms deadline fires once, their 2+1 rows coalesce
    # into ONE 3-row compiled group — the exact shape the 3-scheme grid
    # above already compiled — so the dispatch fetches the cached runner
    # and compiles NOTHING. Results come back over the wire bit-identical
    # to an in-process run_sweep.
    base = cache_stats()
    with SweepServer(SweepService(obj, epochs=6),
                     policy=FlushPolicy(max_rows=24,
                                        max_delay_ms=25)) as server:
        client = SweepClient(server.url)
        rid_a = client.submit(make_grid(schemes=("inconsistent",),
                                        seeds=(1, 2), step_sizes=(2.0,),
                                        taus=(9,), num_threads=10),
                              tenant="team-a")
        rid_b = client.submit(make_grid(schemes=("unlock",), seeds=(3,),
                                        step_sizes=(1.0,), taus=(9,),
                                        num_threads=10), tenant="team-b")

        def best_gap(res):
            return min(res.curve(c)[1][-1] - f_star
                       for c in range(len(res.specs)))

        gap_a = best_gap(client.result(rid_a, timeout=600))
        gap_b = best_gap(client.result(rid_b, timeout=600))
        stats = client.stats()

    s, q = stats["service"], stats["request_latency"]
    print(f"\nserving sweeps over HTTP: 2 tenants, {s['rows_submitted']} "
          f"rows -> {s['flushes']} deadline flush, "
          f"{s['rows_coalesced']} rows coalesced, "
          f"{cache_stats().since(base).compiles} new compile(s), "
          f"request p95 {q['p95_ms']:.0f} ms")
    print(f"  team-a best gap {gap_a:.3e}, team-b best gap {gap_b:.3e}"
          "  (each bit-identical to its own run_sweep)")

    # ---- bring your own objective: the Ridge class above through the
    # SAME engine — same specs, same compiled-path machinery, zero new
    # driver code. Pytree-param objectives work identically (see
    # examples/nonconvex_sweep.py for an MLP through the service tier).
    key = jax.random.PRNGKey(0)
    Xr = jax.random.normal(key, (512, 64)) / 8.0
    yr = jnp.sum(Xr[:, :4], axis=-1)             # planted linear signal
    ridge = Ridge(Xr, yr, l2=1e-3)
    rspecs = [SweepSpec(scheme="inconsistent", step_size=s, tau=3,
                        num_threads=4) for s in (0.5, 1.0)]
    rres = run_sweep(ridge, 4, rspecs)
    print("\nbring-your-own objective (ridge regression), same engine:")
    for c, spec in enumerate(rres.specs):
        print(f"  step={spec.step_size:3.1f}: loss "
              f"{rres.histories[c, 0]:.4f} -> {rres.histories[c, -1]:.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
