"""Device-sharded sweep equivalence suite (forced multi-device CPU).

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``tier1-multidevice`` job); module-skips on a single-device host so the
plain tier-1 run stays green everywhere.

Contract under test: sharding `run_sweep`'s config-row axis over the mesh
`data` axis (shard_map, no cross-row collectives) is BIT-IDENTICAL per row
to the single-device vmapped path — for every algo, for group sizes that
divide the device count and sizes that need padding, and composed with
masked per-row epochs. This is the XLA:CPU calibration of the bit-exactness
contract; re-validate per backend before trusting it on TPU/GPU.
"""
import jax
import numpy as np
import pytest

from repro.core import (LogisticRegression, SweepSpec, run_asysvrg,
                        run_hogwild, run_sweep)
from repro.data.libsvm import make_synthetic_libsvm
from repro.launch.mesh import make_sweep_mesh
from repro.sharding.context import mesh_context

if jax.device_count() < 2:
    pytest.skip("needs >= 2 devices (XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)",
                allow_module_level=True)

SCHEMES = ("consistent", "inconsistent", "unlock")


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


@pytest.fixture(scope="module")
def mesh():
    return make_sweep_mesh()


def _assert_same(res_a, res_b):
    np.testing.assert_array_equal(res_a.histories, res_b.histories)
    np.testing.assert_array_equal(res_a.final_w, res_b.final_w)
    np.testing.assert_array_equal(res_a.effective_passes,
                                  res_b.effective_passes)
    np.testing.assert_array_equal(res_a.total_updates, res_b.total_updates)
    np.testing.assert_array_equal(res_a.epochs_per_row, res_b.epochs_per_row)


def test_sharded_matches_unsharded_asysvrg_unpadded(obj, mesh):
    """Group size = a multiple of the device count (no padding): bit-equal
    per row across schemes / seeds / steps."""
    D = jax.device_count()
    specs = [SweepSpec(scheme=s, step_size=st, tau=3, num_threads=4,
                       inner_steps=25, seed=sd)
             for s in SCHEMES for sd in range(3) for st in (0.25, 0.5)][:2 * D]
    assert len(specs) % D == 0
    base = run_sweep(obj, 2, specs)
    shard = run_sweep(obj, 2, specs, mesh=mesh)
    _assert_same(base, shard)


@pytest.mark.parametrize("rows", [1, 5, 11])
def test_sharded_matches_unsharded_padded_group_sizes(obj, mesh, rows):
    """Group sizes that do NOT divide the device count: padding rows are
    computed and discarded without perturbing real rows."""
    specs = [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=c)
             for c in range(rows)]
    base = run_sweep(obj, 2, specs)
    shard = run_sweep(obj, 2, specs, mesh=mesh)
    _assert_same(base, shard)


def test_sharded_matches_unsharded_all_algos(obj, mesh):
    """Mixed asysvrg / hogwild / svrg grid: every engine's sharded groups
    reproduce the unsharded rows, which themselves match the sequential
    drivers."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=2,
                       num_threads=3, inner_steps=20, seed=1),
             SweepSpec(scheme="unlock", step_size=0.5, tau=2,
                       num_threads=3, inner_steps=20, seed=4),
             SweepSpec(algo="hogwild", scheme="unlock", step_size=0.5,
                       tau=2, num_threads=3, seed=2),
             SweepSpec(algo="hogwild", scheme="consistent", step_size=0.5,
                       tau=0, num_threads=3, seed=3),
             SweepSpec(algo="svrg", step_size=0.5, num_threads=1,
                       inner_steps=30, seed=5)]
    base = run_sweep(obj, 2, specs)
    shard = run_sweep(obj, 2, specs, mesh=mesh)
    _assert_same(base, shard)

    ref = run_asysvrg(obj, 2, specs[0].to_config(), seed=1)
    np.testing.assert_array_equal(np.asarray(ref.history, np.float32),
                                  shard.histories[0])
    ref_h = run_hogwild(obj, 2, 0.5, num_threads=3, scheme="unlock", tau=2,
                        seed=2)
    np.testing.assert_array_equal(np.asarray(ref_h.history, np.float32),
                                  shard.histories[2])


def test_sharded_masked_epochs_match_shorter_runs(obj, mesh):
    """Masked per-row epochs compose with sharding: each row of a sharded
    mixed-budget call equals an independent run of its own length."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=7, epochs=e)
             for e in (1, 2, 3)]
    shard = run_sweep(obj, 3, specs, mesh=mesh)
    for c, spec in enumerate(specs):
        seq = run_asysvrg(obj, spec.epochs, spec.to_config(), seed=7)
        np.testing.assert_array_equal(
            np.asarray(seq.history, np.float32),
            shard.histories[c, :spec.epochs + 1])
        np.testing.assert_array_equal(np.asarray(seq.w, np.float32),
                                      shard.final_w[c])


def test_fig1_paired_budgets_sharded_single_call(obj, mesh):
    """The Fig. 1 shape — AsySVRG E vs Hogwild! 3E — sharded, one call,
    identical to the unsharded single call."""
    E, p = 2, 4
    specs = ([SweepSpec(scheme=s, step_size=0.5, num_threads=p, tau=p - 1,
                        epochs=E) for s in ("inconsistent", "unlock")]
             + [SweepSpec(algo="hogwild", scheme=s, step_size=0.5,
                          num_threads=p, tau=p - 1, epochs=3 * E)
                for s in ("inconsistent", "unlock")])
    base = run_sweep(obj, E, specs)
    shard = run_sweep(obj, E, specs, mesh=mesh)
    _assert_same(base, shard)


def test_ambient_mesh_context_shards(obj, mesh):
    """`with mesh_context(mesh)` shards the sweep with no call-site mesh=
    argument (the launcher integration), with identical bits."""
    specs = [SweepSpec(scheme="consistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=s)
             for s in range(3)]
    explicit = run_sweep(obj, 2, specs, mesh=mesh)
    with mesh_context(mesh):
        ambient = run_sweep(obj, 2, specs)
    _assert_same(explicit, ambient)


def test_service_coalescing_sharded_bit_identical(obj, mesh):
    """The sweep service under a forced 8-device mesh: multi-request
    coalescing (all three algos, mixed per-row epochs, row counts needing
    padding) demuxes bit-identical to standalone `run_sweep` — sharded AND
    unsharded — and the second flush of the same shapes compiles nothing."""
    from repro.service import SweepService, cache_stats

    req_a = [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25, seed=c)
             for c in range(3)]
    req_b = [SweepSpec(scheme="unlock", step_size=0.25, tau=3,
                       num_threads=4, inner_steps=25, seed=9, epochs=1),
             SweepSpec(algo="hogwild", scheme="consistent", step_size=0.5,
                       tau=2, num_threads=3, seed=2),
             SweepSpec(algo="svrg", step_size=0.5, num_threads=1,
                       inner_steps=30, seed=5)]

    svc = SweepService(obj, epochs=2, mesh=mesh)
    rid_a, rid_b = svc.submit(req_a), svc.submit(req_b)
    svc.flush()
    for rid, specs in ((rid_a, req_a), (rid_b, req_b)):
        sharded = run_sweep(obj, 2, specs, mesh=mesh)
        unsharded = run_sweep(obj, 2, specs)
        got = svc.result(rid)
        _assert_same(got, sharded)
        _assert_same(got, unsharded)
    assert svc.stats().rows_coalesced > 0

    base = cache_stats()
    svc.submit(req_a)
    svc.submit(req_b)
    svc.flush()
    assert cache_stats().since(base).compiles == 0


def test_http_server_sharded_bit_identical(obj, mesh):
    """Acceptance (serving tier): results served over HTTP from a SHARDED
    service — background deadline flush, wire round-trip and all — are
    bit-identical to in-process `run_sweep`, sharded and unsharded, for
    every tenant under the forced 8-device mesh."""
    from repro.server import FlushPolicy, SweepClient, SweepServer
    from repro.service import SweepService

    tenants = {
        "team-a": [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.5, tau=3,
                             num_threads=4, inner_steps=25, seed=40 + c)
                   for c in range(3)],
        "team-b": [SweepSpec(algo="hogwild", scheme="consistent",
                             step_size=0.5, tau=2, num_threads=3, seed=41)],
    }
    svc = SweepService(obj, epochs=2, mesh=mesh)
    with SweepServer(svc, policy=FlushPolicy(max_rows=64,
                                             max_delay_ms=25)) as server:
        client = SweepClient(server.url, poll_s=5.0)
        rids = {name: client.submit(specs, tenant=name)
                for name, specs in tenants.items()}
        for name, specs in tenants.items():
            got = client.result(rids[name], timeout=240)
            _assert_same(got, run_sweep(obj, 2, specs, mesh=mesh))
            _assert_same(got, run_sweep(obj, 2, specs))


@pytest.mark.nonconvex
def test_pytree_objectives_sharded_bit_identical(mesh):
    """The pluggable-objective workloads (MLP LM pytree params; nonconvex
    clipped-penalty logistic) under the forced 8-device mesh: sharded ==
    unsharded per row, and `final_params` rebuilds the same pytree."""
    from repro.core import NonconvexLogistic, mlp_lm_objective

    mlp = mlp_lm_objective(n=16, vocab_size=16, seq_len=4, d_model=8,
                           d_hidden=8)
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    ncv = NonconvexLogistic(ds.X, ds.y, lam=1e-3, alpha=10.0)
    for workload in (mlp, ncv):
        specs = [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.1, tau=2,
                           num_threads=3, inner_steps=10, seed=c)
                 for c in range(3)]
        specs.append(SweepSpec(algo="hogwild", scheme="consistent",
                               step_size=0.1, tau=2, num_threads=3, seed=9))
        base = run_sweep(workload, 2, specs)
        shard = run_sweep(workload, 2, specs, mesh=mesh)
        _assert_same(base, shard)
        np.testing.assert_array_equal(
            np.asarray(workload.as_flat(shard.final_params(0))),
            shard.final_w[0])


@pytest.mark.nonconvex
def test_pytree_objectives_http_sharded_bit_identical(mesh):
    """Acceptance: both new workloads end-to-end through SweepService + the
    HTTP server OVER a forced 8-device mesh — results bit-identical to
    in-process sharded and unsharded `run_sweep`, wire round-trip included
    (the nonconvex workload addressed by registry name, service obj=None)."""
    from repro.core import NonconvexLogistic, mlp_lm_objective
    from repro.core.objective import (register_objective,
                                      unregister_objective)
    from repro.server import FlushPolicy, SweepClient, SweepServer
    from repro.service import SweepService

    mlp = mlp_lm_objective(n=16, vocab_size=16, seq_len=4, d_model=8,
                           d_hidden=8)
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    ncv = NonconvexLogistic(ds.X, ds.y, lam=1e-3, alpha=10.0)
    register_objective("sharded-test-ncv", ncv)
    try:
        mlp_specs = [SweepSpec(scheme="inconsistent", step_size=0.1, tau=2,
                               num_threads=3, inner_steps=10, seed=0),
                     SweepSpec(algo="hogwild", scheme="consistent",
                               step_size=0.1, tau=2, num_threads=3, seed=1)]
        ncv_specs = [SweepSpec(scheme="unlock", step_size=0.2, tau=2,
                               num_threads=3, inner_steps=10, seed=0,
                               objective="sharded-test-ncv")]
        svc = SweepService(mlp, epochs=2, mesh=mesh)
        with SweepServer(svc, policy=FlushPolicy(max_rows=64,
                                                 max_delay_ms=25)) as server:
            client = SweepClient(server.url, poll_s=5.0)
            rid_mlp = client.submit(mlp_specs, tenant="mlp")
            rid_ncv = client.submit(ncv_specs, tenant="ncv")
            got_mlp = client.result(rid_mlp, timeout=240)
            got_ncv = client.result(rid_ncv, timeout=240)
        _assert_same(got_mlp, run_sweep(mlp, 2, mlp_specs, mesh=mesh))
        _assert_same(got_mlp, run_sweep(mlp, 2, mlp_specs))
        _assert_same(got_ncv, run_sweep(None, 2, ncv_specs, mesh=mesh))
        _assert_same(got_ncv, run_sweep(None, 2, ncv_specs))
        assert set(got_mlp.final_params(0)) == {"embed", "norm", "w1",
                                                "b1", "w2"}
    finally:
        unregister_objective("sharded-test-ncv")


def test_watchdog_cancel_row_sharded_survivors_bit_identical(obj, mesh):
    """Divergence watchdog under the forced 8-device mesh: the
    step_size=1e30 row NaNs on epoch 1 and is cancelled (cancel_row),
    while every surviving row stays bit-identical to a watchdog-off run —
    sharded and unsharded."""
    from repro.obs.watchdog import Watchdog
    from repro.service import SweepService

    good = [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.5, tau=3,
                      num_threads=4, inner_steps=25, seed=c)
            for c in range(3)]
    bad = SweepSpec(scheme="inconsistent", step_size=1e30, tau=3,
                    num_threads=4, inner_steps=25, seed=99)
    specs = good + [bad]

    svc = SweepService(obj, epochs=2, mesh=mesh,
                       watchdog=Watchdog(policy="cancel_row"))
    rid = svc.submit(specs)
    svc.flush()
    got = svc.result(rid)

    assert got.diverged_rows is not None
    assert np.flatnonzero(got.diverged_rows >= 0).tolist() == [3]
    assert got.epochs_per_row[3] == 0          # frozen at w0
    assert np.isfinite(got.histories[3]).all()

    ref_sharded = run_sweep(obj, 2, good, mesh=mesh)
    ref_unsharded = run_sweep(obj, 2, good)
    for ref in (ref_sharded, ref_unsharded):
        np.testing.assert_array_equal(got.histories[:3], ref.histories)
        np.testing.assert_array_equal(got.final_w[:3], ref.final_w)


def test_model_axis_mesh_degrades_to_unsharded(obj):
    """A mesh without a >1 `data` axis (e.g. the 1×1 host mesh) falls back
    to the single-device path rather than erroring."""
    from repro.launch.mesh import make_host_mesh
    specs = [SweepSpec(scheme="consistent", step_size=0.5, tau=3,
                       num_threads=4, inner_steps=25)]
    base = run_sweep(obj, 1, specs)
    host = run_sweep(obj, 1, specs, mesh=make_host_mesh())
    _assert_same(base, host)
