"""Pluggable-objective protocol suite.

Pins the contracts the objective generalization introduces:

  * PRE-REFACTOR REGRESSION — `run_sweep` / `run_svrg` on the paper's
    `LogisticRegression` workload are BIT-IDENTICAL to the engine before
    the protocol refactor: tests/data/sweep_regression_pin.json (all three
    algos through the sweep engine) and svrg_serial_pin.json were captured
    from the pre-protocol code and must reproduce exactly.
  * PYTREE WORKLOADS END-TO-END — the MLP language model and the
    nonconvex-regularized logistic objective run through `run_sweep`, the
    coalescing `SweepService` and the HTTP server with bit-exact demux and
    wire round-trips, and `SweepResult.final_params` rebuilds the pytree
    bit-exactly from the flat row.
  * REGISTRY ADDRESSING — specs naming a registered objective resolve
    identically in-process and over HTTP (service obj=None); one plan
    never mixes objectives; mixed-objective FLUSHES coalesce without ever
    sharing a compiled group.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    LogisticRegression,
    NonconvexLogistic,
    SweepSpec,
    mlp_lm_objective,
    plan_sweep,
    run_svrg,
    run_sweep,
)
from repro.core.objective import register_objective, unregister_objective
from repro.data.libsvm import make_synthetic_libsvm

PIN_DIR = os.path.join(os.path.dirname(__file__), "data")


def _load_pin(name):
    with open(os.path.join(PIN_DIR, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


@pytest.fixture(scope="module")
def mlp():
    return mlp_lm_objective(n=16, vocab_size=16, seq_len=4, d_model=8,
                            d_hidden=8)


@pytest.fixture(scope="module")
def ncv():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return NonconvexLogistic(ds.X, ds.y, lam=1e-3, alpha=10.0)


def _mlp_specs():
    return [SweepSpec(scheme="inconsistent", step_size=0.1, tau=2,
                      num_threads=3, inner_steps=10, seed=0),
            SweepSpec(scheme="unlock", step_size=0.1, tau=2,
                      num_threads=3, inner_steps=10, seed=1),
            SweepSpec(algo="hogwild", scheme="consistent", step_size=0.1,
                      tau=2, num_threads=3, seed=2)]


def _assert_same(got, want):
    np.testing.assert_array_equal(got.histories, want.histories)
    np.testing.assert_array_equal(got.final_w, want.final_w)
    np.testing.assert_array_equal(got.effective_passes,
                                  want.effective_passes)
    np.testing.assert_array_equal(got.total_updates, want.total_updates)
    np.testing.assert_array_equal(got.epochs_per_row, want.epochs_per_row)
    assert got.param_shapes == want.param_shapes


# ------------------------------------------------- pre-refactor regression
def test_logreg_sweep_bit_identical_to_prerefactor_pin(obj):
    """Acceptance: the refactored engine reproduces the PRE-protocol sweep
    engine bit-for-bit on the paper workload — histories, final iterates
    and accounting, across asysvrg/hogwild/svrg and all read schemes."""
    pin = _load_pin("sweep_regression_pin.json")
    assert pin["dataset"] == {"name": "real-sim", "seed": 11,
                              "scale": 0.002, "l2": 1e-3}
    specs = [SweepSpec(**d) for d in pin["specs"]]
    res = run_sweep(obj, pin["epochs"], specs)
    np.testing.assert_array_equal(
        res.histories, np.asarray(pin["histories"], np.float32))
    np.testing.assert_array_equal(
        res.final_w, np.asarray(pin["final_w"], np.float32))
    np.testing.assert_array_equal(
        res.effective_passes, np.asarray(pin["effective_passes"], np.float64))
    np.testing.assert_array_equal(
        res.total_updates, np.asarray(pin["total_updates"], np.int64))
    # the flat-vector objective reports its params as one unnamed leaf and
    # hands the final row back unchanged
    assert res.param_shapes == (("", (obj.p,), "float32"),)
    np.testing.assert_array_equal(res.final_params(0), res.final_w[0])


def test_svrg_serial_bit_identical_to_prerefactor_pin(obj):
    """Satellite: sequential SVRG on the tree-op formulation is bit-equal
    to the pre-protocol flat-vector implementation."""
    pin = _load_pin("svrg_serial_pin.json")
    w, history = run_svrg(obj, 3, 0.3, num_inner=40, option=2, seed=3)
    np.testing.assert_array_equal(np.asarray(w, np.float32),
                                  np.asarray(pin["w"], np.float32))
    np.testing.assert_array_equal(np.asarray(history, np.float32),
                                  np.asarray(pin["history"], np.float32))


# ------------------------------------------------------- plan-time contracts
def test_plan_requires_an_objective():
    specs = [SweepSpec(scheme="consistent", step_size=0.1, tau=2,
                       num_threads=3, inner_steps=10)]
    with pytest.raises(ValueError, match="objective"):
        plan_sweep(None, 1, specs)


def test_plan_rejects_unknown_registered_name(obj):
    specs = [SweepSpec(scheme="consistent", step_size=0.1, tau=2,
                       num_threads=3, inner_steps=10,
                       objective="never-registered")]
    with pytest.raises(KeyError):
        plan_sweep(obj, 1, specs)


def test_plan_rejects_mixed_objectives_in_one_sweep(obj, mlp):
    """One plan = one objective: rows resolving to DIFFERENT objectives in
    a single run_sweep call are a spec error (coalesce multi-objective work
    through the service, which pools by fingerprint instead)."""
    register_objective("proto-test-mlp-mixed", mlp)
    try:
        specs = [SweepSpec(scheme="consistent", step_size=0.1, tau=2,
                           num_threads=3, inner_steps=10),
                 SweepSpec(scheme="consistent", step_size=0.1, tau=2,
                           num_threads=3, inner_steps=10,
                           objective="proto-test-mlp-mixed")]
        with pytest.raises(ValueError, match="objective"):
            plan_sweep(obj, 1, specs)
    finally:
        unregister_objective("proto-test-mlp-mixed")


# --------------------------------------------------- pytree workloads e2e
@pytest.mark.nonconvex
def test_mlp_rows_batch_composition_independent(mlp):
    """A pytree objective inherits the engine's core guarantee: a row's
    bits do not depend on which other rows share its vmapped group. A
    group holds one algo and one reading scheme, so each of the three
    specs brings two more rows of its own (other seeds and steps): three
    groups of width 3."""
    specs = [dataclasses.replace(s, seed=s.seed + 10 * k,
                                 step_size=s.step_size * (1 + k))
             for s in _mlp_specs() for k in range(3)]
    groups = plan_sweep(mlp, 2, specs).groups
    assert sorted(len(m) for m in groups.values()) == [3, 3, 3]
    together = run_sweep(mlp, 2, specs)
    for c, spec in enumerate(specs):
        alone = run_sweep(mlp, 2, [spec])
        np.testing.assert_array_equal(alone.histories[0],
                                      together.histories[c])
        np.testing.assert_array_equal(alone.final_w[0], together.final_w[c])


@pytest.mark.nonconvex
def test_mlp_final_params_rebuild_bit_exact(mlp):
    """`final_params` rebuilds the {embed, norm, w1, b1, w2} dict from the
    flat row bit-exactly, and re-flattening gives the row back."""
    res = run_sweep(mlp, 2, _mlp_specs()[:1])
    params = res.final_params(0)
    assert set(params) == {"embed", "norm", "w1", "b1", "w2"}
    assert params["embed"].shape == (mlp.vocab_size, mlp.d_model)
    np.testing.assert_array_equal(np.asarray(mlp.as_flat(params)),
                                  res.final_w[0])
    # the nonconvex loss actually went somewhere
    assert res.histories[0, -1] < res.histories[0, 0]


@pytest.mark.nonconvex
def test_mlp_through_service_and_http_bit_identical(mlp):
    """Acceptance: the MLP workload end-to-end through the serving tier —
    coalesced service flush AND HTTP wire round-trip — bit-identical to a
    standalone `run_sweep`, pytree param rebuild included."""
    from repro.server import SweepClient, SweepServer
    from repro.service import SweepService

    specs = _mlp_specs()
    want = run_sweep(mlp, 2, specs)

    svc = SweepService(mlp, epochs=2)
    rid_a = svc.submit(specs[:2])
    rid_b = svc.submit(specs[2:])
    svc.flush()
    np.testing.assert_array_equal(svc.result(rid_a).final_w,
                                  want.final_w[:2])
    np.testing.assert_array_equal(svc.result(rid_b).histories,
                                  want.histories[2:])

    with SweepServer(SweepService(mlp, epochs=2)) as server:
        client = SweepClient(server.url)
        rid = client.submit(specs)
        client.flush()
        got = client.result(rid)
    _assert_same(got, want)
    np.testing.assert_array_equal(
        np.asarray(mlp.as_flat(got.final_params(0))), want.final_w[0])


@pytest.mark.nonconvex
def test_nonconvex_registered_objective_over_http(ncv):
    """Acceptance: the nonconvex workload addressed BY NAME through the
    HTTP tier — the service holds no objective (obj=None); specs name a
    registered one and resolve exactly as an in-process run_sweep."""
    from repro.server import SweepClient, SweepServer
    from repro.service import SweepService

    register_objective("proto-test-ncv", ncv)
    try:
        specs = [SweepSpec(scheme="inconsistent", step_size=0.2, tau=2,
                           num_threads=3, inner_steps=10, seed=0,
                           objective="proto-test-ncv"),
                 SweepSpec(algo="hogwild", scheme="consistent",
                           step_size=0.2, tau=2, num_threads=3, seed=1,
                           objective="proto-test-ncv")]
        want = run_sweep(None, 2, specs)
        assert want.histories[0, -1] < want.histories[0, 0]
        with SweepServer(SweepService(None, epochs=2)) as server:
            client = SweepClient(server.url)
            rid = client.submit(specs)
            client.flush()
            got = client.result(rid)
        _assert_same(got, want)
    finally:
        unregister_objective("proto-test-ncv")


@pytest.mark.nonconvex
def test_mixed_objective_flush_coalesces_without_sharing(obj, mlp):
    """One flush holding requests for DIFFERENT objectives: the group key
    leads with the objective fingerprint, so the rows coalesce in one
    dispatch window yet never share a compiled group — and each request
    demuxes bit-identical to its own standalone run_sweep."""
    from repro.service import SweepService, coalesce

    register_objective("proto-test-mlp", mlp)
    try:
        logreg_specs = [SweepSpec(scheme="inconsistent", step_size=0.5,
                                  tau=3, num_threads=4, inner_steps=25,
                                  seed=s) for s in range(2)]
        mlp_specs = [SweepSpec(scheme="inconsistent", step_size=0.1, tau=2,
                               num_threads=3, inner_steps=10, seed=0,
                               objective="proto-test-mlp")]
        svc = SweepService(obj, epochs=2)
        rid_l = svc.submit(logreg_specs)
        rid_m = svc.submit(mlp_specs)
        batch = coalesce(obj, tuple(svc._pending))
        fps = {key[0] for key in batch.groups}
        assert fps == {obj.fingerprint(), mlp.fingerprint()}
        svc.flush()
        _assert_same(svc.result(rid_l), run_sweep(obj, 2, logreg_specs))
        _assert_same(svc.result(rid_m), run_sweep(None, 2, mlp_specs))
    finally:
        unregister_objective("proto-test-mlp")


@pytest.mark.nonconvex
def test_pytree_job_checkpoint_resume_and_foreign_data_guard(mlp, tmp_path):
    """Satellite: checkpoint-resumable jobs work for PYTREE objectives —
    a preempted MLP job resumes bit-identical to one `run_sweep`, and the
    job fingerprint (now `obj.fingerprint()` over arbitrary pytree data)
    rejects a resume against a different objective's data."""
    from repro.checkpoint import Checkpointer
    from repro.core import mlp_lm_objective
    from repro.service import SweepService

    specs = _mlp_specs()
    svc = SweepService(mlp, epochs=2)
    res, done, calls = None, False, 0
    while not done:
        res, done = svc.run_job(specs,
                                checkpointer=Checkpointer(str(tmp_path)),
                                max_groups=1)
        calls += 1
        assert calls < 10
    assert calls >= 2                          # >=2 groups -> a real resume
    _assert_same(res, run_sweep(mlp, 2, specs))

    other = mlp_lm_objective(n=16, vocab_size=16, seq_len=4, d_model=8,
                             d_hidden=8, seed=99)
    svc_b = SweepService(other, epochs=2)
    ckpt = Checkpointer(str(tmp_path / "partial"))
    _, done = svc.run_job(specs, checkpointer=ckpt, max_groups=1)
    assert not done
    with pytest.raises(ValueError, match="different job"):
        svc_b.run_job(specs, checkpointer=Checkpointer(
            str(tmp_path / "partial")))


# -------------------------------------------- cross-process determinism
_DIGEST_CHILD = r"""
import os, sys, zlib
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from repro.data.libsvm import make_synthetic_libsvm
from repro.data.synthetic_lm import SyntheticLMDataset

ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
crc = zlib.crc32(np.ascontiguousarray(np.asarray(ds.X)).tobytes())
crc = zlib.crc32(np.ascontiguousarray(np.asarray(ds.y)).tobytes(), crc)
lm = SyntheticLMDataset(vocab_size=32, seq_len=8, global_batch=16, seed=7)
for step in (0, 3, 17):
    b = lm.batch_at(step)
    crc = zlib.crc32(np.ascontiguousarray(b["tokens"]).tobytes(), crc)
    crc = zlib.crc32(np.ascontiguousarray(b["targets"]).tobytes(), crc)
print(crc)
"""


def test_datasets_deterministic_across_processes():
    """Satellite: the same (dataset, step) must yield the same bytes in
    EVERY process — `SyntheticLMDataset.batch_at` and the synthetic libsvm
    generator may not depend on per-process state (PYTHONHASHSEED salting
    of `hash(str)` broke exactly this before the zlib.crc32 fix; pinned
    regressions and checkpoint-resume fingerprints rely on it)."""
    digests = set()
    for hashseed in ("0", "1", "random"):
        env = dict(os.environ, PYTHONHASHSEED=hashseed,
                   PYTHONPATH=os.pathsep.join(
                       filter(None, [os.environ.get("PYTHONPATH", ""),
                                     os.path.join(os.path.dirname(PIN_DIR),
                                                  os.pardir, "src")])))
        out = subprocess.run([sys.executable, "-c", _DIGEST_CHILD],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode == 0, out.stderr
        digests.add(out.stdout.strip())
    assert len(digests) == 1, f"dataset bytes vary across processes: {digests}"
