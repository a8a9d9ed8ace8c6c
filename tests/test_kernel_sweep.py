"""Fused Pallas sweep-epoch megakernel parity suite.

Contract under test: with ``engine_mode="fused"`` the sweep engine runs
each group as ONE Pallas launch (config rows on the grid) executing the
SAME per-row epochs-scan functions the vmap engine batches — so under the
Pallas interpreter (every backend in this container) the fused path is
BIT-IDENTICAL to the vmap path: per row, per algo, across group widths,
masked per-row epoch budgets and pytree objectives, and all the way back
to the pre-refactor regression pin. Compiled Mosaic lowering (TPU) is NOT
covered here — see the ROADMAP real-accelerator revalidation item.

Also pins the plumbing that keeps the two engines from cross-serving each
other's programs: the group key carries the resolved engine mode (fused
LAST, key_[0] stays the objective fingerprint), the service runner cache
keys fused bodies separately, and ``REPRO_SWEEP_ENGINE`` /
``REPRO_KERNEL_MODE`` env selection validates and resolves as documented.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import (LogisticRegression, SweepSpec, mlp_lm_objective,
                        plan_sweep, run_asysvrg, run_sweep)
from repro.core.sweep import default_engine_mode
from repro.data.libsvm import make_synthetic_libsvm
from repro.kernels import dispatch
from repro.service.cache import runner_key

PIN_DIR = os.path.join(os.path.dirname(__file__), "data")
SCHEMES = ("consistent", "inconsistent", "unlock")


@pytest.fixture(scope="module")
def obj():
    ds = make_synthetic_libsvm("real-sim", seed=11, scale=0.002)
    return LogisticRegression(ds.X, ds.y, l2_reg=1e-3)


def _fused(specs):
    return [dataclasses.replace(s, engine_mode="fused") for s in specs]


def _assert_same(res_a, res_b):
    np.testing.assert_array_equal(res_a.histories, res_b.histories)
    np.testing.assert_array_equal(res_a.final_w, res_b.final_w)
    np.testing.assert_array_equal(res_a.effective_passes,
                                  res_b.effective_passes)
    np.testing.assert_array_equal(res_a.total_updates, res_b.total_updates)
    np.testing.assert_array_equal(res_a.epochs_per_row, res_b.epochs_per_row)


# ------------------------------------------------------------- bit parity
def test_fused_matches_vmap_all_algos(obj):
    """Acceptance: fused == vmap bit-exact for every engine and read scheme
    in one mixed grid (asysvrg x 3 schemes, hogwild, serial svrg)."""
    specs = [SweepSpec(scheme=s, step_size=0.1, tau=2, num_threads=4,
                       inner_steps=20, seed=i)
             for i, s in enumerate(SCHEMES)]
    specs += [SweepSpec(algo="hogwild", scheme="consistent", step_size=0.1,
                        tau=2, num_threads=3, seed=3),
              SweepSpec(algo="svrg", step_size=0.1, inner_steps=25, seed=4)]
    _assert_same(run_sweep(obj, 2, specs), run_sweep(obj, 2, _fused(specs)))


@pytest.mark.parametrize("rows", [1, 3, 8])
def test_fused_group_widths(obj, rows):
    """One-row groups, odd widths and vector-width multiples all hit the
    same grid mapping: fused == vmap bit-exact at every group width. A
    group holds one reading scheme, so each scheme brings `rows` rows and
    the sweep runs three groups of that width."""
    specs = [SweepSpec(scheme=s, step_size=0.2, tau=3, num_threads=4,
                       inner_steps=15, seed=c)
             for s in SCHEMES for c in range(rows)]
    groups = plan_sweep(obj, 2, specs).groups
    assert sorted(len(m) for m in groups.values()) == [rows] * 3
    _assert_same(run_sweep(obj, 2, specs), run_sweep(obj, 2, _fused(specs)))


def test_fused_masked_row_epochs_match_shorter_runs(obj):
    """Masked per-row epoch budgets inside one fused launch: each row is
    bit-equal to an independent sequential run of its own length (the same
    freeze contract the vmap engine pins)."""
    specs = [SweepSpec(scheme="inconsistent", step_size=0.2, tau=3,
                       num_threads=4, inner_steps=20, seed=7, epochs=e)
             for e in (1, 2, 3)]
    res = run_sweep(obj, 3, _fused(specs))
    for c, spec in enumerate(specs):
        seq = run_asysvrg(obj, spec.epochs, spec.to_config(), seed=7)
        np.testing.assert_array_equal(
            np.asarray(seq.history, np.float32),
            res.histories[c, :spec.epochs + 1])
        np.testing.assert_array_equal(np.asarray(seq.w, np.float32),
                                      res.final_w[c])


@pytest.mark.nonconvex
def test_fused_pytree_objective_matches_vmap():
    """The megakernel is objective-generic: the MLP LM pytree workload
    (multi-arg data tuple, flattened params) runs fused == vmap bit-exact,
    and `final_params` rebuilds the same tree."""
    mlp = mlp_lm_objective(n=16, vocab_size=16, seq_len=4, d_model=8,
                           d_hidden=8)
    specs = [SweepSpec(scheme=SCHEMES[c % 3], step_size=0.1, tau=2,
                       num_threads=3, inner_steps=10, seed=c)
             for c in range(3)]
    specs.append(SweepSpec(algo="hogwild", scheme="consistent",
                           step_size=0.1, tau=2, num_threads=3, seed=9))
    base = run_sweep(mlp, 2, specs)
    fused = run_sweep(mlp, 2, _fused(specs))
    _assert_same(base, fused)
    np.testing.assert_array_equal(
        np.asarray(mlp.as_flat(fused.final_params(0))), fused.final_w[0])


def test_fused_reproduces_prerefactor_regression_pin(obj, monkeypatch):
    """Acceptance (strongest parity statement): the fused path reproduces
    the PRE-refactor engine pin bit-for-bit — the same frozen numbers the
    vmap engine is held to, two engine generations back.

    The pin certifies the DEFAULT kernel config, so $REPRO_KERNEL_MODE is
    cleared for this test (the CI kernels-interpret job exports it, which
    would route the inner svrg-update op through the Pallas interpreter —
    ~1-ulp off the reference path the pin was frozen on) and the runner
    cache is dropped (vmap runner keys don't carry the kernel-mode env, so
    a runner traced under the exported env would otherwise be reused)."""
    from repro.service import clear_cache
    monkeypatch.delenv(dispatch.KERNEL_MODE_ENV, raising=False)
    clear_cache()
    with open(os.path.join(PIN_DIR, "sweep_regression_pin.json")) as fh:
        pin = json.load(fh)
    specs = _fused([SweepSpec(**d) for d in pin["specs"]])
    res = run_sweep(obj, pin["epochs"], specs)
    np.testing.assert_array_equal(
        res.histories, np.asarray(pin["histories"], np.float32))
    np.testing.assert_array_equal(
        res.final_w, np.asarray(pin["final_w"], np.float32))
    np.testing.assert_array_equal(
        res.effective_passes, np.asarray(pin["effective_passes"], np.float64))
    np.testing.assert_array_equal(
        res.total_updates, np.asarray(pin["total_updates"], np.int64))


# ------------------------------------------------- engine-mode selection
def test_engine_mode_validates_at_plan_time(obj):
    with pytest.raises(ValueError, match="engine_mode"):
        plan_sweep(obj, 2, [SweepSpec(engine_mode="bogus")])


def test_engine_mode_defaults_from_env(obj, monkeypatch):
    """Unset specs inherit $REPRO_SWEEP_ENGINE; explicit engine_mode wins;
    a bad env value raises rather than silently running vmap."""
    monkeypatch.setenv("REPRO_SWEEP_ENGINE", "fused")
    assert default_engine_mode() == "fused"
    plan = plan_sweep(obj, 2, [SweepSpec(inner_steps=10)])
    assert all(k[-1] for k in plan.groups)          # fused flag set
    assert plan.specs[0].engine_mode == "fused"
    plan = plan_sweep(obj, 2, [SweepSpec(inner_steps=10,
                                         engine_mode="vmap")])
    assert not any(k[-1] for k in plan.groups)
    monkeypatch.setenv("REPRO_SWEEP_ENGINE", "turbo")
    with pytest.raises(ValueError, match="REPRO_SWEEP_ENGINE"):
        default_engine_mode()


def test_fused_and_vmap_rows_split_groups(obj):
    """Mixed engine modes in one sweep plan into separate groups whose keys
    differ ONLY in the trailing fused flag — key_[0] (the objective
    fingerprint the service scheduler pools on) is unperturbed."""
    specs = [SweepSpec(inner_steps=10, seed=0, engine_mode="vmap"),
             SweepSpec(inner_steps=10, seed=1, engine_mode="fused")]
    plan = plan_sweep(obj, 2, specs)
    keys = sorted(plan.groups, key=lambda k: k[-1])
    assert len(keys) == 2
    assert keys[0][:-1] == keys[1][:-1]
    assert [k[-1] for k in keys] == [False, True]
    assert keys[0][0] == obj.fingerprint()
    # ...and the mixed plan still computes both rows bit-equal to vmap
    base = run_sweep(obj, 2, [dataclasses.replace(s, engine_mode="vmap")
                              for s in specs])
    _assert_same(base, run_sweep(obj, 2, specs))


def test_runner_cache_keys_fused_separately(obj):
    """The persistent runner cache can never serve a vmap body to a fused
    group (or vice versa), and the fused key pins the RESOLVED kernel
    mode so flipping REPRO_KERNEL_MODE mid-process re-keys."""
    common = dict(group_epochs=2, total=10, option=2, buf_len=4,
                  drop_prob=0.02, mesh=None, obj=obj)
    k_vmap = runner_key("asysvrg", **common)
    k_fused = runner_key("asysvrg", fused=True, **common)
    assert k_vmap != k_fused
    assert k_vmap[-1] == ("vmap", dispatch.kernel_mode())
    assert k_fused[-1] == ("fused", dispatch.fused_sweep_mode())


# ------------------------------------------------- unified kernel dispatch
def test_kernel_mode_env_override_wins(monkeypatch):
    """$REPRO_KERNEL_MODE beats flags and backend sniff for ALL kernels;
    the fused sweep mode degrades 'reference' to 'interpret' (the vmap
    engine is its reference); bad values raise."""
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "interpret")
    assert dispatch.kernel_mode() == "interpret"
    assert dispatch.kernel_mode(force_kernel=True) == "interpret"
    assert dispatch.fused_sweep_mode() == "interpret"
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "reference")
    assert dispatch.kernel_mode(interpret=True, force_kernel=True) \
        == "reference"
    assert dispatch.fused_sweep_mode() == "interpret"
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "warp")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        dispatch.kernel_mode()


def test_kernel_mode_historical_contract(monkeypatch):
    """Without the env var the unified helper reproduces the historical
    per-kernel behaviour: kernel body iff force_kernel or TPU backend,
    interpreter iff asked."""
    monkeypatch.delenv(dispatch.KERNEL_MODE_ENV, raising=False)
    monkeypatch.setattr(dispatch, "kernel_backend", lambda: "cpu")
    assert dispatch.kernel_mode() == "reference"
    assert dispatch.kernel_mode(interpret=True) == "reference"
    assert dispatch.kernel_mode(interpret=True, force_kernel=True) \
        == "interpret"
    assert dispatch.kernel_mode(force_kernel=True) == "compiled"
    assert dispatch.fused_sweep_mode() == "interpret"
    monkeypatch.setattr(dispatch, "kernel_backend", lambda: "tpu")
    assert dispatch.kernel_mode() == "compiled"
    assert dispatch.kernel_mode(interpret=True) == "interpret"
    with pytest.raises(ValueError, match="Mosaic"):
        dispatch.fused_sweep_mode()
