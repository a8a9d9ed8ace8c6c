"""The check that decides `correct` fails what it has to fail.

The control (the plain reference computed in bfloat16, put in the system's
place) fails a limit of every configuration. And a whole run of each cell,
at a small size on the CPU with the chip's preflight skipped, comes out
correct on the sound program and not correct with the timed path broken
underneath it: an epoch that returns its iterate unchanged, a snapshot
gradient averaged over half of the data, an answer altered where the
engine produces it.
"""
import argparse
import functools
import time

import jax
import numpy as np
import pytest

from chipbench import cells, check, control, driver, traffic

SMALL = {"n": 160, "p": 256, "nnz_per_row": 8}
BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_control_in_bfloat16_fails_a_limit(config):
    cell = cells.load_cell(next(w["name"] for w in BENCH["workloads"]
                                if w["config"] == config))
    cfg = {**cell.config, **SMALL}
    data = cell.objective.generate(cfg, 2**32 + 1)
    reqs = [control.served_in_bfloat16(cell, cfg, data,
                                       traffic.request_rows(t, i, 0, 7))
            for i, t in enumerate(traffic.tenants(cell.mix)[:4])]
    got = check.worst(check.compare(reqs, cell.objective.reference, data,
                                    cfg, cell.mix))
    assert any(got[k] > cfg["limits"][k] for k in cfg["limits"]), got


@pytest.fixture
def bench_run(monkeypatch, tmp_path):
    """Runs a cell through `driver.run` at a small size on the CPU, with a
    fresh runner cache, and the persistent compile cache and the profile
    in ``tmp_path``, so that runs in other workers cannot remove it."""
    from repro.service import cache
    monkeypatch.setattr(driver, "CACHE_DIR", tmp_path / "jax")
    monkeypatch.setattr(driver, "TRACE_DIR", tmp_path / "trace")
    saved = {k: jax.config.values[k] for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def go(cell, trace=0):
        cache.clear_cache()
        args = argparse.Namespace(workload=cell, seed=2**31 + 5, seconds=3.0,
                                  trace=trace)
        return driver.run(args, time.monotonic(), require_chip=False,
                          config_override=SMALL)

    yield go
    cache.clear_cache()
    for k, v in saved.items():
        jax.config.update(k, v)


def _unchanged_epoch(monkeypatch):
    from repro.core import asysvrg, hogwild
    monkeypatch.setattr(asysvrg, "_epoch_core",
                        lambda obj, data, w, *a, **k: w)
    monkeypatch.setattr(hogwild, "_hogwild_epoch_core",
                        lambda obj, data, w, *a, **k: w)


def _objective_classes():
    """`Objective` and every subclass of it that is loaded."""
    import repro.core  # noqa: F401  (defines the subclasses)
    from repro.core.objective import Objective
    found, todo = [], [Objective]
    while todo:
        cls = todo.pop()
        found.append(cls)
        todo += cls.__subclasses__()
    return found


def _first_half(data, n):
    """Every leaf of ``data`` that leads with the ``n`` samples, cut to the
    first ``n // 2`` of them; the other leaves as they are."""
    return jax.tree.map(
        lambda a: a[:n // 2] if np.ndim(a) and a.shape[0] == n else a, data)


def _half_the_data(monkeypatch):
    """Each objective's snapshot gradient taken by its own class's
    `flat_full_grad`, over the first half of its samples."""
    def on_half(real):
        def half(self, data, w):
            return real(self, _first_half(data, self.num_samples(data)), w)
        return half

    for cls in _objective_classes():
        if "flat_full_grad" in vars(cls):
            monkeypatch.setattr(cls, "flat_full_grad",
                                on_half(vars(cls)["flat_full_grad"]))


def _configured(config):
    """The objective that ``config`` runs, built by its own kind's module
    at the small size, and the same over the first half of its data."""
    cell = cells.load_cell(next(w["name"] for w in BENCH["workloads"]
                                if w["config"] == config))
    cfg = {**cell.config, **SMALL}
    data = cell.objective.generate(cfg, 2**32 + 3)
    n = jax.tree.leaves(data)[0].shape[0]
    return (cell.objective.program(cfg, data),
            cell.objective.program(cfg, _first_half(data, n)))


TOY_N = 10
_TOY = np.random.default_rng(7)
_X = _TOY.standard_normal((TOY_N, 6)).astype(np.float32)
_Y = np.where(_TOY.standard_normal(TOY_N) > 0, 1.0, -1.0).astype(np.float32)
_TOKENS, _TARGETS = _TOY.integers(0, 8, (2, TOY_N, 4))


def _toy(build):
    """An objective over the toy samples, and the same over their first
    half."""
    return lambda: (build(TOY_N), build(TOY_N // 2))


def _toy_cases():
    from repro.core import LogisticRegression, MLPObjective, NonconvexLogistic
    return {
        "LogisticRegression": _toy(
            lambda n: LogisticRegression(_X[:n], _Y[:n])),
        "NonconvexLogistic": _toy(
            lambda n: NonconvexLogistic(_X[:n], _Y[:n])),
        "MLPObjective": _toy(lambda n: MLPObjective(
            _TOKENS[:n], _TARGETS[:n], 8, d_model=4, d_hidden=8)),
    }


HALVED = {**{f"config-{c['name']}": functools.partial(_configured, c["name"])
             for c in BENCH["configs"]}, **_toy_cases()}


@pytest.mark.parametrize("case", HALVED)
def test_half_the_data_takes_the_gradient_over_the_first_half(monkeypatch,
                                                              case):
    """The fault changes the snapshot gradient to the objective's own
    gradient over the first half of its samples: for the objective of
    every configuration, and for each objective class of `repro.core`."""
    obj, first_half = HALVED[case]()
    w = 0.5 * np.random.default_rng(11).standard_normal(
        obj.flat_dim).astype(np.float32)
    whole = np.asarray(obj.flat_full_grad(obj.data_args(), w))
    want = np.asarray(first_half.flat_full_grad(first_half.data_args(), w))
    _half_the_data(monkeypatch)
    got = np.asarray(obj.flat_full_grad(obj.data_args(), w))
    assert not np.allclose(got, whole)
    np.testing.assert_array_equal(got, want)


def _altered_answer(monkeypatch):
    from repro.service import scheduler
    real = scheduler._dispatch_group

    def altered(*args, **kw):
        hist, w = real(*args, **kw)
        w = w.copy()
        top = np.argmax(np.abs(w), axis=1)
        w[np.arange(len(w)), top] *= 1.05
        return hist, w

    monkeypatch.setattr(scheduler, "_dispatch_group", altered)


FAULTS = {"sound": None, "unchanged_epoch": _unchanged_epoch,
          "half_the_data": _half_the_data, "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct_only_on_the_sound_path(bench_run, monkeypatch, cell,
                                               fault):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    result = bench_run(cell)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert np.isfinite(c["limit"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_what_the_cpu_can_read(bench_run, monkeypatch,
                                                  cell):
    """With ``--trace 1`` the result carries the cell's per-layer metrics;
    on the CPU the trace holds no TPU plane, so the device metrics are
    left out rather than read as 0."""
    result = bench_run(cell, trace=1)
    per_layer = cells.load_cell(cell).per_layer
    assert set(result["metrics"]) == {m["name"] for m in per_layer
                                      if m["source"] != "device_trace"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["busy_s"] == 0.0
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["correct"] is True
