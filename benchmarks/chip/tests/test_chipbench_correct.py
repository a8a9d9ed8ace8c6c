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
    fresh runner cache and the persistent compile cache in ``tmp_path``."""
    from repro.service import cache
    monkeypatch.setattr(driver, "CACHE_DIR", tmp_path / "jax")
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


def _half_the_data(monkeypatch):
    from repro.core import objective

    def half(self, data, w):
        X, y, l2 = data
        h = X.shape[0] // 2
        return objective.full_grad_stable(X[:h], y[:h], l2, w)

    monkeypatch.setattr(objective.LogisticRegression, "flat_full_grad", half)


def _altered_answer(monkeypatch):
    from repro.service import scheduler
    real = scheduler._dispatch_group

    def altered(*args, **kw):
        hist, w = real(*args, **kw)
        w = w.copy()
        top = np.argmax(np.abs(w), axis=1)
        w[np.arange(len(w)), top] *= 1.01
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
    layer = {m["name"] for m in cells.load_cell(cell).per_layer}
    device_metrics = {"device_idle", "svrg_update_us"}
    assert set(result["metrics"]) == layer - device_metrics
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["busy_s"] == 0.0
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["correct"] is True
