"""BENCHMARK.json against the rules the harness and its checker rely on."""
import json
import re

import pytest

from chipbench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        entries = [e["name"] for e in BENCH[kind]]
        assert len(entries) == len(set(entries)), kind
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def _reported(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_cell_reports_enough(cell):
    w = {x["name"]: x for x in BENCH["workloads"]}[cell]
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert (cells.ROOT / conf["file"]).is_file()
    assert (cells.BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = [m["name"] for m in BENCH["end_to_end"] if _reported(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if _reported(m, cell)]
    assert layer
    for m in layer:
        assert (cells.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    m = {x["name"]: x for x in BENCH["per_layer"]}[metric]
    moves = {x["name"]: x for x in BENCH["end_to_end"]}[m["moves"]]
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert _reported(moves, cell), (metric, cell)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_matches_its_entry(name):
    conf = {c["name"]: c for c in BENCH["configs"]}[name]
    data = json.loads((cells.ROOT / conf["file"]).read_text())
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert set(data["limits"]) == {"w_gap", "loss_gap"}
    assert (cells.BENCH_DIR / "objectives"
            / f"{data['objective']}.py").is_file()
