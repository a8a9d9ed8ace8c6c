"""Find everything a cell needs by the names in BENCHMARK.json.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's ``file`` holds its sizes and names its objective kind,
whose module under ``objectives/`` makes the data, builds the system's
objective and holds the plain reference. The mix is ``traffic/<name>.json``.
Each per-layer metric is read by ``metrics/<name>.py``. Adding a cell, a
mix, a configuration or a metric adds files and entries; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    mix: dict                 # the traffic file's contents
    objective: object         # the objective kind's module
    end_to_end: List[dict]    # the metrics a --trace 0 run reports
    per_layer: List[dict]     # the metrics a --trace 1 run reports


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    objective = importlib.import_module(f"objectives.{config['objective']}")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                objective=objective,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``: takes the run's readings, returns
    a number or None where the run gives it nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a chip missing from the table is an
    error, never a default."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (have {sorted(table)})")
    return table[device_kind]
