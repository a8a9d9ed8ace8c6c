"""Read the two ends a check limit is set between, on the chip.

    python3 benchmarks/chip/limits.py --workload rcv1.sequential_tuner \
        --seed 123 --seconds 60 [--controls 3]

One process runs the cell as a benchmark run does, for ``--seconds``, but
checks every request the window completed against the plain reference
(the lower reading is the largest gap of these sound runs: one per request
seed). Then, for ``--controls`` of those requests of each kind, the
control (the reference in bfloat16) is put in the system's place and
compared in the same way (the upper reading is its smallest gap). One
JSON line per row read goes to stdout, then a summary line. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.monotonic()
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from chipbench import check, control, driver  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    keep: dict = {}
    result = driver.run(
        argparse.Namespace(workload=args.workload, seed=args.seed,
                           seconds=args.seconds, trace=0),
        T_START, mix_override={"check_per_kind": 1 << 20}, keep=keep)
    for row in keep["checked"]:
        print(json.dumps({"reading": "program", **row}), flush=True)
    cell, data = keep["cell"], keep["data"]
    picked = check.sample(keep["picked"], args.controls, args.seed + 1)
    controls = []
    for req in picked:
        served = control.served_in_bfloat16(cell, cell.config, data,
                                            req["rows"])
        for row in check.compare([served], cell.objective.reference, data,
                                 cell.config, cell.mix):
            controls.append(row)
            print(json.dumps({"reading": "control", **row}), flush=True)
    print(json.dumps({
        "workload": args.workload, "requests_checked": len(keep["checked"]),
        "lower": check.worst(keep["checked"]),
        "upper": {k: min(r[k] for r in controls)
                  for k in ("w_gap", "loss_gap")},
        "run": {k: result[k] for k in ("correct", "metrics", "device",
                                       "checks")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
