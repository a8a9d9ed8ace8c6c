"""Run one cell of the benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cells are the ``workloads`` of BENCHMARK.json at the checkout's root.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each number compared with the reference
beside its limit; the checks are also the last lines of standard error.
The run refuses, printing no result, without a TPU or with another
number of chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import driver  # noqa: E402

if __name__ == "__main__":
    sys.exit(driver.main(t_start=T_START))
