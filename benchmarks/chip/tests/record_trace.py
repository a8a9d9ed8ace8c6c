"""Record the small TPU trace that test_chipbench_trace.py reduces.

    python3 benchmarks/chip/tests/record_trace.py

Run on one TPU v5e. Over n = 512 rows of p = 2,048 features (small, so
the serial loss scans add few events and the file stays small), a 2-row
AsySVRG group (unlock reads, 10 threads of 20 inner steps: 200 steps, 200
`svrg_update` calls of shape (2, 64, 128)) runs once to compile, then
twice under the profiler with a 100 ms host sleep between the two: an
idle gap whose cause is known. Writes ``data/v5e_trace.xplane.pb.gz``
and ``data/v5e_trace.json`` (the sync marker's and the stop's
`time.monotonic_ns()`) beside this file.
"""
import glob
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402

from chipbench import readings  # noqa: E402
from objectives import logreg  # noqa: E402

CONFIG = {"n": 512, "p": 2048, "nnz_per_row": 75, "l2": 1e-4}
SLEEP_S = 0.1


def main() -> int:
    from repro.core import SweepSpec, run_sweep
    assert jax.default_backend() == "tpu", jax.default_backend()
    obj = logreg.program(CONFIG, logreg.generate(CONFIG, 1))
    specs = [SweepSpec(algo="asysvrg", scheme="unlock", step_size=1.0,
                       num_threads=10, tau=9, inner_steps=20, seed=s)
             for s in (1, 2)]
    run_sweep(obj, 1, specs)
    out = HERE / "data"
    out.mkdir(exist_ok=True)
    tmp = out / "tmp_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    with jax.profiler.TraceAnnotation(readings.SYNC_MARKER):
        sync_ns = time.monotonic_ns()
    run_sweep(obj, 1, specs)
    time.sleep(SLEEP_S)
    run_sweep(obj, 1, specs)
    stop_ns = time.monotonic_ns()
    jax.profiler.stop_trace()
    with open(glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb"))[0],
              "rb") as src, gzip.open(out / "v5e_trace.xplane.pb.gz",
                                      "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp)
    (out / "v5e_trace.json").write_text(json.dumps(
        {"sync_ns": sync_ns, "stop_ns": stop_ns, "sleep_s": SLEEP_S,
         "device_kind": jax.devices()[0].device_kind}) + "\n")
    print((out / "v5e_trace.xplane.pb.gz").stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
