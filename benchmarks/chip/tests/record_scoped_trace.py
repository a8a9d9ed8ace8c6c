"""Record the scoped TPU trace that test_chipbench_scopes.py reduces.

    python3 benchmarks/chip/tests/record_scoped_trace.py

Run on one TPU v5e. It makes the recording `record_trace.py` makes (two
runs of a 2-row AsySVRG group of 200 inner steps, a 100 ms host sleep
between them) of the program as it now is, whose device work carries
the epoch cores' named scopes, and writes it beside the recording of the
program before scopes, which stays as it is, as
``data/v5e_scoped_trace.xplane.pb.gz`` and ``data/v5e_scoped_trace.json``.
"""
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import record_trace  # noqa: E402

NAME = "v5e_scoped_trace"


def main() -> int:
    with tempfile.TemporaryDirectory(dir=HERE / "data") as tmp:
        # record_trace writes under <HERE>/data/v5e_trace.*
        record_trace.HERE = Path(tmp)
        record_trace.main()
        for suffix in (".xplane.pb.gz", ".json"):
            shutil.move(str(Path(tmp) / "data" / f"v5e_trace{suffix}"),
                        str(HERE / "data" / f"{NAME}{suffix}"))
    print((HERE / "data" / f"{NAME}.xplane.pb.gz").stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
