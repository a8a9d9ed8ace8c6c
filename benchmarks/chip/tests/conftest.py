import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH.parents[1] / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
