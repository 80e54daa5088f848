"""The benchmark's own tests, run by hand: ``pytest bench/tests`` from the
root of a checkout.  They run on the CPU (Pallas in interpret mode)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
