"""The benchmark's tests run on the CPU: ``PYTHONPATH`` needs nothing, the
program and the benchmark's modules are put on the path here."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
