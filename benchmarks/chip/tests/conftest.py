"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

They put the benchmark's directory and the program on the path, as
``run.py`` does."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP / "tests", CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
