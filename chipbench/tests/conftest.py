"""CPU tests of the chip benchmark's harness.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They import the harness's modules from ``chipbench/`` and the program
from ``src/``; nothing here needs a chip.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
