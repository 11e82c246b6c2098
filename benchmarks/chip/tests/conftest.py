"""CPU tests of the chip benchmark's harness.  Run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They are not among the repository's ``tests/``; nothing here needs a chip.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
