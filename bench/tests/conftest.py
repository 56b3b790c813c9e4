"""Self-tests of the benchmark: run with

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at tiny sizes and need no chip.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
