"""Tests of the benchmark harness, on the CPU.

    PYTHONPATH=src python -m pytest bench/tests

(``bench/tests`` lies outside the ``testpaths`` of ``pytest.ini``.)
JAX runs on the CPU here; the compile cache goes to a fresh temporary
directory so that nothing is written into the checkout.
"""
import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="bench-tests-jax-cache-")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
