"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
