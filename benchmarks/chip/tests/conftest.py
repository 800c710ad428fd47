"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
