"""The benchmark's own tests: a tiny preset on the CPU. Not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
PRESET = os.path.join(ROOT, "benchmarks", "tests", "preset", "BENCHMARK.json")
