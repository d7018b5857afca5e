"""The benchmark's own checks, on the CPU at the cells' rehearsal sizes.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
