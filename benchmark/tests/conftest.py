"""``python -m pytest benchmark/tests`` — by hand, on the CPU; not part of the
repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("HYDRAGNN_COMPILE_CACHE", "0")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]
