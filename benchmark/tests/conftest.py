"""The benchmark's own tests (not the repo's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They hold no chip: JAX is kept to the CPU before anything imports it."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
