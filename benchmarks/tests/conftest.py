"""The benchmark's own tests: run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`` from the
root of the repo. They are not part of the repo's tier-1 run."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "configs")):
    if p not in sys.path:
        sys.path.insert(0, p)
