"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``.
Not part of the repo's tier-1 tests."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
for p in (BENCH, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
