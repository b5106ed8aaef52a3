"""The benchmark's own tests run on the CPU; the harness modules import
as ``harness.*`` (as ``benchmark/run.py`` imports them) and the program
as ``racon_tpu``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
