"""Where the benchmark's files are, for its tests."""

import os

TESTS = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(TESTS, "data")
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
TINY_BENCHMARK = os.path.join(DATA, "BENCHMARK.tiny.json")
