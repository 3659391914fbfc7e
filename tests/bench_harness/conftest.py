"""Tests of the benchmark's own files (BENCHMARK.json ``paths``): the
yardstick is checked on the CPU, no TPU and no topology call at import."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
