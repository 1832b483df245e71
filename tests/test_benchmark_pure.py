"""The benchmark's pure tests (names and files of BENCHMARK.json against
their readers, qgen, the reductions, the comparison; seconds, no cluster)
run in tier-1, so a program PR that breaks a reader learns it here and
not on the chip. Nothing under benchmarks/ is edited for it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.tests.test_benchmark import *  # noqa: E402,F401,F403
