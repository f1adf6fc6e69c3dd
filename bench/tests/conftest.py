"""Tests of the benchmark itself, on the CPU at smoke size. They sit
outside the repository's tier-1 `testpaths`; run them with

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
