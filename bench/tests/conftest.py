"""Benchmark tests run on the CPU at small sizes:
`JAX_PLATFORMS=cpu python3 -m pytest bench/tests`."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
