"""bench/tests run by hand (`pytest bench/tests`), on the CPU backend: they
check the yardstick's own arithmetic and files, never a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
