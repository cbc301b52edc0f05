"""`python -m pytest benchmark/tests -q` from the repository's root.  The
tests run on the CPU at the configurations' `rehearse_scale`; nothing here
is a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
