"""CPU rehearsals of the benchmark's own code.  They sit outside tier-1
(``tests/``): run them with ``python -m pytest benchmark/tests -q``.
Nothing here measures a device; every number is a count or a check."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
