"""The yardstick's own tests: `python -m pytest benchmarks/e2e/tests -q`
from the checkout's root, on the CPU.  test_faults.py boots the program's
server on its default ports (a CPU rehearsal), so run nothing beside it."""

import os
import sys

E2E = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(E2E))
for p in (E2E, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
