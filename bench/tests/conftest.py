"""The benchmark's own self-checks.  They run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

from the root of a checkout.  They are not among the repository's
tier-1 tests (``testpaths`` is ``tests``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
