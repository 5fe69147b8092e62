"""Adds the reduced `hymba_full_lm_4k` cell to the table of reduced cells
before any test module reads it (the tests parametrize over it)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.tests import small_hymba  # noqa: E402

small_hymba.register()
