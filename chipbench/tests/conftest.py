"""Tests of the harness itself: ``python -m pytest chipbench/tests``.
They run on the CPU at tiny stand-in sizes; tier-1 does not collect them."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
