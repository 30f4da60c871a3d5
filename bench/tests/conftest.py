"""Tests of the benchmark itself: ``pytest bench/tests`` (the
repository's own tier-1 run collects ``tests/`` only).  They run on the
CPU at tiny sizes through the same code as a run on the chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
