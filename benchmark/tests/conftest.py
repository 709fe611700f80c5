"""Run by hand: `python3 -m pytest benchmark/tests -q` (CPU, toy sizes, a few
minutes). Not part of tier-1: tests/ does not import this directory."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
