"""Run by hand: ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``.
Tier-1 (``pytest tests/``) does not collect this directory."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "layer_metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)
