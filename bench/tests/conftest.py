"""Shared set-up of the benchmark's own tests (run them with
``JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests``)."""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
