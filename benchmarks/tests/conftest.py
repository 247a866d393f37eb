"""Checks of the harness itself. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not collected by the repo's tier-1 command (which runs `tests/`).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
