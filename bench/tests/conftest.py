"""Tests of the benchmark's pure parts, on the CPU at small sizes.

Run from the checkout root: ``python -m pytest bench/tests``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
