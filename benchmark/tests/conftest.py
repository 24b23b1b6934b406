"""Tests of the benchmark's own yardstick; ``pytest benchmark/tests``.

They run on the CPU (set before jax is imported), at toy sizes."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)
