"""Benchmark tests run on the CPU: the chip is for benchmark runs."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# CPU programs in a cache of their own, apart from the chip's.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, "benchmark", "out", "cpu_cache"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
