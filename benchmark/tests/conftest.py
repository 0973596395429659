"""Benchmark tests run on the CPU: the chip is for benchmark runs.  Four
virtual CPU devices stand in for a four-chip host, so that a cell with
``"chips": 4`` rehearses on a mesh of its size; one-chip cells run on
device 0."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# CPU programs in a cache of their own, apart from the chip's.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, "benchmark", "out", "cpu_cache"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
