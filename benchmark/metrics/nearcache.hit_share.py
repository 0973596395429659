"""Near cache: share of key lookups answered from host memory, over the
window (epoch-aware ``hits`` / (``hits`` + ``misses``))."""

from benchmark.metrics import _counters

snapshot = _counters.nearcache


def read(before, after, run):
    h = after["hits"] - before["hits"]
    n = h + after["misses"] - before["misses"]
    return 100.0 * h / n if n > 0 else None
