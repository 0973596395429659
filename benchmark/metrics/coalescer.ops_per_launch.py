"""Coalescer: ops per device launch over the window (``ops_total`` /
``batches_total``)."""

from benchmark.metrics import _counters

snapshot = _counters.coalescer


def read(before, after, run):
    n = after["launches"] - before["launches"]
    return (after["ops"] - before["ops"]) / n if n > 0 else None
