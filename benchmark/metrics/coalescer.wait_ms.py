"""Coalescer: mean ``coalesce_wait`` span phase per launch over the window
(phase sum / count; submit to flush of the launch's first op)."""

from benchmark.metrics import _counters

snapshot = _counters.phases


def read(before, after, run):
    s0, n0 = before.get("coalesce_wait", (0.0, 0))
    s1, n1 = after.get("coalesce_wait", (0.0, 0))
    return 1e3 * (s1 - s0) / (n1 - n0) if n1 > n0 else None
