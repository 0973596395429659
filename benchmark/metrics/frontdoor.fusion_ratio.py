"""RESP front door: share of commands that rode a fused run, over the
window (``frontdoor_fused_cmds`` / total commands, as ``INFO frontdoor``)."""

from benchmark.metrics import _counters

snapshot = _counters.frontdoor


def read(before, after, run):
    n = after.get("commands", 0) - before.get("commands", 0)
    if n <= 0:
        return None
    return 100.0 * (after["fused"] - before["fused"]) / n
