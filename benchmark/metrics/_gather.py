"""Keyed Bloom launches split by kernel, read from the engine's
``rtpu_dispatch_ops{method}`` counter: the one-pass contains gather
against the sort-chunked scan.  A program without the gather method
counts none under it."""

from __future__ import annotations

from benchmark.metrics._program import _family

GATHER = "bloom_contains_keys_runs"
SCANS = ("bloom_mixed_keys_runs", "bloom_mixed_keys")


def gather_ops(sut) -> dict:
    """{"gather": ops, "scan": ops} under ``rtpu_dispatch_ops{method}``."""
    fam = _family(sut, "rtpu_dispatch_ops_total")
    if fam is None:
        return {}
    ops = {labels[0]: c.value for labels, c in fam.items()}
    return {"gather": ops.get(GATHER, 0),
            "scan": sum(ops.get(m, 0) for m in SCANS)}


def gather_share(before: dict, after: dict):
    """Gather ops / (gather + scan ops) over the window, % (None when no
    keyed Bloom op ran)."""
    if not before or not after:
        return None
    g = after["gather"] - before["gather"]
    n = g + after["scan"] - before["scan"]
    return 100.0 * g / n if n > 0 else None
