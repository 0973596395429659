"""Algorithmic HBM bytes of Bloom ops, from shapes alone.

A ``contains`` moves its key bytes in, reads k 4-byte words (one per
probe) and writes one result bit; an ``add`` does the same and writes
its k words back.  This counts the work the algorithm needs, not what a
kernel happens to move, so the number survives a rewrite of the kernel.
The k reads are random 4-byte gathers, and HBM serves each from a burst
of 32 bytes or more, so the share of the roofline these bytes give sits
far below 1 even for a kernel that is bound by memory.
"""

from __future__ import annotations

WORD = 4


def contains_bytes(k: int, key_bytes: int) -> float:
    return key_bytes + k * WORD + 1 / 8


def add_bytes(k: int, key_bytes: int) -> float:
    return contains_bytes(k, key_bytes) + k * WORD


def window_bytes(run: dict, launched_contains: float) -> float:
    """Bytes of the window's adds (all launched) and of the contains that
    reached the device (the rest were answered on the host)."""
    g = run["geometry"]
    return (run["ops"]["add_keys"] * add_bytes(g["k"], g["key_bytes"])
            + launched_contains * contains_bytes(g["k"], g["key_bytes"]))


def roofline_share(run: dict, nbytes: float):
    """Share of the HBM roofline, %: the window's bytes over what the
    cell's chips could move while busy, chips x peak bytes/s x ``busy_s``
    (the trace's busy seconds, a mean over the chips' device planes).
    None without busy time or bytes."""
    busy_s = run["trace"]["busy_s"]
    if busy_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (run["chips"] * run["peaks"]["hbm_bytes_per_s"]
                             * busy_s)
