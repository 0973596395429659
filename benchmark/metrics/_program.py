"""Program families the launch-path readers share, read from the
engine's registry.  A program that lacks a family (one older than the
family) gives an empty snapshot, and its reader then returns None."""

from __future__ import annotations

import time


def _family(sut, name: str):
    return sut.client._engine.obs.registry.family(name)


def hist(name: str):
    """snapshot(sut) -> {"s": sum, "n": count} over every label set of
    the histogram ``name``."""

    def snapshot(sut) -> dict:
        fam = _family(sut, name)
        if fam is None:
            return {}
        s = n = 0
        for _labels, c in fam.items():
            s += c.sum
            n += c.count
        return {"s": s, "n": n}

    return snapshot


def mean_ms(before: dict, after: dict):
    """Mean observation over the window, in ms (None when nothing was
    observed or the program lacks the family)."""
    if not before or not after or after["n"] <= before["n"]:
        return None
    return 1e3 * (after["s"] - before["s"]) / (after["n"] - before["n"])


def counter_rate(name: str):
    """snapshot(sut) -> {"v": counter total, "t": host clock}."""

    def snapshot(sut) -> dict:
        fam = _family(sut, name)
        if fam is None:
            return {}
        return {"v": sum(c.value for _labels, c in fam.items()),
                "t": time.perf_counter()}

    return snapshot


def ms_per_s(before: dict, after: dict):
    """Counter seconds per window second, in ms/s."""
    if not before or not after or after["t"] <= before["t"]:
        return None
    return 1e3 * (after["v"] - before["v"]) / (after["t"] - before["t"])
