"""Profiler capture and its reduction to numbers.

``capture`` wraps the measured window in a ``jax.profiler`` trace with
the Python tracer off (host annotations only, so the trace stays small).
``load`` reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``
and keeps two lists: device op intervals (the ``XLA Ops`` line of every
``/device:`` plane) and host spans (annotations named ``bench:*`` by
the harness or ``rtpu:*`` by the program).  ``reduce`` is plain
arithmetic on those lists, tested in ``tests/test_tracing.py``.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil

WINDOW = "bench:window"
HOST_PREFIXES = ("bench:", "rtpu:")
TOP = 10


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> dict:
    """{"devices": {plane: [(start_ns, end_ns, name)]},
        "host": [(start_ns, end_ns, name)]} from the newest capture."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            by_line = {ln.name: [(e.start_ns, e.end_ns, e.name)
                                 for e in ln.events] for ln in plane.lines}
            ops = by_line.get("XLA Ops", [])
            if ops:
                devices[plane.name] = named(ops, by_line.get("XLA Modules",
                                                             []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return {"devices": devices, "host": host}


def named(ops: list, modules: list) -> list:
    """Ops renamed ``<module>/<op>``: the HLO instruction's own name (the
    text before " = ") under the program that ran it."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
        out.append((s, e, mod + "/" + name.split(" = ", 1)[0]))
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi), in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _open_span(host: list, t: float) -> str:
    """The innermost (latest-started) host span open at t, other than the
    window itself."""
    best = None
    for s, e, name in host:
        if s <= t < e and name != WINDOW and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else "no host span"


def reduce(trace: dict) -> dict:
    """Busy time, idle share and top device ops over the ``bench:window``
    span, averaged over the device planes, and the named idle gaps in
    which no plane was busy."""
    win = [(s, e) for s, e, n in trace["host"] if n == WINDOW]
    if not win or not trace["devices"]:
        return {}
    lo, hi = win[0]
    window_ns = hi - lo
    busy, per_op, gaps = [], {}, []
    for ops in trace["devices"].values():
        merged = union(ops, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0) + d
    # Idle gaps are the stretches in which no device of the cell ran an
    # op: what the host did then held every chip back.
    merged = union([op for ops in trace["devices"].values() for op in ops],
                   lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            gaps.append((e - s, s, e))
    n = len(trace["devices"])
    busy_ns = sum(busy) / n
    host = trace["host"]
    gaps.sort(reverse=True)
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_open_span(host, (s + e) / 2), d / 1e9]
                      for d, s, e in gaps[:TOP]],
    }
