"""Python runtime: milliseconds per second of window the process spent in
the cyclic collector, from the program's own counter
(``rtpu_gc_pause_seconds``, fed by the hook its observability bundle
installs) over the window's host-clock seconds."""

from benchmark.metrics import _program

snapshot = _program.counter_rate("rtpu_gc_pause_seconds_total")


def read(before, after, run):
    return _program.ms_per_s(before, after)
