"""Executor: mean host-clock time per launch from staged to fetched
(``device_dispatch`` + ``d2h_fetch`` span phases, sum / count, over the
window).  ``device_dispatch`` includes the launch-slot wait."""

from benchmark.metrics import _counters

snapshot = _counters.phases


def read(before, after, run):
    return _counters.launch_ms(before, after)
