"""Executor: mean wait for an in-flight launch slot per launch over the
window, ms (``rtpu_launch_slot_wait_seconds`` sum / count, the program's
own span; it lies inside the ``device_dispatch`` phase)."""

from benchmark.metrics import _program

snapshot = _program.hist("rtpu_launch_slot_wait_seconds")


def read(before, after, run):
    return _program.mean_ms(before, after)
