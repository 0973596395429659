"""Engine: mean submit time per call over the window, ms, from the engine
entry to the returned future without encode (``rtpu_submit_seconds``
sum / count, the program's own span)."""

from benchmark.metrics import _program

snapshot = _program.hist("rtpu_submit_seconds")


def read(before, after, run):
    return _program.mean_ms(before, after)
