"""Client objects: mean ``RObject._encode`` time per call over the window,
ms (``rtpu_encode_seconds`` sum / count, the program's own span)."""

from benchmark.metrics import _program

snapshot = _program.hist("rtpu_encode_seconds")


def read(before, after, run):
    return _program.mean_ms(before, after)
