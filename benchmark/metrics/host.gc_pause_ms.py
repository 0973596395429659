"""Python runtime: milliseconds per second of window that the process
spent in the cyclic garbage collector (every generation), timed on the
host clock by a ``gc.callbacks`` hook.  While it runs, every thread of
the process, the program's and the client's, waits."""

import gc
import time


class _Pauses:
    def __init__(self):
        self.total, self._t = 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total += time.perf_counter() - self._t
            self._t = None


_pauses = _Pauses()


def snapshot(sut):
    if _pauses not in gc.callbacks:
        gc.callbacks.append(_pauses)
    return {"s": _pauses.total, "t": time.perf_counter()}


def read(before, after, run):
    span = after["t"] - before["t"]
    return 1e3 * (after["s"] - before["s"]) / span if span > 0 else None
