"""Sharded executor: host-to-device transfers a launch makes for its
inputs, from the program's ``rtpu_h2d_puts{method}`` over its
``rtpu_dispatches{method}``, summed over the methods that made a transfer
in the window.  A packed partitioned Bloom launch reads 1; one that ships
each column apart reads one a column.  None on a program without the
family or a window that made no transfer."""

from benchmark.metrics._program import _family


def _by_method(fam) -> dict:
    return {labels[0]: c.value for labels, c in fam.items()}


def snapshot(sut):
    puts = _family(sut, "rtpu_h2d_puts_total")
    if puts is None:
        return {}
    return {"puts": _by_method(puts),
            "launches": _by_method(_family(sut, "rtpu_dispatches_total"))}


def read(before, after, run):
    if not before or not after:
        return None
    p0, l0 = before["puts"], before["launches"]
    methods = [m for m, v in after["puts"].items() if v > p0.get(m, 0)]
    puts = sum(after["puts"][m] - p0.get(m, 0) for m in methods)
    launches = sum(after["launches"].get(m, 0) - l0.get(m, 0)
                   for m in methods)
    return puts / launches if launches > 0 else None
