"""Device: share of the traced window in which no op ran on the chip,
1 - (union of device-op intervals / window), averaged over chips."""


def snapshot(sut):
    return {}


def read(before, after, run):
    t = run.get("trace")
    return 100.0 * t["idle_share"] if t else None
