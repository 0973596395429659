"""Kernels: share of the HBM roofline.  Algorithmic bytes of the ops the
window launched (``bloom_bytes``; near-cache and response-cache answers
excluded) over what the cell's chips could move in their busy time
(``bloom_bytes.roofline_share``).  Every device op in these cells serves
the Bloom path, so busy time is the kernels' time.  Random 4-byte gathers
keep it far below 100."""

from benchmark.metrics import _counters, bloom_bytes


def snapshot(sut):
    return {**_counters.nearcache(sut),
            **{"resp_" + k: v for k, v in _counters.frontdoor(sut).items()}}


def read(before, after, run):
    if not run.get("trace"):
        return None
    answered_on_host = (after["hits"] - before["hits"]
                        + after.get("resp_cache_hits", 0)
                        - before.get("resp_cache_hits", 0))
    launched = run["ops"]["contains_keys"] - answered_on_host
    return bloom_bytes.roofline_share(run,
                                      bloom_bytes.window_bytes(run, launched))
