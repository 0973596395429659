"""Program counters the readers share: each function takes the system
under test (``sut.client``, ``sut.server``) and returns plain numbers."""

from __future__ import annotations


def coalescer(sut) -> dict:
    m = sut.client._engine.metrics
    return {"ops": m.ops_total, "launches": m.batches_total}


def phases(sut) -> dict:
    """{phase: (sum_s, count)} over every op label of the span histograms;
    the buckets are log2, so only sum and count are read."""
    out = {}
    for (_op, phase), c in sut.client._engine.obs.spans._phase_hist.items():
        s, n = out.get(phase, (0.0, 0))
        out[phase] = (s + c.sum, n + c.count)
    return out


def nearcache(sut) -> dict:
    nc = sut.client._engine.nearcache
    return {"hits": nc.hits, "misses": nc.misses}


def frontdoor(sut) -> dict:
    if sut.server is None:
        return {}
    obs = sut.server.obs
    tot = lambda fam: sum(int(c.value) for _, c in fam.items())  # noqa: E731
    return {"fused": tot(obs.resp_fused_cmds),
            "commands": tot(obs.resp_commands),
            "cache_hits": tot(obs.resp_cache_hits)}


def delta(before: dict, after: dict, key):
    return after[key] - before[key]


def launch_ms(before: dict, after: dict):
    """(device_dispatch + d2h_fetch) seconds per launch, in ms."""
    n = after.get("device_dispatch", (0, 0))[1] - \
        before.get("device_dispatch", (0, 0))[1]
    if n <= 0:
        return None
    s = sum(after.get(p, (0.0, 0))[0] - before.get(p, (0.0, 0))[0]
            for p in ("device_dispatch", "d2h_fetch"))
    return 1e3 * s / n
