#!/usr/bin/env python3
"""One sweep of offered rates for an open-loop cell, in one process on
the chip: set-up and warm-up once, then a window per rate, lowest first.
Prints offered and achieved requests/s, p50/p99 from due time, and the
median latency of the last quarter of requests over the first quarter
(a backlog that grows through the window shows as a ratio well above 1).
The knee is the highest offered rate with achieved >= 99 % of offered
and no growing backlog; the cell's ``rate`` is 4/5 of it.

    python3 benchmark/tests/sweep_on_chip.py --workload tenants-mixed \
        --seconds 10 --rates 1000 2000 4000
"""

import argparse
import contextlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import drivers, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()
    spec, w, cfg, tr = run.cell(a.workload)
    run.device_gate(w["chips"])
    run.compile_cache()
    drv = drivers.DRIVERS[tr["driver"]](
        a.seed, cfg, tr, lambda n: contextlib.nullcontext())
    sut = drivers.Sut(cfg, drv.resp)
    try:
        drv.setup(sut)
        drv.warmup(sut)
        for i, rate in enumerate(sorted(a.rates)):
            log = drv.run_phase(a.seconds, 100 + i, rate)
            due = np.concatenate([s["due"] for s in log["sched"]])
            done = np.concatenate(log["done"])
            ok = ~np.isnan(done)
            lat = np.where(ok, done, np.inf) - due
            order = np.argsort(due)
            q = len(order) // 4
            first, last = lat[order[:q]], lat[order[-q:]]
            span = np.nanmax(np.where(ok, done, np.nan)) if ok.any() else 0
            print(json.dumps({
                "offered_rps": rate, "requests": int(len(due)),
                "answered": int(ok.sum()),
                "achieved_rps": float(ok.sum() / max(span, a.seconds)),
                "p50_ms": 1e3 * float(np.quantile(lat, 0.5)),
                "p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
                "backlog_ratio": float(np.median(last) / np.median(first)),
            }), flush=True)
            achieved = ok.sum() / max(span, a.seconds)
            if achieved < 0.95 * rate or np.median(last) > 3 * np.median(
                    first):
                break  # past the knee: higher rates only queue longer
    finally:
        drv.close()
        sut.close()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
