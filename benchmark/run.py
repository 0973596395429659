#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic mix ``traffic/<mix>.json`` (read by the
one generator, ``traffic/generator.py``, and driven by the driver it
names in ``drivers.py``), and one reader per per-layer metric,
``metrics/<metric>.py``.  Order of a run: device gate (a TPU or exit
non-zero), compile cache, set-up (timed as ``setup_s``: client, filters,
fill, warm-up of the cell's own shapes), the measured window, then the
comparison of every answer the window returned with ``reference.py``.
Earlier lines carry the set-up phases, generator lateness, compiles in
the window and peak device bytes; the last line is the result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import drivers, tracing  # noqa: E402
from benchmark.traffic import generator  # noqa: E402

OUT = os.path.join(HERE, "out")
# Exact comparison: every answer equals the reference's, every request
# due in the window gets one.
LIMITS = {"mismatches": 0, "unanswered": 0}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WATCHDOG_S = 1150  # a first run that compiles may take 1200 s


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(workload: str):
    """(spec, workload entry, configuration, traffic mix) by name."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    return spec, w, load_json(os.path.join(ROOT, c["file"])), \
        generator.load(w["traffic"])


def metrics_for(spec: dict, section: str, workload: str) -> list:
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    s = importlib.util.spec_from_file_location("benchmark_metric_" + name,
                                               path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def device_gate(chips: int) -> dict:
    """The chip or nothing: no CPU fallback."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise SystemExit(f"benchmark: needs {chips} TPU chip(s), "
                         f"JAX found {info}")
    return info


def compile_cache() -> str:
    """The program's persistent compile cache, caching every program
    however fast it compiles (JAX's default skips those under a second),
    so that only a cell's first run compiles."""
    from redisson_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


class CompileCounter:
    """Programs made ready for the device: JAX times an XLA compile and a
    load from the persistent cache as one backend compile, so a warm
    run's set-up counts its cache loads here too."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def run(args, gate=device_gate, control: bool = False) -> dict:
    spec, w, cfg, tr = cell(args.workload)
    info = gate(w["chips"])
    pk = peaks(info["kind"])

    cache_dir = compile_cache()
    import jax

    compiles = CompileCounter()
    trace = bool(args.trace)
    if trace:
        ann = jax.profiler.TraceAnnotation
    else:
        ann = lambda name: contextlib.nullcontext()  # noqa: E731
    drv = drivers.DRIVERS[tr["driver"]](args.seed, cfg, tr, ann)

    t0 = time.perf_counter()
    with drv.phase("client_create"):
        sut = drivers.Sut(cfg, drv.resp)
    try:
        drv.setup(sut)
        drv.warmup(sut)
        # Every window starts from the same collector state: what set-up
        # left for Python's cyclic GC is collected here, not in the window.
        gc.collect()
        setup_s = time.perf_counter() - t0
        emit({"phase": "setup", "setup_s": setup_s, "parts_s": drv.phases,
              "compiles": compiles.count, "compile_s": compiles.seconds,
              "cache_dir": cache_dir})

        readers = ({m["name"]: load_reader(m["name"])
                    for m in metrics_for(spec, "per_layer", args.workload)}
                   if trace else {})
        before = {n: r.snapshot(sut) for n, r in readers.items()}
        c0 = compiles.count
        tdir = os.path.join(OUT, "trace", args.workload)
        with (tracing.capture(tdir) if trace else contextlib.nullcontext()):
            with ann(tracing.WINDOW):
                drv.window(sut, args.seconds)
        after = {n: r.snapshot(sut) for n, r in readers.items()}
        window = {"phase": "window", "compiles_in_window": compiles.count - c0,
                  "attempted": drv.attempted, "failed": drv.failed,
                  **drv.ops}
        if hasattr(drv, "tails"):
            window.update(drv.tails())
        if getattr(drv, "gen_wait_s", None) is not None:
            window["gen_wait_s"] = drv.gen_wait_s
        if getattr(drv, "errors", None):
            window["errors"] = drv.errors
        peak = memory_peak(w["chips"])
        window["memory_peak_bytes"] = peak
        emit(window)
        e2e = drv.e2e()
    finally:
        drv.close()
        sut.close()

    result_metrics = {}
    device = {**info, "count": w["chips"], "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        t = time.perf_counter()
        raw = tracing.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)  # hundreds of MB
        red = tracing.reduce(raw)
        emit({"phase": "trace", "load_s": time.perf_counter() - t,
              "device_ops": {k: len(v) for k, v in raw["devices"].items()},
              "host_spans": len(raw["host"])})
        run_info = {"trace": red or None, "ops": drv.ops, "peaks": pk,
                    "chips": w["chips"],
                    "geometry": {"k": drv.k, "m": drv.m,
                                 "key_bytes": cfg["key_bytes"]}}
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        for n, r in readers.items():
            v = r.read(before[n], after[n], run_info)
            if v is not None:
                result_metrics[n] = {"value": v, "unit": units[n]}
        if red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        vals = {**e2e, "setup_s": setup_s}
        for m in metrics_for(spec, "end_to_end", args.workload):
            result_metrics[m["name"]] = {"value": vals[m["name"]],
                                         "unit": units[m["name"]]}

    t = time.perf_counter()
    numbers = drv.check()
    emit({"phase": "check", "seconds": time.perf_counter() - t,
          "compared": numbers["compared"]})
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in LIMITS.items()}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": drv.attempted, "failed": drv.failed,
           "metrics": result_metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if control:  # tests/seeds_on_chip.py only, never the benchmark's runs
        t = time.perf_counter()
        out["control"] = {k: {"value": v, "limit": LIMITS[k]}
                          for k, v in drv.control().items() if k in LIMITS}
        out["control_s"] = time.perf_counter() - t
    out["checks"] = checks
    return out


def _watchdog() -> None:
    def fire():
        sys.stderr.write(f"benchmark: watchdog fired after {WATCHDOG_S}s\n")
        sys.stderr.flush()
        os._exit(3)

    t = threading.Timer(WATCHDOG_S, fire)
    t.daemon = True
    t.start()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    _watchdog()
    out = run(args)
    for k, c in out["checks"].items():
        sys.stderr.write(f"check {k} {c['value']} limit {c['limit']}\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: engine threads of a failed run must not keep it alive.
    try:
        rc = main()
    except SystemExit as e:
        sys.stdout.flush()
        if not isinstance(e.code, int):
            print(e.code, file=sys.stderr, flush=True)
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        import traceback

        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
