"""Drive cells end to end at a tiny size on the CPU, past the device
gate (shared by the rehearsal and the fault tests)."""

from __future__ import annotations

import argparse

from benchmark import run

TINY_CONFIG = {"bloom-55m": {"expected_insertions": 20_000},
               "bloom-tenants-1k": {"filters": 16}}
TINY_TRAFFIC = {
    "bulk-contains": {"batch_keys": 2048, "batches_per_call": 2,
                      "call_pool": 2},
    "bulk-add": {"batch_keys": 2048},
    "tenants-mixed": {"rate": 400},
    "tenants-resp": {"producers": 8, "procs": 2, "rate": 800},
}


def cpu_gate(chips: int) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


# Mixes written for cells that BENCHMARK.json does not hold yet (PERF.md,
# Open questions): a later PR adds the entry alone.
PENDING = {"bulk-add": "bloom-55m"}


def _cell(workload: str):
    if workload not in PENDING:
        return ORIG_CELL(workload)
    spec = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    c = next(c for c in spec["configs"] if c["name"] == PENDING[workload])
    w = {"name": workload, "config": c["name"], "traffic": workload,
         "chips": 1}
    return (spec, w, run.load_json(run.os.path.join(run.ROOT, c["file"])),
            run.generator.load(workload))


def tiny_cell(workload: str, dense: bool = False):
    spec, w, cfg, tr = _cell(workload)
    cfg = {**cfg, **TINY_CONFIG[cfg["name"]]}
    if dense:
        # A tiny window sees too few false positives to tell k from k-1;
        # filters a quarter of their pools' size make them common.
        cfg["expected_insertions"] = min(cfg["expected_insertions"],
                                         tr.get("pool_keys", 1 << 62) // 4)
    return spec, w, cfg, {**tr, **TINY_TRAFFIC[w["traffic"]]}


ORIG_CELL = run.cell


def run_tiny(monkeypatch, workload: str, trace: int = 0, seed: int = 7,
             seconds: float = 1.0, control: bool = False) -> dict:
    monkeypatch.setattr(run, "cell",
                        lambda wl: tiny_cell(wl, dense=control))
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return run.run(args, gate=cpu_gate, control=control)
