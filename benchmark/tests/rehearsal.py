"""Drive cells end to end at a tiny size on the CPU, past the device
gate (shared by the rehearsal and the fault tests).

A cell's tiny size is data: the ``tiny`` object of its configuration file
and of its traffic mix, overrides applied over the file's own values.
``run.py`` and the drivers never read it.  So a new configuration, mix or
cell rehearses from its files and its ``BENCHMARK.json`` entry alone.
"""

from __future__ import annotations

import argparse
import os

from benchmark import run
from benchmark.traffic import generator

# Cells whose mix and driver are written and tested, but which
# BENCHMARK.json does not hold yet (PERF.md, Open questions): a later PR
# adds the same entry to ``workloads`` and nothing else.
PENDING = [{"name": "bulk-add", "config": "bloom-55m", "traffic": "bulk-add",
            "chips": 1}]


def cpu_gate(chips: int) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


def spec(root: str = run.ROOT) -> dict:
    return run.load_json(os.path.join(root, "BENCHMARK.json"))


def workloads(sp: dict) -> list:
    """Every cell to rehearse: the benchmark's own, then each pending cell
    that it does not name yet."""
    have = {w["name"] for w in sp["workloads"]}
    return sp["workloads"] + [w for w in PENDING if w["name"] not in have]


def tiny_cell(workload: str, dense: bool = False, root: str = run.ROOT):
    """``run.cell`` for the rehearsal: the files under ``root``, pending
    cells included, each at its ``tiny`` size."""
    sp = spec(root)
    w = next(w for w in workloads(sp) if w["name"] == workload)
    c = next(c for c in sp["configs"] if c["name"] == w["config"])
    cfg = run.load_json(os.path.join(root, c["file"]))
    tr = generator.load(w["traffic"], os.path.join(root, "benchmark",
                                                    "traffic"))
    cfg = {**cfg, **cfg.get("tiny", {})}
    if dense:
        # A tiny window sees too few false positives to tell k from k-1;
        # filters a quarter of their pools' size make them common.
        cfg["expected_insertions"] = min(cfg["expected_insertions"],
                                         tr.get("pool_keys", 1 << 62) // 4)
    return sp, w, cfg, {**tr, **tr.get("tiny", {})}


def run_tiny(monkeypatch, workload: str, trace: int = 0, seed: int = 7,
             seconds: float = 1.0, control: bool = False,
             root: str = run.ROOT) -> dict:
    monkeypatch.setattr(run, "cell",
                        lambda wl: tiny_cell(wl, dense=control, root=root))
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return run.run(args, gate=cpu_gate, control=control)
