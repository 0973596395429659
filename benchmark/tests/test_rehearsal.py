"""CPU rehearsal: every cell, the pending ones too, runs end to end at a
tiny size, prints a result that parses with the contract's keys, and
reports correct.  A cell defined in data alone rehearses too."""

import json
import os
import shutil

import pytest

from benchmark import run
from benchmark.tests.rehearsal import PENDING, run_tiny, spec, workloads

CELLS = [w["name"] for w in workloads(spec())]
PENDING_NAMES = {w["name"] for w in PENDING}


def _check(out, sp, workload, trace):
    out = json.loads(json.dumps(out))
    assert list(out)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in run.metrics_for(sp, section, workload)}
    if workload in PENDING_NAMES and workload not in {
            w["name"] for w in sp["workloads"]}:
        return  # no metric lists it yet; answers are what it proves
    if not trace:
        assert set(out["metrics"]) == names
    else:
        # Trace-derived metrics need a device plane, which the CPU lacks.
        assert set(out["metrics"]) <= names
        assert {n for n in names if not n.startswith(
            ("device.", "bloom_roofline."))} <= set(out["metrics"])


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(monkeypatch, workload, trace):
    _check(run_tiny(monkeypatch, workload, trace), spec(), workload, trace)


def _data_only_root(tmp_path, chips):
    """A copy of the benchmark's data with one more cell, its own
    configuration and its own mix: files and entries, no code."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(run.ROOT, "benchmark", "configs"),
                    root / "benchmark" / "configs")
    shutil.copytree(os.path.join(run.ROOT, "benchmark", "traffic"),
                    root / "benchmark" / "traffic")
    cfg = run.load_json(os.path.join(run.ROOT, "benchmark", "configs",
                                     "bloom-tenants-1k.json"))
    sketch = {"num_shards": chips} if chips > 1 else {}
    cfg.update(name="data-only", filters=300, name_format="d:{}",
               client={"codec": "LongCodec", "tpu_sketch": sketch},
               tiny={"filters": 12})
    (root / "benchmark" / "configs" / "data-only.json").write_text(
        json.dumps(cfg))
    mix = run.load_json(os.path.join(run.ROOT, "benchmark", "traffic",
                                     "tenants-mixed.json"))
    mix.update(producers=3, add_every=2, tiny={"rate": 300})
    (root / "benchmark" / "traffic" / "data-only-mix.json").write_text(
        json.dumps(mix))
    sp = spec()
    sp["configs"].append({"name": "data-only", "source": "a test",
                          "file": "benchmark/configs/data-only.json",
                          "reduced": [], "why": "a test"})
    sp["workloads"].append({"name": "data-only-cell", "config": "data-only",
                            "traffic": "data-only-mix", "chips": chips,
                            "why": "a test"})
    for m in sp["end_to_end"] + sp["per_layer"]:
        if "tenants-mixed" in m.get("workloads", ()):
            m["workloads"].append("data-only-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(sp))
    return str(root), sp


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_in_data_alone_rehearses(monkeypatch, tmp_path, chips, trace):
    root, sp = _data_only_root(tmp_path, chips)
    out = run_tiny(monkeypatch, "data-only-cell", trace, root=root)
    _check(out, sp, "data-only-cell", trace)
    assert out["device"]["count"] == chips


def test_gate_refuses_cpu():
    with pytest.raises(SystemExit):
        run.device_gate(1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        run.peaks("TPU v99")
