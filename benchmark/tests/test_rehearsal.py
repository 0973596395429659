"""CPU rehearsal: every cell runs end to end at a tiny size, prints a
result that parses with the contract's keys, and reports correct."""

import json

import pytest

from benchmark import run
from benchmark.tests.rehearsal import PENDING, run_tiny

CELLS = [w["name"] for w in run.load_json(
    run.os.path.join(run.ROOT, "BENCHMARK.json"))["workloads"]] + list(
        PENDING)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(monkeypatch, workload, trace):
    out = json.loads(json.dumps(run_tiny(monkeypatch, workload, trace)))
    assert list(out)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    spec = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in run.metrics_for(spec, section, workload)}
    if workload in PENDING:
        return  # no metric lists it yet; answers are what it proves
    if not trace:
        assert set(out["metrics"]) == names
    else:
        # Trace-derived metrics need a device plane, which the CPU lacks.
        assert set(out["metrics"]) <= names
        assert {n for n in names if not n.startswith(
            ("device.", "bloom_roofline."))} <= set(out["metrics"])


def test_gate_refuses_cpu():
    with pytest.raises(SystemExit):
        run.device_gate(1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        run.peaks("TPU v99")
