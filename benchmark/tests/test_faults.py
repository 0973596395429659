"""A run with the timed path broken underneath must come out not
correct, once for each fault a cell can have, and the control (the
reference with one probe fewer, in the program's place) must fail the
same comparison.  The cells are the benchmark's own and the pending
ones.  The exchange between chips is a fault only a cell on more than
one chip can have: there each op is answered from the same slot of the
next shard's results."""

import numpy as np
import pytest

from benchmark.tests.rehearsal import run_tiny, spec, workloads

CELLS = workloads(spec())
FAULTS = ["state_unchanged", "half_batch", "answer_altered"]
ACROSS_CHIPS = ["exchange_shifted"]


class _Mapped:
    """A result whose value is changed where it is produced."""

    def __init__(self, fut, fn):
        self._fut, self._fn = fut, fn

    def result(self, *a, **kw):
        return self._fn(np.asarray(self._fut.result(*a, **kw)))

    get = result

    def done(self):
        return self._fut.done()


def _sub(lengths, h):
    return lengths if np.ndim(lengths) == 0 else np.asarray(lengths)[:h]


def _half(n):
    # Half of the batch left out: the first half is computed, and the
    # rest answered with the first half's answers.
    return lambda r: np.resize(r, n)


def _flip(r):
    r = r.copy()
    r[0] = ~r[0]
    return r


def _patch(monkeypatch, fault):
    from redisson_tpu.objects.engines import TpuSketchEngine as E

    add, has, mixed = (E.bloom_add_encoded, E.bloom_contains_encoded,
                       E.bloom_mixed_encoded)
    if fault == "state_unchanged":
        def new_add(self, name, blocks, lengths):
            return _Mapped(has(self, name, blocks, lengths), np.logical_not)

        def new_mixed(self, name, blocks, lengths, flags):
            f = np.asarray(flags, bool)
            return _Mapped(has(self, name, blocks, lengths),
                           lambda r: np.where(f, ~r, r))

        monkeypatch.setattr(E, "bloom_add_encoded", new_add)
        monkeypatch.setattr(E, "bloom_mixed_encoded", new_mixed)
    elif fault == "half_batch":
        def wrap(orig):
            def f(self, name, blocks, lengths, *flags):
                n = blocks.shape[0]
                h = (n + 1) // 2
                fl = tuple(np.asarray(x)[:h] for x in flags)
                return _Mapped(orig(self, name, blocks[:h],
                                    _sub(lengths, h), *fl), _half(n))
            return f

        for name, orig in (("bloom_add_encoded", add),
                           ("bloom_contains_encoded", has),
                           ("bloom_mixed_encoded", mixed)):
            monkeypatch.setattr(E, name, wrap(orig))
    elif fault == "answer_altered":
        def wrap(orig):
            def f(self, *a):
                return _Mapped(orig(self, *a), _flip)
            return f

        for name, orig in (("bloom_add_encoded", add),
                           ("bloom_contains_encoded", has),
                           ("bloom_mixed_encoded", mixed)):
            monkeypatch.setattr(E, name, wrap(orig))
    elif fault == "exchange_shifted":
        from redisson_tpu.executor.sharded_executor import _Partition
        from redisson_tpu.ops import bitops

        def unpack_next_shard(self, packed):
            un = bitops.unpack_bool_u32(
                np.ascontiguousarray(packed).reshape(-1), self.S * self.Bp
            ).reshape(self.S, self.Bp)
            res = np.empty(self.B, bool)
            res[self.order] = un[(self.sh_sorted + 1) % self.S, self.slot]
            return res

        monkeypatch.setattr(_Partition, "unpack_bools", unpack_next_shard)


@pytest.mark.parametrize("workload,fault", [
    (w["name"], f) for w in CELLS
    for f in FAULTS + (ACROSS_CHIPS if w["chips"] > 1 else [])])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    _patch(monkeypatch, fault)
    out = run_tiny(monkeypatch, workload, seed=11)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", [w["name"] for w in CELLS])
def test_control_fails_and_program_passes(monkeypatch, workload):
    out = run_tiny(monkeypatch, workload, seed=12, control=True)
    assert out["correct"], out["checks"]
    assert out["control"]["mismatches"]["value"] > 0
