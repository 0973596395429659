"""Contains-only coalesced Bloom launches: one hashed gather
(executor.bloom_contains_keys_runs) instead of the sort-chunked scan.

Pins the gather bit for bit to the scan with every flag False and to the
host golden engine, the routing by the launch's own add flags, and that
the gather leaves the pool state as it found it."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec, StringCodec, encode_batch
from redisson_tpu.ops import fastpath

# Three filters of one size class (4096 words, k = 7) with different m.
FILTERS = (("g0", 10_000), ("g1", 9_000), ("g2", 7_000))


def _tpu(codec, **kw):
    kw.setdefault("batch_window_us", 500)
    return redisson_tpu.create(Config().set_codec(codec).use_tpu_sketch(
        coalesce=True, min_bucket=64, **kw))


def _keys(codec, lo, n):
    if isinstance(codec, LongCodec):
        return np.arange(lo, lo + n, dtype=np.uint64)
    # Lengths 8..20 bytes: per-op lengths and two hash blocks.
    return [f"key-{i}-{'x' * (i % 13)}" for i in range(lo, lo + n)]


def _segment(codec, sizes):
    """Runs over the filters in turn: (name, keys) per run; the keys
    overlap what _fill added, so both answers occur."""
    runs = []
    for i, n in enumerate(sizes):
        name = FILTERS[i % len(FILTERS)][0]
        runs.append((name, _keys(codec, 900 * i, n)))
    return runs


def _fill(client, codec):
    filters = {}
    for name, n in FILTERS:
        bf = client.get_bloom_filter(name)
        bf.try_init(n, 0.01)
        filters[name] = bf
    for i, (name, _) in enumerate(FILTERS):
        filters[name].add_all(_keys(codec, 0, 1000 + 300 * i))
    return filters


def _launch_args(client, codec, runs, const_len):
    eng = client._engine
    blocks, lengths, rows, ms, starts = [], [], [], [], [0]
    for name, keys in runs:
        b, ln = encode_batch(codec, keys)
        blocks.append(b)
        lengths.append(np.asarray(ln, np.uint32))
        entry = eng.registry.lookup(name)
        rows.append(entry.row)
        ms.append(entry.params["size"])
        starts.append(starts[-1] + len(b))
    L = max(b.shape[1] for b in blocks)
    blocks = np.concatenate(
        [np.pad(b, ((0, 0), (0, L - b.shape[1]))) for b in blocks])
    lengths = np.concatenate(lengths)
    if const_len:
        assert np.all(lengths == lengths[0])
        lengths = np.uint32(lengths[0])
    entry = eng.registry.lookup(runs[0][0])
    return (entry.pool, entry.params["hash_iterations"], blocks, lengths,
            np.array(rows, np.int32), np.array(ms, np.uint32),
            np.array(starts, np.int32))


CASES = {
    # codec, run sizes (the last case pads 777 ops to a 1024 bucket)
    "const-len": (LongCodec, (300, 200, 256, 100)),
    "per-op-len": (StringCodec, (250, 180, 90)),
    "padded": (LongCodec, (400, 77, 300)),
}


@pytest.mark.parametrize("slice_ops", [None, 128])
@pytest.mark.parametrize("case", list(CASES))
def test_gather_equals_scan_and_golden(monkeypatch, case, slice_ops):
    codec_cls, sizes = CASES[case]
    if slice_ops is not None:  # take the lax.map path at a small size
        monkeypatch.setattr(fastpath, "GATHER_SLICE", slice_ops)
    tpu, host = _tpu(codec_cls()), redisson_tpu.create(
        Config().set_codec(codec_cls()))
    try:
        _fill(tpu, codec_cls())
        hfs = _fill(host, codec_cls())
        runs = _segment(codec_cls(), sizes)
        pool, k, blocks, lengths, rr, rm, starts = _launch_args(
            tpu, codec_cls(), runs, const_len=codec_cls is LongCodec)
        if case == "padded":
            assert starts[-1] == 777
        ex = tpu._engine.executor
        tpu._engine._drain()
        got = ex.bloom_contains_keys_runs(
            pool, k, blocks, lengths, rr, rm, starts).result()
        scan = ex.bloom_mixed_keys_runs(
            pool, k, blocks, lengths, rr, rm,
            np.zeros(len(rr), bool), starts).result()
        golden = np.concatenate(
            [hfs[name].contains_each(keys) for name, keys in runs])
        assert got.shape == (int(starts[-1]),)
        np.testing.assert_array_equal(got, scan)
        np.testing.assert_array_equal(got, golden)
        assert 0 < int(got.sum()) < len(got)  # both answers occur
    finally:
        tpu.shutdown()
        host.shutdown()


def _dispatch_ops(client) -> dict:
    fam = client._engine.obs.dispatch_ops
    return {labels[0]: int(c.value) for labels, c in fam.items()}


def test_routing_by_add_flags():
    """A segment with no add run lands on the gather and is counted under
    its method; one add run keeps the whole segment on the scan, whose
    sequential semantics let a contains see an add queued before it."""
    c = _tpu(LongCodec())
    try:
        bf = _fill(c, LongCodec())["g0"]
        eng = c._engine
        entry = eng.registry.lookup("g0")
        pool, k = entry.pool, entry.params["hash_iterations"]
        m = entry.params["size"]
        dispatch = eng._runs_dispatch(pool, k)
        eng._drain()

        def launch(keys_flags):
            cols = (np.concatenate(
                [encode_batch(LongCodec(), keys)[0] for keys, _ in keys_flags]),)
            metas = [(len(keys), (entry.row, m, flag, 8))
                     for keys, flag in keys_flags]
            return dispatch(cols, metas).result()

        before = _dispatch_ops(c)
        fresh = np.arange(50_000, 50_064, dtype=np.uint64)
        got = launch([(np.arange(0, 64, dtype=np.uint64), False),
                      (fresh, False)])
        after = _dispatch_ops(c)
        assert got[:64].all()
        assert after.get("bloom_contains_keys_runs", 0) - before.get(
            "bloom_contains_keys_runs", 0) == 128
        assert after.get("bloom_mixed_keys_runs", 0) == before.get(
            "bloom_mixed_keys_runs", 0)
        # add(fresh) then contains(fresh) in ONE segment: the scan.
        got = launch([(fresh, True), (fresh, False)])
        final = _dispatch_ops(c)
        assert got[64:].all()
        assert final["bloom_mixed_keys_runs"] - after.get(
            "bloom_mixed_keys_runs", 0) == 128
        assert final["bloom_contains_keys_runs"] == after[
            "bloom_contains_keys_runs"]
        assert bf.contains_all(fresh) == 64
    finally:
        c.shutdown()


def test_coalesced_contains_counts_under_gather():
    """Through the client: a contains-only call runs the gather, and the
    jit cache holds its module under the bloom_conk_runs name."""
    c = _tpu(LongCodec())
    try:
        bf = c.get_bloom_filter("cg")
        bf.try_init(5000, 0.01)
        bf.add_all(np.arange(100, dtype=np.uint64))
        before = _dispatch_ops(c).get("bloom_contains_keys_runs", 0)
        assert bf.contains_all(np.arange(200, dtype=np.uint64)) >= 100
        assert _dispatch_ops(c)["bloom_contains_keys_runs"] - before == 200
        assert any(k[0] == "bloom_conk_runs"
                   for k in c._engine.executor._jit_cache)
    finally:
        c.shutdown()


def test_gather_leaves_state_and_later_add_works():
    c = _tpu(LongCodec())
    try:
        _fill(c, LongCodec())
        runs = _segment(LongCodec(), (128, 64))
        pool, k, blocks, lengths, rr, rm, starts = _launch_args(
            c, LongCodec(), runs, const_len=True)
        ex = c._engine.executor
        c._engine._drain()
        state = pool.state
        snapshot = np.asarray(state).copy()
        ex.bloom_contains_keys_runs(
            pool, k, blocks, lengths, rr, rm, starts).result()
        assert pool.state is state  # not swapped, not donated
        assert not state.is_deleted()
        np.testing.assert_array_equal(np.asarray(pool.state), snapshot)
        bf = c.get_bloom_filter("g1")
        fresh = np.arange(90_000, 90_050, dtype=np.uint64)
        assert bf.add_all(fresh) > 0
        assert bf.contains_all(fresh) == 50
    finally:
        c.shutdown()

