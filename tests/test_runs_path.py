"""Run-length segment metadata path (round 4):
per-chunk row/m/is_add ship once per run and expand on device.  These
tests pin equivalence with the per-op-array path and the golden engine."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec


def _client(**kw):
    kw.setdefault("batch_window_us", 500)
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        coalesce=True, min_bucket=64, **kw
    )
    return redisson_tpu.create(cfg)


def test_runs_path_is_selected():
    c = _client()
    try:
        assert c._engine.executor.supports_runs_metadata
        bf = c.get_bloom_filter("sel")
        bf.try_init(1000, 0.01)
        fut = bf.add_all_async(np.arange(10, dtype=np.uint64))
        fut.result()
        # The segment key for the runs path is distinct.
        assert ("bloom_mixk_runs" in str(k) for k in c._engine.executor._jit_cache)
        keys = [k for k in c._engine.executor._jit_cache if k[0] == "bloom_mixk_runs"]
        assert keys, "runs-metadata kernel was not compiled"
    finally:
        c.shutdown()


def test_runs_multi_tenant_segment_matches_golden():
    """Many tenants' chunks coalesce into one segment; results must match
    a per-tenant golden check."""
    c = _client()
    try:
        n_t = 7
        fs = []
        for t in range(n_t):
            bf = c.get_bloom_filter(f"rt{t}")
            bf.try_init(5000, 0.01)
            fs.append(bf)
        rng = np.random.default_rng(1)
        loads = [rng.integers(0, 10_000, 300).astype(np.uint64) for _ in range(n_t)]
        futs = [fs[t].add_all_async(loads[t]) for t in range(n_t)]
        for f in futs:
            f.result()
        # Every loaded key must be present; disjoint high keys mostly not.
        for t in range(n_t):
            assert int(np.sum(fs[t].contains_each(loads[t]))) == len(loads[t])
            miss = rng.integers(1 << 40, 1 << 41, 500).astype(np.uint64)
            fp = int(np.sum(fs[t].contains_each(miss)))
            assert fp < 50  # ~1% nominal
    finally:
        c.shutdown()


def test_runs_mixed_add_contains_order_within_segment():
    """An add submitted before a contains of the same key (same segment)
    must be observed — the sequential mixed kernel semantics."""
    c = _client(batch_window_us=5000)
    try:
        bf = c.get_bloom_filter("ord")
        bf.try_init(2000, 0.01)
        keys = np.arange(100, dtype=np.uint64)
        fa = bf.add_all_async(keys)
        fc = bf.contains_all_async(keys)
        assert int(np.sum(fc.result())) == 100
        assert int(np.sum(fa.result())) == 100
    finally:
        c.shutdown()


def test_runs_variable_length_keys():
    """String keys with differing lengths force the per-op lengths path."""
    cfg = Config().use_tpu_sketch(
        coalesce=True, min_bucket=64,
        batch_window_us=500,
    )
    c = redisson_tpu.create(cfg)
    try:
        bf = c.get_bloom_filter("vl")
        bf.try_init(2000, 0.01)
        short = [f"k{i}" for i in range(50)]
        long = [f"long-key-{'x' * (i % 17)}-{i}" for i in range(50)]
        f1 = bf.add_all_async(short)
        f2 = bf.add_all_async(long)
        f1.result(); f2.result()
        assert bf.contains_all(short) == 50
        assert bf.contains_all(long) == 50
        assert not bf.contains("absent-key")
    finally:
        c.shutdown()


def test_runs_many_tiny_chunks_exceeding_run_bucket():
    """Degenerate shape: >1024 single-op submits in one segment must grow
    the run bucket, not corrupt results."""
    c = _client(batch_window_us=50_000, max_batch=1 << 14)
    try:
        bf = c.get_bloom_filter("tiny")
        bf.try_init(20_000, 0.01)
        futs = [bf.add_async(np.array([i], dtype=np.uint64)) for i in range(1500)]
        for f in futs:
            f.result()
        got = int(np.sum(bf.contains_each(np.arange(1500, dtype=np.uint64))))
        assert got == 1500
    finally:
        c.shutdown()


def test_runs_empty_batch():
    c = _client()
    try:
        bf = c.get_bloom_filter("empty")
        bf.try_init(1000, 0.01)
        assert bf.add_all(np.array([], dtype=np.uint64)) == 0
        assert bf.contains_all(np.array([], dtype=np.uint64)) == 0
    finally:
        c.shutdown()


@pytest.mark.parametrize("sizes", [(5, 0, 3, 8), (1,), (0, 0, 7, 1, 0)])
def test_expand_runs_matches_repeat(sizes):
    """The on-device run expansion equals np.repeat of each run's value,
    empty runs included; ops past the last start take the last padded
    run's value, and int32/uint32 steps that wrap stay exact."""
    import jax.numpy as jnp

    from redisson_tpu.executor.tpu_executor import _expand_runs

    C, Cp, n = len(sizes), 8, 32
    B = sum(sizes)
    starts = np.full(Cp + 1, B, np.int32)
    starts[: C + 1] = np.concatenate([[0], np.cumsum(sizes)])
    rng = np.random.default_rng(C)
    rows = rng.integers(-(1 << 31), 1 << 31, Cp, dtype=np.int64).astype(np.int32)
    ms = rng.integers(0, 1 << 32, Cp, dtype=np.uint64).astype(np.uint32)
    got = _expand_runs(jnp.asarray(starts), n, jnp.asarray(rows),
                       jnp.asarray(ms))
    for col, want in zip(got, (rows, ms)):
        ref = np.concatenate([np.repeat(want[:C], sizes),
                              np.full(n - B, want[Cp - 1])])
        np.testing.assert_array_equal(np.asarray(col), ref)
