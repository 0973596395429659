"""Partitioned Bloom launches on a four-shard virtual CPU mesh ship one
packed input block, placed with the pool state's sharding.

Gates: answers of the sharded Bloom kernel (``bloom_mixed_keys``) for
both key shapes that reach it (8-byte LongCodec keys with one scalar
length, variable-length string keys with a length an op and more lanes)
equal the golden model (``ops/golden.py``, the host engine's reference)
applied op by op in arrival order; every launch places exactly one input block, committed
to the state's sharding, and counts one ``rtpu_h2d_puts``; a mesh that
is not fully addressable keeps the default-device put; one-chip puts
stay uncommitted.
"""

import numpy as np
import pytest
from jax.sharding import NamedSharding

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec
from redisson_tpu.executor import sharded_executor, tpu_executor
from redisson_tpu.ops import bloom, golden
from redisson_tpu.parallel import mesh as pm
from redisson_tpu.tenancy import PoolKind
from redisson_tpu.utils import hashing

S = 4
TENANTS = 13  # more tenants than shards, not a multiple of S


def _client(num_shards=S):
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        num_shards=num_shards, min_bucket=64, coalesce=False)
    return redisson_tpu.create(cfg)


@pytest.fixture
def sharded():
    cl = _client()
    yield cl
    cl.shutdown()


@pytest.fixture
def placed(monkeypatch):
    """Every array the sharded executor's staging helpers place."""
    out = []
    real = sharded_executor._put_staged

    def spy(slot, view, sharding=None):
        dev = real(slot, view, sharding)
        out.append(dev)
        return dev

    monkeypatch.setattr(sharded_executor, "_put_staged", spy)
    return out


def _tenants(cl, n=TENANTS):
    """n stacked filters of one size class: (pool, rows, m, k)."""
    entries = []
    for t in range(n):
        cl.get_bloom_filter(f"t{t}").try_init(2000, 0.01)
        entries.append(cl._engine._require(f"t{t}", PoolKind.BLOOM))
    pool = entries[0].pool
    assert all(e.pool is pool for e in entries)
    m, k = entries[0].params["size"], entries[0].params["hash_iterations"]
    return pool, np.array([e.row for e in entries], np.int32), m, k


class _Golden:
    """One golden filter a tenant, applied op by op in arrival order."""

    def __init__(self, rows, m, k):
        self.m = m
        self.g = {int(r): golden.GoldenBloomFilter(m, k) for r in rows}

    def run(self, rows, keys, is_add, shape="u64"):
        blocks, lengths = _encode(keys, shape)
        lengths = np.broadcast_to(lengths, blocks.shape[:1])
        h1m, h2m = hashing.km_reduce_mod(
            *hashing.hash128_np(blocks, lengths), self.m)
        out = np.zeros(len(keys), bool)
        for i, r in enumerate(rows):
            a = np.array([h1m[i]]), np.array([h2m[i]])
            f = self.g[int(r)]
            out[i] = (f.add_hashed if is_add[i] else f.contains_hashed)(*a)[0]
        return out


def _encode(keys, shape):
    """(blocks, lengths) of integer keys in one of the two key shapes:
    ``u64``, LongCodec's 8 bytes with one scalar length (API callers);
    ``str``, a decimal string padded to 1-39 bytes, one length an op and
    up to three 16-byte blocks (RESP callers)."""
    if shape == "u64":
        return hashing.encode_uint64_batch(keys)[0], np.uint32(8)
    return hashing.encode_bytes_batch(
        [b"k%d:" % int(x) + b"x" * (int(x) % 36) for x in keys])


def _launch(ex, pool, rows, m, k, keys, is_add, shape="u64"):
    blocks, lengths = _encode(keys, shape)
    m_arr = np.full(len(keys), m, np.uint32)
    lr = ex.bloom_mixed_keys(pool, rows, m_arr, k, blocks, lengths, is_add)
    return np.asarray(lr.result())


def _batch(rng, tenant_rows, n, key_space=60):
    """n ops over ``tenant_rows``; a small key space puts the same key
    in one batch several times, as adds and as contains."""
    rows = rng.choice(tenant_rows, size=n).astype(np.int32)
    keys = rng.integers(0, key_space, size=n, dtype=np.uint64)
    return rows, keys, rng.random(n) < 0.4


@pytest.mark.parametrize("shape,n_ops", [
    ("u64", 300), ("str", 300), ("u64", 4000), ("str", 4000)],
    ids=["u64", "str", "u64-scan", "str-scan"])
def test_packed_launch_matches_golden(sharded, shape, n_ops):
    """Mixed add and contains over many tenants, duplicates in a batch,
    several batches on the same state.  The ``-scan`` cases put more
    than 1,024 ops on the busiest shard, so each shard's add scan runs
    several steps carrying its pool's lane view."""
    pool, trows, m, k = _tenants(sharded)
    ex = sharded._engine.executor
    ref = _Golden(trows, m, k)
    rng = np.random.default_rng(7)
    steps = 0
    for _ in range(4 if n_ops < 1000 else 2):
        rows, keys, is_add = _batch(rng, trows, n_ops)
        got = _launch(ex, pool, rows, m, k, keys, is_add, shape)
        np.testing.assert_array_equal(got, ref.run(rows, keys, is_add, shape))
        busiest = int(np.bincount(rows % S).max())
        assert (busiest > bloom.scan_chunk_ops(k)) == (n_ops > 1000)
        steps += bloom.scan_steps(ex._bucket(busiest), k)
    counted = sharded._engine.obs.bloom_scan_steps
    assert counted.get(("bloom_mixed_keys", "lanes")) == steps
    assert counted.get(("bloom_mixed_keys", "flat")) == 0


@pytest.mark.parametrize("shape", ["u64", "str"])
def test_shard_with_no_ops(sharded, shape):
    """Every op of the batch lands on three of the four shards; the
    idle shard's slots are all padding and its rows stay empty."""
    pool, trows, m, k = _tenants(sharded)
    ex = sharded._engine.executor
    busy = trows[trows % S != 2]
    idle = trows[trows % S == 2]
    assert len(idle) and len(busy)
    ref = _Golden(trows, m, k)
    rng = np.random.default_rng(3)
    rows, keys, is_add = _batch(rng, busy, 200)
    got = _launch(ex, pool, rows, m, k, keys, is_add, shape)
    np.testing.assert_array_equal(got, ref.run(rows, keys, is_add, shape))
    # The idle shard's filters saw no add: every key reads absent.
    probe = np.arange(60, dtype=np.uint64)
    prow = np.resize(idle, probe.shape[0]).astype(np.int32)
    got = _launch(ex, pool, prow, m, k, probe, np.zeros(60, bool), shape)
    assert not got.any()


@pytest.mark.parametrize("shape", ["u64", "str"])
def test_busiest_shard_crosses_a_bucket(sharded, placed, shape):
    """One shard takes more ops than the smallest bucket holds, so the
    block's Bp steps up while the other shards stay nearly empty."""
    pool, trows, m, k = _tenants(sharded)
    ex = sharded._engine.executor
    ref = _Golden(trows, m, k)
    rng = np.random.default_rng(5)
    small = ex._bucket(1)
    hot = trows[trows % S == 1]
    for n_hot in (small - 10, small + 10, 3 * small):
        rows = np.concatenate([
            rng.choice(hot, size=n_hot),
            rng.choice(trows[trows % S != 1], size=9)]).astype(np.int32)
        rng.shuffle(rows)
        keys = rng.integers(0, 80, size=rows.shape[0], dtype=np.uint64)
        is_add = rng.random(rows.shape[0]) < 0.4
        got = _launch(ex, pool, rows, m, k, keys, is_add, shape)
        np.testing.assert_array_equal(got, ref.run(rows, keys, is_add, shape))
    bps = [block.shape[2] for block in placed]
    assert bps == [small, ex._bucket(small + 10), ex._bucket(3 * small)]
    assert bps[0] < bps[1] < bps[2]


@pytest.mark.parametrize("shape", ["u64", "str"])
def test_one_block_placed_with_state_sharding(sharded, placed, shape):
    pool, trows, m, k = _tenants(sharded)
    ex = sharded._engine.executor
    rows, keys, is_add = _batch(np.random.default_rng(1), trows, 150)
    _launch(ex, pool, rows, m, k, keys, is_add, shape)
    assert len(placed) == 1
    (block,) = placed
    assert block.sharding == ex.ctx.state_sharding
    assert block.committed
    # The key length and the key lanes left after trimming.
    lanes = ex._trim_lanes(_encode(keys, shape)[0])[0]
    payload = 1 + lanes.shape[1]
    # String keys span more than one 16-byte block; 8-byte keys do not.
    assert (lanes.shape[1] > 4) == (shape == "str")
    Bp = ex._bucket(int(np.bincount(rows % S).max()))
    assert block.shape == (S, pm.BLOOM_HEAD + payload, Bp)
    assert str(block.dtype) == "uint32"


@pytest.mark.parametrize("shape", ["u64", "str"])
def test_h2d_puts_one_per_bloom_launch(sharded, shape):
    pool, trows, m, k = _tenants(sharded)
    ex = sharded._engine.executor
    obs = sharded._engine.obs
    rng = np.random.default_rng(2)
    for i in range(1, 4):
        rows, keys, is_add = _batch(rng, trows, 100)
        _launch(ex, pool, rows, m, k, keys, is_add, shape)
        assert obs.h2d_puts.get(("bloom_mixed_keys",)) == i


def test_other_partitioned_kinds_place_with_state_sharding(sharded, placed):
    """HLL, bitset and CMS launches keep a transfer a column, each
    placed with the state's sharding, validity mask included."""
    ex = sharded._engine.executor
    sharded.get_hyper_log_log("h").add_all(list(range(50)))
    bs = sharded.get_bit_set("b")
    bs.set(5, True)
    assert bs.get(5)
    cms = sharded.get_count_min_sketch("c")
    cms.try_init(4, 1 << 10)
    cms.add_all(list(range(20)))
    assert all(e >= 1 for e in cms.estimate_all(list(range(20))))
    assert placed
    assert all(d.sharding == ex.ctx.state_sharding for d in placed)
    puts = sharded._engine.obs.h2d_puts
    assert sum(c.value for _l, c in puts.items()) == len(placed)


def test_not_fully_addressable_mesh_keeps_default_device_put(monkeypatch,
                                                            placed):
    """A mesh whose sharding spans other processes cannot be placed from
    one host: the executor puts its block on the default device and the
    jit places it, with the same answers."""
    with monkeypatch.context() as mp:
        mp.setattr(NamedSharding, "is_fully_addressable",
                   property(lambda self: False))
        cl = _client()
    try:
        ex = cl._engine.executor
        assert ex._put_sharding is None
        pool, trows, m, k = _tenants(cl)
        ref = _Golden(trows, m, k)
        rows, keys, is_add = _batch(np.random.default_rng(9), trows, 200)
        got = _launch(ex, pool, rows, m, k, keys, is_add)
        np.testing.assert_array_equal(got, ref.run(rows, keys, is_add))
        assert len(placed) == 1 and not placed[0].committed
    finally:
        cl.shutdown()


def test_one_chip_puts_stay_uncommitted(monkeypatch):
    """The one-chip executor shares the staging helper; its puts keep
    the default placement and count one transfer a packed launch."""
    cl = _client(num_shards=1)
    try:
        ex = cl._engine.executor
        assert type(ex) is tpu_executor.TpuCommandExecutor
        pool, trows, m, k = _tenants(cl, n=3)
        seen = []
        real = tpu_executor._put_staged

        def spy(slot, view, sharding=None):
            seen.append(sharding)
            dev = real(slot, view, sharding)
            assert not dev.committed
            return dev

        monkeypatch.setattr(tpu_executor, "_put_staged", spy)
        ref = _Golden(trows, m, k)
        rows, keys, is_add = _batch(np.random.default_rng(4), trows, 100)
        got = _launch(ex, pool, rows, m, k, keys, is_add)
        np.testing.assert_array_equal(got, ref.run(rows, keys, is_add))
        assert seen == [None]
        assert cl._engine.obs.h2d_puts.get(("bloom_mixed_keys",)) == 1
    finally:
        cl.shutdown()
