"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached (the on-chip-measurement guide, section 2).  It
refuses what interpret mode accepts — unaligned slices, too much VMEM, a
program that does not fit HBM — so these compiles guard every PR at the
real BASELINE geometries without chip time.

Single-chip steps are built exactly as ``TpuCommandExecutor`` builds
them (row slice, kernel, row update, packed staging): ``ChipCompiler``
only swaps the pool state for its shape and compiles each step for the
described chip instead of running it.  The mesh programs of the sharded
executor compile over all four described chips.

The topology is described inside a module fixture, never at import: one
process at a time may load libtpu, and pytest-xdist workers all import
this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from redisson_tpu.tenancy import PoolKind
from redisson_tpu.tenancy.registry import (
    SizeClassPool,
    class_words_for_bits,
    spec_for,
)

K = 7  # hash count of a 1% filter


class _Compiled(Exception):
    def __init__(self, compiled):
        super().__init__("compiled")
        self.compiled = compiled


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A chip compile is written to the persistent cache but cannot be
    read back without the chip (a warning per hit): keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                sharding=sharding)


@pytest.fixture(scope="module")
def ex(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding

    from redisson_tpu import Config
    from redisson_tpu.executor.tpu_executor import TpuCommandExecutor

    one_chip = SingleDeviceSharding(topo.devices[0])

    class ChipCompiler(TpuCommandExecutor):
        def make_pool_state(self, capacity, row_units, dtype, kind=""):
            return jax.eval_shape(lambda: super(ChipCompiler, self)
                                  .make_pool_state(capacity, row_units,
                                                   dtype, kind))

        def _jit(self, key, build, donate):
            def compile_for_chip(*args):
                fn = jax.jit(build(), donate_argnums=(0,) if donate else ())
                lowered = fn.lower(*[_spec(a, one_chip) for a in args])
                raise _Compiled(lowered.compile())

            return compile_for_chip

    return ChipCompiler(Config().use_tpu_sketch())


def _compile(call):
    with pytest.raises(_Compiled) as ei:
        call()
    return ei.value.compiled


def _pool(ex, kind, class_key, tenants=8):
    return SizeClassPool(spec_for(kind, class_key), tenants, ex)


def _u32(n, hi, seed=0):
    return np.random.default_rng(seed).integers(0, hi, n).astype(np.uint32)


@pytest.mark.parametrize("op", ["bloom_add", "bloom_contains"])
def test_bloom_config1_row(ex, op):
    """Config 1: one 1M-key 1% filter (m=9,585,059), 1M-op launch."""
    m, B = 9_585_059, 1 << 20
    pool = _pool(ex, PoolKind.BLOOM, (class_words_for_bits(m),))
    rows = np.zeros(B, np.int32)
    m_arr = np.full(B, m, np.uint32)
    c = _compile(lambda: getattr(ex, op)(
        pool, rows, m_arr, K, _u32(B, m), _u32(B, m, 1)))
    assert c.memory_analysis().argument_size_in_bytes >= 4 * pool.row_units


@pytest.mark.parametrize("op", ["bloom_mixed", "bloom_mixed_keys_runs"])
def test_bloom_config4_stacked_pool(ex, op):
    """Config 4: 1000 stacked 10k/1% tenants (pool grown to 1024 rows),
    one 64k-op mixed segment — host-hashed and device-hashed forms."""
    m, B, T = 95_851, 1 << 16, 1024
    pool = _pool(ex, PoolKind.BLOOM, (class_words_for_bits(m),), tenants=T)
    rng = np.random.default_rng(0)
    if op == "bloom_mixed":
        call = lambda: ex.bloom_mixed(  # noqa: E731
            pool, rng.integers(0, 1000, B).astype(np.int32),
            np.full(B, m, np.uint32), K, _u32(B, m), _u32(B, m, 1),
            rng.random(B) < 0.3)
    else:
        C = B // 256  # one run per submitted 256-key chunk
        blocks = np.zeros((B, 4), np.uint32)  # LongCodec: 8-byte keys
        blocks[:, :2] = rng.integers(0, 1 << 32, (B, 2))
        call = lambda: ex.bloom_mixed_keys_runs(  # noqa: E731
            pool, K, blocks, np.uint32(8),
            rng.integers(0, 1000, C).astype(np.int32),
            np.full(C, m, np.uint32), rng.random(C) < 0.3,
            np.arange(0, B + 1, 256, dtype=np.int32))
    _compile(call)


@pytest.mark.parametrize("op", ["hll_add", "hll_add_changed"])
def test_hll_p14(ex, op):
    """Config 2: p=14 registers, 1M-op launch."""
    B = 1 << 20
    pool = _pool(ex, PoolKind.HLL, ())
    rows = np.zeros(B, np.int32)
    _compile(lambda: getattr(ex, op)(
        pool, rows, _u32(B, 1 << 32), _u32(B, 1 << 32, 1),
        _u32(B, 1 << 32, 2)))


@pytest.mark.parametrize("op", ["bitset_set", "bitset_get",
                                "bitset_mixed_runs"])
def test_bitset_2_30_row(ex, op):
    """Config 3: a 2^30-bit row (128 MiB), 1M-op launch."""
    from redisson_tpu.ops import bitset as bitset_ops

    B = 1 << 20
    pool = _pool(ex, PoolKind.BITSET, (class_words_for_bits(1 << 30),))
    idx = _u32(B, 1 << 30)
    if op == "bitset_mixed_runs":
        call = lambda: ex.bitset_mixed_runs(  # noqa: E731
            pool, idx, np.zeros(2, np.int32),
            np.array([bitset_ops.OP_SET, bitset_ops.OP_GET], np.uint32),
            np.array([0, B // 2, B], np.int32))
    else:
        call = lambda: getattr(ex, op)(  # noqa: E731
            pool, np.zeros(B, np.int32), idx)
    c = _compile(call)
    assert c.memory_analysis().argument_size_in_bytes >= 1 << 27


@pytest.mark.parametrize("d,w,B", [
    (5, 1 << 16, 8192),  # config 5
    (8, 1 << 18, 8192),  # the engine's 8 MiB VMEM gate, deep
    (4, 1 << 19, 8192),  # the gate, wide
])
def test_cms_pallas_seq(ex, monkeypatch, d, w, B):
    """Config 5's Pallas streaming kernel inside its executor wrapper.
    The executor picks interpret mode on the CPU backend; the test steers
    it to the Mosaic kernel the chip runs."""
    assert d * w * 4 <= 8 << 20
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = _pool(ex, PoolKind.CMS, (d, w))
    c = _compile(lambda: ex.cms_update_estimate_seq(
        pool, 0, _u32(B, w), _u32(B, w, 1), np.ones(B, np.uint32), d, w))
    assert "tpu_custom_call" in c.as_text()


# -- the sharded executor's mesh programs over four described chips ---------


@pytest.fixture(scope="module")
def mesh4(topo, no_compile_cache):
    from redisson_tpu.parallel.mesh import MeshContext

    return MeshContext(devices=list(topo.devices)[:4])


def _sharded(ctx, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ctx.state_sharding)


def _replicated(ctx, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ctx.replicated)


@pytest.mark.parametrize("op", ["set", "get"])
def test_mesh_msharded_bitset_2_30(mesh4, op):
    """Config 3 over four chips: the 2^30-bit row m-sharded by words."""
    from redisson_tpu.ops import bitset as bitset_ops
    from redisson_tpu.parallel import mesh as pm

    S, Bp = 4, 1 << 18
    WL = (1 << 25) // S
    cap = (1 << 27) // (1 << 25)  # round_capacity's giant-row clamp
    state = _sharded(mesh4, (S, cap * WL + 1), jnp.uint32)
    cols = [_sharded(mesh4, (S, Bp), jnp.int32),
            _sharded(mesh4, (S, Bp), jnp.uint32),
            _sharded(mesh4, (S, Bp), jnp.bool_)]
    fn = (pm.psharded_bitset_rw(mesh4, bitset_ops.bitset_set,
                                words_per_row=WL) if op == "set"
          else pm.psharded_bitset_get(mesh4, words_per_row=WL))
    c = fn.lower(state, *cols).compile()
    assert c.memory_analysis().argument_size_in_bytes >= 4 * cap * WL


def test_mesh_tenant_sharded_bloom(mesh4):
    """64 10k/1% tenants row-sharded over four chips, device-hashed."""
    from redisson_tpu.parallel import mesh as pm

    S, Bp, W = 4, 1 << 14, class_words_for_bits(95_851)
    state = _sharded(mesh4, (S, 16 * W + 1), jnp.uint32)
    fn = pm.psharded_bloom_mixed_keys(mesh4, k=K, words_per_row=W,
                                      target_lanes=4)
    cols = [_sharded(mesh4, (S, Bp), jnp.int32),
            _sharded(mesh4, (S, Bp, 2), jnp.uint32),
            _sharded(mesh4, (S, Bp), jnp.uint32),
            _sharded(mesh4, (S, Bp), jnp.uint32),
            _sharded(mesh4, (S, Bp), jnp.bool_),
            _sharded(mesh4, (S, Bp), jnp.bool_)]
    fn.lower(state, *cols).compile()


@pytest.mark.parametrize("op", ["pfmerge", "bitop_or"])
def test_mesh_cross_shard_collectives(mesh4, op):
    """PFMERGE (pmax) and BITOP OR (psum) across shards."""
    from redisson_tpu.ops.golden import HLL_M
    from redisson_tpu.parallel import mesh as pm

    S = 4
    dst = _replicated(mesh4, (), jnp.int32)
    srcs = _replicated(mesh4, (7,), jnp.int32)
    if op == "pfmerge":
        state = _sharded(mesh4, (S, 16 * HLL_M + 1), jnp.uint8)
        c = pm.sharded_hll_merge(mesh4).lower(state, dst, srcs).compile()
        assert "all-reduce" in c.as_text()
    else:
        W = 1 << 15
        state = _sharded(mesh4, (S, 2 * W + 1), jnp.uint32)
        fn = pm.sharded_bitop(mesh4, words_per_row=W, op="or", n_src=7)
        c = fn.lower(state, dst, srcs,
                     _replicated(mesh4, (), jnp.int64)).compile()
        assert "all-reduce" in c.as_text()
