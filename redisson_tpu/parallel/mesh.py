"""shard_map kernels for multi-chip execution.

Pattern (the embedding-table classic): op batches are replicated to every
shard; each shard computes an ownership mask, routes non-owned ops to its
scratch slot, executes the same single-device kernel from ops/ on its local
pool block, and contributes masked results to a ``psum`` — one ICI
all-reduce per batch, no host round trips.  Writes need no collective at
all (each shard owns its rows).

State layout: ``[S, local_len]`` sharded along axis 0 of a 1-D mesh
(axis name "shard").  Tenant row r → shard ``r % S``, local row ``r // S``
(round-robin keeps hot tenants spread).  A giant single-tenant bitmap
shards along words instead: global word g → shard ``g // W_local``
(contiguous blocks, so range ops touch few shards).

These functions return jitted closures bound to a mesh.  They are exercised
three ways: directly by the parallel test suite, by the driver's
``dryrun_multichip`` on a virtual CPU mesh (SURVEY.md §4's "many
redis-servers on one host" analog), and from the public API through
``ShardedTpuCommandExecutor`` (executor/sharded_executor.py) when
``Config.use_tpu_sketch(num_shards=S)`` selects cluster mode.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from redisson_tpu.ops import bitops, bloom, hll as hll_ops


def _named_jit(fn, name: str, **kw):
    """``jax.jit`` whose XLA module is ``jit_<name>``: every device op
    in a trace then names the builder that made it (a shard_map body is
    otherwise ``inner`` in every program)."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


class MeshContext:
    """Owns the device mesh and sharding specs (the ConnectionManager-role
    object for the device 'cluster', → SURVEY.md §2.4)."""

    def __init__(self, devices=None, n_shards: int | None = None):
        if devices is None:
            devices = jax.devices()
        if n_shards is not None:
            devices = devices[:n_shards]
        self.devices = devices
        self.n_shards = len(devices)
        self.mesh = Mesh(np.array(devices), axis_names=("shard",))
        self.state_sharding = NamedSharding(self.mesh, P("shard"))
        self.replicated = NamedSharding(self.mesh, P())

    def make_state(self, local_len: int, dtype):
        """Allocate a [S, local_len] pool block-sharded over the mesh."""
        return jax.device_put(
            jnp.zeros((self.n_shards, local_len), dtype), self.state_sharding
        )


# --------------------------------------------------------------------------
# Tenant-sharded bloom
# --------------------------------------------------------------------------


def _own_and_local(rows, valid, S: int):
    my = lax.axis_index("shard")
    own = (rows % S == my)
    if valid is not None:
        own = own & valid
    return own, rows // S


def sharded_bloom_add(ctx: MeshContext, *, k: int, words_per_row: int, pack_results: bool = False):
    """Returns jitted fn(state[S,L], rows, h1m, h2m, m_arr, valid) ->
    (new_state, newly bool[B]) with exact single-device semantics.
    ``pack_results``: return newly packed 32-per-uint32 (bitops.pack_bool_u32)
    to shrink D2H bytes."""
    S = ctx.n_shards

    def inner(state, rows, h1m, h2m, m_arr, valid):
        local = state[0]
        own, local_rows = _own_and_local(rows, valid, S)
        new_local, newly = bloom.bloom_add(
            local, local_rows, h1m, h2m, m=m_arr, k=k,
            words_per_row=words_per_row, valid=own,
        )
        newly = lax.psum(jnp.where(own, newly, False).astype(jnp.int32), "shard")
        out = newly > 0
        if pack_results:
            out = bitops.pack_bool_u32(out)
        return new_local[None], out

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P(), P(), P()),
        out_specs=(P("shard"), P()),
    )
    return _named_jit(fn, "sharded_bloom_add", donate_argnums=(0,))


def sharded_bloom_contains(ctx: MeshContext, *, k: int, words_per_row: int, pack_results: bool = False):
    S = ctx.n_shards

    def inner(state, rows, h1m, h2m, m_arr, valid):
        local = state[0]
        own, local_rows = _own_and_local(rows, valid, S)
        safe_rows = jnp.where(own, local_rows, 0)
        res = bloom.bloom_contains(
            local, safe_rows, h1m, h2m, m=m_arr, k=k, words_per_row=words_per_row
        )
        res = lax.psum(jnp.where(own, res, False).astype(jnp.int32), "shard")
        out = res > 0
        if pack_results:
            out = bitops.pack_bool_u32(out)
        return out

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P(), P(), P()),
        out_specs=P(),
    )
    return _named_jit(fn, "sharded_bloom_contains")


# --------------------------------------------------------------------------
# Tenant-sharded HLL
# --------------------------------------------------------------------------


def sharded_hll_add(ctx: MeshContext):
    S = ctx.n_shards

    def inner(state, rows, c0, c1, c2, valid):
        local = state[0]
        own, local_rows = _own_and_local(rows, valid, S)
        safe_rows = jnp.where(own, local_rows, 0)
        new_local = hll_ops.hll_add(local, safe_rows, c0, c1, c2, valid=own)
        return new_local[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P(), P(), P()),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "sharded_hll_add", donate_argnums=(0,))


def sharded_hll_histogram(ctx: MeshContext):
    """PFCOUNT path: row lives on one shard; others contribute zeros."""
    S = ctx.n_shards

    def inner(state, row):
        local = state[0]
        my = lax.axis_index("shard")
        own = (row % S) == my
        hist = hll_ops.hll_histogram(local, jnp.where(own, row // S, 0))
        hist = lax.psum(jnp.where(own, hist, 0), "shard")
        return hist

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P()), out_specs=P()
    )
    return _named_jit(fn, "sharded_hll_histogram")


# --------------------------------------------------------------------------
# m-sharded giant bitmap (config 3: 2^30-bit RBitSet)
# --------------------------------------------------------------------------


def sharded_mbit_set(ctx: MeshContext, *, words_local: int):
    """SETBIT batch on a bitmap sharded along words: global word g lives on
    shard g // words_local.  Returns fn(state[S, words_local+1], idx,
    valid) -> (new_state, prev bool[B])."""
    S = ctx.n_shards

    def inner(state, idx, valid):
        local = state[0]  # [words_local + 1], trailing scratch
        my = lax.axis_index("shard")
        gword = idx >> np.uint32(5)
        bit = idx & np.uint32(31)
        own = (gword // np.uint32(words_local)) == my.astype(jnp.uint32)
        if valid is not None:
            own = own & valid
        local_word = gword - my.astype(jnp.uint32) * np.uint32(words_local)
        # route_invalid_to_scratch overwrites every ~own entry itself —
        # no pre-select needed.
        local_word = bitops.route_invalid_to_scratch(
            local_word, own, words_local + 1
        )
        new_local, prev = bitops.scatter_set_bits(local, local_word, bit)
        prev = lax.psum(jnp.where(own, prev, 0).astype(jnp.int32), "shard")
        return new_local[None], prev > 0

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P()),
        out_specs=(P("shard"), P()),
    )
    return _named_jit(fn, "sharded_mbit_set", donate_argnums=(0,))


def sharded_mbit_get(ctx: MeshContext, *, words_local: int):
    S = ctx.n_shards

    def inner(state, idx):
        local = state[0]
        my = lax.axis_index("shard")
        gword = idx >> np.uint32(5)
        bit = idx & np.uint32(31)
        own = (gword // np.uint32(words_local)) == my.astype(jnp.uint32)
        local_word = jnp.where(
            own, gword - my.astype(jnp.uint32) * np.uint32(words_local), 0
        )
        res = bitops.gather_bits(local, local_word, bit)
        res = lax.psum(jnp.where(own, res, 0).astype(jnp.int32), "shard")
        return res > 0

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P()), out_specs=P()
    )
    return _named_jit(fn, "sharded_mbit_get")


# --------------------------------------------------------------------------
# Partition-by-owner kernels (round 3): the host splits each batch by owner
# shard (row % S) into [S, Bp] op blocks — the slot-routing role of
# CommandBatchService#executeAsync grouping commands per MasterSlaveEntry
# (SURVEY.md §3.2).  in_specs=P("shard") hands every shard ONLY its ops, so
# total device work is B (not S×B as under replicate-and-mask), writes stay
# shard-local, and per-op results come back [S, Bp] with NO collective at
# all.  Collectives remain only where data genuinely crosses shards
# (BITOP/PFMERGE/m-sharded bitmaps below).
# --------------------------------------------------------------------------


def _psharded(ctx: MeshContext, inner, n_op_args: int, *, out_state: bool, donate: bool = True,
              name: str = "psharded"):
    """shard_map wrapper for partitioned op batches: ``inner(local_state,
    *op_cols)`` sees one shard's [Bp]-shaped columns and returns
    (new_local, res[Bp-packed]) or just res.  ``name`` names the XLA
    module (``jit_<name>``): the calling builder's name."""

    def wrapped(state, *ops):
        local = state[0]
        cols = [o[0] for o in ops]
        return inner(local, *cols)

    out_specs = (P("shard"), P("shard")) if out_state else P("shard")
    fn = jax.shard_map(
        wrapped,
        mesh=ctx.mesh,
        in_specs=(P("shard"),) + (P("shard"),) * n_op_args,
        out_specs=out_specs,
    )
    return _named_jit(fn, name, donate_argnums=(0,) if (out_state and donate) else ())


def psharded_bloom_mixed(ctx: MeshContext, *, k: int, words_per_row: int):
    """fn(state, lrows, h1m, h2m, m, is_add, valid) -> (new_state,
    packed[S, Bp/32]); every column [S, Bp], rows already shard-local."""

    def inner(local, lrows, h1m, h2m, m_arr, is_add, valid):
        new_local, res = bloom.bloom_mixed(
            local, lrows, h1m, h2m, is_add,
            m=m_arr, k=k, words_per_row=words_per_row, valid=valid,
        )
        return new_local[None], bitops.pack_bool_u32(res)[None]

    return _psharded(ctx, inner, 6, out_state=True, name="psharded_bloom_mixed")


def psharded_bloom_mixed_keys(ctx: MeshContext, *, k: int, words_per_row: int, target_lanes: int):
    """Device-hash variant: raw codec lanes [S, Bp, L] hash in-kernel (the
    round-2 sharded mode shipped 16-byte host hashes — the fast path now
    works sharded too)."""
    from redisson_tpu.ops import fastpath

    def inner(local, lrows, blocks, lengths, m_arr, is_add, valid):
        new_local, res = fastpath.bloom_mixed_keys(
            local, lrows, blocks, lengths, m_arr, is_add, valid,
            k=k, words_per_row=words_per_row, target_lanes=target_lanes,
        )
        return new_local[None], bitops.pack_bool_u32(res)[None]

    return _psharded(ctx, inner, 6, out_state=True, name="psharded_bloom_mixed_keys")


def psharded_bitset_mixed(ctx: MeshContext, *, words_per_row: int):
    from redisson_tpu.ops import bitset as bitset_ops

    def inner(local, lrows, idx, opcodes, valid):
        new_local, obs = bitset_ops.bitset_mixed(
            local, lrows, idx, opcodes, words_per_row=words_per_row, valid=valid
        )
        return new_local[None], bitops.pack_bool_u32(obs)[None]

    return _psharded(ctx, inner, 4, out_state=True, name="psharded_bitset_mixed")


def psharded_bitset_rw(ctx: MeshContext, kernel, *, words_per_row: int):
    def inner(local, lrows, idx, valid):
        new_local, prev = kernel(
            local, lrows, idx, words_per_row=words_per_row, valid=valid
        )
        return new_local[None], bitops.pack_bool_u32(prev)[None]

    return _psharded(ctx, inner, 3, out_state=True, name="psharded_bitset_rw")


def psharded_bitset_get(ctx: MeshContext, *, words_per_row: int):
    from redisson_tpu.ops import bitset as bitset_ops

    def inner(local, lrows, idx, valid):
        res = bitset_ops.bitset_get(
            local, jnp.where(valid, lrows, 0), idx, words_per_row=words_per_row
        )
        return bitops.pack_bool_u32(res & valid)[None]

    return _psharded(ctx, inner, 3, out_state=False, name="psharded_bitset_get")


def psharded_hll_add_changed(ctx: MeshContext):
    def inner(local, lrows, c0, c1, c2, valid):
        new_local, changed = hll_ops.hll_add_changed(
            local, jnp.where(valid, lrows, 0), c0, c1, c2, valid=valid
        )
        return new_local[None], bitops.pack_bool_u32(changed)[None]

    return _psharded(ctx, inner, 5, out_state=True, name="psharded_hll_add_changed")


def psharded_hll_add_keys(ctx: MeshContext, *, target_lanes: int):
    """Device-hash PFADD: murmur in-kernel, then scatter-max with changed
    flags."""
    from redisson_tpu.ops import fastpath
    from redisson_tpu.utils import hashing

    def inner(local, lrows, blocks, lengths, valid):
        c0, c1, c2, _ = hashing.murmur3_x86_128(
            fastpath.pad_lanes(blocks, target_lanes), lengths, xp=jnp
        )
        new_local, changed = hll_ops.hll_add_changed(
            local, jnp.where(valid, lrows, 0), c0, c1, c2, valid=valid
        )
        return new_local[None], bitops.pack_bool_u32(changed)[None]

    return _psharded(ctx, inner, 4, out_state=True, name="psharded_hll_add_keys")


def psharded_cms_update_estimate(ctx: MeshContext, *, d: int, w: int, cells_per_row: int, estimate_only: bool = False, update_only: bool = False):
    from redisson_tpu.ops import cms as cms_ops

    def inner(local, lrows, h1w, h2w, weights, valid):
        safe_rows = jnp.where(valid, lrows, 0)
        if estimate_only:
            new_local = local
        else:
            wts = jnp.where(valid, weights, 0)
            new_local = cms_ops.cms_update(
                local, safe_rows, h1w, h2w, wts, d=d, w=w, cells_per_row=cells_per_row
            )
        if update_only:
            return new_local[None]
        est = cms_ops.cms_estimate(
            new_local, safe_rows, h1w, h2w, d=d, w=w, cells_per_row=cells_per_row
        )
        est = jnp.where(valid, est, 0)
        if estimate_only:
            return est[None]
        return new_local[None], est[None]

    if estimate_only:
        return _psharded(ctx, inner, 5, out_state=False, name="psharded_cms_update_estimate")
    if update_only:
        def wrapped(state, *ops):
            return inner(state[0], *[o[0] for o in ops])
        fn = jax.shard_map(
            wrapped,
            mesh=ctx.mesh,
            in_specs=(P("shard"),) * 6,
            out_specs=P("shard"),
        )
        return _named_jit(fn, "psharded_cms_update_estimate", donate_argnums=(0,))
    return _psharded(ctx, inner, 5, out_state=True, name="psharded_cms_update_estimate")


# --------------------------------------------------------------------------
# m-sharded multi-tenant bitset pools (config 3, SURVEY.md §7-L4): rows at
# or above Config.mbit_threshold_words split their WORDS contiguously
# across shards — global word g of row r lives on shard g // W_local at
# local row r.  Batch ops partition by word-shard host-side and reuse the
# psharded_* kernels with local coordinates; the builders below cover the
# whole-row ops (scalar reduces, range writes, BITOP), which are
# embarrassingly shard-local — per-shard partial results return [S] to the
# host for combination, no collective at all.
# --------------------------------------------------------------------------


def msharded_row_map(ctx: MeshContext, fn_local):
    """Each shard computes ``fn_local(local_state, row)`` over its word
    slice of the row; results come back stacked [S, ...] for host-side
    combination (sum for popcount, offset-max for length, …)."""

    def inner(state, row):
        v = jnp.asarray(fn_local(state[0], row))
        return v[None]

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P()), out_specs=P("shard")
    )
    return _named_jit(fn, "msharded_row_map")


def msharded_row_write(ctx: MeshContext, *, words_local: int):
    """Overwrite one row: data arrives pre-split [S, W_local]."""

    def inner(state, row, data):
        local = state[0]
        return bitops.row_update(local, row, data[0], words_local)[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P("shard")),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "msharded_row_write", donate_argnums=(0,))


def msharded_set_range(ctx: MeshContext, *, words_local: int, value: bool):
    """Range set/clear: the host clips the global [from, to) to each
    shard's word window; every shard applies its local mask."""

    def inner(state, row, fb, tb):
        local = state[0]
        mask = bitops.range_mask_words(words_local, fb[0], tb[0])
        cur = bitops.row_slice(local, row, words_local)
        new_row = (cur | mask) if value else (cur & ~mask)
        return bitops.row_update(local, row, new_row, words_local)[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P("shard"), P("shard")),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "msharded_set_range", donate_argnums=(0,))


def msharded_bitop(ctx: MeshContext, *, words_local: int, op: str, n_src: int, masked: bool = False):
    """BITOP on m-sharded rows: every operand's words for this shard are
    local, so each shard computes its slice independently — no collective
    (contrast sharded_bitop above, where whole rows live on one shard).
    ``limit`` arrives per-shard (the NOT mask clipped to the local window).
    """
    from redisson_tpu.ops import bitset as bitset_ops

    def inner(state, dst_row, src_rows, limit):
        local = state[0]
        return bitset_ops.bitset_bitop_rows(
            local, dst_row, src_rows, words_per_row=words_local, op=op,
            n_src=n_src, limit_bits=limit[0] if masked else None,
        )[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P("shard")),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "msharded_bitop", donate_argnums=(0,))


# --------------------------------------------------------------------------
# Cross-shard collectives: PFMERGE / BITOP between rows on different shards
# --------------------------------------------------------------------------


def sharded_hll_merge(ctx: MeshContext):
    """dst_row ← max(dst_row, src rows), rows anywhere on the mesh.  Each
    shard broadcasts its owned source rows via psum(max is monotone: zeros
    elsewhere), then only the dst owner writes."""
    S = ctx.n_shards

    def inner(state, dst_row, src_rows):
        from redisson_tpu.ops.golden import HLL_M

        local = state[0]
        my = lax.axis_index("shard")
        regs2d = local[:-1].reshape(-1, HLL_M)
        own_src = (src_rows % S) == my
        contrib = jnp.where(
            own_src[:, None], regs2d[jnp.where(own_src, src_rows // S, 0)], 0
        )
        # pmax, not psum: registers owned by different shards must combine
        # by max (zeros from non-owners are the identity for max too).
        merged_src = lax.pmax(contrib.max(axis=0).astype(jnp.int32), "shard")
        own_dst = (dst_row % S) == my
        dst_local = jnp.where(own_dst, dst_row // S, 0)
        cur = bitops.row_slice(local, dst_local, HLL_M)
        new_row = jnp.maximum(cur, merged_src.astype(jnp.uint8))
        new_row = jnp.where(own_dst, new_row, cur)
        new_local = bitops.row_update(local, dst_local, new_row, HLL_M)
        return new_local[None]

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P(), P()), out_specs=P("shard")
    )
    return _named_jit(fn, "sharded_hll_merge", donate_argnums=(0,))


def sharded_bitop(ctx: MeshContext, *, words_per_row: int, op: str, n_src: int, masked: bool = False):
    """BITOP across shards: operand rows are broadcast via psum (each shard
    contributes rows it owns, zeros otherwise), every shard computes the op,
    only the dst owner writes the result.  ``masked`` (NOT path): the
    complement is ANDed with a [0, limit_bits) mask — the byte-aligned
    logical-length semantics of engines.bitset_bitop."""
    S = ctx.n_shards

    def inner(state, dst_row, src_rows, limit):
        local = state[0]
        my = lax.axis_index("shard")
        rows2d = local[:-1].reshape(-1, words_per_row)
        own_src = (src_rows % S) == my
        gathered = jnp.where(
            own_src[:, None], rows2d[jnp.where(own_src, src_rows // S, 0)], 0
        )
        full = lax.psum(gathered, "shard")  # [n_src, W] now complete rows
        if op == "and":
            res = full[0]
            for i in range(1, n_src):
                res = res & full[i]
        elif op == "or":
            res = full[0]
            for i in range(1, n_src):
                res = res | full[i]
        elif op == "xor":
            res = full[0]
            for i in range(1, n_src):
                res = res ^ full[i]
        elif op == "not":
            res = ~full[0]
            if masked:
                res = res & bitops.range_mask_words(words_per_row, 0, limit)
        else:
            raise ValueError(op)
        own_dst = (dst_row % S) == my
        dst_local = jnp.where(own_dst, dst_row // S, 0)
        cur = bitops.row_slice(local, dst_local, words_per_row)
        new_row = jnp.where(own_dst, res, cur)
        new_local = bitops.row_update(local, dst_local, new_row, words_per_row)
        return new_local[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P()),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "sharded_bitop", donate_argnums=(0,))


# --------------------------------------------------------------------------
# Builders for the sharded executor (executor/sharded_executor.py): the
# remaining op surface — bitset single-bit batches, row scalars/reads/
# writes, CMS, HLL changed-flags — in the same ownership-mask pattern.
# --------------------------------------------------------------------------


def sharded_bitset_set_range(ctx: MeshContext, *, words_per_row: int, value: bool):
    S = ctx.n_shards

    def inner(state, row, from_bit, to_bit):
        local = state[0]
        my = lax.axis_index("shard")
        own = (row % S) == my
        lrow = row // S
        mask = bitops.range_mask_words(words_per_row, from_bit, to_bit)
        cur = bitops.row_slice(local, lrow, words_per_row)
        new_row = (cur | mask) if value else (cur & ~mask)
        new_row = jnp.where(own, new_row, cur)
        return bitops.row_update(local, lrow, new_row, words_per_row)[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P(), P()),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "sharded_bitset_set_range", donate_argnums=(0,))


def sharded_row_reduce(ctx: MeshContext, fn_local):
    """Owner-computes-scalar pattern: ``fn_local(local_state, local_row)``
    runs on the owning shard; everyone else contributes zeros to the psum.
    Serves BITCOUNT/length/bitpos/popcount/histogram (vector results psum
    elementwise the same way)."""
    S = ctx.n_shards

    def inner(state, row):
        local = state[0]
        my = lax.axis_index("shard")
        own = (row % S) == my
        v = fn_local(local, row // S)
        return lax.psum(jnp.where(own, v, 0), "shard")

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P()), out_specs=P()
    )
    return _named_jit(fn, "sharded_row_reduce")


def sharded_row_read(ctx: MeshContext, *, row_units: int):
    """Fetch one tenant row to every shard (psum broadcast from the owner)."""

    S = ctx.n_shards

    def inner(state, row):
        local = state[0]
        my = lax.axis_index("shard")
        own = (row % S) == my
        v = bitops.row_slice(local, row // S, row_units)
        # Only the owner contributes non-zeros, so a native-dtype psum is an
        # exact broadcast (no overflow possible).
        return lax.psum(jnp.where(own, v, jnp.zeros_like(v)), "shard")

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P()), out_specs=P()
    )
    return _named_jit(fn, "sharded_row_read")


def sharded_row_write(ctx: MeshContext, *, row_units: int):
    """Overwrite one tenant row (only the owner applies the update)."""
    S = ctx.n_shards

    def inner(state, row, data):
        local = state[0]
        my = lax.axis_index("shard")
        own = (row % S) == my
        lrow = row // S
        cur = bitops.row_slice(local, lrow, row_units)
        new_row = jnp.where(own, data, cur)
        return bitops.row_update(local, lrow, new_row, row_units)[None]

    fn = jax.shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P("shard"), P(), P()),
        out_specs=P("shard"),
    )
    return _named_jit(fn, "sharded_row_write", donate_argnums=(0,))


def sharded_cms_merge(ctx: MeshContext, *, cells_per_row: int):
    """CMS merge: sources broadcast via psum gather, dst owner adds the sum
    (CMS is linear)."""
    S = ctx.n_shards

    def inner(state, dst_row, src_rows):
        local = state[0]
        my = lax.axis_index("shard")
        rows2d = local[:-1].reshape(-1, cells_per_row)
        own_src = (src_rows % S) == my
        gathered = jnp.where(
            own_src[:, None], rows2d[jnp.where(own_src, src_rows // S, 0)], 0
        )
        full = lax.psum(gathered, "shard")
        summed = full.sum(axis=0, dtype=jnp.uint32)
        own_dst = (dst_row % S) == my
        dst_local = jnp.where(own_dst, dst_row // S, 0)
        cur = bitops.row_slice(local, dst_local, cells_per_row)
        new_row = jnp.where(own_dst, cur + summed, cur)
        return bitops.row_update(local, dst_local, new_row, cells_per_row)[None]

    fn = jax.shard_map(
        inner, mesh=ctx.mesh, in_specs=(P("shard"), P(), P()), out_specs=P("shard")
    )
    return _named_jit(fn, "sharded_cms_merge", donate_argnums=(0,))
