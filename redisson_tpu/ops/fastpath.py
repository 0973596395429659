"""Single-tenant fast-path bloom kernels — the bulk/bench hot path.

Design rationale (measured on the v5e chip): the exact sort-based add in
ops/bitops.py pays an O(B·k log) lexicographic sort per batch for exact
sequential duplicate semantics; this path instead materializes the batch's
bits in an int8 bit-delta ([bits/128, 128] rows, scatter-MAX of one-hot
rows — idempotent, so duplicate bits need no dedup), packs it to uint32
words with a weighted lane reduction, and ORs it into the tenant row.
~4x faster adds.

Semantic difference (documented, opt-in via
``Config.use_tpu_sketch(exact_add_semantics=False)``): the returned
``newly_added`` flags are computed against the PRE-BATCH state — two
identical keys in one batch both report True, where the exact path reports
True then False.  Bit-level results are identical; only duplicate-key
flags within a single batch differ.

The single-tenant restriction keeps the bit-delta at one row's size
(m/8 bytes); multi-tenant coalesced batches use the exact path.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from redisson_tpu.ops import bitops

# Ops per gather slice of bloom_contains_keys: the intermediate holds
# GATHER_SLICE * k rows of 512 bytes (42 MB at k = 5).  On one v5e a 1M-op
# launch took 64 ms at 16,384 ops a slice, 73 at 65,536, 76 at 262,144
# and 74 unsliced (pipelined, wall clock), so the shortest of those.
GATHER_SLICE = 1 << 14


def bloom_add_fast_st(flat_words, row, h1m, h2m, m, valid, *, k: int, words_per_row: int):
    """Single-tenant bulk add.  row and m are traced scalars (no per-op
    arrays to transfer).  Returns (new_flat, newly bool[B] vs pre-batch).
    """
    B = h1m.shape[0]
    idx = bitops.expand_km_indexes(h1m, h2m, m, k)  # [B, k] bit indexes
    # Pre-batch membership for newly flags (row gathers, exact).
    base_word = row.astype(jnp.uint32) * np.uint32(words_per_row)
    gword = base_word + (idx >> np.uint32(5))
    bit = idx & np.uint32(31)
    pre = bitops.gather_bits(flat_words, gword.reshape(-1), bit.reshape(-1))
    newly = (pre == 0).reshape(B, k).any(axis=1)

    # int8 bit-delta over this tenant's row only, plus one padding row that
    # absorbs invalid (batch-padding) ops.
    rb = words_per_row * 32 // 128  # bit-rows in one tenant row
    local_bit = idx.reshape(-1)
    brow = (local_bit >> np.uint32(7)).astype(jnp.int32)
    if valid is not None:
        valid_flat = jnp.broadcast_to(valid[:, None], idx.shape).reshape(-1)
        brow = jnp.where(valid_flat, brow, rb)
    lane = (local_bit & np.uint32(127)).astype(jnp.int32)
    onehot = (
        jnp.arange(128, dtype=jnp.int32)[None, :] == lane[:, None]
    ).astype(jnp.int8)
    delta8 = jnp.zeros((rb + 1, 128), jnp.int8).at[brow].max(onehot)
    # Pack 128 bits/row -> 4 uint32 words/row (weighted lane reduction).
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))[None, None, :]
    packed = (delta8[:rb].reshape(rb, 4, 32).astype(jnp.uint32) * weights).sum(
        axis=-1, dtype=jnp.uint32
    )
    delta_words = packed.reshape(-1)  # [words_per_row]

    cur = bitops.row_slice(flat_words, row, words_per_row)
    new = bitops.row_update(flat_words, row, cur | delta_words, words_per_row)
    return new, newly


def bloom_contains_st(flat_words, row, h1m, h2m, m, *, k: int, words_per_row: int):
    """Single-tenant contains with scalar row/m operands (halves the H2D
    transfer volume vs the per-op array form).  Bit-exact with
    ops/bloom.bloom_contains."""
    B = h1m.shape[0]
    idx = bitops.expand_km_indexes(h1m, h2m, m, k)
    base_word = row.astype(jnp.uint32) * np.uint32(words_per_row)
    gword = base_word + (idx >> np.uint32(5))
    bit = idx & np.uint32(31)
    bits = bitops.gather_bits(flat_words, gword.reshape(-1), bit.reshape(-1))
    return bits.reshape(B, k).all(axis=1)


# --------------------------------------------------------------------------
# Device-side hashing: ship raw codec lanes, hash + reduce in-kernel.
#
# The host pipeline (murmur batch + uint64 km_reduce_mod) tops out around
# ~20M keys/s/core and serializes with dispatch; hashing on the VPU rides
# along with the gather kernel for free and shrinks H2D to the raw key
# bytes.  The 64-bit ``h % m`` that km_reduce_mod does with cheap host
# uint64 is reproduced EXACTLY in uint32 via 64 unrolled bit-Horner steps
# (r = 2r + bit; r -= m if r >= m — one conditional subtract suffices since
# r < m <= 2**31 keeps 2r + bit < 2**32), so device-hashed results are
# bit-identical to the host/golden path and cross-engine parity holds.
# --------------------------------------------------------------------------


def mod64_bits(hi, lo, m32):
    """Exact ``(hi * 2**32 + lo) % m`` for uint32 lanes, m <= 2**31."""
    r = jnp.zeros_like(hi)
    one = np.uint32(1)
    for word in (hi, lo):
        for b in range(31, -1, -1):
            bit = (word >> np.uint32(b)) & one
            r = (r << one) | bit
            r = jnp.where(r >= m32, r - m32, r)
    return r


def pad_lanes(blocks, target_lanes: int):
    """Restore trailing all-zero lanes the host trimmed off before H2D
    (link bytes are scarce; zeros are free to rebuild).  ``target_lanes``
    must be the ORIGINAL lane count — murmur mixes every 16-byte block,
    zeros included, so the block count is part of the hash input."""
    lanes = blocks.shape[-1]
    if lanes == target_lanes:
        return blocks
    return jnp.concatenate(
        [
            blocks,
            jnp.zeros((*blocks.shape[:-1], target_lanes - lanes), blocks.dtype),
        ],
        axis=-1,
    )


def _hash_km_device(blocks, lengths, m, target_lanes: int):
    """murmur3_x86_128 on device → (h1m, h2m) uint32[B], bit-identical to
    hashing.hash128_np + hashing.km_reduce_mod."""
    from redisson_tpu.utils import hashing

    blocks = pad_lanes(blocks, target_lanes)
    c0, c1, c2, c3 = hashing.murmur3_x86_128(blocks, lengths, xp=jnp)
    m32 = m.astype(jnp.uint32) if hasattr(m, "astype") else np.uint32(m)
    # hash128_np: h1 = c0 | c1<<32, h2 = c2 | c3<<32.
    h1m = mod64_bits(c1, c0, m32)
    h2m = mod64_bits(c3, c2, m32)
    return h1m, h2m


def bloom_add_keys_st(flat_words, row, blocks, lengths, m, valid, *, k: int, words_per_row: int, target_lanes: int):
    """Single-tenant bulk add from raw key lanes (device-side hashing)."""
    h1m, h2m = _hash_km_device(blocks, lengths, m, target_lanes)
    return bloom_add_fast_st(
        flat_words, row, h1m, h2m, m, valid, k=k, words_per_row=words_per_row
    )


def bloom_contains_keys_st(flat_words, row, blocks, lengths, m, *, k: int, words_per_row: int, target_lanes: int):
    """Single-tenant contains from raw key lanes (device-side hashing)."""
    h1m, h2m = _hash_km_device(blocks, lengths, m, target_lanes)
    return bloom_contains_st(
        flat_words, row, h1m, h2m, m, k=k, words_per_row=words_per_row
    )


def bloom_contains_keys(flat_words, rows, blocks, lengths, m_arr, *, k: int, words_per_row: int, target_lanes: int):
    """Multi-tenant contains from raw key lanes: device hash, then one
    row gather (ops/bloom.bloom_contains) — no sort, no chunk scan, no
    scatter, and the state is only read.  With no add in the batch every
    op sees the pre-batch state, so this equals ``bloom_mixed_keys`` with
    ``is_add`` all False bit for bit.  Padding ops read whatever word
    their (row, m) lands on; the caller drops their results.

    The row gather holds one 512-byte row per probe, so a batch longer
    than ``GATHER_SLICE`` ops gathers slice by slice (``lax.map``) from
    one lane view of the pool, which bounds that intermediate."""
    from redisson_tpu.ops import bloom

    h1m, h2m = _hash_km_device(blocks, lengths, m_arr, target_lanes)
    B, S = rows.shape[0], GATHER_SLICE

    def contains(words, rows, h1m, h2m, m):
        return bloom.bloom_contains(
            words, rows, h1m, h2m, m=m, k=k, words_per_row=words_per_row)

    if B <= S:
        return contains(flat_words, rows, h1m, h2m, m_arr)
    lanes = bitops.lane_view(flat_words)
    cols = [jnp.broadcast_to(c, (B,)).reshape(B // S, S)
            for c in (rows, h1m, h2m, m_arr)]
    return lax.map(lambda c: contains(lanes, *c), cols).reshape(B)


def bloom_mixed_keys(flat_words, rows, blocks, lengths, m_arr, is_add, valid, *, k: int, words_per_row: int, target_lanes: int):
    """Multi-tenant combined add+contains from raw key lanes: murmur +
    exact 64-bit mod run in-kernel (bit-identical to the host pipeline),
    then the exact sequential mixed kernel.  This is the coalesced hot
    path: producers ship only codec bytes, so host threads never hash —
    the config-4 offered-load regime stops serializing on the GIL."""
    from redisson_tpu.ops import bloom

    h1m, h2m = _hash_km_device(blocks, lengths, m_arr, target_lanes)
    return bloom.bloom_mixed(
        flat_words, rows, h1m, h2m, is_add,
        m=m_arr, k=k, words_per_row=words_per_row, valid=valid,
    )


def hll_add_keys_single(flat_regs, row, blocks, lengths, valid, *, target_lanes: int):
    """Single-tenant PFADD from raw key lanes — murmur on device, then the
    standard scatter-max; returns (new, changed)."""
    from redisson_tpu.ops import hll as hll_ops
    from redisson_tpu.utils import hashing

    c0, c1, c2, _ = hashing.murmur3_x86_128(
        pad_lanes(blocks, target_lanes), lengths, xp=jnp
    )
    return hll_ops.hll_add_single(flat_regs, row, c0, c1, c2, valid=valid)
