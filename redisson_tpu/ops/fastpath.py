"""Device-hash kernels: raw codec lanes in, murmur3 + exact 64-bit mod
on the device, then the ops/bloom.py (or ops/hll.py) kernels.

Every Bloom op of the TPU engine runs one of these.  The host ships key
bytes only (no 16-byte host hashes, no producer-thread hashing), and the
in-kernel hash is bit-identical to the host pipeline
(``hashing.hash128_np`` + ``hashing.km_reduce_mod``), so a device answer
equals the golden model's.

- ``bloom_mixed_keys``: multi-tenant add/contains mix with exact
  sequential semantics (ops/bloom.bloom_mixed's sort-chunked scan).
- ``bloom_contains_keys``: multi-tenant contains-only batch as one row
  gather, sliced by ``GATHER_SLICE``.
- ``bloom_contains_keys_st``: one tenant's contains, row and m as scalars.
- ``hll_add_keys_single``: one HyperLogLog's PFADD.
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


def bloom_contains_keys_st(flat_words, row, blocks, lengths, m, *, k: int, words_per_row: int, target_lanes: int):
    """Single-tenant contains from raw key lanes (device-side hashing);
    ``row`` and ``m`` are scalars, so no per-op column ships."""
    from redisson_tpu.ops import bloom

    h1m, h2m = _hash_km_device(blocks, lengths, m, target_lanes)
    rows = jnp.broadcast_to(row, h1m.shape)
    return bloom.bloom_contains(
        flat_words, rows, h1m, h2m, m=m, k=k, words_per_row=words_per_row)


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
