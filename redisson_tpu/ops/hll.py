"""HyperLogLog device kernels — the math Redis keeps server-side.

The reference client is a thin PFADD/PFCOUNT/PFMERGE command wrapper
(→ org/redisson/RedissonHyperLogLog.java, SURVEY.md §2.2); the sketch
itself (registers, estimator, merge) lives in the Redis server.  Here it is
TPU-native: registers are a stacked ``uint8[T*16384 + 1]`` array (p=14,
6-bit value range 0..51 — Redis geometry, error ≈ 0.81%), PFADD is one
scatter-max (idempotent, so duplicate indexes need no dedup machinery),
PFMERGE is an elementwise max, PFCOUNT builds a device histogram finalized
on the host with the Ertl estimator (golden.ertl_estimate — bit-identical
to the NumPy twin).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from redisson_tpu.ops import bitops
from redisson_tpu.ops.golden import HLL_M, HLL_P, HLL_Q


def hll_index_rank_device(c0, c1, c2):
    """Device twin of golden.hll_index_rank (uint32 lanes -> idx, rank).

    rank = 51 - bit_length(c1 ++ top18(c2)); computed with lax.clz to avoid
    64-bit emulation.  Verified equal to the golden frexp formulation in
    tests.
    """
    idx = (c0 & np.uint32(HLL_M - 1)).astype(jnp.int32)
    u18 = c2 >> np.uint32(14)
    rank = jnp.where(
        c1 != 0,
        lax.clz(c1) + np.uint32(1),
        jnp.where(
            u18 != 0,
            lax.clz(u18) - np.uint32(14) + np.uint32(33),
            np.uint32(HLL_Q + 1),
        ),
    )
    return idx, rank.astype(jnp.uint8)


def hll_add(flat_regs, rows, c0, c1, c2, valid=None):
    """PFADD batch: scatter-max of ranks via the one-hot row form (element
    scatters are pathological on TPU).  Padded ops get rank 0 — a no-op
    under max — so no scratch routing is needed."""
    idx, rank = hll_index_rank_device(c0, c1, c2)
    if valid is not None:
        rank = jnp.where(valid, rank, np.uint8(0))
    gidx = rows * np.int32(HLL_M) + idx
    return bitops.scatter_max_onehot(flat_regs, gidx, rank)


def hll_histogram(flat_regs, row):
    """Register-value histogram int32[52] of one tenant (host finalizes with
    golden.ertl_estimate — exact parity with the golden model)."""
    regs = bitops.row_slice(flat_regs, row, HLL_M)
    return jnp.zeros((HLL_Q + 2,), jnp.int32).at[regs.astype(jnp.int32)].add(1)


def hll_histograms_all(regs2d):
    """Histograms for every tenant row at once: uint8[T, M] -> int32[T, 52].
    One-hot matmul formulation — MXU-friendly for the PFCOUNT bench."""
    onehot = (
        regs2d[:, :, None] == jnp.arange(HLL_Q + 2, dtype=jnp.uint8)[None, None, :]
    )
    return onehot.sum(axis=1, dtype=jnp.int32)


def hll_merge_rows(flat_regs, dst_row, src_rows_regs):
    """PFMERGE: dst = elementwise max(dst, max over sources).

    src_rows_regs: uint8[S, M] — pre-gathered source rows (the tenancy layer
    gathers; cross-shard merge rides a psum-style max collective instead,
    see parallel/).
    """
    dst = bitops.row_slice(flat_regs, dst_row, HLL_M)
    merged = jnp.maximum(dst, src_rows_regs.max(axis=0))
    return bitops.row_update(flat_regs, dst_row, merged, HLL_M)


def hll_merge(flat_regs, dst_row, src_rows):
    """PFMERGE with in-kernel source gather: src_rows is int32[S]."""
    regs2d = flat_regs[:-1].reshape(-1, HLL_M)
    return hll_merge_rows(flat_regs, dst_row, regs2d[src_rows])


def hll_add_changed(flat_regs, rows, c0, c1, c2, valid=None):
    """Multi-tenant PFADD returning per-op 'changed' booleans with exact
    sequential semantics: op j changed its register iff
    rank_j > max(pre-batch value, ranks of earlier ops on the same
    register).  Sort by register + segmented exclusive max scan (the
    coalesced-path variant of RHyperLogLog#add's boolean)."""
    idx, rank = hll_index_rank_device(c0, c1, c2)
    if valid is not None:
        rank = jnp.where(valid, rank, np.uint8(0))
    gidx = (rows * np.int32(HLL_M) + idx).astype(jnp.uint32)
    # Chunked like the bit kernels (bitops.SORT_CHUNK); rank 0 pads.
    return bitops.scan_chunks(
        _add_changed_chunk, flat_regs, (gidx, rank.astype(jnp.int32)),
        (flat_regs.shape[0] - 1, 0),
    )


def _add_changed_chunk(flat_regs, gidx, rank):
    from jax import lax

    new = bitops.scatter_max_onehot(flat_regs, gidx.astype(jnp.int32), rank)
    n = gidx.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sg, sr, sp = lax.sort((gidx, rank, pos), num_keys=1, is_stable=True)
    pre = bitops.gather_words(flat_regs, sg).astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), sg[1:] != sg[:-1]])
    run_prev = bitops.segmented_exclusive_max(first, sr)
    observed = jnp.maximum(pre, run_prev)
    changed_sorted = sr > observed
    changed = jnp.zeros((n,), bool).at[sp].set(changed_sorted)
    return new, changed


def hll_add_single(flat_regs, row, c0, c1, c2, valid=None):
    """PFADD for one tenant, returning (new, changed) — changed is
    RHyperLogLog.add()'s boolean: did any register increase?  Computed as a
    before/after register-sum comparison on the tenant's row (registers only
    ever grow, so sums differ iff something changed)."""
    before = bitops.row_slice(flat_regs, row, HLL_M).astype(jnp.int32).sum()
    rows = jnp.full(c0.shape, row, jnp.int32)
    new = hll_add(flat_regs, rows, c0, c1, c2, valid=valid)
    after = bitops.row_slice(new, row, HLL_M).astype(jnp.int32).sum()
    return new, after != before


def ertl_estimate_device(hist):
    """Fully-on-device Ertl estimator (float32), for the batched PFCOUNT
    bench path.  Fixed-trip-count loops (they converge geometrically well
    within 64/32 iterations at float32 precision); host path keeps the
    float64 golden finalize for count() API calls.
    """
    m = np.float32(HLL_M)
    q = HLL_Q
    hist = hist.astype(jnp.float32)

    # tau(x), x = 1 - C[q+1]/m
    x = 1.0 - hist[..., q + 1] / m

    def tau_body(_, state):
        x, y, z = state
        x = jnp.sqrt(x)
        y = 0.5 * y
        z = z - jnp.square(1.0 - x) * y
        return x, y, z

    x0 = x
    _, _, z_tau = lax.fori_loop(0, 64, tau_body, (x, jnp.float32(1.0), 1.0 - x))
    z_tau = jnp.where((x0 == 0.0) | (x0 == 1.0), 0.0, z_tau / 3.0)

    z = m * z_tau
    for kk in range(q, 0, -1):
        z = 0.5 * (z + hist[..., kk])

    # sigma(x), x = C[0]/m
    xs = hist[..., 0] / m

    def sigma_body(_, state):
        x, y, z = state
        x = x * x
        z = z + x * y
        y = y + y
        return x, y, z

    xs0 = xs
    _, _, z_sig = lax.fori_loop(0, 32, sigma_body, (xs, jnp.float32(1.0), xs))
    z_sig = jnp.where(xs0 == 1.0, jnp.float32(np.inf), z_sig)

    z = z + m * z_sig
    alpha_inf = np.float32(0.5 / np.log(2.0))
    return alpha_inf * m * m / z
