"""Core bit-level device primitives shared by Bloom/BitSet kernels.

TPU-first replacement for what Redis does server-side on SETBIT/GETBIT
(the reference client only ships those commands in a pipelined batch,
→ org/redisson/RedissonBitSet.java, SURVEY.md §3.2): a whole batch of bit
ops becomes ONE XLA program — gathers for reads, and a sort-based
scatter-OR for writes.

Why the sort: XLA scatter with duplicate indexes has no bitwise-OR
combiner, and scatter-add would carry when two ops hit the same (word, bit).
We sort ops lexicographically by (word, bit) — stable, so arrival order is
preserved within a duplicate run — then at most one op of each run whose
bit actually changes scatter-adds ±its mask into the bitmap in place
(distinct bits of one word sum to their OR; a set bit is removed by adding
its two's complement).  The run structure also yields exact *sequential*
result semantics (what value each op observed) matching one-op-at-a-time
Redis execution — SURVEY.md §7 hard part #2.

Sort size: the TPU compiler's time for ``lax.sort`` jumps past 8192
elements (v5e, jax 0.9, PR 21: 2.8 s at 8192, 11 s at 16384, 47 s at
32768, 100 s at 7M — one compile per batch bucket, on the serving path).
So every sort here covers at most ``SORT_CHUNK`` elements; a longer batch
runs as an in-order ``lax.scan`` over chunks with the bitmap threaded
through (``scan_chunks``), and chunk j observes chunks < j exactly as its
elements would in one stable sort.

State convention: a pool of T tenant rows × W words lives as a flat
``uint32[T*W + 1]`` array; the trailing word is a scratch slot that padded
(invalid) ops target, so padding never perturbs run-detection for real ops
and scatters to it are harmless.

All functions here are pure and jittable; the executor layer applies
``jax.jit`` with buffer donation.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

_ONE = np.uint32(1)
SORT_CHUNK = 8192
_U5 = np.uint32(5)
_U7 = np.uint32(7)
_U31 = np.uint32(31)
_U127 = np.uint32(127)


def expand_km_indexes(h1m: jnp.ndarray, h2m: jnp.ndarray, m, k: int):
    """Kirsch–Mitzenmacher expansion: ``index_i = (h1 + i*h2) mod m``.

    Parity with RedissonBloomFilter#hash's index loop (SURVEY.md §2.2), in
    pure uint32: h1m, h2m are pre-reduced mod m on the host
    (hashing.km_reduce_mod), and m <= 2**31 guarantees ``a + b`` never wraps,
    so iterated conditional subtraction is exact.  Returns uint32[B, k].

    ``m`` may be a static int or a per-op ``uint32[B]`` array — the latter
    lets one compiled kernel serve every tenant of a size class even when
    their exact bit counts differ (same k, same word stride).
    """
    if isinstance(m, (int, np.integer)):
        if not 0 < m <= (1 << 31):
            raise ValueError(f"m must be in (0, 2**31], got {m}")
        m32 = np.uint32(m)
    else:
        m32 = m.astype(jnp.uint32)
    idx = h1m
    cols = [idx]
    for _ in range(k - 1):
        idx = idx + h2m
        idx = jnp.where(idx >= m32, idx - m32, idx)
        cols.append(idx)
    return jnp.stack(cols, axis=1)


def sort_runs(gword: jnp.ndarray, bit: jnp.ndarray):
    """Stable lexicographic sort of ops by (word, bit).

    Returns (sw, sb, sp, first, pos_in_run):
      sw/sb: sorted word/bit arrays (uint32),
      sp: original position of each sorted op (int32),
      first: bool mask — first op of each (word, bit) run,
      pos_in_run: 0-based rank of the op within its run (int32).
    """
    n = gword.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sw, sb, sp = lax.sort((gword, bit, pos), num_keys=2, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (sw[1:] != sw[:-1]) | (sb[1:] != sb[:-1])]
    )
    run_start = lax.cummax(jnp.where(first, pos, -1))
    return sw, sb, sp, first, pos - run_start


def scan_chunks(kernel, flat, cols, fills, chunk: int = SORT_CHUNK):
    """(new_flat, out[N]) of ``kernel(flat, *cols) -> (new_flat, out)``
    applied to consecutive ``chunk``-long slices of the 1-D op columns,
    in order.  The tail pads with ``fills`` per column, which the caller
    picks inert (a scratch-word index, an invalid flag, a GET).  Chunk 1-D
    columns only: reshaping an [ops, k] array onto the chunk grid costs
    the TPU compiler more than the sort it avoids."""
    n = cols[0].shape[0]
    if n <= chunk:
        return kernel(flat, *cols)
    nc = -(-n // chunk)
    pad = nc * chunk - n
    xs = tuple(
        jnp.concatenate([c, jnp.full((pad,), f, c.dtype)]).reshape(nc, chunk)
        for c, f in zip(cols, fills)
    )
    new, out = lax.scan(lambda st, x: kernel(st, *x), flat, xs)
    return new, out.reshape(-1)[:n]


def _signed_masks(sb, add, sub):
    """uint32 scatter-add deltas: +2^bit where ``add``, -2^bit (mod 2^32)
    where ``sub`` — exact when each (word, bit) gets at most one entry and
    only flips a bit whose pre-value the caller checked."""
    mask = _ONE << sb
    return jnp.where(add, mask, jnp.where(sub, np.uint32(0) - mask,
                                          np.uint32(0)))


def segmented_exclusive_max(first: jnp.ndarray, vals: jnp.ndarray):
    """Exclusive running max within segments (segment starts where ``first``
    is True).  Classic segmented-scan via associative_scan; used to derive
    exact sequential semantics (what did op j observe?) for sorted
    duplicate runs without a serial loop."""

    def comb(a, b):
        f1, v1 = a
        f2, v2 = b
        return f1 | f2, jnp.where(f2, v2, jnp.maximum(v1, v2))

    _, inc = lax.associative_scan(comb, (first, vals))
    exc = jnp.concatenate([vals[:1] * 0, inc[:-1]])
    return jnp.where(first, vals * 0, exc)


def gather_words(flat: jnp.ndarray, gidx: jnp.ndarray):
    """Element gather from a flat pool array via the [R, 128] row-gather
    form (see gather_bits).  Works for any dtype; exact equivalent of
    ``flat[gidx]`` for in-range indexes."""
    n = flat.shape[0] - 1
    if n % 128 != 0:
        return flat[gidx]
    x2d = flat[:-1].reshape(n // 128, 128)
    rows = jnp.take(x2d, (gidx >> _U7).astype(jnp.int32), axis=0)
    lane = (gidx & _U127).astype(jnp.int32)
    onehot = jnp.arange(128, dtype=jnp.int32)[None, :] == lane[:, None]
    return jnp.sum(jnp.where(onehot, rows, 0), axis=1, dtype=flat.dtype)


def _scatter_onehot(flat, gidx, values, combine: str):
    """Elementwise scatter with duplicate indexes combined by ``combine``
    ('max' or 'add') — via one-hot 128-lane row scatter (the TPU-efficient
    scatter form).  Keeps the trailing scratch element.  Padded ops just
    need value 0 (the identity for both combiners over unsigned values).
    Falls back to element scatter for layouts that aren't 128-lane
    multiples (not produced by the registry)."""
    n = flat.shape[0] - 1
    if n % 128 != 0:
        ref = flat.at[gidx]
        return ref.max(values) if combine == "max" else ref.add(values)
    x2d = flat[:-1].reshape(n // 128, 128)
    brow = (gidx >> _U7).astype(jnp.int32)
    lane = (gidx & _U127).astype(jnp.int32)
    onehot = jnp.arange(128, dtype=jnp.int32)[None, :] == lane[:, None]
    upd = jnp.where(onehot, values[:, None], 0).astype(flat.dtype)
    ref = x2d.at[brow]
    new2d = ref.max(upd) if combine == "max" else ref.add(upd)
    return jnp.concatenate([new2d.reshape(-1), flat[-1:]])


def scatter_max_onehot(flat, gidx, values):
    """flat[gidx] = max(flat[gidx], values), duplicate-safe."""
    return _scatter_onehot(flat, gidx, values, "max")


def scatter_add_onehot(flat, gidx, values):
    """flat[gidx] += values, duplicates accumulate."""
    return _scatter_onehot(flat, gidx, values, "add")


def pack_bool_u32(flags):
    """bool[N] -> uint32[N/32] (N % 32 == 0), little-endian bit order.

    Per-op boolean results (contains hits, newly flags, prev bits) leave
    the device packed 32-to-a-word: D2H link bytes were the scarce
    resource over a remote link (measured ~300x slower than H2D), and
    1 bit/op is
    the information-theoretic floor.  Host side unpacks with
    ``unpack_bool_u32``.
    """
    w = flags.reshape(-1, 32).astype(jnp.uint32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))[None, :]
    return (w * weights).sum(axis=1, dtype=jnp.uint32)


def unpack_bool_u32(words, n: int) -> np.ndarray:
    """Host twin of pack_bool_u32: uint32[N/32] -> bool[n]."""
    b = np.unpackbits(
        np.ascontiguousarray(words, dtype=np.uint32).view(np.uint8),
        bitorder="little",
    )
    return b[:n].astype(bool)


def host_pack_bool_u32(flags: np.ndarray) -> np.ndarray:
    """Host twin of pack_bool_u32 for the H2D direction: bool[N]
    (N % 32 == 0) -> uint32[N/32], same little-endian bit order.  Boolean
    op columns (is_add, opcode flags) ship packed inside the fused
    staging block at 1 bit/op instead of 1 byte/op."""
    by = np.packbits(np.ascontiguousarray(flags), bitorder="little")
    if by.shape[0] % 4:
        by = np.concatenate([by, np.zeros(4 - by.shape[0] % 4, np.uint8)])
    return by.view(np.uint32)


def unpack_bool_u32_dev(words, n: int):
    """Device twin of unpack_bool_u32 for use INSIDE a jit: uint32[n/32]
    -> bool[n] (little-endian bit order, matching host_pack_bool_u32)."""
    idx = jnp.arange(n, dtype=jnp.int32)
    w = words[idx >> 5]
    return ((w >> (idx & 31).astype(jnp.uint32)) & _ONE).astype(jnp.bool_)


def route_invalid_to_scratch(gword, valid, flat_len: int):
    """Send padded ops to the trailing scratch word so they can't perturb
    run-detection or results of real ops (see module docstring)."""
    if valid is None:
        return gword
    return jnp.where(valid, gword, np.uint32(flat_len - 1))


def gather_bits(flat_words: jnp.ndarray, gword: jnp.ndarray, bit: jnp.ndarray):
    """GETBIT batch: uint32[N] of 0/1.

    TPU-shaped formulation: element gathers over a flat array lower to a
    pathological per-element path on TPU (~20x slower, measured on v5e), so
    the word array is viewed as [R, 128] lanes and whole 128-lane rows are
    gathered (the efficient TPU gather form), with the target word selected
    by a one-hot lane compare.  Exactly equivalent to flat_words[gword].

    Pool states keep (len-1) % 128 == 0 (registry classes are 128-word
    multiples); padded ops routed to the scratch word read out of range and
    are clipped by jnp.take's default clamping — their results are masked
    by the caller.
    """
    return (gather_words(flat_words, gword) >> bit) & _ONE


def scatter_set_bits(flat_words, gword, bit):
    """SETBIT(...,1) batch.  Returns (new_flat, prev_bit[N] in arrival order).

    prev_bit has exact sequential semantics: an op observes 1 if the bit was
    set pre-batch OR an earlier op in the batch set it.
    """
    return scan_chunks(_set_bits_chunk, flat_words, (gword, bit),
                       (flat_words.shape[0] - 1, 0))


def _set_bits_chunk(flat_words, gword, bit):
    sw, sb, sp, first, _ = sort_runs(gword, bit)
    pre = gather_bits(flat_words, sw, sb)
    new = flat_words.at[sw].add(_signed_masks(sb, first & (pre == 0), False))
    prev_sorted = jnp.where(first, pre, _ONE)
    prev = jnp.zeros_like(prev_sorted).at[sp].set(prev_sorted)
    return new, prev


def scatter_set_bits_masked(flat_words, gword, bit, is_write):
    """SETBIT batch where only ``is_write`` ops set their bit; EVERY op
    (writer or reader) observes the bit value at its sequence position —
    set pre-batch OR by an earlier *writer* in the batch.

    This is the combined add+contains primitive: mixed read/write traffic
    on one pool coalesces into a single segment (one device launch) while
    keeping the exact one-op-at-a-time semantics of sequential Redis
    execution.  Returns (new_flat, observed uint32[N] 0/1, arrival order).
    """
    return scan_chunks(
        _set_bits_masked_chunk, flat_words,
        (gword, bit, is_write.astype(jnp.int32)),
        (flat_words.shape[0] - 1, 0, 0),
    )


def _set_bits_masked_chunk(flat_words, gword, bit, wr):
    n = gword.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sw, sb, sp, swr = lax.sort((gword, bit, pos, wr), num_keys=2, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (sw[1:] != sw[:-1]) | (sb[1:] != sb[:-1])]
    )
    # Earlier writer exists in this run <=> exclusive segmented max of
    # (pos+1 for writers, 0 for readers) is nonzero.
    earlier_writer = segmented_exclusive_max(first, swr * (sp + 1)) > 0
    pre = gather_bits(flat_words, sw, sb)
    obs_sorted = pre | earlier_writer.astype(jnp.uint32)
    contributes = (swr > 0) & ~earlier_writer & (pre == 0)
    new = flat_words.at[sw].add(_signed_masks(sb, contributes, False))
    obs = jnp.zeros_like(obs_sorted).at[sp].set(obs_sorted)
    return new, obs


def _segmented_affine_scan(first, b, a):
    """Segmented scan of bit-affine maps ``x -> a ^ (b & x)`` composed
    earlier-first.  Returns (eb, ea, ib, ia): exclusive and inclusive
    composites per element (exclusive = identity (1, 0) at segment starts).
    Composition (g after f): b = b_g & b_f, a = a_g ^ (b_g & a_f); the
    segment-reset combine is the standard Blelloch segmented-scan operator,
    associative because the underlying composition is."""

    def comb(x, y):
        f1, b1, a1 = x
        f2, b2, a2 = y
        return (
            f1 | f2,
            jnp.where(f2, b2, b2 & b1),
            jnp.where(f2, a2, a2 ^ (b2 & a1)),
        )

    _, ib, ia = lax.associative_scan(comb, (first, b, a))
    one = jnp.ones_like(b)
    zero = jnp.zeros_like(a)
    eb = jnp.where(first, one, jnp.concatenate([one[:1], ib[:-1]]))
    ea = jnp.where(first, zero, jnp.concatenate([zero[:1], ia[:-1]]))
    return eb, ea, ib, ia


def scatter_bit_affine(flat_words, gword, bit, b_coef, a_coef):
    """Unified GETBIT/SETBIT/clear/flip batch.  Each op applies
    ``x -> a ^ (b & x)`` to its bit — get:(1,0), set:(0,1), clear:(0,0),
    flip:(1,1) — and observes the value just *before* its own application
    (exact sequential semantics, so set/clear/flip report prev and get
    reports current).  One launch serves arbitrarily interleaved opcodes,
    which is what lets the coalescer keep a single segment per bitset pool.
    Returns (new_flat, observed uint32[N] 0/1, arrival order)."""
    return scan_chunks(
        _bit_affine_chunk, flat_words,
        (gword, bit, b_coef.astype(jnp.uint32), a_coef.astype(jnp.uint32)),
        (flat_words.shape[0] - 1, 0, 1, 0),  # padding is a GET
    )


def _bit_affine_chunk(flat_words, gword, bit, b_coef, a_coef):
    n = gword.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sw, sb, sp, sbc, sac = lax.sort(
        (gword, bit, pos, b_coef, a_coef), num_keys=2, is_stable=True
    )
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (sw[1:] != sw[:-1]) | (sb[1:] != sb[:-1])]
    )
    eb, ea, ib, ia = _segmented_affine_scan(first, sbc, sac)
    pre = gather_bits(flat_words, sw, sb)
    obs_sorted = ea ^ (eb & pre)
    # The last element of each run knows the run's final bit value; it
    # alone writes, and only when that value differs from the pre-value.
    last_of_run = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    final = ia ^ (ib & pre)
    change = last_of_run & (final != pre)
    new = flat_words.at[sw].add(
        _signed_masks(sb, change & (pre == 0), change & (pre == 1)))
    obs = jnp.zeros_like(obs_sorted).at[sp].set(obs_sorted)
    return new, obs


def scatter_clear_bits(flat_words, gword, bit):
    """SETBIT(...,0) batch.  Sequential prev semantics (0 after an earlier
    clear in the same batch)."""
    return scan_chunks(_clear_bits_chunk, flat_words, (gword, bit),
                       (flat_words.shape[0] - 1, 0))


def _clear_bits_chunk(flat_words, gword, bit):
    sw, sb, sp, first, _ = sort_runs(gword, bit)
    pre = gather_bits(flat_words, sw, sb)
    new = flat_words.at[sw].add(_signed_masks(sb, False, first & (pre == 1)))
    prev_sorted = jnp.where(first, pre, np.uint32(0))
    prev = jnp.zeros_like(prev_sorted).at[sp].set(prev_sorted)
    return new, prev


def scatter_flip_bits(flat_words, gword, bit):
    """Batch bit flip with parity-exact duplicate handling.

    A run of d flips of the same bit nets to ``d mod 2`` flips; op j in the
    run observes ``pre ^ (j mod 2)``.
    """
    return scan_chunks(_flip_bits_chunk, flat_words, (gword, bit),
                       (flat_words.shape[0] - 1, 0))


def _flip_bits_chunk(flat_words, gword, bit):
    sw, sb, sp, first, pos_in_run = sort_runs(gword, bit)
    pre = gather_bits(flat_words, sw, sb)
    nxt_first = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    odd_run = (pos_in_run & 1) == 0  # run length parity: last element's rank
    last_of_run = nxt_first
    contributes = last_of_run & odd_run  # one entry per odd-length run
    new = flat_words.at[sw].add(_signed_masks(
        sb, contributes & (pre == 0), contributes & (pre == 1)))
    prev_sorted = pre ^ (pos_in_run & 1).astype(jnp.uint32)
    prev = jnp.zeros_like(prev_sorted).at[sp].set(prev_sorted)
    return new, prev


def row_slice(flat_words: jnp.ndarray, row, words_per_row: int):
    """Dynamic view of one tenant row (row may be a traced scalar)."""
    return lax.dynamic_slice(
        flat_words, (row * words_per_row,), (words_per_row,)
    )


def row_update(flat_words: jnp.ndarray, row, new_row: jnp.ndarray, words_per_row: int):
    return lax.dynamic_update_slice(flat_words, new_row, (row * words_per_row,))


def popcount_row(flat_words, row, words_per_row: int):
    """BITCOUNT of one tenant row."""
    words = row_slice(flat_words, row, words_per_row)
    return jnp.sum(lax.population_count(words).astype(jnp.int32))


def bit_length_row(flat_words, row, words_per_row: int):
    """Index of highest set bit + 1 (java BitSet.length()); 0 if empty."""
    words = row_slice(flat_words, row, words_per_row)
    nz = words != 0
    any_set = jnp.any(nz)
    widx = jnp.arange(words_per_row, dtype=jnp.int32)
    last_word = jnp.max(jnp.where(nz, widx, -1))
    w = words[jnp.maximum(last_word, 0)]
    msb = _U31 - lax.clz(w)  # valid only when w != 0
    length = last_word * 32 + msb.astype(jnp.int32) + 1
    return jnp.where(any_set, length, 0)


def bitpos_row(flat_words, row, words_per_row: int, target_bit: int):
    """BITPOS: index of first bit equal to ``target_bit``.

    Redis semantics: no set bit → -1; no clear bit within the value →
    the first index past it (size), never -1 for target 0.
    """
    words = row_slice(flat_words, row, words_per_row)
    if target_bit == 0:
        words = ~words
    nz = words != 0
    widx = jnp.arange(words_per_row, dtype=jnp.int32)
    first_word = jnp.min(jnp.where(nz, widx, words_per_row))
    w = words[jnp.minimum(first_word, words_per_row - 1)]
    # Lowest set bit: count trailing zeros = 31 - clz(w & -w).
    lsb = _U31 - lax.clz(w & (~w + _ONE))
    pos = first_word * 32 + lsb.astype(jnp.int32)
    none_found = np.int32(words_per_row * 32 if target_bit == 0 else -1)
    return jnp.where(jnp.any(nz), pos, none_found)


def range_mask_words(words_per_row: int, from_bit, to_bit):
    """uint32[W] mask with bits [from_bit, to_bit) set (traced scalars ok)."""
    widx = jnp.arange(words_per_row, dtype=jnp.int32)
    base = widx * 32
    # Per word, number of masked bits below/above.
    lo = jnp.clip(from_bit - base, 0, 32)
    hi = jnp.clip(to_bit - base, 0, 32)
    full = np.uint32(0xFFFFFFFF)
    # mask = bits [lo, hi) within the word.
    def below(n):  # bits [0, n) set, n in [0, 32]
        n = n.astype(jnp.uint32)
        return jnp.where(n >= 32, full, (_ONE << n) - _ONE)

    return below(hi) & ~below(lo)
