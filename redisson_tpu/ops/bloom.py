"""Bloom filter device kernels over stacked multi-tenant bitmaps.

Replaces the reference's per-call batched SETBIT/GETBIT fan-out
(→ org/redisson/RedissonBloomFilter.java add/contains via
CommandBatchService, SURVEY.md §3.2): a batch of B keys becomes one XLA
program — KM index expansion in-kernel, one gather (contains) or one
sort+scatter (add) over the pool.

Pool layout: ``uint32[T*W + 1]`` flat words (see ops/bitops.py), all
tenants in one size class share (m, W); per-op tenant rows route each key.
``k`` (hash iterations) is static per launch — the coalescer groups ops by
(size class, k).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from redisson_tpu.ops import bitops


def _op_words(rows, idx, words_per_row: int):
    """(row, bit index) -> flat word index + bit-in-word, uint32."""
    gword = rows.astype(jnp.uint32) * np.uint32(words_per_row) + (idx >> np.uint32(5))
    return gword, idx & np.uint32(31)


def bloom_contains(flat_words, rows, h1m, h2m, *, m: int, k: int, words_per_row: int):
    """bool[B]: all k bits set per key."""
    idx = bitops.expand_km_indexes(h1m, h2m, m, k)  # [B, k]
    gword, bit = _op_words(rows[:, None], idx, words_per_row)
    bits = bitops.gather_bits(flat_words, gword.reshape(-1), bit.reshape(-1))
    return bits.reshape(idx.shape).all(axis=1)


def bloom_add(flat_words, rows, h1m, h2m, *, m: int, k: int, words_per_row: int, valid=None):
    """Insert batch.  Returns (new_flat, newly_added bool[B]).

    newly_added matches Redisson add() semantics under sequential execution:
    True iff at least one of the key's k bits was unset both pre-batch and
    by all earlier keys in the batch.  ``valid``: optional bool[B] padding
    mask — invalid ops are routed to the scratch word and write nothing.
    """
    def kernel(flat, rows, h1m, h2m, valid, m):
        idx = bitops.expand_km_indexes(h1m, h2m, m, k)
        gword, bit = _op_words(rows[:, None], idx, words_per_row)
        gword = bitops.route_invalid_to_scratch(
            gword, valid[:, None], flat.shape[0])
        new, prev = bitops.scatter_set_bits(
            flat, gword.reshape(-1), bit.reshape(-1))
        return new, (prev == 0).reshape(idx.shape).any(axis=1)

    return _scan_ops(kernel, flat_words, k, m, valid, rows, h1m, h2m)


def _scan_ops(kernel, flat_words, k: int, m, valid, *cols):
    """bitops.scan_chunks over OPS (each expands to k sorted elements):
    ``kernel(flat, *cols, valid, m)`` sees one chunk of at most
    SORT_CHUNK // k ops.  Padding ops are invalid (scratch word, m=1)."""
    B = cols[0].shape[0]
    if valid is None:
        valid = jnp.ones((B,), bool)
    chunk = 1 << max(0, (bitops.SORT_CHUNK // k).bit_length() - 1)
    if isinstance(m, (int, np.integer)):  # static m stays static
        return bitops.scan_chunks(
            lambda f, *c: kernel(f, *c, m), flat_words,
            cols + (valid,), (0,) * len(cols) + (False,), chunk)
    return bitops.scan_chunks(
        kernel, flat_words, cols + (valid, m),
        (0,) * len(cols) + (False, 1), chunk)


def bloom_mixed(flat_words, rows, h1m, h2m, is_add, *, m, k: int, words_per_row: int, valid=None):
    """Combined add+contains batch with exact sequential semantics.

    ``is_add`` bool[B] selects per op: add ops set their k bits and report
    newly-added (some bit unset both pre-batch and by all earlier adds in
    the batch); contains ops write nothing and report membership at their
    sequence position (bits set pre-batch or by earlier adds count).

    One kernel for both opcodes lets the coalescer keep a single segment
    per (pool, k) under mixed traffic — the config-4 shape — instead of
    breaking a new segment on every add/contains alternation.
    Returns (new_flat, result bool[B]).
    """
    def kernel(flat, rows, h1m, h2m, is_add, valid, m):
        idx = bitops.expand_km_indexes(h1m, h2m, m, k)
        gword, bit = _op_words(rows[:, None], idx, words_per_row)
        gword = bitops.route_invalid_to_scratch(
            gword, valid[:, None], flat.shape[0])
        wr = jnp.broadcast_to(is_add[:, None], idx.shape).reshape(-1)
        new, obs = bitops.scatter_set_bits_masked(
            flat, gword.reshape(-1), bit.reshape(-1), wr)
        all_set = (obs == 1).reshape(idx.shape).all(axis=1)
        return new, jnp.where(is_add, ~all_set, all_set)

    return _scan_ops(kernel, flat_words, k, m, valid, rows, h1m, h2m, is_add)


def bloom_cardinality(flat_words, row, *, m: int, k: int, words_per_row: int):
    """BITCOUNT-based estimate pieces: returns the set-bit count X of one
    tenant row; the host applies ``-m/k * ln(1 - X/m)``
    (→ RedissonBloomFilter#count)."""
    return bitops.popcount_row(flat_words, row, words_per_row)


def bloom_clear_row(flat_words, row, *, words_per_row: int):
    """Delete/clear one tenant's bitmap (RObject.delete analog)."""
    zeros = jnp.zeros((words_per_row,), dtype=jnp.uint32)
    return bitops.row_update(flat_words, row, zeros, words_per_row)
