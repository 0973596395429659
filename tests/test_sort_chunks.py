"""Chunked exact-sequential kernels (bitops.scan_chunks, PR 21): a batch
longer than SORT_CHUNK elements runs as an in-order scan over chunks and
must give exactly what one-op-at-a-time execution gives — duplicates
straddling chunk boundaries and a padded tail included.  The set kernels
give the same on the pool's lane view, the form the Bloom scan carries
(``-lanes`` cases)."""

import numpy as np
import pytest

import jax.numpy as jnp

from redisson_tpu.ops import bitops, hll

N = 2 * bitops.SORT_CHUNK + 1234  # three chunks, the last one padded
WORDS = 4 * 128  # small bitmap: dense duplicates across chunks


def _ops(seed):
    rng = np.random.default_rng(seed)
    gword = rng.integers(0, WORDS, N).astype(np.uint32)
    bit = rng.integers(0, 32, N).astype(np.uint32)
    flat = rng.integers(0, 1 << 32, WORDS + 1, dtype=np.uint64).astype(
        np.uint32)
    return rng, flat, gword, bit


def _sequential(flat, gword, bit, step):
    """One op at a time: ``step(cur, i) -> (new_bit, observed)``."""
    flat = flat.copy()
    obs = np.zeros(N, np.uint32)
    for i in range(N):
        w, b = int(gword[i]), int(bit[i])
        cur = (int(flat[w]) >> b) & 1
        new, obs[i] = step(cur, i)
        flat[w] = (int(flat[w]) & ~(1 << b) | (new << b)) & 0xFFFFFFFF
    return flat, obs


@pytest.mark.parametrize("kind", ["set", "clear", "flip", "masked", "affine",
                                  "set-lanes", "masked-lanes"])
def test_chunked_bit_kernels_match_sequential(kind):
    kind, lanes = kind.removesuffix("-lanes"), kind.endswith("-lanes")
    rng, flat, gword, bit = _ops(len(kind) + 7 * lanes)
    write = rng.random(N) < 0.5
    opcodes = rng.integers(0, 4, N).astype(np.uint32)
    b_coef, a_coef = (opcodes >> 1) & 1, opcodes & 1
    steps = {
        "set": lambda cur, i: (1, cur),
        "clear": lambda cur, i: (0, cur),
        "flip": lambda cur, i: (cur ^ 1, cur),
        "masked": lambda cur, i: (1 if write[i] else cur, cur),
        "affine": lambda cur, i: (int(a_coef[i]) ^ (int(b_coef[i]) & cur),
                                  cur),
    }
    want_flat, want_obs = _sequential(flat, gword, bit, steps[kind])
    words = jnp.asarray(flat)
    if lanes:
        words = bitops.lane_view(words)
    args = (words, jnp.asarray(gword), jnp.asarray(bit))
    if kind == "set":
        got_flat, got = bitops.scatter_set_bits(*args)
    elif kind == "clear":
        got_flat, got = bitops.scatter_clear_bits(*args)
    elif kind == "flip":
        got_flat, got = bitops.scatter_flip_bits(*args)
    elif kind == "masked":
        got_flat, got = bitops.scatter_set_bits_masked(
            *args, jnp.asarray(write))
    else:
        got_flat, got = bitops.scatter_bit_affine(
            *args, jnp.asarray(b_coef), jnp.asarray(a_coef))
    np.testing.assert_array_equal(np.asarray(got), want_obs)
    got_words = np.asarray(got_flat).reshape(-1)
    np.testing.assert_array_equal(got_words[:WORDS], want_flat[:-1])
    assert got_words.shape[0] == WORDS + (not lanes)


def test_chunked_hll_add_changed_matches_sequential():
    from redisson_tpu.ops.golden import HLL_M

    rng = np.random.default_rng(7)
    rows = rng.integers(0, 2, N).astype(np.int32)
    c0, c1, c2 = (rng.integers(0, 1 << 32, N, dtype=np.uint64)
                  .astype(np.uint32) for _ in range(3))
    regs = np.zeros(2 * HLL_M + 1, np.uint8)
    idx, rank = hll.hll_index_rank_device(
        jnp.asarray(c0), jnp.asarray(c1), jnp.asarray(c2))
    gidx = rows * HLL_M + np.asarray(idx)
    want = np.zeros(N, bool)
    for i in range(N):
        r = int(np.asarray(rank)[i])
        want[i] = r > regs[gidx[i]]
        regs[gidx[i]] = max(regs[gidx[i]], r)
    new, changed = hll.hll_add_changed(
        jnp.zeros(2 * HLL_M + 1, jnp.uint8), jnp.asarray(rows),
        jnp.asarray(c0), jnp.asarray(c1), jnp.asarray(c2))
    np.testing.assert_array_equal(np.asarray(changed), want)
    np.testing.assert_array_equal(np.asarray(new)[:-1], regs[:-1])
