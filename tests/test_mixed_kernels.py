"""Mixed-opcode kernels: combined bloom add+contains and the unified
bitset affine batch — the kernels that keep one coalescer segment per pool
under interleaved traffic (config 4's shape).

Gate: exact sequential (one-op-at-a-time Redis) semantics vs golden models,
including duplicate keys/bits inside one batch and padding.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.ops import bitops, bitset, bloom, golden
from redisson_tpu.utils import hashing


def _hashes(n, seed, m):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 63, size=n, dtype=np.uint64)
    blocks, lengths = hashing.encode_uint64_batch(keys)
    h1, h2 = hashing.hash128_np(blocks, lengths)
    return hashing.km_reduce_mod(h1, h2, m)


class TestBloomMixed:
    M = 1 << 14
    K = 5
    W = (1 << 14) // 32

    def _golden_run(self, g, rows, h1m, h2m, is_add):
        out = np.zeros(len(rows), bool)
        for i in range(len(rows)):
            t = rows[i]
            a = np.array([h1m[i]]), np.array([h2m[i]])
            if is_add[i]:
                out[i] = g[t].add_hashed(*a)[0]
            else:
                out[i] = g[t].contains_hashed(*a)[0]
        return out

    def test_vs_golden_sequential(self):
        T = 3
        pool = jnp.zeros((T * self.W + 1,), jnp.uint32)
        g = [golden.GoldenBloomFilter(self.M, self.K) for _ in range(T)]
        rng = np.random.default_rng(11)
        for step in range(4):
            n = 300
            # Small key space forces duplicates within and across batches,
            # so add/contains interleavings on the same key are exercised.
            keys = rng.integers(0, 150, size=n, dtype=np.uint64)
            blocks, lengths = hashing.encode_uint64_batch(keys)
            h1, h2 = hashing.hash128_np(blocks, lengths)
            h1m, h2m = hashing.km_reduce_mod(h1, h2, self.M)
            rows = rng.integers(0, T, size=n).astype(np.int32)
            is_add = rng.random(n) < 0.5
            pool, res = bloom.bloom_mixed(
                pool,
                jnp.asarray(rows),
                jnp.asarray(h1m),
                jnp.asarray(h2m),
                jnp.asarray(is_add),
                m=self.M,
                k=self.K,
                words_per_row=self.W,
            )
            expect = self._golden_run(g, rows, h1m, h2m, is_add)
            np.testing.assert_array_equal(np.asarray(res), expect)

    def test_padding_routes_to_scratch(self):
        T = 2
        pool = jnp.zeros((T * self.W + 1,), jnp.uint32)
        n, n_pad = 70, 128
        h1m, h2m = _hashes(n, 3, self.M)
        h1p = np.zeros(n_pad, h1m.dtype)
        h2p = np.zeros(n_pad, h2m.dtype)
        h1p[:n], h2p[:n] = h1m, h2m
        rows = np.zeros(n_pad, np.int32)
        is_add = np.zeros(n_pad, bool)
        is_add[:n] = True
        valid = np.zeros(n_pad, bool)
        valid[:n] = True
        m_arr = np.full(n_pad, self.M, np.uint32)
        m_arr[n:] = 1
        new_pool, res = bloom.bloom_mixed(
            pool,
            jnp.asarray(rows),
            jnp.asarray(h1p),
            jnp.asarray(h2p),
            jnp.asarray(is_add),
            m=jnp.asarray(m_arr),
            k=self.K,
            words_per_row=self.W,
            valid=jnp.asarray(valid),
        )
        # Row 1 untouched; row 0 identical to an unpadded add batch.
        ref_pool, newly = bloom.bloom_add(
            pool,
            jnp.zeros(n, jnp.int32),
            jnp.asarray(h1m),
            jnp.asarray(h2m),
            m=self.M,
            k=self.K,
            words_per_row=self.W,
        )
        np.testing.assert_array_equal(
            np.asarray(new_pool)[: self.W], np.asarray(ref_pool)[: self.W]
        )
        np.testing.assert_array_equal(
            np.asarray(new_pool)[self.W : 2 * self.W], 0
        )
        np.testing.assert_array_equal(
            np.asarray(res)[:n], np.asarray(newly)
        )

    @pytest.mark.parametrize("words_per_row", [512, 100],
                             ids=["lanes", "flat"])
    def test_chunked_scan_vs_golden(self, monkeypatch, words_per_row):
        """A launch of 40 steps (SORT_CHUNK shrunk to 64 elements: 8 ops
        a step) against the golden model op by op, on a pool that has a
        lane view (512-word rows: the scan carries the view) and on one
        that has none (100-word rows: the flat fallback).  Three tenants
        of different m; a key repeated across every step boundary, so
        duplicate (word, bit) runs straddle chunks; an add and a later
        contains of one key 30 steps apart; 20 padding ops with real
        rows and m, half adds, routed to scratch.  Every real word is
        checked afterwards, bits past each tenant's m included, and on
        the lane view the scratch word too (the flat fallback may set
        bits there)."""
        monkeypatch.setattr(bitops, "SORT_CHUNK", 64)
        T, W, K, n, Bp = 3, words_per_row, self.K, 300, 320
        step = bloom.scan_chunk_ops(K)
        assert step == 8 and bloom.scan_steps(Bp, K) == 40
        assert bitops.lane_fits(T * W + 1) == (W == 512)
        ms = [W * 32, W * 32 * 5 // 9 + 1, W * 32 // 3 + 7]
        rng = np.random.default_rng(31)
        rows = rng.integers(0, T, Bp).astype(np.int32)
        keys = rng.integers(0, 30, Bp, dtype=np.uint64)
        is_add = rng.random(Bp) < 0.5
        for j in range(step, n, step):  # same key either side of a boundary
            rows[j], keys[j], is_add[j - 1] = rows[j - 1], keys[j - 1], True
        rows[[3, 243]], keys[[3, 243]] = 0, 1000
        is_add[[3, 243]] = True, False
        valid = np.arange(Bp) < n
        blocks, lengths = hashing.encode_uint64_batch(keys)
        h1, h2 = hashing.hash128_np(blocks, lengths)
        h1m, h2m = np.zeros(Bp, np.uint32), np.zeros(Bp, np.uint32)
        for t, m in enumerate(ms):
            sel = rows == t
            h1m[sel], h2m[sel] = hashing.km_reduce_mod(h1[sel], h2[sel], m)
        draw = lambda: rng.integers(0, 1 << 32, T * W + 1,  # noqa: E731
                                    dtype=np.uint64)
        words = (draw() & draw() & draw()).astype(np.uint32)  # 1 bit in 8
        words[-1] = 0xDEADBEEF
        bits = np.unpackbits(words[:-1].view(np.uint8),
                             bitorder="little").reshape(T, W * 32)
        g = []
        for t, m in enumerate(ms):
            g.append(golden.GoldenBloomFilter(m, K))
            g[-1].bits = bits[t, :m].astype(bool)
        want = self._golden_run(g, rows[:n], h1m, h2m, is_add)
        assert want[243]  # the contains sees the add 30 steps before it
        new, res = bloom.bloom_mixed(
            jnp.asarray(words), jnp.asarray(rows), jnp.asarray(h1m),
            jnp.asarray(h2m), jnp.asarray(is_add),
            m=jnp.asarray(np.array(ms, np.uint32)[rows]), k=K,
            words_per_row=W, valid=jnp.asarray(valid))
        np.testing.assert_array_equal(np.asarray(res)[:n], want)
        for t, m in enumerate(ms):
            bits[t, :m] = g[t].bits
        want_words = np.packbits(bits.reshape(-1), bitorder="little").view(
            np.uint32)
        new = np.asarray(new)
        assert new.shape == words.shape
        np.testing.assert_array_equal(new[:-1], want_words)
        if W == 512:
            assert new[-1] == 0xDEADBEEF


class TestBitsetMixed:
    W = 8  # 256-bit rows

    def _sim(self, state_bits, rows, idx, ops):
        out = np.zeros(len(idx), bool)
        for i in range(len(idx)):
            cur = state_bits[rows[i], idx[i]]
            out[i] = cur
            if ops[i] == bitset.OP_SET:
                state_bits[rows[i], idx[i]] = True
            elif ops[i] == bitset.OP_CLEAR:
                state_bits[rows[i], idx[i]] = False
            elif ops[i] == bitset.OP_FLIP:
                state_bits[rows[i], idx[i]] = not cur
        return out

    def test_vs_sequential_sim(self):
        T = 2
        nbits = self.W * 32
        pool = jnp.zeros((T * self.W + 1,), jnp.uint32)
        bits = np.zeros((T, nbits), bool)
        rng = np.random.default_rng(23)
        for step in range(4):
            n = 400
            # Tiny index space → long duplicate runs with mixed opcodes.
            idx = rng.integers(0, 48, size=n).astype(np.uint32)
            rows = rng.integers(0, T, size=n).astype(np.int32)
            ops = rng.integers(0, 4, size=n).astype(np.uint32)
            pool, obs = bitset.bitset_mixed(
                pool,
                jnp.asarray(rows),
                jnp.asarray(idx),
                jnp.asarray(ops),
                words_per_row=self.W,
            )
            expect = self._sim(bits, rows, idx, ops)
            np.testing.assert_array_equal(np.asarray(obs), expect)
            # Full state equality after each batch.
            words = np.asarray(pool)[:-1].reshape(T, self.W)
            got_bits = np.unpackbits(
                words.view(np.uint8), bitorder="little"
            ).reshape(T, nbits)
            np.testing.assert_array_equal(got_bits.astype(bool), bits)

    def test_get_only_batch_leaves_state(self):
        pool = jnp.asarray(
            np.r_[
                np.random.default_rng(1).integers(
                    0, 1 << 32, size=self.W, dtype=np.uint32
                ),
                np.zeros(1, np.uint32),
            ]
        )
        idx = np.arange(64, dtype=np.uint32)
        ops = np.full(64, bitset.OP_GET, np.uint32)
        new, obs = bitset.bitset_mixed(
            pool,
            jnp.zeros(64, jnp.int32),
            jnp.asarray(idx),
            jnp.asarray(ops),
            words_per_row=self.W,
        )
        np.testing.assert_array_equal(np.asarray(new)[:-1], np.asarray(pool)[:-1])
        words = np.asarray(pool)[: self.W]
        expect = (words[idx // 32] >> (idx % 32)) & 1
        np.testing.assert_array_equal(np.asarray(obs), expect.astype(bool))


class TestCoalescedMixedE2E:
    """Interleaved add/contains through the public coalesced API must both
    coalesce (few device batches) and honor arrival order."""

    def test_interleaved_ops_coalesce_and_order(self):
        cl = redisson_tpu.create(
            Config().use_tpu_sketch(
                min_bucket=64, batch_window_us=5000, max_batch=1 << 14
            )
        )
        try:
            bf = cl.get_bloom_filter("mx1")
            bf.try_init(10_000, 0.01)
            a = np.arange(0, 200, dtype=np.uint64)
            b = np.arange(1000, 1200, dtype=np.uint64)
            futs = [
                bf.add_all_async(a),
                bf.contains_all_async(a),   # must see the add before it
                bf.contains_all_async(b),   # not added yet
                bf.add_all_async(b),
                bf.contains_all_async(b),   # must see the 2nd add
            ]
            r = [f.result() for f in futs]
            assert np.all(r[0])            # all newly added
            assert np.all(r[1])            # arrival order: adds visible
            assert not np.any(r[2])        # b not yet added (no FP at 1%*)
            assert np.all(r[3])
            assert np.all(r[4])
            m = cl.get_metrics()
            # 5 interleaved submissions on one pool: a single mixed segment
            # (or two if a flush raced), not one per alternation.
            assert m["batches_total"] <= 2, m
        finally:
            cl.shutdown()

    def test_bitset_interleaved_opcodes(self):
        cl = redisson_tpu.create(
            Config().use_tpu_sketch(min_bucket=64, batch_window_us=5000)
        )
        try:
            eng = cl._engine
            idx = np.arange(100, dtype=np.uint32)
            futs = [
                eng.bitset_set("mxbs", idx, True),   # prev all 0
                eng.bitset_get("mxbs", idx),         # all 1
                eng.bitset_flip("mxbs", idx[:50]),   # prev 1
                eng.bitset_get("mxbs", idx),         # first 50 off
            ]
            r = [np.asarray(f.result()) for f in futs]
            assert not np.any(r[0])
            assert np.all(r[1])
            assert np.all(r[2])
            assert not np.any(r[3][:50]) and np.all(r[3][50:])
        finally:
            cl.shutdown()
