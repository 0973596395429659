"""The reference against a plain loop of the published semantics, and its
hash against the program's documented hash (the one place a test reads
the program: to show that the copy is faithful)."""

import numpy as np

from benchmark import reference
from benchmark.traffic import generator as gen


def _loop(m, k, keys, is_add, fids):
    bits = {}
    out = []
    for key, add, f in zip(keys, is_add, fids):
        p = [(f, int(x)) for x in reference.positions(
            np.array([key], np.uint64), m, k)[0]]
        if add:
            out.append(any(not bits.get(q) for q in p))
            for q in p:
                bits[q] = True
        else:
            out.append(all(bits.get(q) for q in p))
    return np.array(out)


def test_ordered_stream_matches_a_loop():
    m, k = reference.geometry(300, 0.05)
    r = np.random.default_rng(0)
    n = 4000
    keys = gen.keys(1, gen.POOL, r.integers(0, 600, n))
    is_add = r.random(n) < 0.4
    fids = r.integers(0, 3, n)
    want = _loop(m, k, keys, is_add, fids)
    ref = reference.BloomReference(3, m, k)
    pre = 500  # the first ops as a prefix, the rest checked
    ref.prefix_adds(fids[:pre][is_add[:pre]], keys[:pre][is_add[:pre]])
    chk = r.random(n - pre) < 0.7
    got = ref.expected(fids[pre:], keys[pre:], is_add[pre:],
                       np.arange(n - pre), chk)
    assert np.array_equal(got, want[pre:][chk])


def test_hash_equals_the_programs():
    from redisson_tpu.utils import hashing

    keys = gen.keys(3, gen.UNIVERSE, np.arange(50_000))
    lo, hi = reference.hash_u64(keys)
    h1, h2 = hashing.hash128_np(*hashing.encode_uint64_batch(keys))
    assert np.array_equal(lo, h1) and np.array_equal(hi, h2)


def test_keys_are_seeded_and_below_2_62():
    a = gen.keys(2**31 + 7, gen.UNIVERSE, np.arange(1000))
    assert np.array_equal(a, gen.keys(2**31 + 7, gen.UNIVERSE,
                                      np.arange(1000)))
    assert not np.array_equal(a, gen.keys(2**31 + 8, gen.UNIVERSE,
                                          np.arange(1000)))
    assert int(a.max()) < 2**62 and len(set(a.tolist())) == 1000
