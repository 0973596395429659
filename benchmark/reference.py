"""The plain reference: a Bloom filter written from Redisson's published
math, importing nothing of the program under test.

Semantics (org/redisson/RedissonBloomFilter.java, as the program states
them): m = ceil(-n ln p / (ln 2)^2), k = max(1, round(m / n * ln 2));
a key's k bit positions are (h1 mod m + i * (h2 mod m)) mod m for
i = 0..k-1, from a 128-bit hash (h1, h2) of the codec's bytes; ``add``
answers True iff at least one of its bits was not yet set, with every
earlier add of the same filter applied first (exact sequential order);
``contains`` answers True iff all k bits are set.

The hash is the program's documented MurmurHash3 x86_128 variant
(redisson_tpu/utils/hashing.py docstring: block mix, length mixed into
the finalizer), written out here for the one key shape the benchmark
sends: a LongCodec key, 8 little-endian bytes, in one 16-byte block.
It is copied, not imported, so that a later change to the program's
hash shows up as wrong answers instead of moving the yardstick.

``expected`` replays an ordered op stream and returns the answer each
checked op must give.  It keeps, per bit position, the order index of
the first add that set it (``first``): a contains at order t sees the
bit iff first < t, and an add at order t set it newly iff first == t.
Adds that precede every checked op only need "set or not" (-1).
"""

from __future__ import annotations

import math
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SEED = 0x9747B28C
_C1, _C2, _C3, _C4 = 0x239B961B, 0xAB0E9789, 0x38B34AE5, 0xA1E38B93
_N1, _N2, _N3, _N4 = 0x561CCD1B, 0x0BCAA747, 0x96CD1C35, 0x32AC3B17
_F1, _F2 = 0x85EBCA6B, 0xC2B2AE35
_U32 = np.uint32
NEVER = np.iinfo(np.int32).max  # "no add has set this bit"
CHUNK = 1 << 20
THREADS = 8


def geometry(expected_insertions: int, false_probability: float):
    """(m, k) by RedissonBloomFilter's formulas."""
    n = max(1, int(expected_insertions))
    m = math.ceil(-n * math.log(false_probability) / (math.log(2) ** 2))
    m = max(m, 16)
    k = max(1, round(m / n * math.log(2)))
    return m, k


def _rotl_int(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & 0xFFFFFFFF


# Lane 3 of the block is 0, so its mixed lane is the same for every key.
_N3_CONST = ((_rotl_int(SEED, 15) + SEED) * 5 + _N3) & 0xFFFFFFFF


def _rotl(x, r):
    return (x << _U32(r)) | (x >> _U32(32 - r))


def _fmix(h):
    h = h ^ (h >> _U32(16))
    h = h * _U32(_F1)
    h = h ^ (h >> _U32(13))
    h = h * _U32(_F2)
    return h ^ (h >> _U32(16))


def hash_u64(keys: np.ndarray):
    """128-bit hash of 8-byte little-endian keys -> (h1, h2) as uint64."""
    a = np.ascontiguousarray(keys, dtype="<u8").view("<u4").reshape(-1, 2)
    k1, k2 = a[:, 0].astype(_U32), a[:, 1].astype(_U32)
    h = _U32(SEED)
    # One block; lanes 3 and 4 of the zero-padded block are 0.
    k1 = _rotl(k1 * _U32(_C1), 15) * _U32(_C2)
    n1 = _rotl(h ^ k1, 19) + h
    n1 = n1 * _U32(5) + _U32(_N1)
    k2 = _rotl(k2 * _U32(_C2), 16) * _U32(_C3)
    n2 = _rotl(h ^ k2, 17) + h
    n2 = n2 * _U32(5) + _U32(_N2)
    n3 = np.full_like(n1, _N3_CONST)
    n4 = _rotl(h ^ np.zeros_like(n1), 13) + n1
    n4 = n4 * _U32(5) + _U32(_N4)
    ln = _U32(8)
    h1, h2, h3, h4 = n1 ^ ln, n2 ^ ln, n3 ^ ln, n4 ^ ln
    h1 = h1 + h2 + h3 + h4
    h2, h3, h4 = h2 + h1, h3 + h1, h4 + h1
    h1, h2, h3, h4 = _fmix(h1), _fmix(h2), _fmix(h3), _fmix(h4)
    h1 = h1 + h2 + h3 + h4
    h2, h3, h4 = h2 + h1, h3 + h1, h4 + h1
    lo = h1.astype(np.uint64) | (h2.astype(np.uint64) << np.uint64(32))
    hi = h3.astype(np.uint64) | (h4.astype(np.uint64) << np.uint64(32))
    return lo, hi


def positions(keys: np.ndarray, m: int, k: int) -> np.ndarray:
    """int64[n, k] bit positions of each key in a filter of m bits."""
    lo, hi = hash_u64(keys)
    mm = np.uint64(m)
    a = (lo % mm).astype(np.int64)
    b = (hi % mm).astype(np.int64)
    i = np.arange(k, dtype=np.int64)
    return (a[:, None] + i[None, :] * b[:, None]) % m


def _chunked(fn, n: int, pool):
    """fn(lo, hi) over [0, n) in CHUNK slices, yielded in order; threads
    overlap the numpy work (ufuncs release the GIL), at most 2 x THREADS
    chunks ahead so memory stays bounded."""
    ahead = deque()
    for lo in range(0, n, CHUNK):
        ahead.append(pool.submit(fn, lo, min(n, lo + CHUNK)))
        if len(ahead) >= 2 * THREADS:
            yield ahead.popleft().result()
    while ahead:
        yield ahead.popleft().result()


class BloomReference:
    """n_filters filters of one geometry, laid end to end in one bit space."""

    def __init__(self, n_filters: int, m: int, k: int):
        self.n, self.m, self.k = int(n_filters), int(m), int(k)
        self.first = np.full(self.n * self.m, NEVER, np.int32)

    def _gpos(self, fids, keys):
        p = positions(keys, self.m, self.k)
        p += (np.asarray(fids, np.int64) * self.m)[:, None]
        return p

    def prefix_adds(self, fids, keys) -> None:
        """Adds that come before every op that ``expected`` checks."""
        fids = np.broadcast_to(np.asarray(fids, np.int64), keys.shape)
        with ThreadPoolExecutor(THREADS) as pool:
            for p in _chunked(lambda a, b: self._gpos(fids[a:b], keys[a:b]),
                              len(keys), pool):
                self.first[p.ravel()] = -1

    def expected(self, fids, keys, is_add, order, check) -> np.ndarray:
        """Answers of the checked ops of an ordered stream.

        ``order`` is an int order index per op, increasing along each
        filter's own sequence (filters are independent, so any
        interleaving that keeps each filter's order is the same stream).
        ``check`` is a bool mask of the ops whose answers are wanted."""
        fids = np.broadcast_to(np.asarray(fids, np.int64), keys.shape)
        order = np.asarray(order, np.int64)
        if len(order) and order.max() >= NEVER:
            raise ValueError("stream too long for int32 order indexes")
        idx = np.flatnonzero(check)
        with ThreadPoolExecutor(THREADS) as pool:
            pc = np.concatenate(list(_chunked(
                lambda a, b: self._gpos(fids[idx[a:b]], keys[idx[a:b]]),
                len(idx), pool))) if len(idx) else np.zeros((0, self.k),
                                                             np.int64)
            adds = np.flatnonzero(is_add)
            if len(adds):
                interest = np.zeros(self.n * self.m, bool)
                interest[pc.ravel()] = True
            for lo, p in zip(range(0, len(adds), CHUNK), _chunked(
                    lambda a, b: self._gpos(fids[adds[a:b]], keys[adds[a:b]]),
                    len(adds), pool)):
                o = np.repeat(order[adds[lo:lo + len(p)]], self.k)
                p = p.ravel()
                sel = interest[p]
                np.minimum.at(self.first, p[sel], o[sel].astype(np.int32))
        f = self.first[pc]
        t = order[idx][:, None]
        return np.where(np.asarray(is_add)[idx], (f == t).any(axis=1),
                        (f < t).all(axis=1))
