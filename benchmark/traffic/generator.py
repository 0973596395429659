"""The one traffic generator.  Every mix is a JSON file of parameters
beside this file (``<mix>.json``); this module turns one into keys and
schedules from ``--seed`` alone.  It imports numpy and nothing else, so
the RESP load processes can use it without touching JAX.

Keys are 62-bit integers ``mix64(base(seed) + (space << 48) + i)``:
distinct inputs in disjoint spaces, scrambled by the splitmix64
finalizer, so the same seed always gives the same keys and the reference
can regenerate any of them from its index.

Parameters a mix may set (all are read here, none elsewhere):

- ``driver``: ``bulk`` (closed loop, one caller), ``open_api`` (open loop,
  producer threads on the Python API) or ``open_resp`` (open loop, RESP
  connections from load processes that never import JAX).
- bulk: ``op`` (``contains`` | ``add``), ``batch_keys``,
  ``batches_per_call`` (contains: batches per ``contains_many``),
  ``inflight`` (add: batches in flight), ``call_pool`` (contains: distinct
  call-sets cycled through the window), ``present_share``,
  ``setup_fill_share`` (share of the configuration's
  ``expected_insertions`` added in set-up, in order), ``universe_share``
  (add: window keys are drawn from this share of the universe).
- open loops: ``producers`` (threads or connections; producer j owns the
  filters f with f mod producers == j), ``procs`` (RESP load processes),
  ``rate`` (requests per second, all producers together),
  ``keys_per_request``, ``add_every`` (request i of a producer is an add
  iff i mod add_every == 0), ``pool_keys`` (each filter's key pool),
  ``setup_fill_share`` (share of each pool added in set-up),
  ``present_share`` (contains keys drawn from the pool; the rest are
  never added), ``key_dist`` (``uniform`` | ``zipf``, over the pool) and
  ``zipf_s``, ``warmup`` (the phases run before the window, each
  ``[seconds, multiple of rate]``: a ramp for the small launch shapes and
  short bursts for the large ones the coalescer merges).

A mix's ``tiny`` object is not read here: it holds the overrides the CPU
rehearsal (``tests/rehearsal.py``) applies, and no run on the chip uses it.
"""

from __future__ import annotations

import json
import os

import numpy as np

MASK62 = np.uint64((1 << 62) - 1)
UNIVERSE, ABSENT, POOL = 1, 2, 3
POOL_STRIDE = 1 << 24  # pool keys of filter f are indexes f * stride + j

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, directory: str = HERE) -> dict:
    path = os.path.join(directory, name + ".json")
    with open(path) as f:
        return json.load(f)


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: a bijection of uint64."""
    z = np.asarray(x, np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _base(seed: int) -> np.uint64:
    return mix64(np.array([seed & ((1 << 64) - 1)], np.uint64))[0]


def keys(seed: int, space: int, idx) -> np.ndarray:
    """The keys with these indexes in this space; uint64 below 2**62."""
    x = np.asarray(idx, np.uint64) + np.uint64(space << 48) + _base(seed)
    return mix64(x) & MASK62


def rng(seed: int, *stream) -> np.random.Generator:
    """An independent stream per purpose, so adding one changes no other."""
    return np.random.default_rng([seed & ((1 << 63) - 1), *stream])


# -- bulk ---------------------------------------------------------------------


def universe_size(tr: dict, cfg: dict) -> int:
    return int(cfg["expected_insertions"])


def fill_batches(seed: int, tr: dict, cfg: dict):
    """Set-up adds: universe keys 0 .. fill-1 in order, batch_keys each."""
    n = int(universe_size(tr, cfg) * tr["setup_fill_share"])
    b = tr["batch_keys"]
    for lo in range(0, n, b):
        yield keys(seed, UNIVERSE, np.arange(lo, min(n, lo + b)))


def contains_call(seed: int, tr: dict, cfg: dict, c: int) -> list:
    """Call-set c of a bulk contains: batches_per_call batches, each a
    shuffle of present keys (from the filled universe) and absent keys."""
    r = rng(seed, 1, c)
    u = int(universe_size(tr, cfg) * tr["setup_fill_share"])
    out = []
    for _ in range(tr["batches_per_call"]):
        b = tr["batch_keys"]
        npres = int(b * tr["present_share"])
        k = np.concatenate([
            keys(seed, UNIVERSE, r.integers(0, u, npres)),
            keys(seed, ABSENT, r.integers(0, 1 << 46, b - npres)),
        ])
        out.append(k[r.permutation(b)])
    return out


def add_batch(seed: int, tr: dict, cfg: dict, i: int) -> np.ndarray:
    """Window add batch i: keys drawn from the universe's first
    universe_share, which caps the filter at its expected insertions."""
    r = rng(seed, 2, i)
    u = int(universe_size(tr, cfg) * tr["universe_share"])
    return keys(seed, UNIVERSE, r.integers(0, u, tr["batch_keys"]))


# -- open loops ---------------------------------------------------------------


def pool_keys(seed: int, tr: dict, f, j) -> np.ndarray:
    return keys(seed, POOL, np.asarray(f, np.int64) * POOL_STRIDE + j)


def pool_fill(seed: int, tr: dict, f: int) -> np.ndarray:
    """Set-up adds of filter f: the first setup_fill_share of its pool."""
    n = int(tr["pool_keys"] * tr["setup_fill_share"])
    return pool_keys(seed, tr, f, np.arange(n))


def _pool_index(r, tr: dict, n: int) -> np.ndarray:
    P = tr["pool_keys"]
    if tr.get("key_dist", "uniform") == "uniform":
        return r.integers(0, P, n)
    w = 1.0 / np.arange(1, P + 1) ** float(tr["zipf_s"])
    return r.choice(P, size=n, p=w / w.sum())


def schedule(seed: int, tr: dict, n_filters: int, seconds: float,
             producer: int, phase: int = 0) -> dict:
    """Producer ``producer``'s requests for one phase (0 = the window,
    1.. = warm-up steps) at ``rate``: a fixed count, due times uniform over
    the phase (a Poisson process given its count), in due order.

    Returns due (s from phase start), filt (filter index), add (bool) and
    keys (uint64[n, keys_per_request])."""
    P = tr["producers"]
    r = rng(seed, 3, phase, producer)
    n = int(round(tr["rate"] * seconds / P))
    mine = np.arange(producer, n_filters, P)
    kpr = tr["keys_per_request"]
    due = np.sort(r.uniform(0.0, seconds, n))
    filt = mine[r.integers(0, len(mine), n)]
    add = np.arange(n) % tr["add_every"] == 0
    idx = _pool_index(r, tr, n * kpr).reshape(n, kpr)
    ks = pool_keys(seed, tr, filt[:, None], idx)
    absent = ~add[:, None] & (r.random((n, kpr)) >= tr["present_share"])
    na = int(absent.sum())
    # Never-added keys of this producer and phase (both below 256).
    ks[absent] = keys(seed, ABSENT,
                      (np.uint64(producer) << np.uint64(40))
                      + (np.uint64(phase) << np.uint64(32))
                      + np.arange(na, dtype=np.uint64))
    return {"due": due, "filt": filt, "add": add, "keys": ks}
