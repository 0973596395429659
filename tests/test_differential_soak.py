"""Randomized differential property test: the TPU-sketch engine (virtual
mesh) and the host golden engine run the SAME op stream and must agree
exactly — the integration-level analog of the per-kernel golden-twin
tests (SURVEY.md §4).  A longer standalone version lives in
scratch/soak.py."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec


@pytest.mark.parametrize("shards,coalesce", [(1, True), (8, False)])
def test_differential_soak(shards, coalesce):
    rng = np.random.default_rng(shards * 10 + coalesce)
    tpu = redisson_tpu.create(
        Config().set_codec(LongCodec()).use_tpu_sketch(
            min_bucket=64, num_shards=shards, coalesce=coalesce,
            batch_window_us=100, max_batch=4096,
        )
    )
    host = redisson_tpu.create(Config().set_codec(LongCodec()))
    try:
        # Fixed op count, not wall clock: the covered op stream must be
        # identical on every machine (and a failure at step N replays).
        for _step in range(120):
            kind = rng.integers(4)
            oid = int(rng.integers(4))
            keys = rng.integers(
                0, 3000, int(rng.integers(1, 300))
            ).astype(np.uint64)
            if kind == 0:
                a = tpu.get_bloom_filter(f"bf{oid}")
                b = host.get_bloom_filter(f"bf{oid}")
                for f in (a, b):
                    f.try_init(20_000, 0.01)
                if rng.integers(2):
                    assert a.add_all(keys) == b.add_all(keys)
                else:
                    assert np.array_equal(
                        a.contains_each(keys), b.contains_each(keys)
                    )
            elif kind == 1:
                a = tpu.get_hyper_log_log(f"h{oid}")
                b = host.get_hyper_log_log(f"h{oid}")
                a.add_all(keys)
                b.add_all(keys)
                assert a.count() == b.count()
            elif kind == 2:
                a = tpu.get_bit_set(f"bs{oid}")
                b = host.get_bit_set(f"bs{oid}")
                idx = keys.astype(np.uint32)
                a.set_many(idx)
                b.set_many(idx)
                assert a.cardinality() == b.cardinality()
            else:
                a = tpu.get_count_min_sketch(f"c{oid}")
                b = host.get_count_min_sketch(f"c{oid}")
                for c in (a, b):
                    c.try_init(4, 1 << 11, track_top_k=4)
                w = rng.integers(1, 5, len(keys)).astype(np.int64)
                a.add_all(keys, w)
                b.add_all(keys, w)
                assert np.array_equal(
                    a.estimate_all(keys[:8]), b.estimate_all(keys[:8])
                )
            if rng.integers(30) == 0:
                # Mailbox group collect mid-stream — DIFFERENTIALLY
                # checked: collected results must equal the host
                # engine's answers for the same queries.
                queries, futs = [], []
                for _ in range(4):
                    fid = int(rng.integers(4))
                    q = rng.integers(0, 3000, 64).astype(np.uint64)
                    bf = tpu.get_bloom_filter(f"bf{fid}")
                    bf.try_init(20_000, 0.01)
                    host.get_bloom_filter(f"bf{fid}").try_init(20_000, 0.01)
                    queries.append((fid, q))
                    futs.append(bf.contains_all_async(q))
                got = tpu.collect(futs)
                for (fid, q), g in zip(queries, got):
                    want = host.get_bloom_filter(f"bf{fid}").contains_each(q)
                    assert np.array_equal(g, want)
    finally:
        tpu.shutdown()
        host.shutdown()
