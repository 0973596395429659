"""Device-side result mailbox: a group of
launches' packed results concatenates on device and fetches in ONE D2H.
Parity discipline: mailbox-collected results must be bit-identical to
per-launch fetches through every path (direct, bulk API, coalescer)."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec


def make_client(**kw):
    # coalesce=False by default: these tests target the DIRECT dispatch
    # path where futures are LazyResults (or MappedFuture wrappers over
    # them) — the shapes collect_group actually mailboxes.  The hammer
    # test opts back into coalesce=True explicitly.
    kw.setdefault("coalesce", False)
    return redisson_tpu.create(
        Config().set_codec(LongCodec()).use_tpu_sketch(min_bucket=64, **kw)
    )


def test_collect_group_parity():
    c = make_client()
    try:
        bf = c.get_bloom_filter("mb-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(3000, dtype=np.uint64))
        rng = np.random.default_rng(1)
        batches = [
            rng.integers(0, 6000, 256).astype(np.uint64) for _ in range(5)
        ]
        # Reference: per-launch fetches.
        want = [bf.contains_each(b) for b in batches]
        # Mailbox: group dispatch + one collect.
        lazies = [bf.contains_all_async(b) for b in batches]
        c._engine.executor.collect_group(lazies)
        got = [l.result() for l in lazies]
        for w, g in zip(want, got):
            assert np.array_equal(w, g)
    finally:
        c.shutdown()


def test_collect_group_mixed_dtypes_and_resolved():
    c = make_client()
    try:
        bf = c.get_bloom_filter("mb2-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(100, dtype=np.uint64))
        l1 = bf.contains_all_async(np.arange(50, dtype=np.uint64))
        l1.result()  # already resolved: collect_group must skip it
        l2 = bf.contains_all_async(np.arange(50, 100, dtype=np.uint64))
        l3 = bf.contains_all_async(np.arange(100, 150, dtype=np.uint64))
        c._engine.executor.collect_group([l1, None, l2, l3])
        assert np.all(l1.result()) and np.all(l2.result())
        assert not np.any(l3.result())
    finally:
        c.shutdown()


def test_contains_many_bulk_api():
    c = make_client()
    try:
        bf = c.get_bloom_filter("mb3-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(2000, dtype=np.uint64))
        batches = [
            np.arange(i * 500, (i + 1) * 500, dtype=np.uint64)
            for i in range(6)
        ]
        res = bf.contains_many(batches)
        assert len(res) == 6
        for i, r in enumerate(res):
            expect = (np.arange(i * 500, (i + 1) * 500) < 2000)
            # below 2000 all hit; above: FPP-rare
            assert np.array_equal(r[expect], np.ones(expect.sum(), bool))
    finally:
        c.shutdown()


def test_contains_many_host_engine():
    # Host engine returns ImmediateResults — the bulk API must degrade.
    c = redisson_tpu.create(Config().set_codec(LongCodec()))
    try:
        bf = c.get_bloom_filter("mb4-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(100, dtype=np.uint64))
        res = bf.contains_many([np.arange(50, dtype=np.uint64)] * 2)
        assert all(np.all(r) for r in res)
    finally:
        c.shutdown()


@pytest.mark.parametrize("mailbox", [True, False])
def test_coalesced_hammer_parity(mailbox):
    c = make_client(
        coalesce=True, batch_window_us=100, max_batch=4096,
        mailbox_collect=mailbox,
    )
    try:
        filters = [c.get_bloom_filter(f"mbham{i}") for i in range(8)]
        for f in filters:
            f.try_init(5000, 0.01)
        rng = np.random.default_rng(3)
        futs = []
        added: dict = {i: [] for i in range(8)}
        for step in range(60):
            fi = int(rng.integers(8))
            f = filters[fi]
            keys = rng.integers(0, 5000, 64).astype(np.uint64)
            if step % 3 == 0:
                added[fi].append(keys)
                futs.append(f.add_all_async(keys))
            else:
                futs.append(f.contains_all_async(keys))
        for fut in futs:
            fut.result()  # no exceptions, all resolve
        # Ground truth after quiesce: every added key must be present —
        # a group-slice off-by-one in the mailbox path would scramble
        # results without raising.
        for fi, batches in added.items():
            if batches:
                all_keys = np.concatenate(batches)
                assert bool(np.all(filters[fi].contains_each(all_keys)))
    finally:
        c.shutdown()


def test_client_collect_mixed_kinds():
    """client.collect — the RBatch#execute reply-flush applied to
    already-dispatched async calls, across result dtypes/objects."""
    c = make_client()
    try:
        h = c.get_hyper_log_log("cc-h")
        bs = c.get_bit_set("cc-b")
        bf = c.get_bloom_filter("cc-f")
        bf.try_init(1000, 0.01)
        futs = [
            h.add_all_async(np.arange(200, dtype=np.uint64)),
            bf.add_all_async(np.arange(100, dtype=np.uint64)),
            bs.set_many_async(np.arange(64, dtype=np.uint32)),
            bf.contains_all_async(np.arange(100, dtype=np.uint64)),
            bs.get_many_async(np.arange(64, dtype=np.uint32)),
        ]
        out = c.collect(futs)
        assert int(np.sum(out[1])) == 100  # all newly added
        assert bool(np.all(out[3]))  # all present
        assert int(np.sum(out[4])) == 64  # all bits read back set
        # The GROUP path must actually have run (not the per-item
        # degrade): a mailbox concat program was compiled.
        assert any(
            isinstance(k, tuple) and k and k[0] == "mailbox"
            for k in c._engine.executor._jit_cache
        )
    finally:
        c.shutdown()
