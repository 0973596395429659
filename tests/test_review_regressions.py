"""Regression tests for review findings (round 1): wrongtype guards,
rename safety, bitop sizing, dump parity, bitpos edge, top-K via add()."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config


@pytest.fixture(params=["tpu", "host"])
def client(request):
    cfg = Config()
    if request.param == "tpu":
        cfg.use_tpu_sketch(min_bucket=64)
    return redisson_tpu.create(cfg)


def test_rename_missing_source_keeps_destination(client):
    bf = client.get_bloom_filter("dest")
    bf.try_init(100, 0.01)
    bf.add("v")
    assert client._engine.rename("nonexistent", "dest") is False
    assert bf.contains("v")  # destination untouched
    assert client._engine.rename("dest", "dest") is False
    assert bf.contains("v")


def test_wrongtype_guards(client):
    bf = client.get_bloom_filter("typed")
    bf.try_init(100, 0.01)
    with pytest.raises(TypeError):
        client.get_hyper_log_log("typed").add("x")
    with pytest.raises(TypeError):
        client.get_hyper_log_log("typed").count()
    with pytest.raises(TypeError):
        client.get_bit_set("typed").set(1)
    with pytest.raises(TypeError):
        client.get_bit_set("typed").cardinality()
    with pytest.raises(TypeError):
        client.get_count_min_sketch("typed").try_init(2, 64)
    h = client.get_hyper_log_log("reallyhll")
    h.add("x")
    with pytest.raises(TypeError):
        h.count_with("typed")


def test_bitop_with_larger_destination(client):
    big = client.get_bit_set("bigdst")
    big.set(5000)  # larger size class than the sources
    big.clear_bit(5000)
    a = client.get_bit_set("srcA")
    b = client.get_bit_set("srcB")
    a.set_many(np.array([1, 2]))
    b.set_many(np.array([2, 3]))
    client._engine.bitset_bitop("bigdst", ("srcA", "srcB"), "or")
    arr = big.as_bit_array()
    assert sorted(np.nonzero(arr)[0].tolist()) == [1, 2, 3]


def test_to_byte_array_parity_between_modes():
    dumps = {}
    for mode in ("tpu", "host"):
        cfg = Config()
        if mode == "tpu":
            cfg.use_tpu_sketch(min_bucket=64)
        cl = redisson_tpu.create(cfg)
        bs = cl.get_bit_set("dump")
        bs.set(0)
        bs.set(77)
        dumps[mode] = bs.to_byte_array()
    assert dumps["tpu"] == dumps["host"]
    assert len(dumps["tpu"]) == 10  # ceil(78/8)


def test_first_clear_bit_all_set_parity(client):
    bs = client.get_bit_set("full")
    bs.set_range(0, 1024)  # exactly fills the smallest size class
    assert bs.first_clear_bit() == 1024


def test_cms_single_add_feeds_topk(client):
    c = client.get_count_min_sketch("cmstrk")
    c.try_init(4, 1 << 10, track_top_k=3)
    for _ in range(5):
        c.add("solo")
    top = c.top_k(1)
    assert top and top[0] == ("solo", 5)


def test_bitop_not_masks_to_logical_length(client):
    """ADVICE r1: NOT must complement the source's byte-aligned string
    (Redis BITOP NOT semantics) in BOTH engines — never the whole
    physical size-class row."""
    src = client.get_bit_set("notsrc")
    src.set_many(np.array([1, 3, 5]))  # logical length 6 -> 1-byte string
    dst = client.get_bit_set("notdst")
    client._engine.bitset_bitop("notdst", ("notsrc",), "not")
    assert dst.cardinality() == 5  # bits 0, 2, 4 + padding bits 6, 7
    arr = dst.as_bit_array()
    assert sorted(np.nonzero(arr)[0].tolist()) == [0, 2, 4, 6, 7]


def test_bitop_not_parity_between_modes():
    dumps = {}
    for mode in ("tpu", "host"):
        cfg = Config()
        if mode == "tpu":
            cfg.use_tpu_sketch(min_bucket=64)
        cl = redisson_tpu.create(cfg)
        src = cl.get_bit_set("nsrc")
        src.set_many(np.array([0, 9]))
        cl._engine.bitset_bitop("ndst", ("nsrc",), "not")
        dumps[mode] = (
            cl.get_bit_set("ndst").to_byte_array(),
            cl.get_bit_set("ndst").cardinality(),
        )
    assert dumps["tpu"] == dumps["host"]
    assert dumps["tpu"][1] == 14  # 10 logical bits -> 16-bit string, 2 set in src


def test_bitop_overwrites_destination(client):
    """ADVICE r1: Redis BITOP replaces dest entirely — stale high bits of
    a previously-larger dest must not survive."""
    dst = client.get_bit_set("owdst")
    dst.set(5000)  # dest has a high bit + large physical row
    a = client.get_bit_set("owA")
    b = client.get_bit_set("owB")
    a.set_many(np.array([1, 2]))
    b.set_many(np.array([2, 3]))
    client._engine.bitset_bitop("owdst", ("owA", "owB"), "or")
    arr = dst.as_bit_array()
    assert sorted(np.nonzero(arr)[0].tolist()) == [1, 2, 3]
    assert dst.cardinality() == 3


def test_bitop_does_not_inflate_source_logical_length(client):
    a = client.get_bit_set("lenA")
    b = client.get_bit_set("lenB")
    a.set(2)       # logical length 3
    b.set(9000)    # much larger class
    client._engine.bitset_bitop("lenD", ("lenA", "lenB"), "or")
    # Source A keeps its own logical length (3 bits -> 1-byte string):
    # NOT of it has 7 bits set, not thousands from B's size class.
    client._engine.bitset_bitop("lenNA", ("lenA",), "not")
    assert client.get_bit_set("lenNA").cardinality() == 7


def test_cms_counts_wrap_identically_between_modes():
    """ADVICE r1: CMS counters are uint32 in both engines; totals wrap
    mod 2**32 identically instead of silently diverging."""
    ests = {}
    for mode in ("tpu", "host"):
        cfg = Config()
        if mode == "tpu":
            cfg.use_tpu_sketch(min_bucket=64)
        cl = redisson_tpu.create(cfg)
        c = cl.get_count_min_sketch("wrapcms")
        c.try_init(3, 1 << 8)
        big = (1 << 31) + 7
        c.add("k", count=big)
        c.add("k", count=big)  # 2*(2^31+7) wraps to 14 mod 2^32
        ests[mode] = int(c.estimate("k"))
    assert ests["tpu"] == ests["host"] == 14


def test_fast_add_drains_pending_coalesced_reads():
    """ADVICE r1: on the default (coalescing, exact) config an add must
    not overtake an earlier queued contains of the same key."""
    cfg = Config().use_tpu_sketch(
        coalesce=True, batch_window_us=200_000, min_bucket=64,
    )
    cl = redisson_tpu.create(cfg)
    bf = cl.get_bloom_filter("orderbf")
    bf.try_init(1000, 0.01)
    # Queue a contains (sits in the window), then add the same key.
    fut = bf.contains_async("late-key")
    bf.add("late-key")
    # The earlier read must NOT observe the later write.
    assert not np.any(fut.result())
    assert bf.contains("late-key") is True
    cl.shutdown()


class TestRound3AdviceFixes:
    """ADVICE r2: one logical keyspace, read-only lock paths, SET XX TTL,
    lock owner identity."""

    def _client(self):
        import redisson_tpu
        from redisson_tpu import Config

        return redisson_tpu.create(Config().use_tpu_sketch(min_bucket=64))

    def test_cross_backend_wrongtype_grid_then_sketch(self):
        import pytest

        c = self._client()
        try:
            c.get_bucket("shared-name").set("v")
            with pytest.raises(TypeError, match="WRONGTYPE|held by"):
                c.get_bloom_filter("shared-name").try_init(1000, 0.01)
        finally:
            c.shutdown()

    def test_cross_backend_wrongtype_sketch_then_grid(self):
        import pytest

        c = self._client()
        try:
            bf = c.get_bloom_filter("shared-name2")
            bf.try_init(1000, 0.01)
            with pytest.raises(TypeError, match="WRONGTYPE|held by"):
                c.get_bucket("shared-name2").set("v")
        finally:
            c.shutdown()

    def test_readonly_lock_queries_do_not_materialize(self):
        c = self._client()
        try:
            assert not c.get_lock("ro-lock").is_locked()
            assert c.get_lock("ro-lock").get_hold_count() == 0
            assert c.get_semaphore("ro-sem").available_permits() == 0
            assert c.get_count_down_latch("ro-latch").get_count() == 0
            assert c.get_rate_limiter("ro-rl").available_permits() == 0
            names = c.get_keys().get_keys()
            for n in ("ro-lock", "ro-sem", "ro-latch", "ro-rl"):
                assert n not in names, n
        finally:
            c.shutdown()

    def test_set_if_exists_clears_ttl(self):
        import time

        c = self._client()
        try:
            b = c.get_bucket("xx-ttl")
            b.set("v1", ttl_seconds=30.0)
            assert b.remain_time_to_live() > 0
            assert b.set_if_exists("v2")
            # SET XX without KEEPTTL clears the TTL, like set().
            assert b.remain_time_to_live() == -1
            assert b.get() == "v2"
        finally:
            c.shutdown()

    def test_lock_owner_uses_client_uuid(self):
        c1 = self._client()
        c2 = self._client()
        try:
            assert c1.id != c2.id
            lk = c1.get_lock("uuid-lock")
            lk.lock()
            assert lk._me()[0] == c1.id
            lk.unlock()
        finally:
            c1.shutdown()
            c2.shutdown()

    def test_cross_backend_guard_no_deadlock(self):
        """r3 review: foreign-exists probes must be lock-free — a locking
        probe deadlocks AB-BA when both backends create concurrently."""
        import threading

        import redisson_tpu
        from redisson_tpu import Config

        c = redisson_tpu.create(Config())  # host engine (default config)
        try:
            stop = threading.Event()

            def sketch_side():
                i = 0
                while not stop.is_set() and i < 300:
                    c.get_bloom_filter(f"dl-bf-{i}").try_init(100, 0.01)
                    i += 1

            def grid_side():
                i = 0
                while not stop.is_set() and i < 300:
                    c.get_bucket(f"dl-bk-{i}").set(i)
                    i += 1

            t1 = threading.Thread(target=sketch_side, daemon=True)
            t2 = threading.Thread(target=grid_side, daemon=True)
            t1.start(); t2.start()
            t1.join(timeout=10); t2.join(timeout=10)
            alive = t1.is_alive() or t2.is_alive()
            stop.set()
            assert not alive, "cross-backend creation deadlocked"
        finally:
            c.shutdown()

    def test_restore_cannot_shadow_grid(self):
        import pytest

        c = self._client()
        try:
            bf = c.get_bloom_filter("shadow-src")
            bf.try_init(100, 0.01)
            blob = bf.dump()
            c.get_bucket("shadow-dst").set("v")
            with pytest.raises(TypeError, match="WRONGTYPE|held by"):
                c._engine.restore("shadow-dst", blob)
            with pytest.raises(TypeError, match="WRONGTYPE|held by"):
                c._engine.rename("shadow-src", "shadow-dst")
        finally:
            c.shutdown()


def test_concurrent_bitset_grow_no_double_free():
    """Two threads growing the same bitset concurrently: exactly one
    migration wins, data survives, and no pool row is double-freed
    (a duplicate free hands one device row to two future tenants)."""
    import threading

    import numpy as np

    import redisson_tpu
    from redisson_tpu import Config

    c = redisson_tpu.create(
        Config().use_tpu_sketch(min_bucket=64, coalesce=False)
    )
    try:
        for round_ in range(6):
            name = f"growrace{round_}"
            bs = c.get_bit_set(name)
            bs.set_many(np.arange(0, 1024, 3, dtype=np.uint32))
            barrier = threading.Barrier(2)
            errs = []

            def grower(hi):
                try:
                    barrier.wait(5)
                    c.get_bit_set(name).set(hi)  # forces a size-class grow
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            t1 = threading.Thread(target=grower, args=(200_000,))
            t2 = threading.Thread(target=grower, args=(250_000,))
            t1.start(); t2.start(); t1.join(10); t2.join(10)
            assert not errs, errs
            got = c.get_bit_set(name)
            assert bool(np.all(got.get_many(
                np.arange(0, 1024, 3, dtype=np.uint32)
            ))), "pre-grow bits lost in concurrent migration"
            assert got.get(200_000) and got.get(250_000)
            # No pool free-list may contain duplicates (double-free).
            for pool in c._engine.registry.pools():
                assert len(pool._free) == len(set(pool._free)), (
                    "double-freed row in pool free list"
                )
    finally:
        c.shutdown()


def test_host_restore_rejects_kind_model_mismatch():
    import redisson_tpu
    from redisson_tpu import Config

    c = redisson_tpu.create(Config())
    try:
        cms = c.get_count_min_sketch("kmm")
        cms.try_init(4, 1 << 10)
        cms.add(1)
        import json as _json
        import struct as _struct

        raw = cms.dump()
        (hlen,) = _struct.unpack("<I", raw[4:8])
        hdr = _json.loads(raw[8 : 8 + hlen].decode())
        assert hdr["kind"] == "cms"
        hdr["kind"] = "bloom"  # forged: kind disagrees with model_cls
        nh = _json.dumps(hdr).encode()
        forged = raw[:4] + _struct.pack("<I", len(nh)) + nh + raw[8 + hlen :]
        import pytest as _pytest

        with _pytest.raises(ValueError, match="does not match"):
            c._engine.restore("kmm2", forged)
    finally:
        c.shutdown()
