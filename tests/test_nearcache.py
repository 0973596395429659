"""Sketch near cache (ISSUE 4): the epoch-guarded host read tier.

Covers the shared sharded-LRU store (bounds, tenant fairness, eviction),
the epoch discipline (monotone positives vs write-tagged results, the
capture-before-submit install guard), the engine read/write integration
(partial-hit splitting, invalidation on every mutating path, delete /
rename / restore identity changes), the RESP surface (INFO section +
live CONFIG SET), the LocalCachedMap refactor onto the shared store, and
the randomized differential soak against the host golden engine —
interleaved adds/clears/resizes/degradations with every read compared,
the acceptance criterion's zero-stale-reads evidence.
"""

import time

import numpy as np
import pytest

from redisson_tpu import chaos
from redisson_tpu.cache import MISS, ShardedLRUStore, SketchNearCache
from redisson_tpu.chaos import ChaosSchedule
from redisson_tpu.config import Config


@pytest.fixture(autouse=True)
def _chaos_off():
    chaos.clear()
    chaos.reset_counts()
    yield
    chaos.clear()
    chaos.reset_counts()


def make_client(**tpu_kw):
    from redisson_tpu.client import RedissonTpuClient

    tpu_kw.setdefault("batch_window_us", 100)
    cfg = Config().use_tpu_sketch(**tpu_kw)
    cfg.retry_attempts = 2
    cfg.retry_interval_ms = 5
    return RedissonTpuClient(cfg)


def _await(cond, timeout=8.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.02)
    return True


def _flap(fn, attempts=8):
    """Ride out breaker flaps (see test_chaos): a degraded-window op may
    fail typed while the breaker re-opens; retrying resumes from the
    mirror."""
    for _ in range(attempts - 1):
        try:
            return fn()
        except Exception:
            time.sleep(0.05)
    return fn()


# -- shared sharded-LRU store ------------------------------------------------


class TestShardedLRUStore:
    def test_put_get_miss_and_lru_promotion(self):
        s = ShardedLRUStore(max_bytes=1 << 20, nshards=1)
        s.set_tenant_limits("t", max_entries=2)
        assert s.get("t", "a") is MISS
        s.put("t", "a", 1, 100)
        s.put("t", "b", 2, 100)
        assert s.get("t", "a") == 1  # promotes a to MRU
        s.put("t", "c", 3, 100)      # entry bound 2: evicts LRU = b
        assert s.get("t", "b") is MISS
        assert s.get("t", "a") == 1
        assert s.get("t", "c") == 3

    def test_tenant_byte_quota_is_fair(self):
        # One hot tenant fills its OWN quota and recycles its OWN tail —
        # the cold tenant's entries survive untouched.
        s = ShardedLRUStore(max_bytes=10_000, nshards=2,
                            tenant_quota_bytes=1_000)
        for i in range(5):
            s.put("cold", f"c{i}", i, 100)
        for i in range(50):
            s.put("hot", f"h{i}", i, 100)
        assert s.tenant_bytes("hot") <= 1_000
        assert s.tenant_entry_count("cold") == 5
        assert all(s.get("cold", f"c{i}") == i for i in range(5))

    def test_global_budget_bounds_total(self):
        s = ShardedLRUStore(max_bytes=1_000, nshards=2,
                            tenant_quota_bytes=1_000)
        for i in range(40):
            s.put(f"t{i % 4}", f"k{i}", i, 100)
        assert s.bytes() <= 1_000

    def test_oversized_entry_refused(self):
        s = ShardedLRUStore(max_bytes=500, nshards=1)
        assert s.put("t", "big", 1, 600) is False
        assert s.entries() == 0

    def test_oversized_replace_discards_stale_entry(self):
        # A refused replace must still drop the OLD cached value — the
        # caller installed a new one and the old is stale now.
        s = ShardedLRUStore(max_bytes=500, nshards=1,
                            tenant_quota_bytes=500)
        s.put("t", "k", "old", 100)
        assert s.put("t", "k", "new-but-huge", 600) is False
        assert s.get("t", "k") is MISS

    def test_discard_and_invalidate_tenant(self):
        s = ShardedLRUStore(max_bytes=1 << 20, nshards=4)
        for i in range(10):
            s.put("a", i, i, 50)
            s.put("b", i, i, 50)
        s.discard("a", 3)
        assert s.get("a", 3) is MISS
        assert s.invalidate_tenant("a") == 9
        assert s.tenant_entry_count("a") == 0
        assert s.tenant_bytes("a") == 0
        assert s.tenant_entry_count("b") == 10

    def test_on_evict_hook_and_stats(self):
        evicted = []
        s = ShardedLRUStore(max_bytes=300, nshards=1,
                            tenant_quota_bytes=300,
                            on_evict=lambda t, nb: evicted.append((t, nb)))
        for i in range(5):
            s.put("t", i, i, 100)
        assert s.evictions >= 2
        assert len(evicted) == s.evictions
        st = s.stats()
        assert st["bytes"] <= 300 and st["entries"] <= 3

    def test_eviction_rotates_and_keeps_recent_keys(self):
        # Quota-pressure eviction must spread across shards and respect
        # recency: with a fixed start shard, survivors piled into one
        # shard and freshly installed keys in the others died instantly.
        s = ShardedLRUStore(max_bytes=1 << 20, nshards=8,
                            tenant_quota_bytes=10_000)
        for i in range(1000):
            s.put("hot", f"k{i}", i, 100)
        survivors_per_shard = [len(sh.entries) for sh in s._shards]
        assert sum(1 for n in survivors_per_shard if n > 0) >= 4, (
            survivors_per_shard
        )
        recent_alive = sum(
            1 for i in range(990, 1000) if s.get("hot", f"k{i}") is not MISS
        )
        assert recent_alive >= 8, recent_alive

    def test_resize_live(self):
        s = ShardedLRUStore(max_bytes=1 << 20, nshards=1)
        s.put("t", "k", 1, 100)
        s.resize(max_bytes=400)  # trims lazily on the next put
        s.put("t", "k2", 2, 40)  # under the re-derived 400/8 quota
        assert s.bytes() <= 400
        s.resize(max_bytes=120)
        s.put("t", "k3", 3, 10)
        assert s.bytes() <= 120


# -- epoch discipline --------------------------------------------------------


def _nc(**kw):
    return SketchNearCache(
        ShardedLRUStore(max_bytes=1 << 20, nshards=2), **kw
    )


class TestEpochDiscipline:
    def test_tagged_entry_dies_on_write(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.install("o", "k", 7, captured=cap, monotone=False)
        assert nc.probe("o", "k") == 7
        nc.note_write("o")
        assert nc.probe("o", "k") is MISS  # and discarded

    def test_monotone_positive_survives_writes_dies_structural(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.install("o", "k", True, captured=cap, monotone=True)
        nc.note_write("o")
        assert nc.probe("o", "k") is True  # adds never retire a positive
        nc.note_structural("o")
        assert nc.probe("o", "k") is MISS

    def test_monotone_negative_is_write_tagged(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.install("o", "k", False, captured=cap, monotone=True)
        nc.note_write("o")
        assert nc.probe("o", "k") is MISS  # an in-flight add invalidates

    def test_install_blocked_when_capture_stale(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.note_write("o")  # a write landed after the reader captured
        nc.install("o", "k", 5, captured=cap, monotone=False)
        assert nc.probe("o", "k") is MISS

    def test_monotone_positive_installs_across_write_not_structural(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.note_write("o")  # ordinary write: a positive still installs
        nc.install("o", "k", True, captured=cap, monotone=True)
        assert nc.probe("o", "k") is True
        nc.note_structural("o")
        cap2 = cap  # captured before the structural change: blocked
        nc.install("o", "k2", True, captured=cap2, monotone=True)
        assert nc.probe("o", "k2") is MISS

    def test_drop_object_advances_epochs_forever(self):
        nc = _nc()
        cap = nc.epochs("o")
        nc.install("o", "k", 1, captured=cap, monotone=False)
        nc.drop_object("o")
        assert nc.probe("o", "k") is MISS
        # A successor object under the same name continues the sequence:
        # the old capture can never install as fresh.
        nc.install("o", "k", 1, captured=cap, monotone=False)
        assert nc.probe("o", "k") is MISS

    def test_invalidate_all_retires_never_mutated_names(self):
        # A name with NO per-name epoch entry (never written in this
        # process — e.g. restored from a snapshot) must also stop
        # matching captures taken before invalidate_all: the floor moves.
        nc = _nc()
        cap = nc.epochs("restored-only")  # floor pair
        nc.invalidate_all()
        nc.install("restored-only", "k", 7, captured=cap, monotone=False)
        assert nc.probe("restored-only", "k") is MISS
        nc.install("restored-only", "p", True, captured=cap, monotone=True)
        assert nc.probe("restored-only", "p") is MISS

    def test_resize_recomputes_defaulted_tenant_quota(self):
        s = ShardedLRUStore(max_bytes=64 << 20)  # quota defaults to /8
        assert s.tenant_quota_bytes == 8 << 20
        s.resize(max_bytes=1 << 30)
        assert s.tenant_quota_bytes == (1 << 30) // 8
        s.resize(tenant_quota_bytes=123456)  # explicit: sticks
        s.resize(max_bytes=64 << 20)
        assert s.tenant_quota_bytes == 123456
        s.resize(tenant_quota_bytes=0)  # back to defaulted
        assert s.tenant_quota_bytes == 8 << 20

    def test_invalidate_all_and_set_enabled(self):
        nc = _nc()
        nc.install("o", "k", 1, captured=nc.epochs("o"), monotone=False)
        nc.invalidate_all()
        assert nc.probe("o", "k") is MISS
        nc.install("o", "k", 2, captured=nc.epochs("o"), monotone=False)
        nc.set_enabled(False)
        assert nc.store.entries() == 0
        nc.set_enabled(True)
        assert nc.probe("o", "k") is MISS

    def test_active_respects_max_batch(self):
        nc = _nc(max_batch=8)
        assert nc.active(8) and not nc.active(9) and not nc.active(0)

    def test_disabled_cache_refuses_installs(self):
        # A future created before CONFIG SET nearcache no resolves after
        # it: the install must bail, or the "disabled" store holds bytes
        # nothing will ever evict.
        nc = _nc()
        captured = nc.epochs("o")
        nc.set_enabled(False)
        nc.install("o", "k", 1, captured=captured, monotone=False)
        nc.install("o", "p", True, captured=captured, monotone=True)
        assert nc.store.entries() == 0 and nc.store.bytes() == 0
        nc.set_enabled(True)
        assert nc.probe("o", "k") is MISS

    def test_epoch_dict_bounded_under_name_churn(self):
        # TTL'd per-session sketches mint names forever; the per-name
        # epoch dict must fold dead names into the floor, not leak one
        # entry per name for the process lifetime.
        nc = _nc()
        nc._epoch_cap = nc._epoch_prune_at = 32
        nc.note_write("live")  # mutated + live entries → survives prunes
        nc.install("live", "k", 7, captured=nc.epochs("live"),
                   monotone=False)
        live_epochs = nc.epochs("live")
        for i in range(1000):
            name = f"ephemeral-{i}"
            nc.note_write(name)
            nc.drop_object(name)
        assert len(nc._epochs) <= 2 * 32 + 2
        # Pruned names resume FROM the raised floor: strictly past any
        # epoch they ever held, so an in-flight pre-prune read can
        # neither serve nor install.
        assert nc.epochs("ephemeral-0") == nc._floor
        assert nc._floor > (1, 1)
        # The name with live cached entries kept its own sequence and
        # its entry still serves.
        assert nc.epochs("live") == live_epochs
        assert nc.probe("live", "k") == 7


# -- engine integration ------------------------------------------------------


class TestEngineIntegration:
    def setup_method(self):
        self.c = make_client()
        self.nc = self.c._engine.nearcache

    def teardown_method(self):
        self.c._engine.shutdown()

    def test_bloom_negative_invalidated_by_add(self):
        bf = self.c.get_bloom_filter("nc-bf")
        bf.try_init(10_000, 0.01)
        assert bf.contains("ghost") is False  # cached negative
        bf.add("ghost")  # submit-time bump: the negative must die NOW
        assert bf.contains("ghost") is True

    def test_bloom_positive_survives_other_adds_and_hits(self):
        bf = self.c.get_bloom_filter("nc-bf2")
        bf.try_init(10_000, 0.01)
        bf.add("hot")
        assert bf.contains("hot") is True  # installs monotone positive
        h0 = self.nc.hits
        bf.add("other-key")  # ordinary write: positive survives
        assert bf.contains("hot") is True
        assert self.nc.hits > h0

    def test_bloom_partial_hit_split(self):
        bf = self.c.get_bloom_filter("nc-bf3")
        bf.try_init(10_000, 0.01)
        keys = [f"k{i}" for i in range(10)]
        bf.add_all(keys[:5])
        got_warm = bf.contains_each(keys[:5])  # caches 5 positives
        assert all(got_warm)
        self.nc.hits = self.nc.misses = 0
        got = bf.contains_each(keys)
        assert self.nc.hits == 5 and self.nc.misses == 5
        # The assembled result must equal an uncached read bit-for-bit.
        self.nc.store.clear()
        want = bf.contains_each(keys)
        assert np.array_equal(np.asarray(got, bool), np.asarray(want, bool))

    def test_bitset_get_cached_and_clear_is_structural(self):
        bs = self.c.get_bit_set("nc-bs")
        bs.set(5)
        assert bs.get(5) is True
        h0 = self.nc.hits
        assert bs.get(5) is True  # hit
        assert self.nc.hits > h0
        bs.set(5, False)  # structural: retires the monotone positive
        assert bs.get(5) is False
        bs.flip(5)
        assert bs.get(5) is True

    def test_bitset_scalars_invalidate_on_write(self):
        bs = self.c.get_bit_set("nc-bs2")
        bs.set_many(np.array([1, 3, 5]))
        assert bs.cardinality() == 3
        assert bs.cardinality() == 3  # cached
        bs.set(7)
        assert bs.cardinality() == 4
        assert bs.length() == 8
        assert bs.first_set_bit() == 1

    def test_cms_estimate_invalidated_by_add(self):
        cms = self.c.get_count_min_sketch("nc-cms")
        cms.try_init(4, 256)
        cms.add("k", 3)
        assert cms.estimate("k") == 3
        assert cms.estimate("k") == 3  # cached
        cms.add("k", 2)
        assert cms.estimate("k") == 5

    def test_hll_count_invalidated_by_add(self):
        h = self.c.get_hyper_log_log("nc-hll")
        h.add_all([f"v{i}" for i in range(100)])
        n = h.count()
        assert h.count() == n  # cached
        h.add_all([f"w{i}" for i in range(100)])
        assert h.count() > n

    def test_delete_drops_cached_entries(self):
        bf = self.c.get_bloom_filter("nc-del")
        bf.try_init(10_000, 0.01)
        bf.add("x")
        assert bf.contains("x") is True  # cached positive
        bf.delete()
        bf.try_init(10_000, 0.01)
        assert bf.contains("x") is False  # successor: no stale positive

    def test_rename_drops_both_names(self):
        bf = self.c.get_bloom_filter("nc-rn")
        bf.try_init(10_000, 0.01)
        bf.add("x")
        assert bf.contains("x") is True
        bf.rename("nc-rn2")
        bf2 = self.c.get_bloom_filter("nc-rn2")
        assert bf2.contains("x") is True  # re-read from device, not cache

    def test_bitset_grow_is_structural(self):
        bs = self.c.get_bit_set("nc-grow")
        bs.set(1)
        assert bs.get(1) is True  # cached
        s_before = self.nc.epochs("nc-grow")[1]
        bs.set(300_000)  # size-class migration
        assert self.nc.epochs("nc-grow")[1] > s_before
        assert bs.get(1) is True and bs.get(300_000) is True

    def test_big_batches_bypass(self):
        bf = self.c.get_bloom_filter("nc-bulk")
        bf.try_init(100_000, 0.01)
        keys = np.arange(2048, dtype=np.uint64)  # > nearcache_max_batch
        bf.add_all(keys)
        bf.contains_each(keys)
        assert self.nc.store.entries() == 0

    def test_disabled_never_populates(self):
        c2 = make_client(nearcache=False)
        try:
            bf = c2.get_bloom_filter("nc-off")
            bf.try_init(10_000, 0.01)
            bf.add("x")
            assert bf.contains("x") is True
            nc = c2._engine.nearcache
            assert nc.store.entries() == 0 and nc.hits == 0
        finally:
            c2._engine.shutdown()

    def test_metrics_counters_and_gauges(self):
        bf = self.c.get_bloom_filter("nc-met")
        bf.try_init(10_000, 0.01)
        bf.add("x")
        bf.contains("x")
        bf.contains("x")
        text = self.c.render_prometheus()
        assert "rtpu_nearcache_hits" in text
        assert "rtpu_nearcache_bytes" in text
        st = self.nc.stats()
        assert st["hits"] >= 1 and st["entries"] >= 1


# -- RESP surface ------------------------------------------------------------


class TestRespSurface:
    def test_info_section_and_live_config_set(self):
        from redisson_tpu.serve.resp import RespServer

        c = make_client()
        server = RespServer(c, host="127.0.0.1", port=0)
        try:
            bf = c.get_bloom_filter("resp-bf")
            bf.try_init(10_000, 0.01)
            bf.add("x")
            bf.contains("x")
            bf.contains("x")
            info = server._cmd_INFO([b"nearcache"]).decode()
            assert "# Nearcache" in info
            assert "nearcache_enabled:1" in info
            assert "nearcache_hits:" in info
            out = server._cmd_CONFIG([b"GET", b"nearcache*"]).decode()
            assert "nearcache-max-bytes" in out
            # Live retune: byte budget + disable (drops every entry).
            server._cmd_CONFIG([b"SET", b"nearcache-max-bytes", b"1048576"])
            nc = c._engine.nearcache
            assert nc.store.max_bytes == 1 << 20
            server._cmd_CONFIG([b"SET", b"nearcache", b"no"])
            assert nc.enabled is False and nc.store.entries() == 0
            info = server._cmd_INFO([b"nearcache"]).decode()
            assert "nearcache_enabled:0" in info
            server._cmd_CONFIG([b"SET", b"nearcache", b"yes"])
            assert nc.enabled is True
            # Unknown-value rejection.
            from redisson_tpu.serve.resp import RespError

            with pytest.raises(RespError):
                server._cmd_CONFIG([b"SET", b"nearcache", b"maybe"])
        finally:
            server.close()
            c._engine.shutdown()

    def test_host_engine_has_no_nearcache_keys(self):
        import redisson_tpu
        from redisson_tpu.serve.resp import RespError, RespServer

        c = redisson_tpu.create(Config())
        server = RespServer(c, host="127.0.0.1", port=0)
        try:
            with pytest.raises(RespError):
                server._cmd_CONFIG([b"SET", b"nearcache", b"yes"])
            info = server._cmd_INFO([b"nearcache"]).decode()
            assert "# Nearcache" not in info  # honesty: no tier to report
        finally:
            server.close()
            c.shutdown()


# -- LocalCachedMap on the shared store --------------------------------------


class TestLocalCachedMapSharedStore:
    def test_byte_quota_and_stats(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-store", cache_size=128,
                                       cache_max_bytes=4096)
            for i in range(64):
                m.put(f"k{i}", "v" * 100)
            st = m.cache_stats()
            assert st["bytes"] <= 4096
            assert st["evictions"] > 0
            assert m.cached_size() == st["entries"]
            # Reads served from the near cache count as store hits.
            m.get("k63")
            assert m.cache_stats()["hits"] >= 1
        finally:
            c.shutdown()

    def test_oversized_overwrite_never_serves_stale(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-big", cache_max_bytes=1024)
            m.put("k", "small")
            big = "v" * 4096  # over the byte budget: uncacheable
            m.put("k", big)
            assert m.get("k") == big  # backing map, never the stale entry
        finally:
            c.shutdown()

    def test_cache_size_zero_disables_caching(self):
        # Seed semantics: cache_size=0 means NO near cache (the old
        # OrderedDict evicted down to the bound after every put).  The
        # store's max_entries=0 means "unbounded" — the handle must not
        # pass the caller's opt-out through as that inversion.
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-off", cache_size=0)
            for i in range(32):
                m.put(f"k{i}", f"v{i}")
                m.get(f"k{i}")
            assert m.cached_size() == 0
            assert m.cache_stats()["bytes"] == 0
            assert m.get("k7") == "v7"  # served by the backing map
        finally:
            c.shutdown()

    def test_single_tenant_owns_whole_byte_budget(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-budget", cache_size=10_000,
                                       cache_max_bytes=64 << 10)
            for i in range(200):
                m.put(f"k{i}", "v" * 100)
            # ~200 entries * ~220B ≈ 44KB fits the 64KB budget whole —
            # the old default-quota bug capped the tenant at budget/8.
            assert m.cache_stats()["evictions"] == 0
            assert m.cached_size() == 200
        finally:
            c.shutdown()

    def test_entry_bound_still_enforced(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-bound", cache_size=4)
            for i in range(10):
                m.put(f"k{i}", i)
            assert m.cached_size() <= 4
            # Backing map still holds everything.
            assert all(m.get(f"k{i}") == i for i in range(10))
        finally:
            c.shutdown()


# -- differential soak -------------------------------------------------------


BLOOM_POINTS = (
    "dispatch.bloom_mixed_keys", "dispatch.bloom_contains_keys_st",
    "dispatch.bloom_mixed_keys_runs", "dispatch.bloom_contains_keys_runs",
)
BITSET_POINTS = ("dispatch.bitset_mixed", "dispatch.bitset_mixed_runs")


class TestDifferentialSoak:
    """Randomized cached-vs-golden interleaving (acceptance criterion):
    adds, clears, resizes and a full degradation/reconcile cycle, every
    read equality-checked against the host golden engine — one stale
    cached read anywhere fails the run."""

    def _mk_pair(self):
        import redisson_tpu

        gold = redisson_tpu.create(Config())
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=600)
        return c, gold

    def _check_reads(self, rng, pairs, keyspace):
        (tb, gb), (tbs, gbs), (tcm, gcm), (th, gh) = pairs
        ks = rng.integers(0, keyspace, int(rng.integers(1, 24))).astype(
            np.uint64
        )
        got = _flap(lambda: tb.contains_each(ks))
        want = gb.contains_each(ks)
        assert np.array_equal(np.asarray(got, bool), np.asarray(want, bool))
        idx = rng.integers(0, 4096, int(rng.integers(1, 16)))
        got = _flap(lambda: tbs.get_many(idx))
        want = gbs.get_many(idx)
        assert np.array_equal(np.asarray(got, bool), np.asarray(want, bool))
        est_t = _flap(lambda: tcm.estimate_all(ks))
        est_g = gcm.estimate_all(ks)
        assert np.array_equal(
            np.asarray(est_t, np.int64), np.asarray(est_g, np.int64)
        )
        assert _flap(lambda: th.count()) == gh.count()
        assert _flap(lambda: tbs.cardinality()) == gbs.cardinality()

    def _mixed_writes(self, rng, pairs, keyspace):
        (tb, gb), (tbs, gbs), (tcm, gcm), (th, gh) = pairs
        op = int(rng.integers(0, 6))
        if op == 0:
            ks = rng.integers(0, keyspace, 8).astype(np.uint64)
            _flap(lambda: tb.add_all(ks))
            gb.add_all(ks)
        elif op == 1:
            idx = rng.integers(0, 4096, 8)
            val = bool(rng.integers(0, 2))
            _flap(lambda: tbs.set_many(idx, val))
            gbs.set_many(idx, val)
        elif op == 2:
            idx = int(rng.integers(0, 4096))
            _flap(lambda: tbs.flip(idx))
            gbs.flip(idx)
        elif op == 3:
            ks = rng.integers(0, keyspace, 8).astype(np.uint64)
            w = rng.integers(1, 5, 8)
            _flap(lambda: tcm.add_all(ks, w))
            gcm.add_all(ks, w)
        elif op == 4:
            ks = rng.integers(0, keyspace, 16).astype(np.uint64)
            _flap(lambda: th.add_all(ks))
            gh.add_all(ks)
        else:
            lo = int(rng.integers(0, 2048))
            hi = lo + int(rng.integers(1, 64))
            val = bool(rng.integers(0, 2))
            _flap(lambda: tbs.set_range(lo, hi)) if val else _flap(
                lambda: tbs.clear_range(lo, hi)
            )
            gbs.set_range(lo, hi) if val else gbs.clear_range(lo, hi)

    def test_zero_stale_reads_across_chaos(self):
        c, gold = self._mk_pair()
        eng = c._engine
        KEYSPACE = 2000
        try:
            pairs = []
            tb, gb = (x.get_bloom_filter("soak-bf") for x in (c, gold))
            for h in (tb, gb):
                h.try_init(20_000, 0.01)
            pairs.append((tb, gb))
            pairs.append(tuple(x.get_bit_set("soak-bs") for x in (c, gold)))
            tcm, gcm = (x.get_count_min_sketch("soak-cms") for x in (c, gold))
            for h in (tcm, gcm):
                h.try_init(4, 512)
            pairs.append((tcm, gcm))
            pairs.append(
                tuple(x.get_hyper_log_log("soak-hll") for x in (c, gold))
            )
            rng = np.random.default_rng(7)

            # Phase 1: healthy interleaving, incl. clears + a resize.
            for i in range(60):
                self._mixed_writes(rng, pairs, KEYSPACE)
                if i % 3 == 0:
                    self._check_reads(rng, pairs, KEYSPACE)
                if i == 30:  # size-class migration mid-soak (structural)
                    _flap(lambda: pairs[1][0].set(300_000))
                    pairs[1][1].set(300_000)
                if i == 40:
                    _flap(lambda: tcm.add("reset-probe", 3))
                    gcm.add("reset-probe", 3)
                    c._engine.cms_reset("soak-cms")
                    gcm._engine.cms_reset("soak-cms")
                    assert _flap(lambda: tcm.estimate("reset-probe")) == 0

            # Phase 2: breaker-open degradation — bloom + bitset serve
            # from host mirrors; mirror writes MUST keep bumping epochs.
            chaos.install(ChaosSchedule(
                seed=5, rate=1.0, points=BLOOM_POINTS + BITSET_POINTS
            ))
            for i in range(12):
                try:
                    tb.add(np.uint64(900_000 + i))
                    gb.add(np.uint64(900_000 + i))
                except Exception:
                    pass
                try:
                    pairs[1][0].set(int(4096 + i))
                    pairs[1][1].set(int(4096 + i))
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            # Golden re-sync for the sacrificial ops whose TPU-side throw
            # prevented the paired golden apply: replay them on BOTH
            # sides (idempotent monotone ops — safe to double-apply).
            for i in range(12):
                _flap(lambda i=i: tb.add(np.uint64(900_000 + i)))
                gb.add(np.uint64(900_000 + i))
                _flap(lambda i=i: pairs[1][0].set(int(4096 + i)))
                pairs[1][1].set(int(4096 + i))
            for i in range(24):
                self._mixed_writes(rng, pairs, KEYSPACE)
                if i % 3 == 0:
                    self._check_reads(rng, pairs, KEYSPACE)

            # Phase 3: heal, reconcile, full comparison sweep.
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            for i in range(24):
                self._mixed_writes(rng, pairs, KEYSPACE)
                if i % 3 == 0:
                    self._check_reads(rng, pairs, KEYSPACE)
            probe = np.arange(0, KEYSPACE, 7, dtype=np.uint64)
            for lo in range(0, len(probe), 512):
                ks = probe[lo : lo + 512]
                assert np.array_equal(
                    np.asarray(tb.contains_each(ks), bool),
                    np.asarray(gb.contains_each(ks), bool),
                )
            idx = np.arange(4096)
            for lo in range(0, 4096, 1024):
                assert np.array_equal(
                    np.asarray(pairs[1][0].get_many(idx[lo : lo + 1024]), bool),
                    np.asarray(pairs[1][1].get_many(idx[lo : lo + 1024]), bool),
                )
            assert pairs[3][0].count() == pairs[3][1].count()
        finally:
            chaos.clear()
            eng.shutdown()
            gold.shutdown()


class TestLocalCachedMapCrossHandleSharing:
    """ISSUE 6 satellite (ROADMAP near-cache-reach): map gets route
    through ONE per-client store, so two handles to one map share hits."""

    def test_two_handles_share_hits(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            a = c.get_local_cached_map("lcm-share")
            b = c.get_local_cached_map("lcm-share")
            assert a._cache is b._cache  # one store per client
            a.put("k", "v")
            # A's invalidation message asynchronously discards through
            # B's listener (the converging-writes rule): drain the bus,
            # then settle one read-through install via A.
            c._topic_bus.drain(timeout=10)
            assert a.get("k") == "v"
            h0 = b.cache_stats()["hits"]
            assert b.get("k") == "v"
            assert b.cache_stats()["hits"] >= h0 + 1
        finally:
            c.shutdown()

    def test_write_through_one_handle_invalidates_shared_entry(self):
        import time

        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            a = c.get_local_cached_map("lcm-coh")
            b = c.get_local_cached_map("lcm-coh")
            a.put("k", "v1")
            assert b.get("k") == "v1"
            b.put("k", "v2")  # writer maintains the shared store itself
            assert a.get("k") == "v2"
            a.remove("k")
            assert b.get("k") is None
        finally:
            c.shutdown()

    def test_generation_guard_blocks_stale_install(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            m = c.get_local_cached_map("lcm-gen")
            m.put("k", "v1")
            m.clear_local_cache()
            gen = m._hub.gen("lcm-gen")  # reader samples here...
            m.put("k", "v2")             # ...a write lands in between
            ok = m._hub.install_if(
                "lcm-gen", m._enc_key("k"), "v1", 64, gen
            )
            assert not ok               # the stale install is refused
            assert m.get("k") == "v2"
        finally:
            c.shutdown()

    def test_disabled_handle_neither_serves_nor_erases_peer_bound(self):
        # Review regression: a cache_size=0 handle must stay fully
        # opted out (read-through, no shared-store hits) and must NOT
        # pass its 0 into the shared tenant limits — the store reads
        # max_entries=0 as UNBOUNDED, erasing the enabled peer's bound.
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            a = c.get_local_cached_map("lcm-dis", cache_size=4)
            b = c.get_local_cached_map("lcm-dis", cache_size=0)
            for i in range(12):
                a.put(f"k{i}", i)
            c._topic_bus.drain(timeout=10)
            for i in range(12):
                a.get(f"k{i}")
            assert a.cached_size() <= 4  # peer bound survives b
            h0 = b.cache_stats()["hits"]
            assert b.get("k11") == 11    # reads through...
            assert b.cache_stats()["hits"] == h0  # ...never a shared hit
        finally:
            c.shutdown()

    def test_distinct_maps_keep_distinct_quotas(self):
        import redisson_tpu

        c = redisson_tpu.create(Config())
        try:
            small = c.get_local_cached_map("lcm-q-small",
                                           cache_max_bytes=2048)
            big = c.get_local_cached_map("lcm-q-big")
            for i in range(64):
                small.put(f"k{i}", "v" * 100)
                big.put(f"k{i}", "v" * 100)
            st = small.cache_stats()
            assert st["tenant_bytes"] <= 2048
            assert big.cache_stats()["tenant_bytes"] > 2048
        finally:
            c.shutdown()
