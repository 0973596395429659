"""Tiered sketch storage (ISSUE 14): the heat-based residency ladder.

Covers the heat tracker (fake clock — no DEBUG SLEEP-style waits), the
DEVICE ⇄ HOST ⇄ DISK transitions for every sketch kind (bit-exact
through the degraded-tier codecs), born-cold creation past the device
budget, the maintenance cycle (budget enforcement, admission-aware
promotion, host-bytes spill, quarantine reclaim), the RESP surface
(OBJECT FREQ/IDLETIME/ENCODING, CONFIG SET residency-*, INFO memory,
DEBUG RESIDENCY), chaos at the storage.spill/storage.load points, the
randomized differential soak (interleaved ops + forced transitions +
breaker degradation, every read equality-checked against the host
golden engine), mixed-tier snapshot/journal recovery, and the slow
kill -9 soak riding the crashchild harness with forced mid-stream
transitions.
"""

import os
import socket
import time

import numpy as np
import pytest

from redisson_tpu import chaos
from redisson_tpu.config import Config
from redisson_tpu.storage import DEVICE, DISK, HOST, HeatTracker


@pytest.fixture(autouse=True)
def _chaos_off():
    chaos.clear()
    chaos.reset_counts()
    yield
    chaos.clear()
    chaos.reset_counts()


def make_client(tmp_path=None, **tpu_kw):
    from redisson_tpu.client import RedissonTpuClient

    tpu_kw.setdefault("batch_window_us", 100)
    tpu_kw.setdefault("min_bucket", 64)
    if tmp_path is not None:
        tpu_kw.setdefault("residency_dir", str(tmp_path / "blobs"))
    cfg = Config().use_tpu_sketch(**tpu_kw)
    cfg.retry_attempts = 2
    cfg.retry_interval_ms = 5
    return RedissonTpuClient(cfg)


# -- heat tracker (fake clock) ------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_heat_decays_by_half_life():
    clk = _FakeClock()
    h = HeatTracker(half_life_s=10.0, clock=clk)
    for _ in range(8):
        h.touch("a")
    assert h.heat("a") == pytest.approx(8.0)
    clk.t += 10.0
    assert h.heat("a") == pytest.approx(4.0)
    clk.t += 20.0
    assert h.heat("a") == pytest.approx(1.0)
    assert h.idle_s("a") == pytest.approx(30.0)
    assert h.heat("never") == 0.0 and h.idle_s("never") == 0.0


def test_heat_rename_drop_and_prune():
    clk = _FakeClock()
    h = HeatTracker(half_life_s=10.0, clock=clk, max_entries=8)
    h.touch("x", 5)
    h.rename("x", "y")
    assert h.heat("y") == pytest.approx(5.0) and h.heat("x") == 0.0
    h.drop("y")
    assert h.heat("y") == 0.0
    # Prune folds away the coldest half once past the bound.
    for i in range(6):
        h.touch(f"hot{i}", 10)
    for i in range(9):
        h.touch(f"cold{i}", 1)
    assert len(h) <= 8
    assert h.heat("hot0") > 0.0  # hottest survive


# -- ladder transitions, all kinds, bit-exact ---------------------------------


def _truth(eng, name):
    return np.asarray(eng._host_row(eng.registry.lookup(name))).copy()


def test_full_ladder_every_kind_bit_exact(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(2000, 0.01)
        bf.add_all([1, 2, 3])
        bs = cl.get_bit_set("bs")
        bs.set_many([5, 700], True)
        cms = cl.get_count_min_sketch("cms")
        cms.try_init(4, 512)
        cms.add(7, 3)
        h = cl.get_hyper_log_log("hll")
        h.add_all(list(range(200)))
        names = ["bf", "bs", "cms", "hll"]
        before = {n: _truth(eng, n) for n in names}
        for n in names:
            assert rm.demote(n), n
            e = eng.registry.lookup(n)
            assert e.row < 0 and e.residency == HOST
            assert np.array_equal(_truth(eng, n), before[n]), n
        for n in names:
            assert rm.spill(n), n
            assert eng.registry.lookup(n).residency == DISK
            assert n not in eng._mirrors
        # Reads on the DISK tier load the blob and serve bit-identical.
        assert bf.contains(1) and not bf.contains(999999)
        assert bs.get(5) and not bs.get(6)
        assert cms.estimate(7) >= 3
        h.count()
        for n in names:
            if eng.registry.lookup(n).residency == DISK:
                assert rm.load(n), n
            assert np.array_equal(_truth(eng, n), before[n]), n
        for n in names:
            assert rm.promote(n), n
            e = eng.registry.lookup(n)
            assert e.row >= 0 and e.residency == DEVICE
            assert np.array_equal(_truth(eng, n), before[n]), n
        st = rm.stats()
        assert st["demotions"] == 4 and st["promotions"] == 4
        assert st["spills"] == 4 and st["loads"] == 4
        # Quarantined rows recycle after a later drain.
        assert rm.reclaim() == 4
    finally:
        cl.shutdown()


def test_writes_on_every_tier_are_acked_and_kept(tmp_path):
    """Demoted is not degraded: mutations land on whatever tier the
    object occupies and survive the full ladder round trip."""
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(2000, 0.01)
        bf.add(1)
        assert rm.demote("bf")
        bf.add(2)  # HOST-tier write
        assert eng.health.board.open_count() == 0
        assert not eng.health.any_degraded  # no breaker involved
        assert rm.spill("bf")
        bf.add(3)  # DISK-tier write: loads, then applies to the mirror
        assert eng.registry.lookup("bf").residency == HOST
        assert rm.promote("bf")
        for k in (1, 2, 3):
            assert bf.contains(k), k
        # Bitset size-class growth while demoted.
        bs = cl.get_bit_set("bs")
        bs.set(1, True)
        assert rm.demote("bs")
        bs.set(100_000, True)  # grows past the original class
        assert bs.get(100_000) and bs.get(1)
        assert rm.promote("bs")
        assert bs.get(100_000) and bs.get(1) and not bs.get(2)
    finally:
        cl.shutdown()


def test_demote_refuses_replicated_and_breaker_degraded(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add(1)
        # Breaker owns the kind: demote refuses (the mirror lifecycle
        # belongs to reconcile while degraded).
        orig = eng.health.degraded_kind
        try:
            eng.health.degraded_kind = lambda kind: kind == "bloom"
            assert not rm.demote("bf")
        finally:
            eng.health.degraded_kind = orig
        assert rm.demote("bf")
        assert rm.promote("bf")
    finally:
        cl.shutdown()


# -- born-cold creation + maintenance ----------------------------------------


def test_born_cold_past_budget_and_heat_promotion(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        seed = cl.get_bloom_filter("warm")
        seed.try_init(500, 0.01)
        seed.add(1)
        rm.set_budget(device_rows=rm.device_rows_used())
        cold = cl.get_bloom_filter("cold")
        cold.try_init(500, 0.01)
        e = eng.registry.lookup("cold")
        assert e.row < 0 and e.residency == HOST  # born cold, no row
        cold.add(42)
        assert cold.contains(42) and not cold.contains(43)
        # Heat it: maintenance swaps it in against the colder tenant.
        for _ in range(40):
            cold.contains(42)
        out = rm.maintain()
        assert out["promoted"] >= 1
        assert eng.registry.lookup("cold").row >= 0
        assert eng.registry.lookup("warm").row < 0  # the cold victim
        assert cold.contains(42) and seed.contains(1)
    finally:
        cl.shutdown()


def test_maintenance_budget_and_spill_and_admission(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        for i in range(6):
            bf = cl.get_bloom_filter(f"t{i}")
            bf.try_init(500, 0.01)
            bf.add(i)
        used = rm.device_rows_used()
        rm.set_budget(device_rows=max(1, used - 3))
        out = rm.maintain()
        assert out["demoted"] >= 3
        # Demoted rows sit QUARANTINED (still counted used) until a
        # later cycle's drain reclaims them — the no-stale-reads half
        # of the transition protocol.
        assert rm.reclaim() >= 3
        assert rm.device_rows_used() <= rm.device_rows
        # Host-bytes cap: everything demoted spills.
        rm.set_budget(max_host_bytes=1)
        out = rm.maintain()
        assert out["spilled"] >= 1
        assert rm.disk_objects() >= 1
        # Admission-blocked: promotion is deferred, never stormed.
        rm.promote_heat = 0.0
        blocked = {"v": True}
        rm._admission_blocked = lambda: blocked["v"]
        out = rm.maintain()
        assert out["promoted"] == 0
        blocked["v"] = False
    finally:
        cl.shutdown()


# -- chaos at the storage points ----------------------------------------------


def test_chaos_spill_and_load_fail_clean(tmp_path):
    from redisson_tpu.chaos import FaultInjected

    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add_all([1, 2])
        want = _truth(eng, "bf")
        assert rm.demote("bf")
        chaos.inject("storage.spill", kind="error", rate=1.0, seed=7)
        with pytest.raises(FaultInjected):
            rm.spill("bf")
        # Entry intact on the HOST tier, state unharmed.
        assert eng.registry.lookup("bf").residency == HOST
        assert np.array_equal(_truth(eng, "bf"), want)
        chaos.clear()
        assert rm.spill("bf")
        chaos.inject("storage.load", kind="error", rate=1.0, seed=7)
        with pytest.raises(FaultInjected):
            rm.load("bf")
        assert eng.registry.lookup("bf").residency == DISK
        chaos.clear()
        assert rm.load("bf")
        assert np.array_equal(_truth(eng, "bf"), want)
    finally:
        cl.shutdown()


def test_torn_blob_refuses_instead_of_serving_garbage(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add(1)
        assert rm.demote("bf") and rm.spill("bf")
        info = rm.disk_index()["bf"]
        path = os.path.join(rm.directory, info["file"])
        blob = open(path, "rb").read()
        mid = len(blob) // 2
        open(path, "wb").write(
            blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]
        )
        with pytest.raises(ValueError, match="CRC"):
            rm.load("bf")
    finally:
        cl.shutdown()


# -- identity ops across tiers ------------------------------------------------


def test_delete_rename_expire_drop_tier_state(tmp_path):
    cl = make_client(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("a")
        bf.try_init(500, 0.01)
        bf.add(1)
        assert rm.demote("a") and rm.spill("a")
        assert eng.rename("a", "b")
        assert rm.disk_index().get("b") and not rm.disk_index().get("a")
        bf2 = cl.get_bloom_filter("b")
        assert bf2.contains(1)  # loaded from the renamed blob
        assert eng.delete("b")
        assert rm.disk_index() == {} and rm.host_objects() == 0
        assert "b" not in eng._mirrors
        # Expiry reaps tier state too.
        bf3 = cl.get_bloom_filter("c")
        bf3.try_init(500, 0.01)
        bf3.add(1)
        assert rm.demote("c")
        eng.expire_at("c", time.time() - 1.0)
        assert not eng.exists("c")
        assert "c" not in eng._mirrors and rm.host_objects() == 0
    finally:
        cl.shutdown()


# -- RESP surface -------------------------------------------------------------


def _resp(cl):
    from redisson_tpu.serve.resp import RespServer

    srv = RespServer(cl)
    s = socket.create_connection((srv.host, srv.port))

    def cmd(*args):
        from redisson_tpu.serve import wireutil

        return wireutil.exchange(
            s, [[str(a).encode() for a in args]]
        )[0]

    return srv, s, cmd


def test_object_introspection_rides_the_heat_tracker(tmp_path):
    cl = make_client(tmp_path)
    srv = s = None
    try:
        eng = cl._engine
        rm = eng.residency
        clk = _FakeClock()
        rm.heat = HeatTracker(half_life_s=10.0, clock=clk)
        srv, s, cmd = _resp(cl)
        cmd("BF.RESERVE", "bf", "0.01", "1000")
        for _ in range(6):
            cmd("BF.ADD", "bf", "1")
        assert cmd("OBJECT", "ENCODING", "bf") == b"device"
        # ISSUE 16 satellite: FREQ reports the redis 0-255 LOGARITHMIC
        # LFU scale — min(255, round(32*log2(1+h))) over the decayed
        # heat h.  ~7 touches -> h≈7 -> 96; three half-lives later
        # h≈0.9 -> ~30 (still >0: log scale compresses, it never lies
        # that a warm key is stone cold).
        hot_freq = cmd("OBJECT", "FREQ", "bf")
        assert 64 <= hot_freq <= 255
        clk.t += 30.0  # fake clock, no DEBUG SLEEP
        assert cmd("OBJECT", "IDLETIME", "bf") == 30
        cold_freq = cmd("OBJECT", "FREQ", "bf")
        assert cold_freq < hot_freq
        assert cold_freq <= 32
        assert cmd("DEBUG", "RESIDENCY", "DEMOTE", "bf") == 1
        assert cmd("OBJECT", "ENCODING", "bf") == b"host"
        assert cmd("DEBUG", "RESIDENCY", "SPILL", "bf") == 1
        assert cmd("OBJECT", "ENCODING", "bf") == b"disk"
        assert cmd("DEBUG", "RESIDENCY", "PROMOTE", "bf") == 1
        assert cmd("OBJECT", "ENCODING", "bf") == b"device"
        # Grid kinds keep the classic encodings.
        cmd("XADD", "st", "*", "f", "v")
        assert cmd("OBJECT", "ENCODING", "st") == b"stream"
    finally:
        if s is not None:
            s.close()
            srv.close()
        cl.shutdown()


def test_resp_config_and_info_surface(tmp_path):
    from redisson_tpu.serve.wireutil import ReplyError

    cl = make_client(tmp_path)
    srv = s = None
    try:
        srv, s, cmd = _resp(cl)
        got = dict(zip(*[iter(cmd("CONFIG", "GET", "residency-*"))] * 2))
        assert got[b"residency-device-rows"] == b"0"
        assert cmd("CONFIG", "SET", "residency-device-rows", "8") == b"OK"
        assert cl._engine.residency.device_rows == 8
        assert cl._engine.residency._thread is not None  # budget armed it
        bad = cmd("CONFIG", "SET", "residency-max-host-bytes", "-3")
        assert isinstance(bad, ReplyError)
        bad = cmd("CONFIG", "SET", "residency-device-rows", "x")
        assert isinstance(bad, ReplyError)
        info = cmd("INFO", "memory").decode()
        for line in ("residency_device_rows_budget:8",
                     "residency_host_objects:", "residency_disk_bytes:",
                     "residency_promotions:"):
            assert line in info, line
        tick = cmd("DEBUG", "RESIDENCY", "TICK")
        assert any(r.startswith(b"reclaimed") for r in tick)
    finally:
        if s is not None:
            s.close()
            srv.close()
        cl.shutdown()


def test_object_is_shed_exempt():
    from redisson_tpu.serve.resp import _SHED_EXEMPT

    assert "OBJECT" in _SHED_EXEMPT


# -- near-cache reach satellite (stream/geo scalars) --------------------------


def test_stream_and_geo_scalars_ride_the_near_cache(tmp_path):
    cl = make_client(tmp_path)
    try:
        nc = cl._engine.nearcache
        st = cl.get_stream("s1")
        st.add({b"f": b"v"})
        assert st.size() == 1  # miss, installs
        base_hits = nc.hits
        assert st.size() == 1  # hit
        assert nc.hits == base_hits + 1
        st.add({b"f": b"v2"})  # bump retires the cached scalar
        assert st.size() == 2
        st.remove(st.last_id())
        assert st.size() == 1
        geo = cl.get_geo("g1")
        geo.add(13.361389, 38.115556, b"palermo")
        geo.add(15.087269, 37.502669, b"catania")
        d1 = geo.dist(b"palermo", b"catania", "km")
        hits0 = nc.hits
        assert geo.dist(b"palermo", b"catania", "km") == d1  # hit
        assert nc.hits == hits0 + 1
        geo.add(15.0, 37.0, b"catania")  # move: epoch bump
        d2 = geo.dist(b"palermo", b"catania", "km")
        assert d2 != d1
        p = geo.pos(b"palermo")
        p[b"palermo"] = (0.0, 0.0)  # caller mutation must not poison
        assert geo.pos(b"palermo")[b"palermo"] != (0.0, 0.0)
        # Store-level delete invalidates the grid tenant.
        st.delete()
        assert st.size() == 0
        # TTL semantics survive the cache (review finding): cached
        # scalars carry the key's deadline — expiry is observed at
        # READ time, not at the next sweep; EXPIRE/PERSIST on a
        # cached key retire the stale deadline through the store hook.
        st2 = cl.get_stream("s2")
        st2.add({b"f": b"v"})
        assert st2.size() == 1      # installs (no TTL yet)
        st2.expire(0.05)            # EXPIRE invalidates the cached pair
        assert st2.size() == 1      # re-installs WITH the deadline
        time.sleep(0.08)
        assert st2.size() == 0      # deadline observed by the cached read
        assert not st2.is_exists()
    finally:
        cl.shutdown()


# -- randomized differential soak --------------------------------------------


def _flap(fn, attempts=8):
    """Ride out breaker flaps (the test_nearcache soak idiom): a
    degraded-window op may fail typed while the breaker opens — the
    chaos error fires PRE-mutation, so a failed op never applied and a
    retry applies exactly once."""
    for _ in range(attempts - 1):
        try:
            return fn()
        except Exception:
            time.sleep(0.05)
    return fn()


def test_differential_soak_vs_golden_with_forced_transitions(tmp_path):
    """The acceptance soak: interleaved ops + forced promote / demote /
    spill / load + breaker degradation on the SAME objects, every read
    equality-checked against the host golden engine — zero stale reads,
    zero acked-write loss."""
    import random

    import redisson_tpu

    rng = random.Random(20260804)
    gold = redisson_tpu.create(Config())
    cl = make_client(
        tmp_path, breaker_failure_threshold=2, breaker_open_ms=400
    )
    try:
        eng = cl._engine
        rm = eng.residency
        tb, gb = (x.get_bloom_filter("soak-bf") for x in (cl, gold))
        for h in (tb, gb):
            h.try_init(20_000, 0.01)
        tbs, gbs = (x.get_bit_set("soak-bs") for x in (cl, gold))
        tcm, gcm = (
            x.get_count_min_sketch("soak-cms") for x in (cl, gold)
        )
        for h in (tcm, gcm):
            h.try_init(4, 512)
        th, gh = (x.get_hyper_log_log("soak-hll") for x in (cl, gold))
        names = ("soak-bf", "soak-bs", "soak-cms", "soak-hll")
        K = 2000
        degraded_until = 0.0
        for step in range(300):
            roll = rng.random()
            if roll < 0.12:
                n = names[rng.randrange(4)]
                verb = rng.randrange(4)
                if verb == 0:
                    rm.demote(n)
                elif verb == 1:
                    rm.promote(n)
                elif verb == 2:
                    rm.demote(n)
                    rm.spill(n)
                else:
                    rm.load(n)
            elif roll < 0.15 and not degraded_until:
                # Breaker degradation on the same objects (demoted is
                # NOT degraded — the soak exercises both on one
                # keyspace).
                chaos.inject(
                    "dispatch.bloom_mixed_keys_runs", kind="error", rate=1.0,
                    seed=step,
                )
                degraded_until = time.monotonic() + 0.2
            elif roll < 0.40:
                ks = [rng.randrange(K) for _ in range(6)]
                _flap(lambda: tb.add_all(ks))
                gb.add_all(ks)
            elif roll < 0.55:
                idx = [rng.randrange(4096) for _ in range(4)]
                val = rng.random() < 0.8
                _flap(lambda: tbs.set_many(idx, val))
                gbs.set_many(idx, val)
            elif roll < 0.65:
                ks = [rng.randrange(K) for _ in range(4)]
                w = [1 + rng.randrange(4) for _ in range(4)]
                _flap(lambda: tcm.add_all(ks, w))
                gcm.add_all(ks, w)
            elif roll < 0.72:
                ks = [rng.randrange(K) for _ in range(8)]
                _flap(lambda: th.add_all(ks))
                gh.add_all(ks)
            else:
                ks = [rng.randrange(K) for _ in range(8)]
                got = _flap(lambda: tb.contains_each(ks))
                want = gb.contains_each(ks)
                assert np.array_equal(
                    np.asarray(got, bool), np.asarray(want, bool)
                ), f"step {step}: stale bloom read"
                idx = [rng.randrange(4096) for _ in range(4)]
                got = _flap(lambda: tbs.get_many(idx))
                want = gbs.get_many(idx)
                assert np.array_equal(
                    np.asarray(got, bool), np.asarray(want, bool)
                ), f"step {step}: stale bitset read"
                est_t = _flap(lambda: tcm.estimate_all(ks))
                est_g = gcm.estimate_all(ks)
                assert np.array_equal(
                    np.asarray(est_t, np.int64),
                    np.asarray(est_g, np.int64),
                ), f"step {step}: stale cms read"
                assert _flap(lambda: th.count()) == gh.count(), (
                    f"step {step}: stale hll count"
                )
            if degraded_until and time.monotonic() > degraded_until:
                chaos.clear()
                degraded_until = 0.0
        chaos.clear()
        # Breaker may still be open from the last window: wait it out
        # so the final comparison sees reconciled state, then compare
        # the WHOLE keyspace (zero acked-write loss).
        deadline = time.monotonic() + 8.0
        while eng.health.any_degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        for n in names:
            rm.load(n)
            rm.promote(n)
        ks = list(range(K))
        assert np.array_equal(
            np.asarray(_flap(lambda: tb.contains_each(ks)), bool),
            np.asarray(gb.contains_each(ks), bool),
        )
        idx = list(range(4096))
        assert np.array_equal(
            np.asarray(_flap(lambda: tbs.get_many(idx)), bool),
            np.asarray(gbs.get_many(idx), bool),
        )
        assert np.array_equal(
            np.asarray(_flap(lambda: tcm.estimate_all(ks)), np.int64),
            np.asarray(gcm.estimate_all(ks), np.int64),
        )
        st = rm.stats()
        assert st["demotions"] > 0 and st["promotions"] > 0
        assert st["spills"] > 0
    finally:
        chaos.clear()
        cl.shutdown()
        gold.shutdown()


# -- snapshot / recovery across tiers -----------------------------------------


def _mk_durable(tmp_path):
    from redisson_tpu.client import RedissonTpuClient

    cfg = Config().use_tpu_sketch(
        min_bucket=64, batch_window_us=100,
        residency_dir=str(tmp_path / "blobs"),
    )
    cfg.snapshot_dir = str(tmp_path / "snap")
    cfg.journal_dir = str(tmp_path / "journal")
    cfg.journal_fsync = "always"
    cfg.retry_attempts = 2
    cfg.retry_interval_ms = 5
    return RedissonTpuClient(cfg)


def test_mixed_tier_recovery_bit_identical(tmp_path):
    """A DEVICE + HOST + DISK population snapshots, takes post-snapshot
    journaled writes on every tier, and a fresh engine recovers every
    object bit-identically — the DISK sketch restoring as DISK and
    loading without a device write."""
    cl = _mk_durable(tmp_path)
    eng = cl._engine
    rm = eng.residency
    for n in ("dev", "host", "disk", "disk-idle"):
        bf = cl.get_bloom_filter(n)
        bf.try_init(1000, 0.01)
        bf.add_all([1, 2])
    assert rm.demote("host")
    assert rm.demote("disk") and rm.spill("disk")
    assert rm.demote("disk-idle") and rm.spill("disk-idle")
    eng.snapshot(str(tmp_path / "snap"))
    cl.get_bloom_filter("dev").add(10)
    cl.get_bloom_filter("host").add(20)
    cl.get_bloom_filter("disk").add(30)  # loads → HOST, journaled
    truth = {
        n: _truth(eng, n) for n in ("dev", "host", "disk", "disk-idle")
    }
    # Abandon without shutdown (a clean shutdown would re-snapshot).
    j = eng.journal
    eng.journal = None
    j.close()
    eng.config.snapshot_dir = None
    cl.config.snapshot_dir = None
    cl.shutdown()

    cl2 = _mk_durable(tmp_path)
    try:
        eng2 = cl2._engine
        e_idle = eng2.registry.lookup("disk-idle")
        # Untouched-by-tail DISK sketch restores ON the disk tier.
        assert e_idle.residency == DISK and e_idle.row < 0
        for n, want in truth.items():
            got = _truth(eng2, n)
            assert np.array_equal(got, want), n
        assert cl2.get_bloom_filter("disk").contains(30)
        assert cl2.get_bloom_filter("host").contains(20)
        assert cl2.get_bloom_filter("dev").contains(10)
    finally:
        cl2.shutdown()


def test_blob_gc_never_deletes_snapshot_referenced_files(tmp_path):
    cl = _mk_durable(tmp_path)
    try:
        eng = cl._engine
        rm = eng.residency
        bf = cl.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add(1)
        assert rm.demote("bf") and rm.spill("bf")
        blob1 = rm.disk_index()["bf"]["file"]
        eng.snapshot(str(tmp_path / "snap"))  # snapshot references blob1
        # Load + re-spill: blob1 retires but may NOT be GC'd (the
        # latest snapshot still names it; a crash would restore from
        # it and replay the tail).
        assert rm.load("bf")
        bf.add(2)
        assert rm.spill("bf")
        blob2 = rm.disk_index()["bf"]["file"]
        assert blob2 != blob1
        rm.gc_blobs()
        assert os.path.exists(os.path.join(rm.directory, blob1))
        # After the NEXT snapshot (referencing blob2), blob1 may go.
        eng.snapshot(str(tmp_path / "snap"))
        rm.gc_blobs()
        assert not os.path.exists(os.path.join(rm.directory, blob1))
        assert os.path.exists(os.path.join(rm.directory, blob2))
    finally:
        cl.shutdown()


# -- kill -9 soak with forced mid-stream transitions (slow) -------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_kill9_residency_soak_recovers_bit_identical(tmp_path):
    """The tiered-soak CI job's core: the crashchild applies a
    deterministic op stream while FORCING demote/spill/promote cycles
    every few ops; a SIGKILL lands mid-stream (possibly mid-demotion
    or mid-spill), and recovery must restore a state bit-identical to
    a golden engine fed an acked-covering prefix — across whatever
    tier each object died in."""
    import random
    import signal
    import subprocess
    import sys

    from redisson_tpu.chaos import crashchild

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seed = random.randrange(1 << 30)
    ops = 240
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "redisson_tpu.chaos.crashchild",
            "--dir", str(tmp_path), "--fsync", "always",
            "--seed", str(seed), "--ops", str(ops),
            "--residency-every", "7",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        cwd=repo, env=env, text=True,
    )
    acked = {}
    first_ack = None
    finished = False
    try:
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("ACK "):
                _t, idx, ts = line.split()
                acked[int(idx)] = float(ts)
                if first_ack is None:
                    first_ack = time.monotonic()
                if time.monotonic() - first_ack >= 0.5:
                    os.kill(proc.pid, signal.SIGKILL)
                    break
            elif line == "DONE":
                finished = True
                os.kill(proc.pid, signal.SIGKILL)
                break
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("ACK ") and len(line.split()) == 3:
                _t, idx, ts = line.split()
                acked[int(idx)] = float(ts)
            elif line == "DONE":
                finished = True
    finally:
        proc.stdout.close()
        proc.wait(timeout=30)
    assert acked, "child never acked a write"
    max_acked = max(acked)

    # Recover (tiers restore from the snapshot-less journal lineage —
    # residency state is perf state; the JOURNAL carries every acked
    # write whatever tier served it).
    rec = crashchild.build_client(str(tmp_path), "always", residency=True)
    eng = rec._engine
    eng._drain()
    rows = {
        e.name: np.asarray(eng._host_row(e)).copy()
        for e in eng.registry.entries()
    }
    eng.config.snapshot_dir = None
    rec.config.snapshot_dir = None
    j = eng.journal
    if j is not None:
        eng.journal = None
        j.close()
    rec.shutdown()
    assert rows, "recovery produced an empty keyspace"

    # Golden match: a plain engine (no residency) fed the same stream.
    class _Matched(Exception):
        def __init__(self, r):
            self.r = r

    import redisson_tpu as _rt
    from redisson_tpu.codecs import LongCodec

    gcfg = Config().set_codec(LongCodec()).use_tpu_sketch(min_bucket=64)
    golden_cl = _rt.create(gcfg)
    geng = golden_cl._engine

    def same():
        geng._drain()
        got = {
            e.name: np.asarray(geng._host_row(e))
            for e in geng.registry.entries()
        }
        if set(got) != set(rows):
            return False
        return all(np.array_equal(got[n], rows[n]) for n in got)

    lower = max_acked + 1
    matched = None

    def ack(i):
        nonlocal matched
        if i + 1 >= lower and matched is None and same():
            raise _Matched(i + 1)

    try:
        crashchild.apply_ops(golden_cl, seed, ops, ack=ack)
        if matched is None and same():
            matched = ops
    except _Matched as mm:
        matched = mm.r
    finally:
        golden_cl.shutdown()
    assert matched is not None, (
        f"recovered state matches no acked-covering prefix "
        f"(max_acked={max_acked}, finished={finished})"
    )
