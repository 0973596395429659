"""Crash-safe durability tier (ISSUE 10): op journal, group commit,
point-in-time recovery, snapshot coordination, and the RESP
persistence surface.

The crash harness proper (subprocess kill -9 soak) lives in
tests/test_crash_recovery.py (slow-marked); these are the
deterministic, tier-1-speed pieces.
"""

import os
import struct
import time
import zlib

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config, chaos
from redisson_tpu.codecs import LongCodec
from redisson_tpu.durability.journal import (
    JournalError,
    OpJournal,
    decode_record,
    encode_record,
)
from redisson_tpu.utils import hashing


def make_cfg(tmp_path, fsync="always", journal=True, snap=True, **kw):
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        min_bucket=64, **kw
    )
    if snap:
        cfg.snapshot_dir = str(tmp_path / "snap")
    if journal:
        cfg.journal_dir = str(tmp_path / "journal")
        cfg.journal_fsync = fsync
    return cfg


def make_client(tmp_path, **kw):
    return redisson_tpu.create(make_cfg(tmp_path, **kw))


def crash(client):
    """Tear a client down WITHOUT the clean-shutdown snapshot, so the
    journal tail is what recovery has to work with.  (The journal's
    own close flushes what a crashed OS would eventually have written;
    torn-tail cases are driven explicitly via chaos/truncation.)"""
    eng = client._engine
    j = eng.journal
    if j is not None:
        eng.journal = None
        j.close()
    eng.config.snapshot_dir = None
    client.config.snapshot_dir = None
    client.shutdown()


def engine_rows(eng):
    eng._drain()
    out = {}
    for e in eng.registry.entries():
        out[e.name] = np.asarray(
            eng.executor.read_row(e.pool, e.row)
        ).copy()
    return out


@pytest.fixture(autouse=True)
def _chaos_clean():
    chaos.clear()
    yield
    chaos.clear()


# -- record codec -------------------------------------------------------------


class TestRecordCodec:
    def test_round_trip(self):
        rec = {
            "op": "bloom.add",
            "name": "x",
            "h1": np.arange(5, dtype=np.uint64),
            "h2": np.arange(5, dtype=np.uint32) * 7,
            "blocks": np.arange(12, dtype=np.uint32).reshape(3, 4),
            "flag": True,
            "n": 42,
            "f": 0.5,
            "names": ["a", "b"],
            "blob": b"\x00\x01\xff",
        }
        out = decode_record(encode_record(rec))
        assert out["op"] == "bloom.add" and out["name"] == "x"
        assert out["flag"] is True and out["n"] == 42 and out["f"] == 0.5
        assert out["names"] == ["a", "b"]
        np.testing.assert_array_equal(out["h1"], rec["h1"])
        assert out["h1"].dtype == np.uint64
        np.testing.assert_array_equal(out["blocks"], rec["blocks"])
        assert out["blocks"].shape == (3, 4)
        assert np.asarray(out["blob"], np.uint8).tobytes() == rec["blob"]

    def test_malformed_payload_rejected(self):
        good = encode_record({"op": "x", "name": "y"})
        with pytest.raises(ValueError):
            decode_record(good[:2])
        # Header length overruns the payload.
        bad = struct.pack("<I", 1 << 20) + good[4:]
        with pytest.raises(ValueError):
            decode_record(bad)

    def test_declared_array_overrun_rejected(self):
        rec = {"op": "x", "name": "y", "a": np.arange(8, dtype=np.uint32)}
        enc = encode_record(rec)
        with pytest.raises(ValueError):
            decode_record(enc[:-8])  # truncated array bytes


# -- journal core (no engine) -------------------------------------------------


class TestJournalCore:
    def test_always_ack_is_durable(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="always")
        seq = j.append({"op": "x", "name": "a", "v": 1})
        assert j.wait_durable(seq, timeout=10.0)
        assert j.is_durable(seq)
        assert j.stats()["fsyncs"] >= 1
        j.close()

    def test_everysec_durable_within_window(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="everysec")
        seq = j.append({"op": "x", "name": "a"})
        deadline = time.monotonic() + 5.0
        while not j.is_durable(seq) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert j.is_durable(seq), "everysec never fsynced"
        j.close()

    def test_no_policy_fence_forces_fsync(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="no")
        seq = j.append({"op": "x", "name": "a"})
        # The explicit fence is the one durability promise 'no' makes.
        assert j.wait_durable(seq, timeout=10.0)
        assert j.stats()["fsyncs"] >= 1
        j.close()

    def test_rotation_and_replay_order(self, tmp_path):
        j = OpJournal(
            str(tmp_path), fsync_policy="always",
            max_segment_bytes=1 << 12,
        )
        for i in range(200):
            j.append({"op": "x", "name": "a", "i": i})
        j.wait_durable(timeout=30.0)
        st = j.stats()
        assert st["segments"] > 1, "tiny segments must rotate"
        recs = list(j.records_after(0))
        assert len(recs) == 200
        assert [r["i"] for _s, r in recs] == list(range(200))
        assert [s for s, _r in recs] == list(range(1, 201))
        j.close()

    def test_reopen_continues_sequence(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="always")
        for i in range(5):
            j.append({"op": "x", "name": "a", "i": i})
        j.wait_durable(timeout=10.0)
        j.close()
        j2 = OpJournal(str(tmp_path), fsync_policy="always")
        assert j2.cut() == 5
        s = j2.append({"op": "x", "name": "a", "i": 5})
        assert s == 6
        j2.wait_durable(timeout=10.0)
        assert len(list(j2.records_after(0))) == 6
        j2.close()

    def test_torn_tail_truncates_not_corrupts(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="always")
        for i in range(10):
            j.append({"op": "x", "name": "a", "i": i})
        j.wait_durable(timeout=10.0)
        seg = j.stats()
        j.close()
        assert seg["segments"] == 1
        path = [
            os.path.join(str(tmp_path), fn)
            for fn in os.listdir(str(tmp_path)) if fn.endswith(".rtj")
        ][0]
        # Simulate a crash mid-write: half a frame of garbage.
        payload = encode_record({"op": "x", "name": "a", "i": 99})
        frame = struct.pack(
            "<II", len(payload), zlib.crc32(payload)
        ) + payload
        with open(path, "ab") as f:
            f.write(frame[: len(frame) // 2])
        pre = os.path.getsize(path)
        j2 = OpJournal(str(tmp_path), fsync_policy="always")
        recs = list(j2.records_after(0))
        assert len(recs) == 10, "torn tail must truncate to the prefix"
        assert [r["i"] for _s, r in recs] == list(range(10))
        assert os.path.getsize(path) < pre, "tail not truncated"
        j2.close()

    def test_corrupt_mid_segment_drops_later_segments(self, tmp_path):
        j = OpJournal(
            str(tmp_path), fsync_policy="always",
            max_segment_bytes=1 << 12,
        )
        for i in range(200):
            # Per-record durability keeps batches small, so the tiny
            # segment bound rotates many times.
            j.wait_durable(j.append({"op": "x", "name": "a", "i": i}),
                           timeout=10.0)
        j.close()
        segs = sorted(
            fn for fn in os.listdir(str(tmp_path)) if fn.endswith(".rtj")
        )
        assert len(segs) > 2
        # Flip a byte inside the FIRST segment's frame area: everything
        # from that record on — later segments included — is untrusted.
        victim = os.path.join(str(tmp_path), segs[0])
        with open(victim, "r+b") as f:
            f.seek(40)
            b = f.read(1)
            f.seek(40)
            f.write(bytes([b[0] ^ 0xFF]))
        j2 = OpJournal(str(tmp_path), fsync_policy="always")
        recs = list(j2.records_after(0))
        assert len(recs) < 200
        # The surviving prefix is contiguous from seq 1.
        assert [s for s, _ in recs] == list(range(1, len(recs) + 1))
        remaining = [
            fn for fn in os.listdir(str(tmp_path)) if fn.endswith(".rtj")
        ]
        assert len(remaining) <= 2  # truncated head + fresh tail segment
        j2.close()

    def test_mark_snapshot_retires_segments(self, tmp_path):
        j = OpJournal(
            str(tmp_path), fsync_policy="always",
            max_segment_bytes=1 << 12,
        )
        for i in range(200):
            j.append({"op": "x", "name": "a", "i": i})
        j.wait_durable(timeout=30.0)
        before = j.stats()["segments"]
        cut = j.cut()
        retired = j.mark_snapshot(cut)
        assert retired > 0 and before > j.stats()["segments"] - 1
        assert list(j.records_after(cut)) == []
        # Post-truncation appends still replay correctly.
        j.append({"op": "x", "name": "a", "i": 999})
        j.wait_durable(timeout=10.0)
        tail = list(j.records_after(cut))
        assert len(tail) == 1 and tail[0][1]["i"] == 999
        j.close()

    def test_torn_tail_chaos_point_breaks_then_recovers(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="always")
        j.append({"op": "x", "name": "a", "i": 0})
        j.wait_durable(timeout=10.0)
        chaos.inject("journal.torn_tail", kind="error", rate=1.0)
        seq = j.append({"op": "x", "name": "a", "i": 1})
        with pytest.raises(JournalError):
            j.wait_durable(seq, timeout=10.0)
        with pytest.raises(JournalError):
            j.append({"op": "x", "name": "a", "i": 2})
        chaos.clear()
        j.close()
        # Recovery: the half-written frame truncates; record 0 intact.
        j2 = OpJournal(str(tmp_path), fsync_policy="always")
        recs = list(j2.records_after(0))
        assert [r["i"] for _s, r in recs] == [0]
        j2.close()

    def test_fsync_error_breaks_journal(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="always")
        chaos.inject("journal.fsync", kind="error", rate=1.0)
        seq = j.append({"op": "x", "name": "a"})
        with pytest.raises(JournalError):
            j.wait_durable(seq, timeout=10.0)
        chaos.clear()
        j.close()

    def test_lag_estimate_only_under_always(self, tmp_path):
        j = OpJournal(str(tmp_path), fsync_policy="everysec")
        assert j.lag_s() == 0.0
        j.set_policy("always")
        assert j.policy == "always"
        j.close()


# -- engine-level recovery ----------------------------------------------------


class TestEngineRecovery:
    def _fill(self, client, n=40):
        bf = client.get_bloom_filter("bf")
        bf.try_init(10_000, 0.01)
        for i in range(n):
            bf.add(i)
        h = client.get_hyper_log_log("hll")
        h.add_all(list(range(100)))
        bs = client.get_bit_set("bs")
        bs.set(5)
        bs.set(77)
        bs.flip(5)
        cms = client.get_count_min_sketch("cms")
        cms.try_init(4, 256)
        for i in range(10):
            cms.add(i, 3)

    def test_full_replay_without_snapshot(self, tmp_path):
        c1 = make_client(tmp_path, snap=False)
        self._fill(c1)
        want = engine_rows(c1._engine)
        crash(c1)
        c2 = make_client(tmp_path, snap=False)
        got = engine_rows(c2._engine)
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])
        assert c2._engine.obs.journal_replayed.get(()) > 0
        bf = c2.get_bloom_filter("bf")
        assert bf.contains(7) and not bf.contains(987654)
        crash(c2)

    def test_snapshot_plus_tail_replay(self, tmp_path):
        c1 = make_client(tmp_path)
        self._fill(c1)
        pre_cut = c1._engine.journal.cut()
        c1._engine.snapshot(c1.config.snapshot_dir)
        # The snapshot retired the covered records.
        assert list(c1._engine.journal.records_after(0)) == []
        # Tail ops after the snapshot.
        bf = c1.get_bloom_filter("bf")
        for i in range(1000, 1020):
            bf.add(i)
        cms = c1.get_count_min_sketch("cms")
        cms.add(999, 7)
        want = engine_rows(c1._engine)
        tail = len(list(c1._engine.journal.records_after(0)))
        assert tail > 0
        crash(c1)
        c2 = make_client(tmp_path)
        assert c2._engine._restored_journal_seq >= pre_cut
        got = engine_rows(c2._engine)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], err_msg=name)
        bf2 = c2.get_bloom_filter("bf")
        assert bf2.contains(1010) and bf2.contains(3)
        assert c2.get_count_min_sketch("cms").estimate(999) >= 7
        crash(c2)

    def test_clean_shutdown_replays_nothing(self, tmp_path):
        c1 = make_client(tmp_path)
        self._fill(c1, n=10)
        c1.shutdown()  # final snapshot covers + retires the journal
        c2 = make_client(tmp_path)
        assert c2._engine.obs.journal_replayed.get(()) == 0
        assert c2.get_bloom_filter("bf").contains(3)
        crash(c2)

    def test_structural_ops_replay(self, tmp_path):
        c1 = make_client(tmp_path, snap=False)
        bf = c1.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add(1)
        dump = c1._engine.dump("bf")
        c1._engine.delete("bf")
        h = c1.get_hyper_log_log("h1")
        h.add(1)
        c1._engine.rename("h1", "h2")
        c1._engine.restore("bf-restored", dump)
        want = engine_rows(c1._engine)
        crash(c1)
        c2 = make_client(tmp_path, snap=False)
        got = engine_rows(c2._engine)
        assert set(got) == set(want) == {"h2", "bf-restored"}
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])
        assert c2.get_bloom_filter("bf-restored").contains(1)
        crash(c2)

    def test_merge_ops_replay(self, tmp_path):
        c1 = make_client(tmp_path, snap=False)
        a = c1.get_hyper_log_log("a")
        a.add_all(list(range(50)))
        b = c1.get_hyper_log_log("b")
        b.add_all(list(range(40, 90)))
        a.merge_with("b")
        ca = c1.get_count_min_sketch("ca")
        ca.try_init(4, 256)
        cb = c1.get_count_min_sketch("cb")
        cb.try_init(4, 256)
        ca.add(1, 5)
        cb.add(1, 9)
        ca.merge("cb")
        bs1 = c1.get_bit_set("x")
        bs1.set_many([1, 5, 9])
        bs2 = c1.get_bit_set("y")
        bs2.set_many([5, 6])
        c1._engine.bitset_bitop("z", ["x", "y"], "and")
        want = engine_rows(c1._engine)
        crash(c1)
        c2 = make_client(tmp_path, snap=False)
        got = engine_rows(c2._engine)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], err_msg=name)
        assert c2.get_count_min_sketch("ca").estimate(1) >= 14
        assert list(np.nonzero(
            c2.get_bit_set("z").as_bit_array()
        )[0]) == [5]
        crash(c2)

    def test_direct_add_journals_keyed_record(self, tmp_path):
        """A direct engine (no coalescer) journals its adds as raw key
        material (bloom.addk), like the coalesced engine; replay restores
        the same membership and bits."""
        c1 = make_client(tmp_path, snap=False, coalesce=False)
        bf = c1.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add_all(list(range(50)))
        bf.add(7)
        ops = [rec["op"] for _, rec in c1._engine.journal.records_after(0)]
        assert ops == ["bloom.init", "bloom.addk", "bloom.addk"]
        want = engine_rows(c1._engine)
        crash(c1)
        c2 = make_client(tmp_path, snap=False, coalesce=False)
        got = engine_rows(c2._engine)
        np.testing.assert_array_equal(got["bf"], want["bf"])
        bf2 = c2.get_bloom_filter("bf")
        assert all(bf2.contains_each(list(range(50))))
        assert not bf2.contains(987654)
        crash(c2)

    def test_hashed_bloom_add_record_still_replays(self, tmp_path):
        """Journals written before every add was keyed hold host-hashed
        bloom.add records (raw 64-bit hash twins); recovery replays them
        to the same bits a keyed add of those keys sets."""
        keys = np.arange(40, dtype=np.uint64) + np.uint64(1000)
        c1 = make_client(tmp_path, snap=False)
        c1.get_bloom_filter("old").try_init(1000, 0.01)
        c1.get_bloom_filter("new").try_init(1000, 0.01)
        h1, h2 = hashing.hash128_np(*hashing.encode_uint64_batch(keys))
        j = c1._engine.journal
        assert j.wait_durable(
            j.append({"op": "bloom.add", "name": "old", "h1": h1, "h2": h2}),
            timeout=10.0)
        c1.get_bloom_filter("new").add_all(list(keys))
        crash(c1)
        c2 = make_client(tmp_path, snap=False)
        got = engine_rows(c2._engine)
        assert got["old"].any()
        np.testing.assert_array_equal(got["old"], got["new"])
        assert all(c2.get_bloom_filter("old").contains_each(list(keys)))
        crash(c2)

    def test_always_future_done_tracks_durability(self, tmp_path):
        c1 = make_client(tmp_path, snap=False, fsync="always")
        eng = c1._engine
        bf = c1.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        res = eng.bloom_add_encoded(
            "bf", *hashing.encode_uint64_batch(np.array([1], np.uint64)))
        from redisson_tpu.objects.engines import _DurableResult

        assert isinstance(res, _DurableResult)
        res.result()
        assert eng.journal.is_durable(eng.journal.cut())
        crash(c1)

    def test_journal_lag_rides_admission_estimate(self, tmp_path):
        c1 = make_client(tmp_path, snap=False, fsync="always")
        eng = c1._engine
        assert eng.coalescer.journal_lag_s.__self__ is eng.journal
        # Pending records + a non-zero fsync EWMA → non-zero estimate.
        eng.journal._fsync_ewma_s = 0.5
        with eng.journal._lock:
            eng.journal._next_seq += 10  # simulate a 10-record backlog
        assert eng.coalescer.estimate_wait_s() > 0.0
        with eng.journal._lock:
            eng.journal._next_seq -= 10
        crash(c1)


# -- recovery edge cases (ISSUE 10 satellite) ---------------------------------


class TestRecoveryEdgeCases:
    def test_replay_onto_resharded_topology(self, tmp_path):
        c1 = make_client(tmp_path, fsync="always")
        bf = c1.get_bloom_filter("bf")
        bf.try_init(10_000, 0.01)
        for i in range(30):
            bf.add(i)
        c1._engine.snapshot(c1.config.snapshot_dir)  # S_old = 1
        for i in range(1000, 1030):
            bf.add(i)  # journal tail
        crash(c1)
        # Recover onto S_new = 2: restore_snapshot's reshard path +
        # topology-agnostic tail replay through the current executor.
        c2 = redisson_tpu.create(
            make_cfg(tmp_path, fsync="always", num_shards=2)
        )
        assert getattr(c2._engine.executor, "S", 1) == 2
        bf2 = c2.get_bloom_filter("bf")
        assert all(bf2.contains(i) for i in range(30))
        assert all(bf2.contains(i) for i in range(1000, 1030))
        assert not bf2.contains(777777)
        crash(c2)

    def test_replay_interleaved_with_ttl_expiry(self, tmp_path):
        c1 = make_client(tmp_path, snap=False)
        short = c1.get_hyper_log_log("short")
        short.add_all([1, 2, 3])
        c1._engine.expire_at("short", time.time() + 0.2)
        long = c1.get_hyper_log_log("long")
        long.add_all([1, 2, 3])
        c1._engine.expire_at("long", time.time() + 3600.0)
        crash(c1)
        time.sleep(0.3)  # the short TTL lapses across the "crash"
        c2 = make_client(tmp_path, snap=False)
        eng = c2._engine
        assert eng._live_lookup("short") is None, \
            "expired object must not resurrect through replay"
        entry = eng._live_lookup("long")
        assert entry is not None and entry.expire_at is not None
        assert c2.get_hyper_log_log("long").count() == 3
        crash(c2)

    def test_mid_degradation_snapshot_with_journaled_mirror_writes(
        self, tmp_path
    ):
        # Breaker open → writes land in the host golden mirror; both the
        # snapshot (mirror overlay) and the journal tail must carry them.
        c1 = make_client(
            tmp_path, fsync="always",
            breaker_failure_threshold=1, breaker_open_ms=3_600_000,
        )
        bf = c1.get_bloom_filter("bf")
        bf.try_init(10_000, 0.01)
        bf.add(1)
        chaos.inject("dispatch.bloom_mixed_keys", kind="error", rate=1.0)
        for point in ("dispatch.bloom_mixed_keys_runs",
                      "dispatch.bloom_contains_keys_runs"):
            chaos.inject(point, kind="error", rate=1.0)
        # Drive the breaker open (the first add surfaces the typed
        # failure), then every retried add lands mirror-acked.
        for i in range(100, 110):
            for _attempt in range(10):
                try:
                    bf.add(i)
                    break
                except Exception:
                    continue
            else:
                pytest.fail(f"add({i}) never acked via the mirror")
        assert c1._engine._mirrors, "expected degraded mirror"
        c1._engine.snapshot(c1.config.snapshot_dir)  # mid-degradation
        for i in range(200, 210):
            bf.add(i)  # journaled mirror writes (the tail)
        chaos.clear()
        crash(c1)
        c2 = make_client(tmp_path)
        bf2 = c2.get_bloom_filter("bf")
        assert all(bf2.contains(i) for i in (1, *range(100, 110),
                                             *range(200, 210)))
        crash(c2)


# -- snapshot crash-safety (ISSUE 10 satellite) -------------------------------


class TestSnapshotCrashSafety:
    def test_crash_between_write_and_rename_keeps_old_snapshot(
        self, tmp_path
    ):
        c1 = make_client(tmp_path, journal=False)
        bf = c1.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        bf.add(1)
        c1._engine.snapshot(c1.config.snapshot_dir)  # good snapshot
        bf.add(2)
        chaos.inject("snapshot.rename", kind="error", rate=1.0)
        with pytest.raises(chaos.FaultInjected):
            c1._engine.snapshot(c1.config.snapshot_dir)
        chaos.clear()
        c1.config.snapshot_dir = None
        c1._engine.config.snapshot_dir = None
        c1.shutdown()
        # The interrupted attempt must leave the PREVIOUS snapshot
        # fully loadable (fsynced files, renamed-in atomically).
        c2 = make_client(tmp_path, journal=False)
        bf2 = c2.get_bloom_filter("bf")
        assert bf2.contains(1)
        c2.config.snapshot_dir = None
        c2._engine.config.snapshot_dir = None
        c2.shutdown()

    def test_torn_install_detected_by_crc(self, tmp_path):
        c1 = make_client(tmp_path, journal=False)
        bf = c1.get_bloom_filter("bf")
        bf.try_init(1000, 0.01)
        c1._engine.snapshot(c1.config.snapshot_dir)
        c1.config.snapshot_dir = None
        c1._engine.config.snapshot_dir = None
        c1.shutdown()
        pools = os.path.join(str(tmp_path / "snap"), "sketch_pools.npz")
        with open(pools, "r+b") as f:
            f.seek(0, os.SEEK_END)
            f.write(b"garbage")  # new-blob-under-old-meta stand-in
        with pytest.raises(Exception, match="torn snapshot"):
            make_client(tmp_path, journal=False)


# -- RESP persistence surface -------------------------------------------------


class TestRespPersistence:
    @pytest.fixture
    def served(self, tmp_path):
        from tests.test_resp_server import RespClient
        from redisson_tpu.serve.resp import RespServer

        client = make_client(tmp_path, fsync="everysec")
        server = RespServer(client)
        conn = RespClient(server.host, server.port)
        yield conn, client
        conn.close()
        server.close()
        client.config.snapshot_dir = None
        client._engine.config.snapshot_dir = None
        client.shutdown()

    def test_config_appendonly_live(self, served):
        conn, client = served
        assert conn.cmd("CONFIG", "GET", "appendonly") == [
            b"appendonly", b"yes"
        ]
        assert conn.cmd("CONFIG", "GET", "appendfsync") == [
            b"appendfsync", b"everysec"
        ]
        assert conn.cmd(
            "CONFIG", "SET", "appendfsync", "always"
        ) == "OK"
        assert client._engine.journal.policy == "always"
        assert conn.cmd("CONFIG", "SET", "appendonly", "no") == "OK"
        assert client._engine.journal is None
        assert conn.cmd("CONFIG", "SET", "appendonly", "yes") == "OK"
        assert client._engine.journal is not None
        with pytest.raises(RuntimeError):
            conn.cmd("CONFIG", "SET", "appendfsync", "sometimes")

    def test_wait_is_a_journal_fence(self, served):
        conn, client = served
        conn.cmd("BF.RESERVE", "bf", "0.01", "1000")
        conn.cmd("BF.ADD", "bf", "123")
        assert conn.cmd("WAIT", "0", "5000") == 0
        j = client._engine.journal
        assert j.durable_seq() == j.cut(), \
            "WAIT must fence every appended record"

    def test_info_persistence_and_save_family(self, served):
        conn, client = served
        conn.cmd("BF.RESERVE", "bf", "0.01", "1000")
        conn.cmd("BF.ADD", "bf", "123")
        info = conn.cmd("INFO", "persistence").decode()
        assert "aof_enabled:1" in info
        assert "appendfsync:everysec" in info
        assert conn.cmd("LASTSAVE") == 0
        assert conn.cmd("SAVE") == "OK"
        assert conn.cmd("LASTSAVE") > 0
        assert conn.cmd("BGREWRITEAOF").startswith("Background")
        assert conn.cmd("BGSAVE").startswith("Background")
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if "rdb_last_save_time:0" not in conn.cmd(
                "INFO", "persistence"
            ).decode():
                break
            time.sleep(0.05)

    def test_tenant_aware_ingress_shed(self, served):
        conn, client = served
        gov = client._engine.governor
        gov.set_limits(rate_limit=5, burst=5, max_inflight=0)
        # Drain the hot tenant's bucket at the engine boundary.
        gov.admit("hot", 5)
        assert gov.peek_over_quota("hot")
        assert not gov.peek_over_quota("cold")
        with pytest.raises(RuntimeError, match="BUSY.*tenant"):
            conn.cmd("BF.EXISTS", "hot", "x")
        # A well-behaved tenant passes the door untouched...
        conn.cmd("BF.RESERVE", "cold", "0.01", "1000")
        # ...and the exempt surface stays usable during the incident.
        assert "redis_version" in conn.cmd("INFO", "server").decode()
        shed = client._engine.obs.resp_ingress_shed.get(("tenant",))
        assert shed >= 1
        gov.set_limits(rate_limit=0, burst=0, max_inflight=0)
