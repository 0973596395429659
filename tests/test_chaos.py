"""Chaos engine + self-healing dispatch (ISSUE 3).

Deterministic fault injection (seeded schedules, named points), circuit
breakers (CLOSED -> OPEN -> HALF_OPEN), graceful degradation to the host
golden mirror with reconcile-on-close, the DEBUG INJECT admin surface,
and the satellites (script watchdog, XAUTOCLAIM deleted ids).

The disabled-overhead guard and the randomized soak live here too (the
soak is slow+chaos marked; tier-1 runs everything else).
"""

import threading
import time

import numpy as np
import pytest

from redisson_tpu import chaos
from redisson_tpu.chaos import ChaosSchedule, FaultInjected
from redisson_tpu.config import Config
from redisson_tpu.executor.health import (
    BreakerBoard,
    CLOSED,
    DispatchHealth,
    HALF_OPEN,
    OPEN,
    kind_of_op,
)


@pytest.fixture(autouse=True)
def _chaos_off():
    """Every test starts and ends with chaos disarmed."""
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


# -- schedule determinism ----------------------------------------------------


class TestSchedule:
    def test_same_seed_same_fire_pattern(self):
        def pattern(seed):
            (rule,) = ChaosSchedule(
                seed=seed, rate=0.3, points=("dispatch",)
            ).rules()
            return [rule.roll() for _ in range(200)]

        assert pattern(7) == pattern(7)
        assert pattern(7) != pattern(8)

    def test_rate_zero_never_fires_rate_one_always(self):
        (never,) = ChaosSchedule(seed=1, rate=0.0, points=("p",)).rules()
        (always,) = ChaosSchedule(seed=1, rate=1.0, points=("p",)).rules()
        assert not any(never.roll() for _ in range(100))
        assert all(always.roll() for _ in range(100))

    def test_points_roll_independently(self):
        sched = ChaosSchedule(seed=3, rate=0.5, points=("a", "b"))
        ra, rb = sched.rules()
        assert [ra.roll() for _ in range(64)] != [rb.roll() for _ in range(64)]

    def test_install_clear_toggle_guard(self):
        assert not chaos.ENABLED
        chaos.install(ChaosSchedule(seed=0, rate=1.0, points=("x",)))
        assert chaos.ENABLED
        chaos.clear()
        assert not chaos.ENABLED

    def test_fire_kinds(self):
        chaos.inject("err", kind="error", rate=1.0)
        with pytest.raises(FaultInjected):
            chaos.fire("err")
        chaos.inject("corr", kind="corrupt", rate=1.0)
        with pytest.raises(chaos.CorruptionDetected):
            chaos.fire("corr", data=np.arange(8, dtype=np.uint32))
        chaos.inject("lat", kind="latency", rate=1.0, latency_s=0.01)
        t0 = time.monotonic()
        chaos.fire("lat")  # must NOT raise
        assert time.monotonic() - t0 >= 0.009
        assert chaos.counts()[("err", "error")] == 1

    def test_prefix_match_for_dispatch_points(self):
        chaos.inject("dispatch", kind="error", rate=1.0)
        with pytest.raises(FaultInjected):
            chaos.fire("dispatch.bloom_mixed_keys")
        chaos.clear()
        chaos.inject("dispatch.read_row", kind="error", rate=1.0)
        chaos.fire("dispatch.bloom_mixed_keys")  # no rule for this method
        with pytest.raises(FaultInjected):
            chaos.fire("dispatch.read_row")


# -- disabled-overhead guard -------------------------------------------------


def test_disabled_guard_never_consults_fire(monkeypatch):
    """With chaos disabled, ``fire`` must be unreachable from the hot
    paths — the module-level guard is the ONLY cost."""
    calls = []
    monkeypatch.setattr(chaos, "fire", lambda *a, **k: calls.append(a))
    c = make_client()
    bf = c.get_bloom_filter("guard-bf")
    bf.try_init(1000, 0.01)
    assert bf.add("k") is True
    assert bf.contains("k") is True
    c._engine.shutdown()
    assert calls == []


def test_disabled_injection_overhead():
    """The guard (`if chaos.ENABLED: fire(...)`) must add no measurable
    submit overhead when chaos is off — same min-of-paired-ratios
    discipline as test_observability's ≤10% harness, on the coalescer
    submit path the guard fronts."""
    import gc

    from redisson_tpu.executor.coalescer import BatchCoalescer

    class _Lazy:
        def __init__(self, v):
            self._v = v

        def result(self):
            return self._v

    def plain_dispatch(cols):
        return _Lazy(np.concatenate(cols))

    def guarded_dispatch(cols):
        if chaos.ENABLED:  # the exact call-site shape
            chaos.fire("dispatch.bench")
        return _Lazy(np.concatenate(cols))

    arr = np.arange(64, dtype=np.int64)
    N = 500

    def make():
        return BatchCoalescer(
            batch_window_us=30_000_000, max_batch=1 << 22,
            max_queued_ops=1 << 24,
        )

    def round_time(c, dispatch):
        t0 = time.perf_counter()
        for _ in range(N):
            c.submit(("op",), dispatch, (arr,), 64)
        return time.perf_counter() - t0

    history = []
    for _ in range(6):
        plain, guarded = [], []
        cs = []
        gc.disable()
        try:
            for r in range(6):
                ca, cb = make(), make()
                cs += [ca, cb]
                round_time(ca, plain_dispatch)
                round_time(cb, guarded_dispatch)
                if r % 2 == 0:
                    plain.append(round_time(ca, plain_dispatch))
                    guarded.append(round_time(cb, guarded_dispatch))
                else:
                    guarded.append(round_time(cb, guarded_dispatch))
                    plain.append(round_time(ca, plain_dispatch))
        finally:
            gc.enable()
            for c in cs:
                c.shutdown()
        ratio = min(q / p for p, q in zip(plain, guarded))
        ratio = min(ratio, min(guarded) / min(plain))
        history.append(ratio)
        if ratio <= 1.10:
            return
    raise AssertionError(f"chaos guard >10% submit overhead: {history}")


# -- circuit breaker unit ----------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestBreaker:
    def test_closed_open_halfopen_close(self):
        clk = _Clock()
        b = BreakerBoard(failure_threshold=3, open_s=1.0, clock=clk)
        for _ in range(2):
            b.record_failure(0, "bloom_mix", RuntimeError("x"))
        assert b.states()[(0, "bloom_mix")] == CLOSED
        assert b.allow(0, "bloom_mix")
        b.record_failure(0, "bloom_mix", RuntimeError("x"))
        assert b.states()[(0, "bloom_mix")] == OPEN
        assert not b.allow(0, "bloom_mix")  # open: fail fast
        clk.t = 1.5
        assert b.allow(0, "bloom_mix")  # the probe
        assert b.states()[(0, "bloom_mix")] == HALF_OPEN
        assert not b.allow(0, "bloom_mix")  # one probe at a time
        b.record_success(0, "bloom_mix")
        assert b.states()[(0, "bloom_mix")] == CLOSED
        assert b.allow(0, "bloom_mix")

    def test_probe_failure_reopens(self):
        clk = _Clock()
        b = BreakerBoard(failure_threshold=1, open_s=1.0, clock=clk)
        b.record_failure(0, "cms_mix", RuntimeError("x"))
        assert b.states()[(0, "cms_mix")] == OPEN
        clk.t = 1.1
        assert b.allow(0, "cms_mix")
        b.record_failure(0, "cms_mix", RuntimeError("probe died"))
        assert b.states()[(0, "cms_mix")] == OPEN
        assert not b.allow(0, "cms_mix")
        clk.t = 2.5  # a second window elapses
        assert b.allow(0, "cms_mix")

    def test_success_resets_failure_streak(self):
        b = BreakerBoard(failure_threshold=3, open_s=1.0)
        b.record_failure(0, "hll_add", RuntimeError("x"))
        b.record_failure(0, "hll_add", RuntimeError("x"))
        b.record_success(0, "hll_add")
        b.record_failure(0, "hll_add", RuntimeError("x"))
        assert b.states()[(0, "hll_add")] == CLOSED  # streak broke

    def test_transition_callbacks(self):
        events = []
        b = BreakerBoard(failure_threshold=1, open_s=0.0)
        b.on_open = lambda s, o: events.append(("open", s, o))
        b.on_close = lambda s, o: events.append(("close", s, o))
        b.record_failure(1, "bs_mix", RuntimeError("x"))
        assert b.allow(1, "bs_mix")  # open_s=0: immediate half-open probe
        b.record_success(1, "bs_mix")
        assert events == [("open", 1, "bs_mix"), ("close", 1, "bs_mix")]

    def test_kind_of_op(self):
        assert kind_of_op("bloom_mixkr") == "bloom"
        assert kind_of_op("bs_mix") == "bitset"
        assert kind_of_op("bitset_get") == "bitset"
        assert kind_of_op("hll_add") == "hll"
        assert kind_of_op("cms_updest") == "cms"
        assert kind_of_op("write_row") is None


# -- engine-level: degrade, serve from mirror, reconcile ---------------------


BLOOM_POINTS = (
    "dispatch.bloom_mixed_keys", "dispatch.bloom_contains_keys_st",
    "dispatch.bloom_mixed_keys_runs", "dispatch.bloom_contains_keys_runs",
)


def _await(cond, timeout=8.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.02)
    return True


def _flap(fn, attempts=8):
    """Run a degraded-phase op, riding out breaker FLAPS: with an
    opcode-targeted fault the monitor's read_row probe legitimately
    succeeds, briefly closing the breaker — the next real dispatch then
    fails typed and re-opens it (correct behavior for a fault that only
    one kernel hits).  Ops landing in that window fail typed; retrying
    resumes from the mirror.  State stays consistent across flaps: the
    reconcile wrote the mirror to the device, and the re-seed reads it
    back."""
    for _ in range(attempts - 1):
        try:
            return fn()
        except Exception:
            time.sleep(0.05)
    return fn()


class TestDegradedServe:
    def test_degrade_serve_reconcile_bloom(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=1500)
        eng = c._engine
        try:
            bf = c.get_bloom_filter("deg-bf")
            bf.try_init(50_000, 0.01)
            pre = [f"pre{i}" for i in range(50)]
            bf.add_all(pre)
            assert all(bf.contains(k) for k in pre)
            chaos.install(ChaosSchedule(seed=2, rate=1.0, points=BLOOM_POINTS))
            # Drive the breaker open: failures surface typed, never hang.
            for i in range(8):
                try:
                    bf.add(f"open{i}")
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            # Degraded serve: writes AND reads keep working, pre-fault
            # state is visible (mirror seeded from the device row).
            assert _flap(lambda: bf.add("while-down")) is True
            assert _flap(lambda: bf.contains("while-down")) is True
            assert _flap(lambda: bf.add("while-down")) is False  # present
            assert all(_flap(lambda k=k: bf.contains(k)) for k in pre)
            assert not _flap(lambda: bf.contains("never-added"))
            assert _await(lambda: "deg-bf" in eng._mirrors)
            mirror_bits = eng._mirrors["deg-bf"].model.bits.copy()
            # Heal the device: monitor probe closes the breaker and the
            # mirror reconciles back to the device row.
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            assert not eng._mirrors
            # Golden parity: the device row equals the mirror state.
            entry = eng.registry.lookup("deg-bf")
            row = eng.executor.read_row(entry.pool, entry.row)
            from redisson_tpu.objects.degraded import _bits_from_words

            device_bits = _bits_from_words(row, entry.params["size"])
            assert np.array_equal(device_bits, mirror_bits)
            # Device-served reads confirm the reconciled state.
            assert bf.contains("while-down")
            assert all(bf.contains(k) for k in pre)
            assert eng.health.summary()["recoveries"] >= 1
        finally:
            eng.shutdown()

    def test_snapshot_while_degraded_keeps_mirror_writes(self, tmp_path):
        """snapshot() taken mid-degradation must not crash on the
        read-only D2H arrays and must persist mirror-acked writes (the
        degraded overlay), so a crash during the window doesn't lose
        them."""
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=60_000)
        eng = c._engine
        try:
            bf = c.get_bloom_filter("snap-bf")
            bf.try_init(50_000, 0.01)
            bf.add("pre-fault")
            chaos.install(ChaosSchedule(seed=4, rate=1.0, points=BLOOM_POINTS))
            for i in range(8):
                try:
                    bf.add(f"open{i}")
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            assert _flap(lambda: bf.add("mirror-only")) is True
            assert _await(lambda: "snap-bf" in eng._mirrors)
            eng.snapshot(str(tmp_path))  # crashed before the overlay copy
        finally:
            chaos.clear()
            eng.shutdown()
        c2 = make_client()
        try:
            assert c2._engine.restore_snapshot(str(tmp_path))
            bf2 = c2.get_bloom_filter("snap-bf")
            assert bf2.contains("pre-fault")
            assert bf2.contains("mirror-only")  # the mirror-acked write
            assert not bf2.contains("never-added")
        finally:
            c2.shutdown()

    def test_no_lost_futures_while_breaker_opens(self):
        """Every future submitted across the failure window resolves —
        with a value or a typed error — none hang."""
        from redisson_tpu.executor.failures import RedissonTpuError

        c = make_client(breaker_failure_threshold=2, breaker_open_ms=1500)
        eng = c._engine
        try:
            bf = c.get_bloom_filter("nl-bf")
            bf.try_init(10_000, 0.01)
            bf.add("seed")
            chaos.install(ChaosSchedule(seed=5, rate=1.0, points=BLOOM_POINTS))
            outcomes = []
            for i in range(12):
                try:
                    outcomes.append(("ok", bf.add(f"k{i}")))
                except RedissonTpuError as e:
                    outcomes.append(("err", type(e).__name__))
                except Exception as e:  # chaos surfaces raw on direct paths
                    outcomes.append(("err", type(e).__name__))
            assert len(outcomes) == 12  # nothing hung
            # Once degraded, ops succeed from the mirror.
            assert _await(lambda: eng.health.any_degraded)
            assert _flap(lambda: bf.add("mirror-op")) is True
        finally:
            chaos.clear()
            eng.shutdown()

    def test_degraded_flag_in_info_and_debug_inject(self):
        import socket

        from redisson_tpu.serve.resp import RespServer

        c = make_client(breaker_failure_threshold=1, breaker_open_ms=60_000)
        eng = c._engine
        server = RespServer(c, host="127.0.0.1", port=0)
        try:
            sock = socket.create_connection((server.host, server.port))
            f = sock.makefile("rwb")

            def cmd(*parts):
                out = b"*" + str(len(parts)).encode() + b"\r\n"
                for p in parts:
                    p = p if isinstance(p, bytes) else str(p).encode()
                    out += b"$" + str(len(p)).encode() + b"\r\n" + p + b"\r\n"
                f.write(out)
                f.flush()
                line = f.readline()
                if line[:1] == b"$":
                    n = int(line[1:])
                    return f.read(n + 2)[:-2]
                return line.strip()

            # DEBUG INJECT arms a rule; LIST shows it; OFF clears.
            assert cmd("DEBUG", "INJECT", "dispatch.bloom_mixed_keys",
                       "error", "1.0", "7") == b"+OK"
            assert chaos.active() == {
                "dispatch.bloom_mixed_keys": ("error", 1.0, 7)
            }
            info = cmd("INFO", "stats").decode()
            assert "degraded:0" in info
            assert "breakers_open:0" in info
            assert cmd("DEBUG", "INJECT", "OFF") == b"+OK"
            assert chaos.active() == {}
            # Degrade for real and read the flag back through INFO.
            chaos.install(
                ChaosSchedule(seed=2, rate=1.0, points=BLOOM_POINTS)
            )
            bf = c.get_bloom_filter("info-bf")
            bf.try_init(1000, 0.01)
            for i in range(4):
                try:
                    bf.add(f"x{i}")
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            assert bf.add("seed-mirror") is True  # lazily seeds the mirror
            info = cmd("INFO", "stats").decode()
            assert "degraded:1" in info
            assert "degraded_objects:1" in info
            sock.close()
        finally:
            chaos.clear()
            server.close()
            eng.shutdown()

    def test_debug_inject_gated_like_scripting(self, monkeypatch):
        from redisson_tpu.serve.resp import RespError, RespServer

        c = make_client()
        try:
            server = RespServer(c, host="127.0.0.1", port=0)
            try:
                # Simulate a non-loopback unauthenticated bind.
                server._inject_allowed = False
                with pytest.raises(RespError, match="requirepass"):
                    server._cmd_DEBUG([b"INJECT", b"dispatch", b"error", b"1"])
                # Loopback (the real bind here) allows it.
                server._inject_allowed = True
                server._cmd_DEBUG([b"INJECT", b"OFF"])
            finally:
                server.close()
        finally:
            c._engine.shutdown()


class TestNearCacheChaos:
    """Near cache × chaos (ISSUE 4 satellite): under breaker-open
    degradation every MIRROR write must bump the write epoch — a cached
    pre-degradation read can never serve stale — and reconcile-on-close
    must leave cache and device bit-identical."""

    def test_mirror_writes_bump_epochs_no_stale_negative(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=60_000)
        eng = c._engine
        try:
            bf = c.get_bloom_filter("ncc-bf")
            bf.try_init(20_000, 0.01)
            bf.add("pre")
            # Cache a negative AND a positive before the fault lands.
            assert bf.contains("late-add") is False
            assert bf.contains("pre") is True
            assert eng.nearcache.store.entries() >= 2
            chaos.install(ChaosSchedule(seed=4, rate=1.0, points=BLOOM_POINTS))
            for i in range(8):
                try:
                    bf.add(f"open{i}")
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            # The mirror write bumps the epoch at submit: the cached
            # negative must NOT answer this read.
            assert _flap(lambda: bf.add("late-add")) is True
            assert _flap(lambda: bf.contains("late-add")) is True
            # The monotone positive is still warm and still true.
            assert _flap(lambda: bf.contains("pre")) is True
        finally:
            chaos.clear()
            eng.shutdown()

    def test_reconcile_leaves_cache_and_device_bit_identical(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=600)
        eng = c._engine
        nc = eng.nearcache
        try:
            bf = c.get_bloom_filter("ncc-rec")
            bf.try_init(20_000, 0.01)
            pre = [f"pre{i}" for i in range(20)]
            bf.add_all(pre)
            chaos.install(ChaosSchedule(seed=6, rate=1.0, points=BLOOM_POINTS))
            for i in range(8):
                try:
                    bf.add(f"open{i}")
                except Exception:
                    pass
                if eng.health.any_degraded:
                    break
            assert _await(lambda: eng.health.any_degraded)
            during = [f"during{i}" for i in range(20)]
            for k in during:
                assert _flap(lambda k=k: bf.add(k)) is True
            # Cache some degraded-window reads (mirror-served).
            assert all(_flap(lambda k=k: bf.contains(k)) for k in during)
            # Heal: breaker closes, mirror reconciles to the device row.
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            assert not eng._mirrors
            probe = pre + during + [f"ghost{i}" for i in range(20)]
            cached = [bf.contains(k) for k in probe]  # may serve from cache
            nc.store.clear()  # force the next pass to the device
            device = [bf.contains(k) for k in probe]
            assert cached == device  # bit-identical, entry for entry
        finally:
            chaos.clear()
            eng.shutdown()


class TestDegradedKinds:
    """Mirror parity for the other sketch kinds (hll/bitset/cms)."""

    def _degrade(self, eng, op, points, seed=3):
        chaos.install(ChaosSchedule(seed=seed, rate=1.0, points=points))
        for _ in range(6):
            try:
                op()
            except Exception:
                pass
            if eng.health.any_degraded:
                break
        assert _await(lambda: eng.health.any_degraded)

    def test_bloom_fast_paths_degrade_too(self):
        """A direct engine (no coalescer) launches its keyed add
        (bloom_mixed_keys) and contains (bloom_contains_keys_st) itself.
        Once the kind degrades, both must fail over to the mirror like
        every other bloom op: with every Bloom dispatch point faulted,
        nothing may reach the device until the breaker closes."""
        c = make_client(
            breaker_failure_threshold=2, breaker_open_ms=1500,
            coalesce=False,
        )
        eng = c._engine
        try:
            assert eng.coalescer is None
            bf = c.get_bloom_filter("fast-bf")
            bf.try_init(10_000, 0.01)
            bf.add("pre")
            chaos.install(ChaosSchedule(seed=11, rate=1.0,
                                        points=BLOOM_POINTS))
            # A direct dispatch raises to its caller and feeds no
            # breaker, so open the Bloom breaker as a coalesced flush's
            # failures would.
            with pytest.raises(FaultInjected):
                bf.add("x")
            for _ in range(2):
                eng.health.record_failure(
                    "bloom_mixed_keys", FaultInjected("dispatch"))
            assert _await(lambda: eng.health.any_degraded)
            assert _flap(lambda: bf.add("down-add")) is True  # mirror
            assert _flap(lambda: bf.add("down-add")) is False
            assert _flap(lambda: bf.contains("down-add")) is True
            assert _flap(lambda: bf.contains("pre")) is True
            assert not _flap(lambda: bf.contains("never-added"))
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            assert bf.contains("down-add") and bf.contains("pre")
        finally:
            chaos.clear()
            eng.shutdown()

    def test_bitset_mirror_and_reconcile(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=1500)
        eng = c._engine
        try:
            bs = c.get_bit_set("deg-bs")
            bs.set(3, True)
            bs.set(77, True)
            assert bs.get(3) and bs.get(77)
            self._degrade(
                eng, lambda: bs.set(5, True),
                ("dispatch.bitset_mixed", "dispatch.bitset_mixed_runs"),
            )
            # Degraded: mirror serves reads and writes with history.
            assert _flap(lambda: bs.get(3))
            assert not _flap(lambda: bs.set(100, True))  # prev bit
            assert _flap(lambda: bs.get(100))
            assert _flap(lambda: bs.cardinality()) >= 3
            # A degraded-window GROW (bitset_ensure migrates the entry to
            # a larger size class — not breaker-gated): the mirror must
            # grow with it and reconcile at the NEW row size.
            assert _await(lambda: "deg-bs" in eng._mirrors)
            seed_bits = eng._mirrors["deg-bs"].row_units * 32
            big = seed_bits + 513
            assert not _flap(lambda: bs.set(big, True))
            assert _flap(lambda: bs.get(big))
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            assert bs.get(100)  # reconciled to device
            assert bs.get(3) and bs.get(77)
            assert bs.get(big)  # grown row reconciled at the new size
            assert not bs.get(big - 1)
        finally:
            chaos.clear()
            eng.shutdown()

    def test_hll_mirror_and_reconcile(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=1500)
        eng = c._engine
        try:
            hll = c.get_hyper_log_log("deg-hll")
            hll.add_all([f"pre{i}" for i in range(500)])
            pre_count = hll.count()
            assert pre_count > 400
            self._degrade(
                eng, lambda: hll.add("x"),
                ("dispatch.hll_add_changed", "dispatch.hll_add_single",
                 "dispatch.hll_add", "dispatch.hll_add_keys_single"),
            )
            _flap(lambda: hll.add_all([f"down{i}" for i in range(500)]))
            degraded_count = _flap(lambda: hll.count())
            assert degraded_count > pre_count  # mirror kept counting
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            # Post-reconcile device count equals the mirror's last answer.
            assert hll.count() == degraded_count
        finally:
            chaos.clear()
            eng.shutdown()

    def test_cms_mirror_and_reconcile(self):
        c = make_client(breaker_failure_threshold=2, breaker_open_ms=1500)
        eng = c._engine
        try:
            cms = c.get_count_min_sketch("deg-cms")
            cms.try_init(4, 256)
            for _ in range(5):
                cms.add("hot")
            assert cms.estimate("hot") >= 5
            self._degrade(
                eng, lambda: cms.add("x"),
                ("dispatch.cms_update_estimate",
                 "dispatch.cms_update_estimate_seq",
                 "dispatch.cms_update", "dispatch.cms_estimate"),
            )
            for _ in range(7):
                _flap(lambda: cms.add("hot"))
            assert _flap(lambda: cms.estimate("hot")) >= 12  # pre + degraded
            chaos.clear()
            assert _await(lambda: not eng.health.any_degraded)
            assert cms.estimate("hot") >= 12  # reconciled to device
        finally:
            chaos.clear()
            eng.shutdown()


# -- randomized soak ---------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_soak_10k_ops_5pct():
    """10k mixed ops at a 5% seeded fault rate over every dispatch
    boundary: every future resolves (value or typed error), and after
    chaos lifts + breakers close, device state matches a golden oracle
    of the acknowledged-successful ops (monotone workloads, so 'applied
    but reported failed' can only ADD state, never lose it)."""
    rng = np.random.default_rng(42)
    c = make_client(breaker_failure_threshold=4, breaker_open_ms=100)
    eng = c._engine
    try:
        bf = c.get_bloom_filter("soak-bf")
        bf.try_init(200_000, 0.01)
        bs = c.get_bit_set("soak-bs")
        bs.set(0, True)
        hll = c.get_hyper_log_log("soak-hll")
        hll.add("seed")
        cms = c.get_count_min_sketch("soak-cms")
        cms.try_init(4, 1024)
        chaos.install(ChaosSchedule(
            seed=42, rate=0.05,
            points=("dispatch", "fetch", "h2d.staging"),
        ))
        ok_bloom, ok_bits, ok_hll, cms_ok = set(), set(), set(), 0
        resolved = 0
        for i in range(10_000):
            kind = i % 4
            try:
                if kind == 0:
                    k = f"b{rng.integers(0, 4000)}"
                    bf.add(k)
                    ok_bloom.add(k)
                elif kind == 1:
                    bit = int(rng.integers(0, 5000))
                    bs.set(bit, True)
                    ok_bits.add(bit)
                elif kind == 2:
                    k = f"h{rng.integers(0, 4000)}"
                    hll.add(k)
                    ok_hll.add(k)
                else:
                    cms.add("heavy")
                    cms_ok += 1
            except Exception:
                pass  # typed failure: resolved, not lost
            resolved += 1
        assert resolved == 10_000
        chaos.clear()
        # Let breakers close and mirrors reconcile, then verify against
        # the oracle of ACKNOWLEDGED ops (monotone: no acked write lost).
        deadline = time.monotonic() + 20
        while eng.health.board.open_count() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert eng.health.board.open_count() == 0
        assert not eng._mirrors
        missing = [k for k in ok_bloom if not bf.contains(k)]
        assert not missing, f"lost acked bloom adds: {missing[:5]}"
        lost_bits = [b for b in ok_bits if not bs.get(b)]
        assert not lost_bits, f"lost acked bitset sets: {lost_bits[:5]}"
        n = hll.count()
        assert n >= 0.8 * len(ok_hll)
        assert cms.estimate("heavy") >= cms_ok
    finally:
        chaos.clear()
        eng.shutdown()


# -- satellites: script watchdog + XAUTOCLAIM deleted ids --------------------


class TestScriptWatchdog:
    def _server(self, timeout_ms=150):
        from redisson_tpu.client import RedissonTpuClient
        from redisson_tpu.serve.resp import RespServer

        cfg = Config()
        cfg.enable_python_scripts = True
        cfg.script_timeout_ms = timeout_ms
        client = RedissonTpuClient(cfg)
        return client, RespServer(client, host="127.0.0.1", port=0)

    def test_busy_reply_while_script_runs_and_kill(self):
        client, server = self._server(timeout_ms=100)
        try:
            results = {}

            def run_loop():
                try:
                    results["script"] = server._cmd_EVAL(
                        [b"import time\nwhile True: time.sleep(0.005)", b"0"]
                    )
                except Exception as e:
                    results["script"] = e

            t = threading.Thread(target=run_loop, daemon=True)
            t.start()
            deadline = time.monotonic() + 5
            while not server._script_busy() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server._script_busy()
            # Another connection now gets BUSY...
            from redisson_tpu.serve.resp import _ConnCtx, RespError

            class _Ctx:
                authed = True
                in_multi = False
                in_exec = False
                subs = {}

            with pytest.raises(RespError, match="BUSY"):
                server._dispatch([b"PING"], _Ctx())
            # ...but SCRIPT KILL goes through and stops the loop.
            reply = server._dispatch([b"SCRIPT", b"KILL"], _Ctx())
            assert reply == b"+OK\r\n"
            t.join(timeout=5)
            assert not t.is_alive()
            assert isinstance(results["script"], RespError)
            assert "killed" in str(results["script"]).lower()
            assert server._script_run is None
            # Server serves normally again.
            assert server._dispatch([b"PING"], _Ctx()) == b"+PONG\r\n"
        finally:
            server.close()

    def test_nested_script_kill_uncatchable(self):
        """A script looping `try: redis.call(EVAL ...) except Exception`
        must still die to ONE SCRIPT KILL: the kill stays a BaseException
        through nested frames and only the outermost converts it."""
        client, server = self._server(timeout_ms=100)
        try:
            results = {}
            body = (
                "while True:\n"
                "    try:\n"
                "        redis.call('EVAL', '1 + 1', '0')\n"
                "    except Exception:\n"
                "        pass"
            )

            def run_loop():
                try:
                    results["script"] = server._cmd_EVAL([body.encode(), b"0"])
                except Exception as e:
                    results["script"] = e

            t = threading.Thread(target=run_loop, daemon=True)
            t.start()
            deadline = time.monotonic() + 5
            while not server._script_busy() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server._script_busy()
            from redisson_tpu.serve.resp import RespError

            class _Ctx:
                authed = True
                in_multi = False
                in_exec = False
                subs = {}

            assert server._dispatch([b"SCRIPT", b"KILL"], _Ctx()) == b"+OK\r\n"
            t.join(timeout=5)
            assert not t.is_alive(), "nested runaway survived SCRIPT KILL"
            assert isinstance(results["script"], RespError)
            assert server._script_run is None
        finally:
            server.close()

    def test_kill_without_script_is_notbusy(self):
        from redisson_tpu.serve.resp import RespError

        client, server = self._server()
        try:
            with pytest.raises(RespError, match="NOTBUSY"):
                server._cmd_SCRIPT([b"KILL"])
        finally:
            server.close()

    def test_compile_error_before_grid_lock(self):
        """A syntactically broken script fails at compile — without ever
        taking (or leaking) the grid lock."""
        client, server = self._server()
        try:
            with pytest.raises(SyntaxError):
                server._run_script("def broken(:", [], [])
            assert client._grid.lock.acquire(timeout=1)
            client._grid.lock.release()
            assert server._script_run is None
        finally:
            server.close()


class TestXAutoClaimDeleted:
    def test_deleted_ids_reported(self):
        from redisson_tpu.client import RedissonTpuClient

        client = RedissonTpuClient(Config())
        s = client.get_stream("xac")
        s.create_group("g", from_id="0-0")
        ids = [s.add({"v": str(i)}) for i in range(3)]
        s.read_group("g", "c1")
        # Remove one pending entry from the stream: the PEL still holds
        # it until a sweep notices.
        s.remove(ids[1])
        cursor, claimed, deleted = s.auto_claim(
            "g", "c2", 0, count=10, with_cursor=True
        )
        assert deleted == [ids[1]]
        assert [eid for eid, _ in claimed] == [ids[0], ids[2]]
        assert cursor == "0-0"

    def test_justid_leaves_delivery_count_untouched(self):
        """JUSTID is an inspection sweep: it claims ownership but must
        not inflate the PEL delivery counter (Redis contract — dead-
        letter logic keyed on the count would discard entries that were
        never actually redelivered)."""
        from redisson_tpu.client import RedissonTpuClient

        client = RedissonTpuClient(Config())
        s = client.get_stream("xacj")
        s.create_group("g", from_id="0-0")
        eid = s.add({"v": "1"})
        s.read_group("g", "c1")  # delivery count 1

        def count():
            with s._store.lock:
                return s._group("g")["pending"][
                    next(iter(s._group("g")["pending"]))
                ]["count"]

        base = count()
        _, claimed, _ = s.auto_claim(
            "g", "c2", 0, count=10, with_cursor=True, justid=True
        )
        assert [e for e, _ in claimed] == [eid]
        assert count() == base  # JUSTID: untouched
        s.auto_claim("g", "c3", 0, count=10, with_cursor=True)
        assert count() == base + 1  # full claim still increments

    def test_resp_reply_third_element(self):
        from redisson_tpu.client import RedissonTpuClient
        from redisson_tpu.serve.resp import RespServer

        client = RedissonTpuClient(Config())
        server = RespServer(client, host="127.0.0.1", port=0)
        try:
            s = client.get_stream("xac2")
            s.create_group("g", from_id="0-0")
            ids = [s.add({"v": str(i)}) for i in range(2)]
            s.read_group("g", "c1")
            s.remove(ids[0])
            reply = server._cmd_XAUTOCLAIM(
                [b"xac2", b"g", b"c2", b"0", b"0-0"]
            )
            # *3 header and a non-empty third (deleted-ids) array.
            assert reply.startswith(b"*3\r\n")
            assert ids[0].encode() in reply
            assert not reply.endswith(b"*0\r\n")  # deleted list is real
        finally:
            server.close()
