"""Launch-path host regions and their histograms/counters.

Every thread that does launch-path work names it in a ``jax.profiler``
capture with a constant ``rtpu:*`` region; device programs are named by
op; and the families behind the benchmark's launch-path metrics take
exactly one observation per call, launch or collection.  No test here
compares a duration with a wall-clock threshold except the clock test,
whose bound is on a difference of two clocks read microseconds apart.
"""

import gc
import glob
import inspect
import os
import socket
import statistics
import sys
import threading
import time

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec
from redisson_tpu.executor.coalescer import BatchCoalescer
from redisson_tpu.obs import Observability
from redisson_tpu.obs import spans as spans_mod
from redisson_tpu.serve.resp import RespServer
from redisson_tpu.serve.wireutil import skip_reply_frame, wire_command
from redisson_tpu.tenancy import PoolKind


def _capture(log_dir, body):
    """Run ``body()`` under a capture shaped like the benchmark's (Python
    tracer off, host tracer level 1); return the loaded ProfileData."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))
    return ProfileData.from_file(paths[-1])


def _host_lines(pd):
    """[(set of rtpu:* names, [(start_ns, name)])] per host thread line."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.name) for e in line.events]
            out.append(({n for _, n in evs if n.startswith("rtpu:")}, evs))
    return out


def _client(**kw):
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(min_bucket=64, **kw)
    return redisson_tpu.create(cfg)


def _fam_total(obs, name, field="count"):
    fam = obs.registry.family(name)
    return sum(getattr(c, field) for _, c in fam.items())


def _blocked_in_wait_lock(thread) -> bool:
    """True once ``thread`` sits in WaitRegionLock's BLOCKING acquire
    (its non-blocking try already failed, so its region is open)."""
    src, first = inspect.getsourcelines(spans_mod.WaitRegionLock.acquire)
    line = first + next(i for i, l in enumerate(src)
                        if "acquire(True, timeout)" in l)
    f = sys._current_frames().get(thread.ident)
    while f is not None:
        if (f.f_code is spans_mod.WaitRegionLock.acquire.__code__
                and f.f_lineno == line):
            return True
        f = f.f_back
    return False


def _resp_exchange(socks, cmds_per_sock):
    for s, cmds in zip(socks, cmds_per_sock):
        s.sendall(b"".join(wire_command(c) for c in cmds))
    for s, cmds in zip(socks, cmds_per_sock):
        data, pos, n = b"", 0, 0
        s.settimeout(60)
        while n < len(cmds):
            try:
                pos = skip_reply_frame(data, pos)
                n += 1
                continue
            except (IndexError, ValueError):
                pass
            chunk = s.recv(1 << 16)
            assert chunk, "connection closed"
            data += chunk


# -- (a) the capture names every region, on the right thread ----------------


def test_capture_names_every_launch_path_region(tmp_path):
    # A 2 s window: a fresh segment waits in rtpu:coalesce:window until
    # a blocking .result() hints the flush.
    cl = _client(batch_window_us=2_000_000, adaptive_window=False)
    srv = RespServer(cl, host="127.0.0.1", port=0)
    eng = cl._engine
    try:
        bfs = [cl.get_bloom_filter(f"bf{i}") for i in range(3)]
        for bf in bfs:
            bf.try_init(1000, 0.01)
        bfs[0].add(1)  # compile outside the capture
        srv_socks = [socket.create_connection(("127.0.0.1", srv.port))
                     for _ in range(3)]
        _resp_exchange(srv_socks[:1], [[[b"BF.RESERVE", b"rbf", b"0.01",
                                         b"1000"]]])

        def body():
            # Producer side: encode + submit; a blocked add shows the
            # journal gate's contended wait.
            futs = [bf.add_all_async(np.arange(8) + i)
                    for i, bf in enumerate(bfs)]
            time.sleep(0.05)  # the flush thread meets a young segment
            futs += [bf.contains_all_async(np.arange(8)) for bf in bfs]
            for f in futs:
                f.result()
            eng._journal_gate.acquire()
            t = threading.Thread(target=lambda: bfs[1].add(99),
                                 name="blocked-producer")
            t.start()
            try:
                deadline = time.monotonic() + 60
                while not _blocked_in_wait_lock(t):
                    assert time.monotonic() < deadline, "never blocked"
                    time.sleep(0.001)
            finally:
                eng._journal_gate.release()
            t.join(60)
            # RESP: three connections make the reactor gather; a mixed
            # BF.ADD/BF.EXISTS run fuses; DEBUG rides a worker thread.
            _resp_exchange(srv_socks, [
                [[b"BF.ADD", b"rbf", b"%d" % i],
                 [b"BF.EXISTS", b"rbf", b"%d" % i]] for i in range(3)])
            _resp_exchange(srv_socks[:1], [[[b"DEBUG", b"SLEEP", b"0"]]])
            gc.collect()

        pd = _capture(tmp_path, body)
        for s in srv_socks:
            s.close()
    finally:
        srv.close()
        cl.shutdown()

    lines = _host_lines(pd)
    names = set().union(*(n for n, _ in lines))
    want = {
        "rtpu:encode", "rtpu:submit:bloom_add", "rtpu:submit:bloom_contains",
        "rtpu:lock_wait", "rtpu:coalesce:window", "rtpu:coalesce:stage",
        "rtpu:coalesce:slot_wait", "rtpu:dispatch:bloom_mixkr",
        "rtpu:bloom_mixed_keys_runs", "rtpu:complete:fetch",
        "rtpu:complete:resolve", "rtpu:resp:read", "rtpu:resp:gather",
        "rtpu:resp:run", "rtpu:resp:write", "rtpu:resp:detached", "rtpu:gc",
    }
    assert want <= names, sorted(want - names)
    # Region names are constants: no ids or counts ride them.
    assert all(not any(ch.isdigit() for ch in n.split(":", 1)[1])
               for n in names), sorted(names)
    flush = [n for n, _ in lines if "rtpu:coalesce:slot_wait" in n]
    assert len(flush) == 1, "slot waits on more than one thread"
    assert {"rtpu:coalesce:stage", "rtpu:dispatch:bloom_mixkr"} <= flush[0]
    assert not flush[0] & {"rtpu:complete:fetch", "rtpu:submit:bloom_add",
                           "rtpu:resp:run"}
    # Programs are named by op: no jitted module is ``f``.
    programs = {n for _, evs in lines for _, n in evs
                if n.startswith("PjitFunction(")}
    assert "PjitFunction(bloom_mixk_runs)" in programs, sorted(programs)
    assert "PjitFunction(f)" not in programs


def test_executor_programs_are_named_by_op():
    cl = _client(coalesce=False)
    try:
        bf = cl.get_bloom_filter("named")
        bf.try_init(1000, 0.01)
        bf.add_all(np.arange(40))
        bf.contains_all(np.arange(40))
        cache = cl._engine.executor._jit_cache
        assert cache
        for key, fn in cache.items():
            assert fn.__name__ == key[0], (key, fn.__name__)
    finally:
        cl.shutdown()


# -- (b) the capture's host clock is time.monotonic less one constant -------


def test_region_start_is_monotonic_stamp_plus_constant(tmp_path):
    cl = _client()
    eng = cl._engine
    try:
        bf = cl.get_bloom_filter("clock")
        bf.try_init(1000, 0.01)
        bf.add(0)  # compile outside the capture
        n = 12

        def body():
            for i in range(n):
                bf.add(i + 1)

        pd = _capture(tmp_path, body)
        spans = sorted(eng.obs.spans.recent(), key=lambda s: s.t0)[-n:]
    finally:
        cl.shutdown()
    starts = sorted(s for _, evs in _host_lines(pd) for s, name in evs
                    if name == "rtpu:coalesce:stage")
    assert len(starts) == n
    # _stage stamps coalesce_wait first thing inside the region.
    stamps = [dict(s.stamps)["coalesce_wait"] * 1e9 for s in spans]
    offsets = [a - b for a, b in zip(starts, stamps)]
    mid = statistics.median(offsets)
    # Either reading may be delayed by a thread switch in between; the
    # relation itself holds for all but such an outlier.
    near = [o for o in offsets if abs(o - mid) <= 100_000]
    assert len(near) >= n - 1, offsets


# -- (c) one observation per call, launch or collection ---------------------


def test_encode_and_submit_observe_once_per_call():
    cl = _client()
    obs = cl._engine.obs
    try:
        bf = cl.get_bloom_filter("once")
        bf.try_init(1000, 0.01)
        bf.add(0)
        e0 = _fam_total(obs, "rtpu_encode_seconds")
        s0 = {lv: c.count for lv, c in
              obs.registry.family("rtpu_submit_seconds").items()}
        futs = [bf.add_all_async([1, 2]), bf.contains_all_async([1, 3]),
                bf.mixed_async([4, 5], [True, False]),
                bf.mixed_async([6], [True])]  # uniform: delegates once
        for f in futs:
            f.result()
        assert _fam_total(obs, "rtpu_encode_seconds") - e0 == 4
        s1 = {lv: c.count for lv, c in
              obs.registry.family("rtpu_submit_seconds").items()}
        got = {lv[0]: s1[lv] - s0.get(lv, 0) for lv in s1}
        assert got == {"bloom_add": 1, "bloom_contains": 1,
                       "bloom_mixed": 2}
    finally:
        cl.shutdown()


def test_slot_wait_once_per_launch_and_phases_still_partition():
    """(c) + (d): one slot-wait observation per launch, which stays
    inside device_dispatch — the phases still sum to end to end."""
    cl = _client()
    obs = cl._engine.obs
    try:
        bf = cl.get_bloom_filter("slots")
        bf.try_init(1000, 0.01)
        for i in range(10):
            bf.add(i)
        fam = obs.registry.family("rtpu_launch_slot_wait_seconds")
        waits = {lv[0]: c.count for lv, c in fam.items()}
        launches = {lv[0]: c.count for lv, c in obs.spans._total_hist.items()}
        assert waits == launches and sum(waits.values()) >= 10
        for s in obs.spans.recent():
            assert sum(s.phases().values()) == pytest.approx(
                s.end_to_end(), abs=1e-9)
            assert set(s.phases()) == set(spans_mod.PHASES)
    finally:
        cl.shutdown()


def test_admit_wait_observed_once_per_waiting_submit():
    obs = Observability()

    class _Lazy:
        def result(self):
            return None

    c = BatchCoalescer(batch_window_us=30_000_000, max_batch=1 << 20,
                       max_queued_ops=64, obs=obs)
    arr = np.arange(64, dtype=np.int64)
    try:
        c.submit(("op",), lambda cols: _Lazy(), (arr,), 64)  # fills it
        t = threading.Thread(
            target=lambda: c.submit(("op",), lambda cols: _Lazy(),
                                    (arr[:1],), 1))
        t.start()
        deadline = time.monotonic() + 60
        while not c._admit_q:
            assert time.monotonic() < deadline, "second submit never waited"
            time.sleep(0.001)
        c.flush_hint()  # pops the first segment: the waiter admits
        t.join(60)
        assert not t.is_alive()
        assert _fam_total(obs, "rtpu_admit_wait_seconds") == 1
    finally:
        c.shutdown()


def test_gc_pause_and_collections_once_per_collection():
    a, b = Observability(), Observability()
    was = gc.isenabled()
    gc.disable()  # only the explicit collections below run
    try:
        before = [(o.gc_collections.get(("2",)), o.gc_pause_seconds.get())
                  for o in (a, b)]
        for _ in range(3):
            gc.collect()
        for o, (n0, s0) in zip((a, b), before):
            assert o.gc_collections.get(("2",)) - n0 == 3
            assert o.gc_pause_seconds.get() > s0
    finally:
        if was:
            gc.enable()


def test_h2d_bytes_recorded_once_per_dispatch():
    cl = _client(coalesce=False)
    obs = cl._engine.obs
    try:
        bf = cl.get_bloom_filter("h2d")
        bf.try_init(1000, 0.01)
        entry = cl._engine._require("h2d", PoolKind.BLOOM)
        ex = cl._engine.executor
        m, k = entry.params["size"], entry.params["hash_iterations"]
        B = 100
        rows = np.full(B, entry.row, np.int32)
        h = np.arange(B, dtype=np.uint32)
        m_arr = np.full(B, m, np.uint32)

        def shipped():
            return obs.h2d_bytes.get(("bloom_add",))

        ex.bloom_add(entry.pool, rows, m_arr, k, h, h).result()
        one = shipped()
        Bp = ex._bucket(B)
        # rows, h1, h2, m: 4 bytes an op; the validity mask: 1.
        assert one == 4 * 4 * Bp + Bp
        ex.bloom_add(entry.pool, rows, m_arr, k, h, h).result()
        assert shipped() == 2 * one
    finally:
        cl.shutdown()


def test_compiles_count_a_new_shape_once_under_its_method():
    """(f) a forced new shape compiles once, labelled by the executor
    method that dispatched it; the same shape again compiles nothing."""
    cl = _client(coalesce=False)
    obs = cl._engine.obs
    try:
        bf = cl.get_bloom_filter("compiles")
        bf.try_init(1000, 0.01)
        entry = cl._engine._require("compiles", PoolKind.BLOOM)
        ex = cl._engine.executor
        m, k = entry.params["size"], entry.params["hash_iterations"]
        B = 5000  # bucket 8192: no other test in this file uses it
        rows = np.full(B, entry.row, np.int32)
        h = np.arange(B, dtype=np.uint32)
        m_arr = np.full(B, m, np.uint32)
        n0 = obs.compiles.get(("bloom_add",))
        s0 = obs.compile_seconds.get()
        ex.bloom_add(entry.pool, rows, m_arr, k, h, h).result()
        assert obs.compiles.get(("bloom_add",)) - n0 == 1
        assert obs.compile_seconds.get() > s0
        ex.bloom_add(entry.pool, rows, m_arr, k, h, h).result()
        assert obs.compiles.get(("bloom_add",)) - n0 == 1
    finally:
        cl.shutdown()


# -- regions with no capture active -----------------------------------------


def test_regions_are_shared_noops_without_a_capture():
    assert spans_mod.region("rtpu:encode") is spans_mod.region("rtpu:gc")
    lock = spans_mod.WaitRegionLock(threading.RLock(), "rtpu:lock_wait")
    with lock:
        with lock:  # reentrant like the lock it wraps
            pass
    assert lock.acquire(blocking=False)
    lock.release()
