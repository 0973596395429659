"""Observability subsystem (ISSUE 1): labeled registry, log2 histograms,
lifecycle spans, SLOWLOG, INFO commandstats/latencystats over a live
RESP connection, Prometheus exposition, and the hot-path overhead guard.
"""

import time

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec
from redisson_tpu.obs import Observability
from redisson_tpu.obs.registry import (
    N_TIME_BUCKETS,
    MetricsRegistry,
    bucket_index_us,
    bucket_upper_bound_us,
)
from redisson_tpu.obs.slowlog import SlowLog
from redisson_tpu.serve.metrics import Metrics, Profiler
from redisson_tpu.serve.resp import RespServer

from test_resp_server import RespClient


# -- histogram buckets ------------------------------------------------------


def test_log2_bucket_boundaries():
    # Boundaries are le = 2^i microseconds: a value EQUAL to a boundary
    # lands in that boundary's bucket, one ulp above rolls over.
    assert bucket_index_us(0.0) == 0
    assert bucket_index_us(1.0) == 0
    assert bucket_index_us(2.0) == 1
    assert bucket_index_us(3.0) == 2
    assert bucket_index_us(4.0) == 2
    assert bucket_index_us(5.0) == 3
    for i in range(1, N_TIME_BUCKETS):
        assert bucket_index_us(float(1 << i)) == i
        assert bucket_index_us(float((1 << i) + 1)) == i + 1 or i + 1 > N_TIME_BUCKETS
    # Beyond the last finite bucket: +Inf.
    assert bucket_index_us(float(1 << 30)) == N_TIME_BUCKETS
    assert bucket_upper_bound_us(N_TIME_BUCKETS) == float("inf")
    assert bucket_upper_bound_us(3) == 8.0


def test_histogram_observe_and_render():
    reg = MetricsRegistry()
    h = reg.histogram("rtpu_test_seconds", "t", ("op",))
    h.observe(("x",), 3e-6)  # 3us -> bucket le=4us
    h.observe(("x",), 3e-6)
    c = h.child(("x",))
    assert c.count == 2
    assert c.buckets[2] == 2 and sum(c.buckets) == 2
    text = reg.render_prometheus()
    assert "# TYPE rtpu_test_seconds histogram" in text
    # Cumulative buckets: the le=4us line carries both observations.
    assert 'rtpu_test_seconds_bucket{op="x",le="4e-06"} 2' in text
    assert 'rtpu_test_seconds_count{op="x"} 2' in text


def test_percentile_edge_cases():
    reg = MetricsRegistry()
    h = reg.histogram("rtpu_p_seconds", "t", ("op",))
    # No samples: all-zero percentiles.
    assert h.percentiles(("x",), (50, 99)) == [0.0, 0.0]
    # n=1: every percentile is that one bucket's upper bound.
    h.observe(("x",), 3e-6)
    p50, p99 = h.percentiles(("x",), (50, 99))
    assert p50 == p99 == 4e-6
    # all-equal: still one bucket, p50 == p99.
    for _ in range(100):
        h.observe(("y",), 100e-6)  # -> le=128us
    p50, p99 = h.percentiles(("y",), (50, 99))
    assert p50 == p99 == 128e-6
    # Mixed: p50 in the low bucket, p99 in the high one.
    for _ in range(98):
        h.observe(("z",), 1e-6)
    for _ in range(2):
        h.observe(("z",), 1000e-6)
    p50, p99 = h.percentiles(("z",), (50, 99))
    assert p50 == 1e-6
    assert p99 == 1024e-6


def test_counter_total_suffix_and_overflow_cap():
    reg = MetricsRegistry()
    c = reg.counter("rtpu_things", "t", ("who",), max_children=4)
    assert c.name == "rtpu_things_total"
    for i in range(10):
        c.inc((f"t{i}",))
    # Cardinality cap: 4 real children, the rest collapse into overflow.
    labels = {lv for lv, _ in c.items()}
    assert len(labels) == 5
    assert ("_overflow",) in labels
    assert c.get(("_overflow",)) == 6


# -- slowlog ----------------------------------------------------------------


def test_slowlog_threshold_and_ring_eviction():
    sl = SlowLog(max_len=3, threshold_us=1000)
    assert not sl.maybe_add(0.0005, [b"GET", b"k"])  # below threshold
    assert len(sl) == 0
    for i in range(5):
        assert sl.maybe_add(0.002, [b"GET", b"k%d" % i])
    assert len(sl) == 3  # ring evicted the two oldest
    entries = sl.entries()
    assert [e.args[1] for e in entries] == [b"k4", b"k3", b"k2"]  # newest 1st
    assert [e.id for e in entries] == [4, 3, 2]  # ids keep increasing
    assert all(e.duration_us >= 1000 for e in entries)
    assert sl.entries(1)[0].id == 4
    sl.reset()
    assert len(sl) == 0
    # threshold < 0 disables logging entirely (Redis semantics).
    sl.set_threshold_us(-1)
    assert not sl.maybe_add(10.0, [b"GET"])


def test_slowlog_arg_truncation():
    sl = SlowLog(max_len=8, threshold_us=0)
    big = b"x" * 500
    sl.maybe_add(0.001, [b"SET", big])
    e = sl.entries()[0]
    assert e.args[1].startswith(b"x" * 128)
    assert e.args[1].endswith(b"... (372 more bytes)")
    sl.maybe_add(0.001, [b"MSET"] + [b"a"] * 40)
    e = sl.entries()[0]
    assert len(e.args) == 32
    assert e.args[-1] == b"... (10 more arguments)"


# -- spans ------------------------------------------------------------------


@pytest.fixture
def tpu_client():
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        batch_window_us=100, min_bucket=64
    )
    cl = redisson_tpu.create(cfg)
    yield cl
    cl.shutdown()


def test_span_phase_sum_matches_end_to_end(tpu_client):
    bf = tpu_client.get_bloom_filter("span-bf")
    bf.try_init(10_000, 0.01)
    bf.add_all(np.arange(512, dtype=np.uint64))
    bf.contains_each(np.arange(512, dtype=np.uint64))
    spans = tpu_client.obs.spans.recent()
    assert spans, "coalesced launches must leave spans"
    for s in spans:
        phases = s.phases()
        # The four lifecycle phases partition the end-to-end latency.
        assert set(phases) == {
            "coalesce_wait", "host_stage", "device_dispatch", "d2h_fetch"
        }
        assert sum(phases.values()) == pytest.approx(
            s.end_to_end(), rel=1e-6, abs=1e-6
        )
        assert s.nops > 0 and not s.error
    # The registry saw the same launches.
    snap = tpu_client.get_metrics()
    assert snap["ops"], snap
    assert any(
        st["ops"] >= 1024 and st["p99_ms"] > 0
        for st in snap["ops"].values()
    ), snap["ops"]
    # Per-tenant dimension.
    assert snap["tenants"].get("span-bf", 0) >= 1024


def test_direct_dispatch_records_ops():
    """coalesce=False (the sharded-engine default test shape) must not
    report zero ops — the executor records through record_dispatch."""
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        coalesce=False, min_bucket=64
    )
    cl = redisson_tpu.create(cfg)
    try:
        bf = cl.get_bloom_filter("d-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(256, dtype=np.uint64))
        snap = cl.get_metrics()
        assert snap["ops_total"] >= 256
        assert snap["batches_total"] >= 1
        # Per-method dispatch counters in the labeled registry.
        fam = cl.obs.registry.family("rtpu_dispatches_total")
        assert sum(c.value for _, c in fam.items()) >= 1
    finally:
        cl.shutdown()


def test_sharded_direct_dispatch_records_ops_and_shards():
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        num_shards=8, coalesce=False, min_bucket=64
    )
    cl = redisson_tpu.create(cfg)
    try:
        bf = cl.get_bloom_filter("sh-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(256, dtype=np.uint64))
        snap = cl.get_metrics()
        assert snap["ops_total"] >= 256, snap
        shard_fam = cl.obs.registry.family("rtpu_shard_ops_total")
        total = sum(c.value for _, c in shard_fam.items())
        assert total >= 256
    finally:
        cl.shutdown()


# -- legacy Metrics fixes (satellites) --------------------------------------


def test_legacy_render_prometheus_counter_types():
    m = Metrics()
    m.record_batch(nops=8, wait_s=0.001, flush_s=0.002)
    text = m.render_prometheus()
    assert "# TYPE redisson_tpu_ops_total counter" in text
    assert "# TYPE redisson_tpu_batches_total counter" in text
    assert "# TYPE redisson_tpu_ops_per_sec gauge" in text
    assert "# TYPE redisson_tpu_p99_wait_ms gauge" in text
    assert "redisson_tpu_ops_total 8" in text


def test_device_memory_reports_all_devices():
    import jax

    mem = Profiler.device_memory()
    assert isinstance(mem, dict)
    # conftest forces 8 virtual CPU devices: every one must be keyed.
    assert len(mem) == len(jax.devices())
    for d in jax.devices():
        assert f"{d.platform}:{d.id}" in mem


# -- RESP wire surface ------------------------------------------------------


@pytest.fixture
def resp():
    cl = redisson_tpu.create(Config())
    srv = RespServer(cl)
    conn = RespClient(srv.host, srv.port)
    yield conn, srv, cl
    srv.close()
    cl.shutdown()


def test_info_commandstats_wire_format(resp):
    conn, srv, cl = resp
    assert conn.cmd("SET", "k", "v") == "OK"
    assert conn.cmd("GET", "k") == b"v"
    conn.cmd("GET", "k")
    with pytest.raises(RuntimeError):
        conn.cmd("EXEC")  # EXEC without MULTI -> counted as failed
    info = conn.cmd("INFO", "commandstats").decode()
    lines = dict(
        line.split(":", 1)
        for line in info.strip().splitlines()
        if ":" in line
    )
    assert "cmdstat_get" in lines and "cmdstat_set" in lines
    get_fields = dict(
        kv.split("=") for kv in lines["cmdstat_get"].split(",")
    )
    assert get_fields["calls"] == "2"
    assert int(get_fields["usec"]) >= 0
    assert float(get_fields["usec_per_call"]) >= 0
    exec_fields = dict(
        kv.split("=") for kv in lines["cmdstat_exec"].split(",")
    )
    assert exec_fields["failed_calls"] == "1"
    # latencystats section exists and carries percentile fields.
    lat = conn.cmd("INFO", "latencystats").decode()
    assert "latency_percentiles_usec_get:p50=" in lat
    # Default INFO excludes commandstats (Redis parity), INFO all includes.
    assert "cmdstat_" not in conn.cmd("INFO").decode()
    assert "cmdstat_" in conn.cmd("INFO", "all").decode()
    # CONFIG RESETSTAT zeroes the section.
    assert conn.cmd("CONFIG", "RESETSTAT") == "OK"
    info = conn.cmd("INFO", "commandstats").decode()
    assert "cmdstat_get" not in info


def test_slowlog_over_resp(resp):
    conn, srv, cl = resp
    assert conn.cmd("SLOWLOG", "LEN") == 0
    assert conn.cmd("SLOWLOG", "GET") == []
    # Default threshold (10ms): a DEBUG SLEEP is slow, a PING is not.
    conn.cmd("PING")
    conn.cmd("DEBUG", "SLEEP", "0.02")
    assert conn.cmd("SLOWLOG", "LEN") == 1
    entries = conn.cmd("SLOWLOG", "GET")
    assert len(entries) == 1
    eid, ts, dur_us, args, addr, name = entries[0]
    assert dur_us >= 10_000
    assert args == [b"DEBUG", b"SLEEP", b"0.02"]
    assert b":" in addr  # client ip:port travels with the entry
    # Threshold 0 logs everything; max-len bounds the ring.
    assert conn.cmd("CONFIG", "SET", "slowlog-log-slower-than", "0") == "OK"
    assert conn.cmd("CONFIG", "SET", "slowlog-max-len", "4") == "OK"
    for i in range(8):
        conn.cmd("PING")
    entries = conn.cmd("SLOWLOG", "GET", "-1")
    assert len(entries) == 4
    ids = [e[0] for e in entries]
    assert ids == sorted(ids, reverse=True)  # newest first
    assert conn.cmd("SLOWLOG", "RESET") == "OK"
    # The RESET itself logs at threshold 0 — Redis does the same.
    assert conn.cmd("SLOWLOG", "LEN") <= 1
    assert any(b"GET [<count>|-1]" in h for h in conn.cmd("SLOWLOG", "HELP"))
    # get_metrics grows the command view without breaking the dict shape.
    snap = cl.get_metrics()
    assert snap["commands"]["PING"]["calls"] >= 9
    assert "slowlog_len" in snap


def test_slowlog_redacts_auth_and_multi_counts_once(resp):
    conn, srv, cl = resp
    assert conn.cmd("CONFIG", "SET", "slowlog-log-slower-than", "0") == "OK"
    # AUTH on a passwordless server errors — but its args must still be
    # redacted in the slowlog (the password was typed either way).
    with pytest.raises(RuntimeError):
        conn.cmd("AUTH", "s3cret-password")
    flat = repr(conn.cmd("SLOWLOG", "GET", "-1"))
    assert "s3cret-password" not in flat
    assert "(redacted)" in flat
    # HELLO ... AUTH user pass: only the credential pair is redacted.
    with pytest.raises(RuntimeError):
        conn.cmd("HELLO", "3", "AUTH", "default", "hello-secret")
    flat = repr(conn.cmd("SLOWLOG", "GET", "-1"))
    assert "hello-secret" not in flat
    # MULTI queue-time must not double-count commandstats: one queued
    # SET executed by EXEC records exactly one SET call.
    assert conn.cmd("CONFIG", "RESETSTAT") == "OK"
    assert conn.cmd("MULTI") == "OK"
    assert conn.cmd("SET", "mk", "mv") == "QUEUED"
    assert conn.cmd("EXEC") == ["OK"]
    stats = cl.get_metrics()["commands"]
    assert stats["SET"]["calls"] == 1, stats
    assert stats["EXEC"]["calls"] == 1
    # Blocking commands: parked time is wait, not work — calls count
    # but no latency sample and no slowlog entry (threshold is 0 here,
    # so ANY recorded duration would enter the ring).
    before = len(cl.obs.slowlog)
    assert conn.cmd("BLPOP", "absent-q", "0.15") is None
    stats = cl.get_metrics()["commands"]
    assert stats["BLPOP"]["calls"] == 1
    assert stats["BLPOP"]["usec"] == 0  # no latency observed
    assert not any(
        e.args and e.args[0] == b"BLPOP"
        for e in cl.obs.slowlog.entries()
    )
    assert len(cl.obs.slowlog) >= before  # other commands still log


# -- prometheus endpoint ----------------------------------------------------


def test_prometheus_labels_and_types(tpu_client):
    srv = RespServer(tpu_client)
    conn = RespClient(srv.host, srv.port)
    try:
        conn.cmd("SET", "k", "v")
        conn.cmd("GET", "k")
        bf = tpu_client.get_bloom_filter("prom-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(256, dtype=np.uint64))
        text = tpu_client.render_prometheus()
        # Per-command labeled series, typed counter with _total suffix.
        assert "# TYPE rtpu_resp_commands_total counter" in text
        assert 'rtpu_resp_commands_total{cmd="GET"} 1' in text
        # Per-tenant labeled series.
        assert "# TYPE rtpu_tenant_ops_total counter" in text
        assert 'tenant="prom-bf"' in text
        # Phase histograms are real histogram families.
        assert "# TYPE rtpu_op_phase_seconds histogram" in text
        assert 'phase="device_dispatch"' in text
        # Executor health gauges typed gauge.
        assert "# TYPE rtpu_coalescer_queued_ops gauge" in text
        assert "# TYPE rtpu_tenants gauge" in text
        assert 'rtpu_tenants{kind="bloom"} 1' in text
        assert "# TYPE rtpu_pool_rows gauge" in text
        # Legacy aggregate rides along with corrected types.
        assert "# TYPE redisson_tpu_ops_total counter" in text
    finally:
        conn.close()
        srv.close()


def test_metrics_http_endpoint(tpu_client):
    import http.client

    bf = tpu_client.get_bloom_filter("http-bf")
    bf.try_init(10_000, 0.01)
    bf.add_all(np.arange(64, dtype=np.uint64))
    srv = tpu_client.start_metrics_endpoint()
    assert tpu_client.start_metrics_endpoint() is srv  # one shared server
    with pytest.raises(RuntimeError):  # conflicting rebind must not be
        tpu_client.start_metrics_endpoint(port=srv.port + 1)  # silent
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/plain")
    body = resp.read().decode()
    assert "rtpu_tenant_ops_total" in body
    assert "redisson_tpu_ops_total" in body
    conn.request("GET", "/nope")
    assert conn.getresponse().status == 404
    conn.close()


# -- metric-catalog doc sync (ISSUE 13 satellite) ---------------------------


def test_metric_catalog_matches_doc():
    """docs/observability.md's labeled-registry table is CANONICAL:
    every family/gauge a fully-featured engine registers must appear in
    the table, and every table row must exist in the registry — the
    catalog can never drift again (it was missing the PR 10-12
    families when ISSUE 13 landed)."""
    import os

    doc_path = os.path.join(
        os.path.dirname(__file__), "..", "docs", "observability.md"
    )
    with open(doc_path) as f:
        doc = f.read()
    # Rows of the "Labeled registry" table only (the legacy aggregate
    # table and prose mentions don't count).
    section = doc.split("### Labeled registry", 1)[1]
    section = section.split("\n## ", 1)[0]
    doc_names = set()
    for line in section.splitlines():
        m = __import__("re").match(r"\|\s*`(rtpu_[a-z0-9_]+)`", line)
        if m:
            doc_names.add(m.group(1))
    assert doc_names, "doc table parse found no rows"

    # A fully-featured engine: coalescer + prewarmer + journal gauges.
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        min_bucket=64, prewarm=True
    )
    cl = redisson_tpu.create(cfg)
    try:
        reg = cl.obs.registry
        registered = set(reg._families)
        registered |= {name for name, _, _, _ in reg._callbacks}
    finally:
        cl.shutdown()

    # Load-attribution families (ISSUE 16): pinned by name so a rename
    # that dodges the generic diff below still fails loudly here.
    assert {
        "rtpu_tenant_device_us_total", "rtpu_loadmap_slot_ops",
        "rtpu_loadmap_sampled_keys", "rtpu_loadmap_tracked_keys",
    } <= registered

    missing_from_doc = registered - doc_names
    assert not missing_from_doc, (
        f"families registered but absent from the "
        f"docs/observability.md table: {sorted(missing_from_doc)}"
    )
    stale_in_doc = doc_names - registered
    assert not stale_in_doc, (
        f"doc table rows with no registered family: "
        f"{sorted(stale_in_doc)}"
    )


def test_spanrecorder_public_reset():
    """Satellite 6: the bench lifecycle reset is a PUBLIC SpanRecorder
    surface — no more reaching into ``spans._phase_hist`` privates."""
    obs = Observability()
    s = obs.spans.start("op-x", 8)
    s.stamp("d2h_fetch")
    s.finish()
    assert obs.spans.recent()
    assert obs.spans._total_hist.items()
    obs.spans.reset()
    assert obs.spans.recent() == []
    assert not obs.spans._total_hist.items()
    assert not obs.spans._ops.items()
    # Observability.reset_op_stats delegates to it (bench call site).
    s2 = obs.spans.start("op-y", 1)
    s2.stamp("d2h_fetch")
    s2.finish()
    obs.reset_op_stats()
    assert obs.spans.recent() == []


# -- overhead guard ---------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("path", ["submit", "producer"])
def test_metrics_overhead_under_ten_percent(path):
    """Hot-path guard (ISSUE 1 acceptance): op submit through an
    instrumented engine path must be ≤10% slower than through a no-op
    metrics stub.

    ``submit``: the instrumentation ``coalescer.submit`` pays — a
    span-recording obs bundle and a tenant label riding every submit
    (per-tenant accounting defers to the completer thread), against the
    identical calls with obs disabled.  ``producer``: the whole client
    call ``add_all_async`` through the engine, whose encode and submit
    are timed (``rtpu_encode_seconds`` / ``rtpu_submit_seconds``) inside
    regions that are no-ops with no capture active, against the same
    client with those two timers stubbed out.  A long batch window keeps
    the flush thread parked, so the timing covers the producer alone
    rather than GIL contention with dispatch; rounds interleave A/B with
    GC paused and compare MINIMA (the noise-free intrinsic cost)."""
    import contextlib
    import gc

    from redisson_tpu.executor.coalescer import BatchCoalescer

    class _Lazy:
        def __init__(self, v):
            self._v = v

        def result(self):
            return self._v

    def dispatch(cols):
        return _Lazy(np.concatenate(cols))

    arr = np.arange(64, dtype=np.int64)
    N = 2000 if path == "submit" else 500
    rounds = 12 if path == "submit" else 8
    null = contextlib.nullcontext()
    clients = []

    def make(instrumented):
        if path == "submit":
            # Window >> test duration and max_batch > N*64: nothing
            # flushes while the timed loop runs (drained at shutdown).
            c = BatchCoalescer(
                batch_window_us=30_000_000, max_batch=1 << 22,
                max_queued_ops=1 << 24,
                obs=Observability() if instrumented else None,
            )
            tenant = "bench-tenant" if instrumented else None
            return c, lambda: c.submit(("op",), dispatch, (arr,), 64,
                                       tenant=tenant)
        cl = redisson_tpu.create(Config().set_codec(LongCodec()).use_tpu_sketch(
            min_bucket=64, batch_window_us=30_000_000, max_batch=1 << 22,
            max_queued_ops=1 << 24, adaptive_window=False,
        ))
        if not instrumented:
            cl._engine.obs.encode_timer = lambda kind: null
            cl._engine.obs.submit_timer = lambda op: null
        bf = cl.get_bloom_filter("overhead")
        bf.try_init(1_000_000, 0.01)
        return cl, lambda: bf.add_all_async(arr)

    def round_time(call):
        t0 = time.perf_counter()
        for _ in range(N):
            call()
        return time.perf_counter() - t0

    def measure():
        plain, instrumented = [], []
        gc.disable()
        try:
            for r in range(rounds):
                if path == "submit" or not clients:
                    pair = (make(False), make(True))
                    clients.extend(pair)
                (_, ca), (_, cb) = clients[-2:]
                # Warm both paths' allocator/lock state before timing,
                # then alternate A/B order per round so bursty load on a
                # shared box can't systematically tax one arm.
                round_time(ca)
                round_time(cb)
                if r % 2 == 0:
                    plain.append(round_time(ca))
                    instrumented.append(round_time(cb))
                else:
                    instrumented.append(round_time(cb))
                    plain.append(round_time(ca))
        finally:
            gc.enable()
        return plain, instrumented

    # External load only ever INFLATES a sample, so the intrinsic
    # overhead is bounded by the cleanest observation: min of per-round
    # PAIRED ratios (adjacent measurements share any load burst), with a
    # few attempts to find a quiet window.
    history = []
    try:
        for _ in range(4):
            plain, instrumented = measure()
            ratio = min(q / p for p, q in zip(plain, instrumented))
            ratio = min(ratio, min(instrumented) / min(plain))
            history.append(ratio)
            if ratio <= 1.10:
                return
    finally:
        for c, _ in clients:
            c.shutdown()
    raise AssertionError(f"instrumented {path} >10% slower: {history}")


@pytest.mark.slow
def test_trace_off_overhead_under_five_percent():
    """ISSUE 13 overhead guard, same harness as the ≤10% guard above:
    with sampling OFF, the trace hooks on the submit path must cost
    ≤5% over the same path with the trace module stubbed out entirely.

    The stub arm replaces the coalescer's ``_trace`` module with a
    bare ``ENABLED = False`` namespace — identical flag-read cost, but
    any future regression that does REAL work on the off path (calling
    current(), minting contexts, taking locks) shows up only in the
    live arm and trips the ratio."""
    import gc

    from redisson_tpu.executor import coalescer as co_mod
    from redisson_tpu.executor.coalescer import BatchCoalescer

    assert not co_mod._trace.ENABLED, (
        "a tracer leaked an armed sample rate into this test"
    )

    class _Lazy:
        def __init__(self, v):
            self._v = v

        def result(self):
            return self._v

    def dispatch(cols):
        return _Lazy(np.concatenate(cols))

    class _Stub:
        ENABLED = False

    arr = np.arange(64, dtype=np.int64)
    N = 2000

    def make():
        return BatchCoalescer(
            batch_window_us=30_000_000, max_batch=1 << 22,
            max_queued_ops=1 << 24, obs=Observability(),
        )

    def round_time(c):
        t0 = time.perf_counter()
        for _ in range(N):
            c.submit(("op",), dispatch, (arr,), 64, tenant="t")
        return time.perf_counter() - t0

    def measure():
        live, stubbed = [], []
        coalescers = []
        real = co_mod._trace
        gc.disable()
        try:
            for r in range(12):
                ca, cb = make(), make()
                coalescers += [ca, cb]
                round_time(ca)
                round_time(cb)
                if r % 2 == 0:
                    co_mod._trace = real
                    live.append(round_time(ca))
                    co_mod._trace = _Stub
                    stubbed.append(round_time(cb))
                else:
                    co_mod._trace = _Stub
                    stubbed.append(round_time(cb))
                    co_mod._trace = real
                    live.append(round_time(ca))
        finally:
            co_mod._trace = real
            gc.enable()
            for c in coalescers:
                c.shutdown()
        return stubbed, live

    history = []
    for _ in range(4):
        stubbed, live = measure()
        ratio = min(q / p for p, q in zip(stubbed, live))
        ratio = min(ratio, min(live) / min(stubbed))
        history.append(ratio)
        if ratio <= 1.05:
            return
    raise AssertionError(
        f"sampling-off tracing >5% over stubbed hooks: {history}"
    )
