"""Warm-path dispatch (ISSUE 2 tentpole): AOT bucket pre-compilation,
pinned staging buffers / fused H2D, and the adaptive flush window.

Covers the satellite test checklist:
- pre-warm populates the jit cache for every bucket ≤ max_batch;
- staging buffers are reused across flushes (no per-flush allocation
  growth);
- the adaptive window converges down under sparse traffic and up under
  a burst;
- warm-path results are identical to the host golden engine;
- (slow) no jit compile occurs after pre-warm completes, and warm-path
  submit overhead stays bounded.
"""

import time

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec
from redisson_tpu.executor import prewarm
from redisson_tpu.utils import hashing


def _client(**kw):
    cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
        min_bucket=64, **kw
    )
    return redisson_tpu.create(cfg)


# -- AOT pre-warm -----------------------------------------------------------


def test_prewarm_populates_every_bucket():
    """The first encoded submit schedules the per-op keyed ladder
    (bloom_mixed_keys, the replica and mixed-flag launch) at every
    bucket; the submit itself runs the run-length path."""
    cl = _client(prewarm=True, max_batch=2048)
    try:
        bf = cl.get_bloom_filter("pw-bf")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(100, dtype=np.uint64))
        assert cl.prewarm_wait(300), "pre-warm did not drain"
        ex = cl._engine.executor
        pw = cl._engine.prewarmer
        ladder = pw.ladder()
        assert ladder[-1] >= 2048 and len(ladder) >= 4
        cached = {k[3] for k in ex._jit_cache if k[0] == "bloom_mixed_keys"}
        assert cached == set(ladder), (sorted(cached), ladder)
        assert pw.errors == 0
    finally:
        cl.shutdown()


def test_prewarm_keyed_ladder_registers_on_first_submit():
    """The codec-shaped (lane count / trim depth) runs-path ladder can't
    be known at pool attach; the FIRST encoded submit schedules it, for
    both run-length kernels: the contains-only gather and the scan."""
    cl = _client(prewarm=True, max_batch=1024)
    try:
        bf = cl.get_bloom_filter("pw-keyed")
        bf.try_init(10_000, 0.01)
        bf.add_all(np.arange(100, dtype=np.uint64))
        assert cl.prewarm_wait(300)
        ex = cl._engine.executor
        ladder = set(cl._engine.prewarmer.ladder())
        for op in ("bloom_mixk_runs", "bloom_conk_runs"):
            runs = {k[3] for k in ex._jit_cache if k[0] == op}
            assert ladder <= runs, (op, sorted(runs), sorted(ladder))
        assert cl._engine.prewarmer.errors == 0
        # Warm dispatches ran against scratch state: tenant data intact.
        assert bf.contains_all(np.arange(100, dtype=np.uint64)) == 100
        assert bf.contains(np.uint64(999_983)) is False
    finally:
        cl.shutdown()


def test_prewarm_reruns_on_pool_growth():
    """Growth changes the state shape → every jit key; the ladder must
    re-run against the new layout."""
    cl = _client(prewarm=True, max_batch=512,
                 initial_tenants_per_class=2)
    try:
        filters = []
        for i in range(3):  # 3 tenants > capacity 2 -> one growth
            bf = cl.get_bloom_filter(f"grow-{i}")
            bf.try_init(1000, 0.01)
            filters.append(bf)
            if i == 0:
                # The first submit registers the pool's keyed ladders;
                # the third tenant's growth must re-run them.
                bf.add_all(np.arange(10, dtype=np.uint64))
                assert cl.prewarm_wait(300)
        assert cl.prewarm_wait(300)
        ex = cl._engine.executor
        pool = cl._engine.registry.lookup("grow-0").pool
        assert pool.generation >= 1
        state_len = pool.state.shape[0]
        cached = {
            k[3] for k in ex._jit_cache
            if k[0] == "bloom_mixed_keys" and k[2] == state_len
        }
        assert cached == set(cl._engine.prewarmer.ladder()), (
            sorted(k for k in ex._jit_cache if k[0] == "bloom_mixed_keys"),
            state_len,
            cl._engine.prewarmer.errors,
        )
    finally:
        cl.shutdown()


def test_prewarm_rebinds_on_change_topology():
    """A live reshard retires the executor: the pre-warmer must adopt
    the successor and re-run its ladders (a stale binding would silently
    skip every warm task while prewarm_wait reported success)."""
    cl = _client(prewarm=True, max_batch=512)
    try:
        bf = cl.get_bloom_filter("rt-bf")
        bf.try_init(1000, 0.01)
        assert cl.prewarm_wait(300)
        assert cl.change_topology(2) is True
        assert cl._engine.prewarmer._executor is cl._engine.executor
        assert cl.prewarm_wait(300)
        assert cl._engine.prewarmer.errors == 0
        bf.add_all(np.arange(50, dtype=np.uint64))
        assert bf.contains_all(np.arange(50, dtype=np.uint64)) == 50
    finally:
        cl.shutdown()


# -- pinned staging ---------------------------------------------------------


def test_staging_buffers_reused_across_flushes():
    """Same-bucket dispatches must land in the SAME host staging buffers
    (ring reuse) — no per-flush allocation growth.  Exercised on the
    executor directly (the rings are thread-local, so the test thread
    dispatching directly observes its own ring)."""
    cl = _client(coalesce=False)
    try:
        bf = cl.get_bloom_filter("stage-bf")
        bf.try_init(10_000, 0.01)
        ex = cl._engine.executor
        entry = cl._engine.registry.lookup("stage-bf")
        pool, row = entry.pool, entry.row
        m, k = entry.params["size"], entry.params["hash_iterations"]
        rng = np.random.default_rng(0)

        def dispatch_once():
            B = 64
            rows = np.full(B, row, np.int32)
            m_arr = np.full(B, m, np.uint32)
            keys = rng.integers(1 << 40, 1 << 50, B).astype(np.uint64)
            blocks, lengths = hashing.encode_uint64_batch(keys)
            ex.bloom_mixed_keys(pool, rows, m_arr, k, blocks, lengths,
                                np.zeros(B, bool))

        for _ in range(10):  # > ring depth: every slot allocated
            dispatch_once()
        rings = ex._staging.rings  # this thread's rings
        # Keys of 2 lanes in use (Lt = 2) after trimming.
        key = ("bloom_mixed_keys", ex._bucket(64), 2)
        assert key in rings
        bufs_before = {id(s.buf) for s in rings[key][1]}
        assert len(bufs_before) <= 8  # bounded by ring depth
        for _ in range(40):  # 5x ring depth more flushes
            dispatch_once()
        bufs_after = {id(s.buf) for s in rings[key][1]}
        # Same-bucket flushes cycled the SAME buffers — zero new
        # allocations after the ring filled.
        assert bufs_after == bufs_before
    finally:
        cl.shutdown()


def test_fused_block_roundtrip_preserves_results():
    """The packed-block encode (host) and slice/bitcast decode (device)
    must be lossless: interleaved add/contains with duplicate keys via
    the fused kernels matches the golden host engine exactly."""
    # Default codec (string keys ride the encoded device-hash path).
    tpu = redisson_tpu.create(
        Config().use_tpu_sketch(min_bucket=64, batch_window_us=500)
    )
    host = redisson_tpu.create(Config())
    try:
        rng = np.random.default_rng(42)
        a = tpu.get_bloom_filter("diff-bf")
        b = host.get_bloom_filter("diff-bf")
        for f in (a, b):
            f.try_init(5000, 0.01)
        for _ in range(8):
            keys = rng.integers(0, 3000, 257).astype(np.uint64)
            assert a.add_all(keys) == b.add_all(keys)
            probe = rng.integers(0, 6000, 511).astype(np.uint64)
            np.testing.assert_array_equal(
                a.contains_each(probe), b.contains_each(probe)
            )
        # HLL + bitset + CMS through their fused coalesced kernels.
        ha, hb = tpu.get_hyper_log_log("diff-h"), host.get_hyper_log_log("diff-h")
        ha.add_all(np.arange(5000, dtype=np.uint64))
        hb.add_all(np.arange(5000, dtype=np.uint64))
        assert ha.count() == hb.count()
        sa, sb = tpu.get_bit_set("diff-bs"), host.get_bit_set("diff-bs")
        idx = rng.integers(0, 4096, 300).astype(np.uint32)
        np.testing.assert_array_equal(sa.set_many(idx), sb.set_many(idx))
        np.testing.assert_array_equal(
            sa.get_many(np.arange(4096, dtype=np.uint32)),
            sb.get_many(np.arange(4096, dtype=np.uint32)),
        )
        ca, cb = (
            tpu.get_count_min_sketch("diff-c"),
            host.get_count_min_sketch("diff-c"),
        )
        ca.try_init(4, 1 << 10)
        cb.try_init(4, 1 << 10)
        ca.add_all(["hot"] * 100 + ["cold"] * 3)
        cb.add_all(["hot"] * 100 + ["cold"] * 3)
        assert ca.estimate("hot") == cb.estimate("hot")
        assert ca.estimate("cold") == cb.estimate("cold")
    finally:
        tpu.shutdown()
        host.shutdown()


def test_many_run_segment_takes_array_path_and_stays_correct():
    """>1024 runs in one coalesced segment expand to per-op arrays (the
    runs kernel's Cp compile space stays the single pre-warmed 1024
    bucket) — results must be unchanged."""
    cl = _client(batch_window_us=200_000, max_batch=1 << 18)
    try:
        bf = cl.get_bloom_filter("runs-bf")
        bf.try_init(10_000, 0.01)
        # 1200 single-key submits join ONE segment (long window, same
        # pool) -> 1200 runs at flush.
        futs = [bf.add_async(np.uint64(i % 700)) for i in range(1200)]
        results = [f.result() for f in futs]
        assert sum(results) == 700  # duplicates report False, exactly
        ex = cl._engine.executor
        # No runs-kernel key beyond Cp=1024 was compiled.
        assert not any(
            k[0] == "bloom_mixk_runs" and k[7] > 1024
            for k in ex._jit_cache
        ), [k for k in ex._jit_cache if k[0] == "bloom_mixk_runs"]
        assert bf.contains_all(np.arange(700, dtype=np.uint64)) == 700
    finally:
        cl.shutdown()


# -- adaptive flush window --------------------------------------------------


def _bare_coalescer(**kw):
    from redisson_tpu.executor.coalescer import BatchCoalescer

    class _Lazy:
        def __init__(self, v):
            self._v = v

        def result(self):
            return self._v

    c = BatchCoalescer(
        batch_window_us=kw.pop("batch_window_us", 1000),
        max_batch=kw.pop("max_batch", 4096),
        **kw,
    )
    return c, lambda cols: _Lazy(np.concatenate(cols))


def test_adaptive_window_converges_down_when_sparse():
    c, dispatch = _bare_coalescer(batch_window_us=1000)
    try:
        arr = np.arange(4, dtype=np.int64)
        for _ in range(30):  # sparse trickle: a few ops, long gaps
            c.submit(("op",), dispatch, (arr,), 4).result(10)
            time.sleep(0.01)
        assert c.window_s <= c.base_window_s, (c.window_s, c.base_window_s)
        assert c.window_s == pytest.approx(c.min_window_s, rel=0.5)
    finally:
        c.shutdown()


def test_adaptive_window_grows_under_burst():
    c, dispatch = _bare_coalescer(batch_window_us=1000, max_batch=1 << 20)
    try:
        arr = np.arange(4096, dtype=np.int64)
        futs = [
            c.submit(("op",), dispatch, (arr,), 4096) for _ in range(200)
        ]
        deadline = time.monotonic() + 5.0
        while c.window_s < c.max_window_s * 0.5 and time.monotonic() < deadline:
            futs.append(c.submit(("op",), dispatch, (arr,), 4096))
            if len(futs) > 400:
                for f in futs[:200]:
                    f.result(10)
                del futs[:200]
        assert c.window_s > c.base_window_s, (c.window_s, c.base_window_s)
        for f in futs:
            f.result(10)
    finally:
        c.shutdown()


def test_adaptive_window_stays_inside_bounds_and_can_disable():
    c, _ = _bare_coalescer(
        batch_window_us=1000, min_window_us=200, max_window_us=5000
    )
    try:
        assert c.min_window_s == pytest.approx(200e-6)
        assert c.max_window_s == pytest.approx(5000e-6)
    finally:
        c.shutdown()
    c2, dispatch = _bare_coalescer(batch_window_us=777, adaptive_window=False)
    try:
        arr = np.arange(8, dtype=np.int64)
        for _ in range(5):
            c2.submit(("op",), dispatch, (arr,), 8).result(10)
        assert c2.window_s == pytest.approx(777e-6)  # fixed when disabled
    finally:
        c2.shutdown()


# -- slow guards ------------------------------------------------------------


@pytest.mark.slow
def test_no_compile_after_prewarm_completes():
    """The acceptance teeth: once pre-warm drains, a serving-shaped
    workload over every bucket of the ladder triggers ZERO XLA backend
    compiles (counted via the jax.monitoring hook)."""
    cl = _client(prewarm=True, max_batch=2048, batch_window_us=200)
    try:
        bf = cl.get_bloom_filter("nc-bf")
        bf.try_init(10_000, 0.01)
        # First encoded submit reveals the codec signature (pays its own
        # compile) and schedules the keyed ladders.
        bf.add_all(np.arange(64, dtype=np.uint64))
        assert cl.prewarm_wait(600)
        before = prewarm.compile_count()
        rng = np.random.default_rng(1)
        for nops in (1, 33, 64, 100, 128, 500, 1024, 2000):
            keys = rng.integers(0, 20_000, nops).astype(np.uint64)
            bf.add_all(keys)
            bf.contains_all(keys)
        cl._engine._drain()
        after = prewarm.compile_count()
        assert after == before, f"{after - before} compiles on warm path"
    finally:
        cl.shutdown()


@pytest.mark.slow
def test_warm_path_submit_overhead_bounded():
    """Warm-path producer overhead guard: with every kernel pre-warmed,
    the mean async submit cost for a 256-op chunk stays well under a
    millisecond (paired-minimum measurement, tolerant of shared-box
    noise)."""
    cl = _client(prewarm=True, max_batch=1 << 16, batch_window_us=500)
    try:
        bf = cl.get_bloom_filter("ov-bf")
        bf.try_init(100_000, 0.01)
        bf.add_all(np.arange(256, dtype=np.uint64))
        assert cl.prewarm_wait(600)
        rng = np.random.default_rng(2)
        chunks = [
            rng.integers(0, 80_000, 256).astype(np.uint64) for _ in range(64)
        ]
        best = float("inf")
        for _ in range(5):
            futs = []
            t0 = time.perf_counter()
            for ch in chunks:
                futs.append(bf.add_all_async(ch))
            dt = (time.perf_counter() - t0) / len(chunks)
            best = min(best, dt)
            for f in futs:
                f.result()
        assert best < 2e-3, f"warm submit cost {best * 1e3:.2f} ms/chunk"
    finally:
        cl.shutdown()
