"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.json): Bloom ``contains()`` ops/sec/chip on the
steady-state batched path through the full public API (codec encode →
device-side hash → kernel → bit-packed result transfer).

The other tracked BASELINE metrics ride in ``extra``:
- ``hll_pfadd_ops_per_sec``: config-2 HLL add throughput at the full
  10M-cardinality stream geometry (19 x 512k disjoint key batches);
- ``p99_batch_ms`` / ``p50_batch_ms``: config-4 multi-tenant run — 1000
  tenants, mixed add/contains through the coalescer — measured by the
  in-framework Metrics class (enqueue→flush);
- ``config4_mixed_ops_per_sec``: throughput of that coalesced mixed run;
- ``measured_fpp``: observed false-positive rate of the loaded config-1
  filter (target ≤ ~1.2 * nominal 1%), the FPP-drift evidence.

``vs_baseline``: null — the bench env ships no redis-server, so the
Redis-backed comparison cannot be MEASURED here (BASELINE.md comparison
row); ``vs_host_engine`` is the measured ratio against the NumPy golden
engine (the Redis-server stand-in) through the identical client path.
"""

import json
import threading
import time

import numpy as np


_rt_probe = None


def measure_rt_sample():
    """ONE quick resident-round-trip sample (~3 fetches of a ready 4KB
    array) — interleaved between measurement passes so every latency/
    throughput number travels with the link RT measured in ITS window
    (phase-conditional reporting: over the remote link the early rounds
    used, RT swung 0.2 ms-2.5 s between minutes on identical code).  The
    probe program and array are cached module-wide: a fresh jit(lambda)
    per call would recompile and re-upload each sample (jit caches by
    function identity)."""
    global _rt_probe
    import jax

    if _rt_probe is None:
        x = jax.device_put(np.ones(1024, np.uint32))
        f = jax.jit(lambda a: a.sum())
        f(x).block_until_ready()
        _rt_probe = (f, x)
    f, x = _rt_probe
    t0 = time.perf_counter()
    for _ in range(3):
        int(f(x))
    return round((time.perf_counter() - t0) / 3 * 1000, 2)


def bench_bloom_contains(client):
    """Config 1: 1M keys / 1% FPP, steady-state contains throughput.

    RT-insensitive shape (round-5): each measured pass is ONE collect
    group of ~16M ops — every launch dispatches with its eager D2H
    prefetch suppressed (client.defer_fetch inside contains_many), and
    the whole pass resolves through ONE device-concat mailbox fetch.
    With a single sync per pass, a 263 ms link RT costs 263 ms out of a
    ~1.5 s pass instead of one RT per launch chunk — the capture
    converges toward the device-kernel number in ANY link phase
    (extra.ops_per_sync records the group size)."""
    bf = client.get_bloom_filter("bench-bf")
    bf.try_init(1_000_000, 0.01)

    n_load = 1 << 20
    adds = [
        bf.add_all_async(np.arange(i << 18, (i + 1) << 18, dtype=np.uint64))
        for i in range(n_load >> 18)
    ]
    n_added = sum(int(np.sum(r.result())) for r in adds)
    assert 0.97 * n_load <= n_added <= n_load, n_added

    rng = np.random.default_rng(0)

    def run_pass(B, iters):
        batches = [
            rng.integers(0, 2 * n_load, size=B).astype(np.uint64)
            for _ in range(iters)
        ]
        t0 = time.perf_counter()
        # Pipelined bulk form (the RBatch idiom): all launches dispatch,
        # results come home in one device-concat mailbox fetch — each
        # host fetch over a remote link costs a full round trip, so one
        # reply flush per pass instead of per batch.
        results = bf.contains_many(batches)
        n_hits = sum(int(np.sum(r)) for r in results)
        dt = time.perf_counter() - t0
        assert 0.3 < n_hits / (iters * B) < 0.7, n_hits
        return iters * B / dt

    # The remote link's cost structure was phase-dependent: some phases charge
    # ~one round trip per FETCH only (H2D streams at GB/s), others charge
    # ~one RT per TRANSFER — H2D and dispatch included (r5 measured 2 ms
    # and 325 ms for the same 2 MB device_put minutes apart).  The only
    # shape fast in BOTH regimes is few, huge launches: the probe ranges
    # up to 8M-key batches, so a measured pass is 2-4 H2D+launches plus
    # ONE mailbox fetch — a handful of RTs per 16-32M ops, whatever the
    # phase charges per RT.  (Big-bucket kernels compile once and ride
    # the persistent compile cache across runs.)
    PROBE_OPS = 1 << 23
    # Warm EVERY bucket the probe and the measured passes can hit,
    # OUTSIDE any timed window: probe passes with iters>=2 concatenate
    # to the PROBE_OPS bucket and measured passes to the TOTAL bucket —
    # a cold compile landing inside a timed pass would bias the argmax
    # toward whichever candidate dodged it.
    for WB in (1 << 20, 1 << 21, 1 << 22, 1 << 23, 1 << 24):
        bf.contains_all_async(np.arange(WB, dtype=np.uint64)).result()
    probe = {}
    for B in (1 << 20, 1 << 21, 1 << 22, 1 << 23):
        probe[B] = run_pass(B, max(1, PROBE_OPS // B))
    B = max(probe, key=probe.get)

    # 16-32M ops per pass, ONE mailbox sync per pass (ops_per_sync): at
    # that scale the per-pass sync cost is a single round trip, so the
    # number is link-phase-insensitive.  Best-of-3 measured passes with
    # an interleaved RT sample per pass: per-pass numbers + same-window
    # RT travel in extra so a drop is attributable (engine regression vs
    # link phase) from the JSON alone.
    TOTAL = 1 << 24  # flat: a deep-slow phase must not blow wall-clock
    iters = max(2, TOTAL // B)
    passes = []
    pass_rt_ms = []
    # Phase BRACKETS on the headline itself (ROADMAP measurement-debt
    # note, ISSUE 14 satellite): each measured pass travels with
    # [pre, post] samples of BOTH link probes, so an r03->r05-style
    # headline decline is attributable to the link phase from
    # BENCH.json alone — the config4 pass-link discipline applied to
    # the headline keys.
    pass_link = []
    bracket = measure_pass_link_sample()
    for _pass in range(3):
        passes.append(run_pass(B, iters))
        post = measure_pass_link_sample()
        pass_link.append({
            k: [bracket[k], post[k]]
            for k in ("link_h2d_put_rt_ms", "link_resident_rt_ms")
        })
        pass_rt_ms.append(post["link_resident_rt_ms"])
        bracket = post

    # Measured FPP: probe keys strictly outside the loaded range.
    fp_keys = rng.integers(3 * n_load, 8 * n_load, size=1 << 17).astype(np.uint64)
    fpp = float(np.mean(bf.contains_each(fp_keys)))
    return max(passes), fpp, passes, B, iters * B, pass_rt_ms, pass_link


def bench_hll_pfadd(client):
    """Config 2 at FULL spec geometry: a 10M-cardinality stream of PFADDs
    (warm + 4 x 2M disjoint keys ≈ 10.5M) + estimate sanity.  Few, huge
    batches stay fast in BOTH link regimes (per-fetch-RT and
    per-transfer-RT — see bench_bloom_contains)."""
    h = client.get_hyper_log_log("bench-hll")
    B = 1 << 21
    h.add_all_async(np.arange(B, dtype=np.uint64)).result()  # warm
    iters = 4  # warm + 4 x 2M disjoint keys ≈ the 10M-cardinality spec
    # Measured batches are DISJOINT from the warm batch ([0, B)) — the
    # expected-cardinality check below counts warm + iters distinct keys.
    batches = [
        np.arange((i + 1) * B, (i + 2) * B, dtype=np.uint64)
        for i in range(iters)
    ]
    t0 = time.perf_counter()
    # One mailbox flush for all passes' 'changed' flags (client.collect)
    # instead of one link round trip per batch; defer_fetch suppresses
    # the per-launch eager D2H so the flush is the ONLY sync.
    with client.defer_fetch():
        futs = [h.add_all_async(b) for b in batches]
    client.collect(futs)
    dt = time.perf_counter() - t0
    n = (iters + 1) * B
    est = h.count()
    assert abs(est - n) / n < 0.05, (est, n)
    return iters * B / dt


def _paced_load(filters, *, n_threads, chunk, offered_qps, duration_s,
                seed_base=100):
    """Paced offered load against a tenant set: each producer paces its
    submissions against the wall clock; back-pressure is the ENGINE's
    (max_queued_ops admission control in the coalescer) — producers hold
    futures without any client-side window, shedding completed ones
    without blocking.  Returns sustained ops/s."""
    import threading
    from collections import deque

    n_tenants = len(filters)
    per_thread_qps = offered_qps / n_threads
    chunk_interval = chunk / per_thread_qps
    counts = [0] * n_threads

    def worker(tid):
        trng = np.random.default_rng(seed_base + tid)
        futs = deque()
        t_start = time.perf_counter()
        step = 0
        while True:
            now = time.perf_counter() - t_start
            if now >= duration_s:
                break
            target_steps = int(now / chunk_interval)
            if step >= target_steps:
                time.sleep(min(chunk_interval, 0.001))
                continue
            t = int(trng.integers(n_tenants))
            keys = trng.integers(0, 50_000, chunk).astype(np.uint64)
            if step % 3 == 0:
                futs.append(filters[t].add_all_async(keys))
            else:
                futs.append(filters[t].contains_all_async(keys))
            step += 1
            while futs and futs[0].done():  # shed resolved, never block;
                futs.popleft().result()  # .result() surfaces op failures
        for f in futs:
            f.result(timeout=600.0)  # a cold-pass compile may be in flight
        counts[tid] = step * chunk

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(n_threads)
    ]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return sum(counts) / (time.perf_counter() - t0)


def bench_config4_mixed(make_client):
    """Config 4: 1000-tenant stacked blooms, mixed add/contains through the
    coalescer at the spec's offered-load regime (1M QPS target): producers
    are PACED slightly above the target, so the reported throughput is
    "can the engine sustain the offered load" and p50/p99 batch wait is
    the queueing delay at that load — not at saturation.

    Warm/cold split (ISSUE 2): the COLD pass starts immediately after
    client creation, while the AOT pre-warmer is still compiling the
    bucket ladder in the background — it measures the residual cliff a
    cold process serves (r05 measured 9,933 ops/s with compiles landing
    INSIDE the serving window).  The WARM pass runs after prewarm_wait +
    a steady-state warm burst, with metrics reset, so its percentiles
    describe the pure warm path.

    Knobs (swept over a remote link in round 3, not yet measured on an
    attached chip): max_batch=256k lets a
    backlog collapse into few big launches (merge-at-pop); max_inflight=16
    bounds dispatched-but-uncollected segments — with the completer
    collecting promptly, 16 measured best (the ~12-dispatch cliff applies
    to UN-collected queues); min_bucket=4096 bounds the set of padded
    shapes so the pre-warm ladder covers every compile.
    """
    client = make_client(coalesce=True, exact_add_semantics=True,
                         batch_window_us=200, max_batch=1 << 18,
                         min_bucket=4096, max_inflight=16, min_inflight=4,
                         max_queued_ops=1 << 19, prewarm=True)
    n_tenants = 1000
    filters = []
    for t in range(n_tenants):
        bf = client.get_bloom_filter(f"t{t}")
        bf.try_init(10_000, 0.01)
        filters.append(bf)
    rng = np.random.default_rng(7)
    # COLD pass: measured right away — background pre-warm is racing the
    # producers, so this number shows what the cliff costs a process that
    # did NOT wait for warmup (and how much the pre-warmer absorbs).
    cold_ops = _paced_load(
        filters, n_threads=4, chunk=256, offered_qps=400_000,
        duration_s=3.0, seed_base=500,
    )
    # AOT pre-warm barrier: every (opcode, bucket) ≤ max_batch compiled
    # off the serving path (executor/prewarm.py).
    client.prewarm_wait(timeout=900.0)
    # Backstop: one exact-size submission per bucket through the REAL
    # traffic path.  If the pre-warmer drained these are all cache hits
    # (milliseconds); if a slow link phase left stragglers, the
    # compile lands HERE — still outside the measured window.
    nbucket = 4096
    while nbucket <= (1 << 18):
        keys = rng.integers(0, 50_000, nbucket).astype(np.uint64)
        t = int(rng.integers(n_tenants))
        filters[t].add_all_async(keys).result(timeout=600.0)
        nbucket *= 2
    # A burst of small mixed chunks (the steady-state arrival shape)
    # settles allocator/ring state, then zero the latency reservoirs so
    # the measured window sees no warmup residue.
    warm = []
    for i in range(64):
        keys = rng.integers(0, 50_000, 256).astype(np.uint64)
        t = int(rng.integers(n_tenants))
        if i % 3 == 0:
            warm.append(filters[t].add_all_async(keys))
        else:
            warm.append(filters[t].contains_all_async(keys))
    for f in warm:
        f.result()
    client._engine.metrics.reset()
    # Also zero the span-phase histograms: metrics_snapshot.phases is
    # the warm-path evidence view, and compile-era/cold-pass samples in
    # it would re-average the very cliff the split isolates.
    client.obs.reset_op_stats()

    # WARM pass: 8 producers, 1.15M QPS aggregate target (15% above the
    # 1M spec).
    warm_ops = _paced_load(
        filters, n_threads=8, chunk=256, offered_qps=1_150_000,
        duration_s=12.0,
    )
    snap = client.get_metrics()
    client.shutdown()
    return warm_ops, snap, cold_ops


def measure_pass_link_sample():
    """Both link-regime probes in ONE window (per-pass attribution,
    ISSUE 4 satellite): ``link_h2d_put_rt_ms`` is the per-transfer-RT
    regime's tell (small device_put), ``link_resident_rt_ms`` the
    fetch-RT regime's (resident-array fetch).  A stalled pass travels
    with the RT evidence that explains it."""
    import jax

    small = np.ones(1024, np.uint32)
    t0 = time.perf_counter()
    for _ in range(4):
        jax.device_put(small).block_until_ready()
    return {
        "link_h2d_put_rt_ms": round((time.perf_counter() - t0) * 250, 2),
        "link_resident_rt_ms": measure_rt_sample(),
    }


def bench_nearcache_hotkeys(make_client):
    """ISSUE 4 tentpole evidence: a zipf-skewed HOT-KEY read pass in the
    near cache's regime — INDIVIDUAL ``contains()`` calls (the
    SISMEMBER/GETBIT serving shape the tentpole names), hot keys
    dominating — run twice with identical traffic, nearcache on vs off.
    Every uncached single-key read pays a coalesce window plus a launch
    retirement that a remote link priced at 10-350 ms per round trip; a hit
    answers from host memory in microseconds.  The ratio is attributable
    to the tier independently of link phase (the off pass rides the same
    phase and is capped at N_OFF ops so a slow phase can't blow the
    bench wall-clock — per-op means make the two counts comparable).
    Reports ops/s both ways + the measured hit rate from the engine's
    epoch-aware counters."""
    N_KEYS = 100_000
    WARM = 4096   # cache-seeding prefix — DISJOINT from the measured reads
    N_ON = 4096   # measured single-key reads, cache on (hits are µs)
    N_OFF = 512   # cache off: each op costs a real link round trip
    rng = np.random.default_rng(21)
    # Zipf-skewed key stream: a small hot set dominates (the workload
    # shape that motivates a near cache, SURVEY §2 RLocalCachedMap).
    # The ON pass warms on the PREFIX and measures the SUFFIX: the
    # published hit rate is the zipf locality the tier actually captures
    # (hot keys recur across the split, the cold tail misses and pays
    # the link).  Warming with the measured keys themselves would pin
    # the hit rate at 1.0 for ANY key distribution — true by
    # construction, measuring nothing.
    stream = (rng.zipf(1.3, size=WARM + N_ON) % N_KEYS).astype(np.uint64)
    out = {}
    for label, enabled, n_meas in (("on", True, N_ON),
                                   ("off", False, N_OFF)):
        # Fixed flush window, both passes: the adaptive controller tunes
        # for BATCH throughput and inflates the window around the ON
        # pass's lone misses (arrival gaps the hit bursts create — a
        # penalty the OFF pass's steady single-op stream never sees),
        # skewing the ratio away from what it claims to measure.
        client = make_client(coalesce=True, nearcache=enabled,
                             batch_window_us=200, adaptive_window=False)
        bf = client.get_bloom_filter("nc-bf")
        bf.try_init(N_KEYS, 0.01)
        bf.add_all_async(
            np.arange(0, N_KEYS, 2, dtype=np.uint64)
        ).result(timeout=600.0)
        # Warm-up: the ON pass seeds the cache with the disjoint
        # prefix's hot set (steady-state hot-key serving); the OFF pass
        # only needs the single-op compile bucket warm — a full uncached
        # replay would cost 2x the capped measured work in link round
        # trips, the very wall-clock blowup N_OFF exists to bound.
        for k in stream[: WARM if enabled else 32]:
            bf.contains(k)
        nc = getattr(client._engine, "nearcache", None)
        if nc is not None:
            nc.hits = nc.misses = 0
        t0 = time.perf_counter()
        for k in stream[WARM : WARM + n_meas]:
            bf.contains(k)
        dt = time.perf_counter() - t0
        out[f"nearcache_{label}_ops_per_sec"] = round(n_meas / dt)
        out[f"nearcache_{label}_ops_measured"] = n_meas
        if enabled and nc is not None:
            st = nc.stats()
            out["nearcache_hit_rate"] = st["hit_rate"]
            out["nearcache_bytes"] = st["bytes"]
        client.shutdown()
    out["nearcache_speedup"] = round(
        out["nearcache_on_ops_per_sec"]
        / max(1, out["nearcache_off_ops_per_sec"]), 2
    )
    out["nearcache_pass_link"] = measure_pass_link_sample()
    return out


def _resp_skip_frame(buf: bytes, i: int) -> int:
    from redisson_tpu.serve.wireutil import skip_reply_frame

    return skip_reply_frame(buf, i)


def _resp_wire(args) -> bytes:
    from redisson_tpu.serve.wireutil import wire_command

    return wire_command(args)


def bench_config6_frontdoor(make_client):
    """Config 6 — front-door command-stream vectorization (ISSUE 6).

    Loopback RESP server, P pipelined connections, each streaming batches
    of mixed hot-read/write commands (zipf BF.EXISTS + BF.ADD on one
    filter, repeated GETs on a hot string set, SETBIT/GETBIT on one
    bitmap).  Interleaved A/B: alternating passes with the vectorizer ON
    and OFF on the SAME server/connections, so link phase and cache state
    can't favor one arm.  Publishes fused-vs-unfused pipelined cmds/s,
    the fusion ratio, and the response-cache hit rate — the tentpole's
    headline, captured in BENCH_rN.json rather than prose.  A second
    mini-A/B toggles the coalescer's phase-aware merge cap
    (max_batch_slow_phase) and reports the observed link phase with both
    numbers: the cap must pay ONLY in the slow phase, so in a fast-phase
    window the two arms read ~equal."""
    import socket as _socket

    from redisson_tpu.serve.resp import RespServer

    P = 4            # pipelined connections
    DEPTH = 256      # commands per pipelined batch
    PASS_S = 1.5     # seconds per measured pass
    N_ITEMS = 512    # hot bloom keyspace
    client = make_client(batch_window_us=200)
    server = RespServer(client)
    try:
        bf = client.get_bloom_filter("fd-bf")
        bf.try_init(100_000, 0.01)
        bf.add_all_async(
            np.arange(0, N_ITEMS, 2, dtype=np.uint64)
        ).result(timeout=600.0)
        seed_sock = _socket.create_connection((server.host, server.port))
        seed = [
            [b"SET", b"fd-s%d" % i, b"value-%d" % i] for i in range(4)
        ] + [[b"SETBIT", b"fd-bs", b"%d" % i, b"1"] for i in range(0, 64, 2)]
        seed_sock.sendall(b"".join(_resp_wire(c) for c in seed))
        buf = b""
        got = 0
        while got < len(seed):
            buf += seed_sock.recv(1 << 16)
            pos = 0
            got = 0
            while True:
                try:
                    pos = _resp_skip_frame(buf, pos)
                    got += 1
                except (IndexError, ValueError):
                    break
        seed_sock.close()

        rng = np.random.default_rng(17)

        def make_batch():
            # Burst-shaped pipeline (the redis-benchmark / bulk-client
            # pattern the tentpole targets): a client streams a SPAN of
            # same-family commands before switching — mixed hot
            # reads/writes INSIDE each span (BF.ADD among BF.EXISTS,
            # SETBIT among GETBIT, SET among GET), so every span
            # exercises the mixed fused path, not a read-only fast case.
            cmds = []
            while len(cmds) < DEPTH:
                burst = min(int(rng.integers(16, 49)), DEPTH - len(cmds))
                hot = (rng.zipf(1.3, burst) - 1) % N_ITEMS
                fam = rng.random()
                if fam < 0.5:  # bloom span, ~15% writes
                    for i in range(burst):
                        if rng.random() < 0.15:
                            cmds.append(
                                [b"BF.ADD", b"fd-bf", b"%d" % hot[i]]
                            )
                        else:
                            cmds.append(
                                [b"BF.EXISTS", b"fd-bf", b"%d" % hot[i]]
                            )
                elif fam < 0.8:  # hot string span, ~4% writes
                    for i in range(burst):
                        k = b"fd-s%d" % (int(hot[i]) % 4)
                        if rng.random() < 0.04:
                            cmds.append(
                                [b"SET", k, b"value-%d" % int(hot[i])]
                            )
                        else:
                            cmds.append([b"GET", k])
                else:  # bitmap span, ~20% writes
                    for i in range(burst):
                        off = b"%d" % (hot[i] % 64)
                        if rng.random() < 0.2:
                            cmds.append([b"SETBIT", b"fd-bs", off, b"1"])
                        else:
                            cmds.append([b"GETBIT", b"fd-bs", off])
            return b"".join(_resp_wire(c) for c in cmds)

        batches = [make_batch() for _ in range(8)]

        def pass_cmds_per_sec(duration_s):
            stop = time.perf_counter() + duration_s
            counts = [0] * P
            errors = []

            def worker(t, sock):
                try:
                    k = t
                    while time.perf_counter() < stop:
                        payload = batches[k % len(batches)]
                        k += 1
                        sock.sendall(payload)
                        buf = b""
                        got = 0
                        pos = 0
                        while got < DEPTH:
                            buf += sock.recv(1 << 16)
                            while True:
                                try:
                                    pos = _resp_skip_frame(buf, pos)
                                    got += 1
                                except (IndexError, ValueError):
                                    break
                        counts[t] += got
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            socks = [
                _socket.create_connection((server.host, server.port))
                for _ in range(P)
            ]
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker, args=(t, socks[t]))
                for t in range(P)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
            for s in socks:
                s.close()
            if errors:
                raise errors[0]
            return sum(counts) / dt

        obs = server.obs

        def counter_total(fam):
            return sum(int(c.value) for _, c in fam.items())

        # Warm both arms (compile buckets, seed caches) before timing.
        for vec in (True, False):
            server.vectorize = vec
            pass_cmds_per_sec(0.4)
        # Interleaved A/B: on/off alternating, 3 passes each.  Counter
        # deltas accumulate around the ON passes ONLY — the OFF arm
        # dispatches every command sequentially on purpose, and folding
        # its unfused commands into the denominator would dilute the
        # published fusion ratio by however slow that arm happens to be.
        on_passes, off_passes = [], []
        fused = total = rch = rcm = 0
        for _ in range(3):
            server.vectorize = True
            f0, t0 = (
                counter_total(obs.resp_fused_cmds),
                counter_total(obs.resp_commands),
            )
            h0, m0 = (
                counter_total(obs.resp_cache_hits),
                counter_total(obs.resp_cache_misses),
            )
            on_passes.append(pass_cmds_per_sec(PASS_S))
            fused += counter_total(obs.resp_fused_cmds) - f0
            total += counter_total(obs.resp_commands) - t0
            rch += counter_total(obs.resp_cache_hits) - h0
            rcm += counter_total(obs.resp_cache_misses) - m0
            server.vectorize = False
            off_passes.append(pass_cmds_per_sec(PASS_S))
        server.vectorize = True
        out = {
            "frontdoor_cmds_per_sec": round(float(np.median(on_passes))),
            "frontdoor_unfused_cmds_per_sec": round(
                float(np.median(off_passes))
            ),
            "frontdoor_passes": [round(p) for p in on_passes],
            "frontdoor_unfused_passes": [round(p) for p in off_passes],
            "frontdoor_speedup": round(
                float(np.median(on_passes))
                / max(1.0, float(np.median(off_passes))), 2
            ),
            "frontdoor_fusion_ratio": (
                round(fused / total, 4) if total else 0.0
            ),
            "frontdoor_response_cache_hit_rate": (
                round(rch / (rch + rcm), 4) if rch + rcm else 0.0
            ),
            "frontdoor_connections": P,
            "frontdoor_pipeline_depth": DEPTH,
        }
        # Merge-cap mini A/B (satellite): same fused traffic with the
        # phase-aware cap armed vs disabled, plus the phase the link was
        # actually in (the cap only ENGAGES when the put-RT EWMA says
        # slow) — fast-phase windows should read ~equal, which is the
        # "pays only where intended" evidence on a fast link.
        co = getattr(client._engine, "coalescer", None)
        if co is not None:
            ab = {}
            for label, cap in (("on", co.max_batch * 4), ("off", 0)):
                co.max_batch_slow_phase = cap
                ab[label] = round(pass_cmds_per_sec(0.8))
            co.max_batch_slow_phase = 0
            ab["phase_slow"] = bool(co._put_rt_ewma > co.slow_launch_s)
            ab["put_rt_ewma_ms"] = round(co._put_rt_ewma * 1000, 2)
            out["frontdoor_merge_cap_ab"] = ab
        return out
    finally:
        server.close()
        client.shutdown()


def bench_config8_reactor(make_client):
    """Config 8 — reactor front door A/B (ISSUE 11).

    (a) Unpipelined-client throughput: IDLE mostly-silent connections +
    ACTIVE closed-loop clients each keeping ONE command in flight (the
    client shape the reactor exists for — no pipeline window to fuse
    within a connection), measured with the reactor ON vs the legacy
    thread-per-connection path on separate same-config servers.  The ON
    arm's win comes from cross-connection fusion + the merged window's
    shared response cache + not context-switching IDLE+ACTIVE threads.
    (b) Idle-connection scaling: with the reactor ON, ramp idle
    connections toward 5k and record the serving THREAD count (fixed)
    and process fd count — connections cost descriptors, not threads.
    Publishes reactor_* BENCH keys."""
    import os as _os
    import socket as _socket

    from redisson_tpu.serve.resp import RespServer

    IDLE = 1000
    ACTIVE = 32
    PASS_S = 1.5
    N_ITEMS = 512
    IDLE_SCALE_TARGET = 5000

    try:  # lift the fd soft limit toward the hard limit (5k sockets)
        import resource as _resource

        soft, hard = _resource.getrlimit(_resource.RLIMIT_NOFILE)
        if soft < hard:
            _resource.setrlimit(_resource.RLIMIT_NOFILE, (hard, hard))
    except (ImportError, ValueError, OSError):
        pass

    def _seed(server):
        sock = _socket.create_connection((server.host, server.port))
        cmds = [[b"BF.RESERVE", b"rx-bf", b"0.01", b"100000"]]
        cmds += [
            [b"BF.MADD", b"rx-bf"] + [b"%d" % i for i in range(j, j + 64)]
            for j in range(0, N_ITEMS, 64)
        ]
        cmds += [
            [b"SET", b"rx-s%d" % i, b"value-%d" % i] for i in range(4)
        ]
        cmds += [
            [b"SETBIT", b"rx-bs", b"%d" % i, b"1"] for i in range(0, 64, 2)
        ]
        # Deterministic fused-path warm (BOTH arms fuse pipelined
        # batches): the fused bloom read/mixed and bitset kernels
        # compile HERE, not inside a measured pass — without this the
        # reactor arm pays first-touch compiles the thread arm never
        # triggers (its unpipelined traffic never fuses).
        for _ in range(3):
            cmds += [
                [b"BF.EXISTS", b"rx-bf", b"%d" % i] for i in range(32)
            ]
            cmds += [
                [b"BF.ADD", b"rx-bf", b"%d" % i] if i % 4 == 0 else
                [b"BF.EXISTS", b"rx-bf", b"%d" % i] for i in range(32)
            ]
            cmds += [
                [b"GETBIT", b"rx-bs", b"%d" % (i % 64)] for i in range(32)
            ]
            cmds += [[b"GET", b"rx-s%d" % (i % 4)] for i in range(16)]
        sock.sendall(b"".join(_resp_wire(c) for c in cmds))
        buf = b""
        got = pos = 0
        while got < len(cmds):
            buf += sock.recv(1 << 16)
            while True:
                try:
                    pos = _resp_skip_frame(buf, pos)
                    got += 1
                except (IndexError, ValueError):
                    break
        sock.close()

    def _open_idle(server, n, have=None):
        socks = have if have is not None else []
        try:
            while len(socks) < n:
                socks.append(
                    _socket.create_connection(
                        (server.host, server.port), timeout=10
                    )
                )
        except OSError:
            pass  # fd/limit ceiling: report what we achieved
        return socks

    def _serving_threads():
        return sum(
            1 for t in threading.enumerate()
            if t.name.startswith("rtpu-resp")
        )

    N_PROCS = 8  # client processes (ACTIVE conns split across them)

    def _client_proc(host, port, conns, stop_at, seed, q):
        """Closed-loop unpipelined clients, one thread per connection,
        in a FORKED process: in-process client threads would contend
        for the server's GIL and cap BOTH arms at the client's own
        throughput — the measurement must load the server from outside
        its interpreter."""
        counts = [0] * conns
        lats: list = [[] for _ in range(conns)]

        def worker(t):
            rng = np.random.default_rng(seed * 100 + t)
            sock = _socket.create_connection((host, port))
            sock.setsockopt(
                _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
            )
            try:
                while time.time() < stop_at:
                    # Hot-working-set read mix (the tentpole's target
                    # client shape): mostly repeated reads over a small
                    # hot set, a trickle of writes keeping the epochs
                    # and fused write paths honest.
                    hot = int((rng.zipf(1.3) - 1) % N_ITEMS)
                    r = rng.random()
                    if r < 0.03:
                        cmd = [b"BF.ADD", b"rx-bf", b"%d" % hot]
                    elif r < 0.38:
                        cmd = [b"BF.EXISTS", b"rx-bf", b"%d" % hot]
                    elif r < 0.88:
                        cmd = [b"GET", b"rx-s%d" % (hot % 4)]
                    else:
                        cmd = [b"GETBIT", b"rx-bs", b"%d" % (hot % 64)]
                    t0 = time.perf_counter()
                    sock.sendall(_resp_wire(cmd))
                    data = b""
                    closed = False
                    while True:
                        chunk = sock.recv(1 << 16)
                        if not chunk:
                            closed = True  # server dropped us: stop,
                            break          # don't spin past stop_at
                        data += chunk
                        try:
                            _resp_skip_frame(data, 0)
                            break
                        except (IndexError, ValueError):
                            # ValueError also covers a reply whose
                            # first "\r\n" hasn't arrived yet
                            # (bytes.index) — wait for more bytes like
                            # every other wire loop in this file.
                            continue
                    if closed:
                        break
                    lats[t].append(time.perf_counter() - t0)
                    counts[t] += 1
            finally:
                sock.close()

        t0 = time.time()
        threads = [
            threading.Thread(target=worker, args=(t,))
            for t in range(conns)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        q.put((sum(counts), time.time() - t0,
               [x for la in lats for x in la]))

    def _measure(server, duration_s):
        """Closed-loop unpipelined pass: returns (cmds/s, p99 ms)."""
        import multiprocessing as _mp

        ctx = _mp.get_context("fork")
        q = ctx.Queue()
        stop_at = time.time() + duration_s + 0.3  # absorb fork startup
        per = ACTIVE // N_PROCS
        procs = [
            ctx.Process(
                target=_client_proc,
                args=(server.host, server.port, per, stop_at, i, q),
            )
            for i in range(N_PROCS)
        ]
        for p in procs:
            p.start()
        results = [q.get(timeout=duration_s + 60) for _ in procs]
        for p in procs:
            p.join(timeout=30)
        total = sum(r[0] for r in results)
        dt = float(np.median([r[1] for r in results]))
        all_lat = sorted(x for r in results for x in r[2])
        p99 = all_lat[int(len(all_lat) * 0.99)] if all_lat else 0.0
        return total / max(1e-9, dt), p99 * 1000

    # Both arms live SIMULTANEOUSLY, measured in alternating passes
    # (the config6 interleaving discipline): ambient load on a shared
    # bench host would otherwise poison whichever arm ran in the bad
    # window — interleaved A/B charges drift to both arms equally, and
    # the published numbers are per-arm MEDIANS over 3 passes.
    out = {}
    arms = {}
    try:
        for arm in (True, False):
            client = make_client(batch_window_us=200)
            client.config.resp_reactor = arm
            server = RespServer(
                client,
                max_connections=(
                    max(IDLE, IDLE_SCALE_TARGET) + ACTIVE + 16
                ),
            )
            _seed(server)
            arms[arm] = (client, server, _open_idle(server, IDLE))
        for arm in (True, False):  # warm (residual compiles, caches)
            _measure(arms[arm][1], 1.0)
        passes = {True: [], False: []}
        for _ in range(3):
            for arm in (True, False):
                passes[arm].append(_measure(arms[arm][1], PASS_S))
        for arm, label in ((True, "reactor"), (False, "reactor_off")):
            cps = sorted(p[0] for p in passes[arm])[1]  # median of 3
            p99 = sorted(p[1] for p in passes[arm])[1]
            out[f"{label}_cmds_per_sec"] = round(cps)
            out[f"{label}_passes"] = [
                round(p[0]) for p in passes[arm]
            ]
            out[f"{label}_p99_ms"] = round(p99, 2)
        server = arms[True][1]
        out["reactor_cross_conn_fused_ops"] = sum(
            int(c.value)
            for _, c in server.obs.cross_conn_fused_ops.items()
        )
        out["reactor_off_serving_threads_at_idle"] = sum(
            1 for t in threading.enumerate()
            if t.name == "rtpu-resp-conn"
        )
        # (b) idle scaling, reactor arm only: ramp toward the 5k target
        # and record the serving-thread + fd census.  The thread arm is
        # shut down FIRST so its 1k per-connection threads don't sit in
        # the census.
        arms[False][1].close()
        arms[False][0].shutdown()
        for s in arms[False][2]:
            s.close()
        del arms[False]
        idle = _open_idle(server, IDLE_SCALE_TARGET, have=arms[True][2])
        for s in idle[:: max(1, len(idle) // 8)]:
            s.sendall(_resp_wire([b"PING"]))
            assert s.recv(64).startswith(b"+PONG")
        try:
            nfds = len(_os.listdir("/proc/self/fd"))
        except OSError:
            nfds = None
        out["reactor_idle_scale"] = {
            "target_conns": IDLE_SCALE_TARGET,
            "achieved_conns": len(idle),
            "serving_threads": _serving_threads(),
            "reactor_threads": server.reactor.nthreads,
            "process_fds": nfds,
        }
    finally:
        for client, server, idle in arms.values():
            for s in idle:
                try:
                    s.close()
                except OSError:
                    pass
            server.close()
            client.shutdown()
    out["reactor_idle_conns"] = IDLE
    out["reactor_active_conns"] = ACTIVE
    out["reactor_speedup"] = round(
        out["reactor_cmds_per_sec"]
        / max(1.0, out["reactor_off_cmds_per_sec"]), 2
    )
    return out


def bench_config9_cluster(_make_client):
    """Config 9 — cluster-mode scaling A/B (ISSUE 12 tentpole).

    (a) 1 vs 3 server PROCESSES (the slot-sharded topology layer) under
    the SAME total closed-loop client population, clients in forked
    processes driving the slot-aware ClusterClient (routing + redirect
    chasing included in the measured path — that is the real deployment
    cost).  Both arms live simultaneously, measured in alternating
    passes, per-arm 3-pass MEDIANS published (the config8 interleaving
    discipline).  The headline is cluster_speedup: N front doors = N
    GILs = N engines, so near-linear scaling is the acceptance bar
    (>= 2.2x at 3 nodes).
    (b) Live slot migration under traffic: a writer keeps acking writes
    into one hash-tagged slot while the slot migrates between nodes;
    afterwards EVERY acked write must read back through the refreshed
    table (cluster_migration_* keys, differential-checked — the
    zero-acked-write-loss criterion).

    Nodes run on the CPU backend: N processes cannot share the one
    bench accelerator, and what this config measures is the topology
    layer's process-level scaling, not kernel rate (the per-node device
    slice is a deployment concern — docs/clustering.md)."""
    from redisson_tpu.cluster.slots import key_slot
    from redisson_tpu.cluster.supervisor import (
        ClusterSupervisor,
        migrate_slot,
    )

    N_KEYS = 512
    PASS_S = 1.5
    N_PROCS = 9  # forked client processes...
    CONNS = 4    # ...each running this many closed-loop router threads
    # Scatter batch per round: deep enough that a 3-way slot split
    # still leaves each per-node pipeline leg in the server's efficient
    # regime (~BATCH/3 deep) — at shallow batches the measurement
    # compares depth-B pipelines on the 1-node arm against depth-B/3
    # legs on the 3-node arm and understates the topology win.  The
    # single-node arm plateaus (is genuinely saturated) at this depth.
    BATCH = 192

    def _client_proc(seeds, stop_at, seed, q):
        """Closed-loop slot-routing clients in a FORKED process (the
        config8 rationale: in-process client threads would contend for
        the bench interpreter, not the servers).  Each round builds a
        mixed zipf-hot batch and ships it through execute_many — the
        pipelined multi-slot scatter/gather path IS the client shape
        this config exists to measure."""
        from redisson_tpu.cluster.client import ClusterClient, ClusterError

        counts = [0] * CONNS
        lats: list = [[] for _ in range(CONNS)]

        def worker(t):
            rng = np.random.default_rng(seed * 100 + t)
            cc = ClusterClient(seeds)
            try:
                while time.time() < stop_at:
                    cmds = []
                    for _ in range(BATCH):
                        hot = int((rng.zipf(1.2) - 1) % N_KEYS)
                        if rng.random() < 0.1:
                            cmds.append(
                                ("SET", "ck%d" % hot, "w%d" % hot)
                            )
                        else:
                            cmds.append(("GET", "ck%d" % hot))
                    t0 = time.perf_counter()
                    cc.execute_many(cmds)
                    lats[t].append(time.perf_counter() - t0)
                    counts[t] += BATCH
            except (OSError, ClusterError):
                # Arm teardown racing the clock (scatter legs wrap
                # socket errors in ClusterError): keep the counts
                # gathered so far.
                pass
            finally:
                cc.close()

        t0 = time.time()
        threads = [
            threading.Thread(target=worker, args=(t,))
            for t in range(CONNS)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        q.put((sum(counts), time.time() - t0,
               [x for la in lats for x in la]))

    def _measure(seeds, duration_s):
        import multiprocessing as _mp

        ctx = _mp.get_context("fork")
        q = ctx.Queue()
        stop_at = time.time() + duration_s + 0.3
        procs = [
            ctx.Process(target=_client_proc, args=(seeds, stop_at, i, q))
            for i in range(N_PROCS)
        ]
        for p in procs:
            p.start()
        results = [q.get(timeout=duration_s + 120) for _ in procs]
        for p in procs:
            p.join(timeout=30)
        total = sum(r[0] for r in results)
        dt = float(np.median([r[1] for r in results]))
        all_lat = sorted(x for r in results for x in r[2])
        p99 = all_lat[int(len(all_lat) * 0.99)] if all_lat else 0.0
        return total / max(1e-9, dt), p99 * 1000

    out = {}
    sups = {}
    try:
        for n in (1, 3):
            sup = ClusterSupervisor(n_nodes=n, platform="cpu")
            sup.start()
            sups[n] = sup
            cc = sup.client()
            acks = cc.execute_many(
                [("SET", "ck%d" % i, "v%d" % i) for i in range(N_KEYS)]
            )
            assert all(a == b"OK" for a in acks)
            cc.close()
        for n in (3, 1):  # warm pass (connection setup, route tables)
            _measure(sups[n].addrs, 0.8)
        passes = {1: [], 3: []}
        for _ in range(3):
            for n in (3, 1):
                passes[n].append(_measure(sups[n].addrs, PASS_S))
        for n, label in ((1, "cluster_1node"), (3, "cluster_3node")):
            cps = sorted(p[0] for p in passes[n])[1]
            p99 = sorted(p[1] for p in passes[n])[1]
            out[f"{label}_cmds_per_sec"] = round(cps)
            out[f"{label}_passes"] = [round(p[0]) for p in passes[n]]
            out[f"{label}_batch_p99_ms"] = round(p99, 2)
        out["cluster_speedup"] = round(
            out["cluster_3node_cmds_per_sec"]
            / max(1.0, out["cluster_1node_cmds_per_sec"]), 2
        )
        out["cluster_client_population"] = N_PROCS * CONNS
        out["cluster_scatter_batch"] = BATCH

        # (b) live migration differential on the 3-node arm.
        sup = sups[3]
        tag = "{mig9}"
        slot = key_slot(tag)
        from redisson_tpu.cluster.client import ClusterClient

        acked: dict = {}
        stop = threading.Event()
        failures: list = []

        def writer():
            w = ClusterClient(sup.addrs)
            i = 0
            try:
                while not stop.is_set():
                    k = "%sw%d" % (tag, i)
                    if w.execute("SET", k, "v%d" % i) == b"OK":
                        acked[k] = b"v%d" % i
                    i += 1
            except Exception as e:
                failures.append(repr(e))
            finally:
                w.close()

        th = threading.Thread(target=writer)
        th.start()
        time.sleep(0.4)
        per = 16384 // 3
        dst = (min(slot // per, 2) + 1) % 3
        moved = sup.migrate_slot(slot, dst)
        time.sleep(0.2)
        stop.set()
        th.join()
        cc = sup.client()
        got = cc.execute_many([("GET", k) for k in acked])
        lost = sum(
            1 for k, g in zip(acked, got) if g != acked[k]
        )
        cc.close()
        out["cluster_migration_keys_moved"] = moved
        out["cluster_migration_acked_writes"] = len(acked)
        out["cluster_migration_acked_lost"] = lost
        out["cluster_migration_writer_errors"] = failures
        out["cluster_migration_ok"] = (
            lost == 0 and not failures and moved > 0
        )
    finally:
        for sup in sups.values():
            sup.shutdown()
    return out


def bench_journal_ab(_make_client):
    """ISSUE 10 acceptance: journal-on overhead A/B.  The same batched
    bloom add pass (the acked-write hot path) runs with journaling off,
    ``everysec``, and ``always`` — identical traffic, fresh directories.
    ``always`` pays a group-commit fsync barrier per blocking call on a
    single producer (no other writers to amortize with), so its key is
    the honest worst case; ``everysec`` shows the steady-state serving
    cost (append + background fsync)."""
    import os
    import shutil
    import tempfile

    import redisson_tpu
    from redisson_tpu import Config
    from redisson_tpu.codecs import LongCodec

    N_CALLS, B = 48, 1024
    rng = np.random.default_rng(17)
    keys = rng.integers(0, 1 << 40, size=(N_CALLS, B), dtype=np.uint64)
    out = {}
    for label, fsync in (
        ("off", None), ("everysec", "everysec"), ("always", "always")
    ):
        tmp = tempfile.mkdtemp(prefix="rtpu-journal-ab-")
        cfg = Config().set_codec(LongCodec()).use_tpu_sketch(
            min_bucket=256
        )
        if fsync is not None:
            cfg.journal_dir = os.path.join(tmp, "journal")
            cfg.journal_fsync = fsync
        client = redisson_tpu.create(cfg)
        try:
            bf = client.get_bloom_filter("journal-ab")
            bf.try_init(1_000_000, 0.01)
            bf.add_all(keys[0])  # compile warm-up, excluded
            t0 = time.perf_counter()
            for i in range(1, N_CALLS):
                bf.add_all(keys[i])
            dt = time.perf_counter() - t0
            out[f"journal_{label}_ops_per_sec"] = round(
                (N_CALLS - 1) * B / dt
            )
            j = client._engine.journal
            if j is not None:
                st = j.stats()
                out[f"journal_{label}_fsyncs"] = st["fsyncs"]
                out[f"journal_{label}_bytes"] = st["bytes_written"]
        finally:
            client.shutdown()
            shutil.rmtree(tmp, ignore_errors=True)
    off = out.get("journal_off_ops_per_sec") or 0
    for label in ("everysec", "always"):
        on = out.get(f"journal_{label}_ops_per_sec")
        out[f"journal_{label}_overhead_pct"] = (
            round(100.0 * (1.0 - on / off), 1) if off and on else None
        )
    return out


def bench_config7_overload(make_client):
    """Config 7 (ISSUE 7): open-loop overload A/B.  Offered load is held
    at ~2x the measured saturation throughput; the ON arm attaches an op
    deadline (admission control sheds fast when the estimated queue wait
    exceeds the residual budget), the OFF arm is the pre-overload
    blocking behavior.  Graceful degradation = ON holds bounded p99 of
    ACCEPTED ops and near-peak goodput while OFF's completed-op latency
    grows with the queue.  A fairness mini-pass measures what a
    within-quota tenant keeps of its solo throughput during a co-tenant
    burst under the token-bucket governor."""
    import threading

    # nearcache off: the A/B measures the DISPATCH path under overload
    # (a host-tier hit dodges the queue entirely and its result type
    # carries no completion callback).  prewarm + the exact-size ladder
    # backstop below: a first-touch bucket compile landing inside the
    # OFF arm would masquerade as queue collapse.
    # max_batch/max_inflight deliberately modest: the A/B needs offered
    # load the PRODUCERS can actually generate to exceed engine
    # capacity — a wide-open engine on the smoke host absorbs anything
    # four paced threads can offer and no queue ever forms.
    client = make_client(
        coalesce=True, batch_window_us=200, max_batch=1024,
        max_inflight=2, adaptive_inflight=False,
        max_queued_ops=1 << 15, adaptive_window=False, nearcache=False,
        min_bucket=512, prewarm=True,
    )
    bf = client.get_bloom_filter("ov")
    bf.try_init(100_000, 0.01)
    rng = np.random.default_rng(11)
    chunk = 512
    client.prewarm_wait(timeout=900.0)
    nbucket = 512
    while nbucket <= 1024:  # ladder backstop through the real path
        bf.contains_all_async(
            rng.integers(0, 100_000, nbucket).astype(np.uint64)
        ).result(timeout=600.0)
        bf.add_all_async(
            rng.integers(0, 100_000, nbucket).astype(np.uint64)
        ).result(timeout=600.0)
        nbucket *= 2
    for _ in range(16):  # prime the admission EWMAs at the real chunk
        bf.contains_all_async(
            rng.integers(0, 100_000, chunk).astype(np.uint64)
        ).result(timeout=600.0)

    def open_loop(offered_qps, duration_s, deadline_ms):
        """Paced producer; per-chunk latency is recorded at COMPLETION
        (done callback on the completer thread), never at drain time —
        charging a resolved future its sit-in-the-deque time would
        inflate the OFF arm's percentiles for free.  Submission blocks
        at the queue bound in the no-deadline arm (that block IS the
        collapse being measured: the producer falls behind its offered
        rate while completed-op latency grows with the queue)."""
        interval = chunk / offered_qps
        lat: list = []
        counts = {"done": 0, "shed": 0}
        lock = threading.Lock()

        def submit_one(keys):
            ts = time.perf_counter()

            def cb(f):
                ok = not f.cancelled() and f.exception() is None
                dt = time.perf_counter() - ts
                with lock:
                    counts["done"] += chunk
                    if ok:
                        lat.append(dt)
                    else:
                        counts["shed"] += chunk

            try:
                if deadline_ms:
                    with client.op_deadline(deadline_ms):
                        f = bf.contains_all_async(keys)
                else:
                    f = bf.contains_all_async(keys)
            except Exception:
                with lock:
                    counts["done"] += chunk
                    counts["shed"] += chunk
                return
            f.add_done_callback(cb)

        n_threads = 4  # one producer cannot outrun the engine on-host
        per_thread_interval = interval * n_threads
        offered_counts = [0] * n_threads

        def producer(tid):
            trng = np.random.default_rng(1000 + tid)
            t0 = time.perf_counter()
            next_t = 0.0
            while True:
                now = time.perf_counter() - t0
                if now >= duration_s:
                    break
                if now < next_t:
                    time.sleep(min(next_t - now, 0.001))
                    continue
                next_t += per_thread_interval
                offered_counts[tid] += chunk
                submit_one(
                    trng.integers(0, 100_000, chunk).astype(np.uint64)
                )

        threads = [
            threading.Thread(target=producer, args=(i,), daemon=True)
            for i in range(n_threads)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        offered = sum(offered_counts)
        deadline_drain = time.perf_counter() + 120.0
        while counts["done"] < offered and (
            time.perf_counter() < deadline_drain
        ):
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        accepted = offered - counts["shed"]
        return {
            "goodput": accepted / wall,
            "p99_ms": (
                round(float(np.percentile(lat, 99)) * 1e3, 2)
                if lat else None
            ),
            "shed": counts["shed"],
            "offered": offered,
        }

    # Saturation: drive far past any plausible capacity — the blocking
    # queue bound paces the producer AT capacity, so goodput here IS
    # the saturation throughput (a shallow closed-loop window would
    # underestimate it).
    rough = open_loop(20_000.0, 1.5, 0)["goodput"]
    sat = open_loop(rough * 20.0, 2.0, 0)["goodput"]
    unsat = open_loop(sat * 0.25, 3.0, 0)
    unsat_p99 = unsat["p99_ms"] or 1.0
    # Deadline at 4x the unsaturated p99: accepted ops then land within
    # the 5x acceptance bound with room for completion overshoot (an op
    # admitted with the estimate just under its budget still finishes).
    deadline_ms = max(25.0, 4.0 * unsat_p99)
    off = open_loop(sat * 2.0, 4.0, 0)
    on = open_loop(sat * 2.0, 4.0, deadline_ms)
    client.shutdown()

    # Fairness mini-pass: victim paced at ~5% of saturation under a
    # quota of ~20%, while a co-tenant bursts closed-loop far past it.
    # Rate limit well UNDER engine capacity: the GOVERNOR must be the
    # binding constraint on the burster — a limit near capacity lets
    # the burster legally fill the queue and the victim stalls behind
    # honest FIFO, which is a queueing result, not a fairness one.
    fair_rate = int(max(1_000, sat * 0.05))
    fc = make_client(
        coalesce=True, batch_window_us=200, max_batch=1024,
        max_queued_ops=1 << 14, nearcache=False,
        tenant_rate_limit=fair_rate,
        tenant_burst_ops=max(500, fair_rate // 2),
    )
    victim = fc.get_bloom_filter("victim")
    victim.try_init(100_000, 0.01)
    burster = fc.get_bloom_filter("burster")
    burster.try_init(100_000, 0.01)
    vkeys = rng.integers(0, 100_000, 64).astype(np.uint64)
    victim.contains_all_async(vkeys).result(timeout=600.0)
    # Warm the burster at its REAL chunk size: a first-touch bucket
    # compile landing inside the contested window would serialize the
    # victim behind the dispatch lock and poison the ratio.
    burster.add_all_async(
        rng.integers(0, 100_000, 1024).astype(np.uint64)
    ).result(timeout=600.0)
    pace_s = 64 / (fair_rate * 0.2)  # victim at 20% of its own quota

    def victim_rate(duration_s):
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < duration_s:
            victim.contains_all_async(vkeys).result()
            n += 64
            time.sleep(pace_s)
        return n / (time.perf_counter() - t0)

    solo = victim_rate(1.5)
    stop = threading.Event()

    def burst():
        while not stop.is_set():
            try:
                burster.add_all_async(
                    rng.integers(0, 100_000, 1024).astype(np.uint64)
                ).result()
            except Exception:
                time.sleep(0.001)

    t = threading.Thread(target=burst, daemon=True)
    t.start()
    try:
        contested = victim_rate(1.5)
    finally:
        stop.set()
        t.join(timeout=30.0)
    fc.shutdown()

    return {
        "overload_saturation_ops_per_sec": round(sat),
        "overload_offered_x": 2.0,
        "overload_unsat_p99_ms": unsat_p99,
        "overload_deadline_ms": round(deadline_ms, 1),
        "overload_on_p99_ms": on["p99_ms"],
        "overload_off_p99_ms": off["p99_ms"],
        "overload_on_goodput_ops_per_sec": round(on["goodput"]),
        "overload_off_goodput_ops_per_sec": round(off["goodput"]),
        "overload_on_shed_ratio": round(
            on["shed"] / max(1, on["offered"]), 4
        ),
        # Acceptance view: ON holds accepted-op p99 within 5x unsat p99
        # (enforced by the deadline itself) AND keeps goodput >= 90% of
        # peak; OFF's p99 collapse factor is reported alongside.
        "overload_graceful": bool(
            on["p99_ms"] is not None
            and on["p99_ms"] <= 5.0 * unsat_p99 + 1e-9
            and on["goodput"] >= 0.9 * sat
        ),
        "overload_off_p99_collapse_x": (
            None if not off["p99_ms"] else
            round(off["p99_ms"] / unsat_p99, 1)
        ),
        "overload_fairness_victim_solo_ops_per_sec": round(solo),
        "overload_fairness_victim_contested_ops_per_sec": round(contested),
        "overload_fairness_victim_ratio": round(contested / solo, 3),
    }


def bench_config10_trace(_make_client):
    """Config 10 — fleet-tracing A/B (ISSUE 13).

    3 forked cluster nodes under one scatter/gather client population;
    alternating passes with the client tracer OFF vs ON at rate 1.0
    (every batch head-sampled — the worst-case tracing cost, so the
    published ratio bounds any real deployment's <1 rate).  Each batch
    leads with one BF.ADD per node partition, so the traced leg heads
    are ENGINE commands and the exemplar trace embedded in BENCH.json
    shows the full fleet path: client root -> per-node legs -> ingress
    spans -> device-launch phases."""
    from redisson_tpu.cluster.client import ClusterClient
    from redisson_tpu.cluster.slots import NSLOTS, key_slot
    from redisson_tpu.cluster.supervisor import ClusterSupervisor
    from redisson_tpu.obs.trace import Tracer

    PASS_S = 1.2
    BATCH = 96
    N_NODES = 3

    def node_key(prefix, idx):
        per = NSLOTS // N_NODES
        lo = idx * per
        hi = NSLOTS - 1 if idx == N_NODES - 1 else lo + per - 1
        for i in range(100_000):
            k = f"{prefix}-{i}"
            if lo <= key_slot(k.encode()) <= hi:
                return k
        raise RuntimeError("no key for partition")

    sup = ClusterSupervisor(n_nodes=N_NODES).start()
    tracer = Tracer(sample_rate=0.0, max_spans=8192)
    try:
        bloom_keys = [node_key("c10bf", i) for i in range(N_NODES)]
        client = ClusterClient(sup.addrs, tracer=tracer)
        try:
            for k in bloom_keys:
                client.execute("BF.RESERVE", k, "0.01", "10000")

            seq = [0]

            def one_pass():
                ncmds = 0
                stop = time.time() + PASS_S
                while time.time() < stop:
                    cmds = [
                        ("BF.ADD", k, "it%d" % seq[0])
                        for k in bloom_keys
                    ]
                    cmds += [
                        ("SET", "c10k%d" % ((seq[0] + j) % 512), "v")
                        for j in range(BATCH - len(cmds))
                    ]
                    seq[0] += BATCH
                    client.execute_many(cmds)
                    ncmds += len(cmds)
                return ncmds / PASS_S

            one_pass()  # warm both arms' pools/ladders
            off_passes, on_passes = [], []
            for i in range(6):
                if i % 2 == 0:
                    tracer.set_sample_rate(0.0)
                    off_passes.append(one_pass())
                else:
                    tracer.set_sample_rate(1.0)
                    on_passes.append(one_pass())
            tracer.set_sample_rate(0.0)
            off_med = float(np.median(off_passes))
            on_med = float(np.median(on_passes))
            # Exemplar multi-node trace: the newest client root whose
            # fleet merge shows all three nodes' serving spans.
            exemplar = None
            roots = [
                s for s in tracer.spans()
                if s["name"] == "client:execute_many"
            ]
            deadline = time.time() + 10.0
            while roots and exemplar is None and time.time() < deadline:
                tid = roots[-1]["trace_id"]
                merged = client.fleet_traces(tid).get(tid, [])
                nodes = {
                    s["attrs"].get("node")
                    for s in merged
                    if s["name"].startswith("resp:")
                }
                if len(nodes) >= N_NODES and any(
                    s["name"].startswith("launch:") for s in merged
                ):
                    exemplar = {"trace_id": tid, "spans": merged[:48]}
                else:
                    time.sleep(0.2)
            return {
                "config10_trace_off_cmds_per_sec": round(off_med),
                "config10_trace_on_cmds_per_sec": round(on_med),
                "config10_trace_off_passes": [
                    round(p) for p in off_passes
                ],
                "config10_trace_on_passes": [
                    round(p) for p in on_passes
                ],
                "config10_trace_overhead_ratio": round(
                    on_med / off_med, 4
                ) if off_med else None,
                "config10_trace_sampled_batches": tracer.sampled,
                "config10_trace_exemplar": exemplar,
            }
        finally:
            client.close()
    finally:
        tracer.set_sample_rate(0.0)
        sup.shutdown()


def bench_config11_tiered(make_client):
    """Config 11 — tiered sketch storage (ISSUE 14): a zipf(1.1)
    tenant population 100x the configured device-row budget served
    through the residency ladder (DEVICE rows as a cache over host
    golden mirrors over disk blobs).

    Three claims, measured:
    - the WHOLE population serves WITHOUT ERROR (config11_errors=0 —
      cold tenants answer from host mirrors, not exhaustion errors);
    - after the ladder converges, hot-set throughput is the device's:
      the same hot-only pass runs against an ALL-RESIDENT client
      holding only the hot set (no budget, pre-ISSUE-14 shape), and
      config11_hot_ratio = resident/tiered must stay near 1 (the
      acceptance bar is 1.25x);
    - pay-for-use: the ladder OFF path is the headline/config4 runs
      themselves (budget 0 arms nothing — no thread, no alloc gate),
      so cross-PR BENCH.json trajectories ARE the no-regression arm.

    Residency tier counters travel in config11_residency so the JSON
    shows the ladder actually moved (demotions from budget pressure,
    promotions of the hot set, host-tier serves for the cold tail)."""
    import shutil
    import tempfile

    BUDGET = 16                 # device-row budget (fast tier)
    POP = 100 * BUDGET          # tenant population: 100x device capacity
    N_HOT = 8                   # zipf(1.1) head the ladder must keep fast
    MIX_STEPS = 1024            # mixed-phase ops across the population
    MIX_B = 128                 # keys per mixed op
    HOT_B = 1 << 14             # keys per hot-pass op
    HOT_PASSES = 4
    blob_dir = tempfile.mkdtemp(prefix="rtpu-bench-resid-")
    out = {
        "config11_tiered_population": POP,
        "config11_device_rows_budget": BUDGET,
    }
    rng = np.random.default_rng(11)
    # zipf(1.1) tenant stream; the measured hot set is the stream's
    # actual head (what the heat tracker sees), not an assumption.
    stream = (rng.zipf(1.1, size=MIX_STEPS) % POP).astype(np.int64)
    counts = np.bincount(stream, minlength=POP)
    hot_ids = np.argsort(counts)[::-1][:N_HOT]

    def hot_pass(filters):
        keys = [
            rng.integers(0, 1 << 18, HOT_B).astype(np.uint64)
            for _ in range(HOT_PASSES)
        ]
        for f in filters:  # warm (compile + promote) outside the clock
            f.contains_all_async(keys[0]).result(timeout=600.0)
        t0 = time.perf_counter()
        for kp in keys:
            futs = [f.contains_all_async(kp) for f in filters]
            for fu in futs:
                fu.result(timeout=600.0)
        return HOT_PASSES * len(filters) * HOT_B / (
            time.perf_counter() - t0
        )

    try:
        # -- tiered arm: POP tenants over a BUDGET-row fast tier ------
        client = make_client(
            coalesce=True,
            residency_device_rows=BUDGET,
            residency_dir=blob_dir,
            # Host cap low enough that the cold tail spills — the
            # bench proves all THREE tiers serve, not two.
            residency_max_host_bytes=POP * 256,
            residency_heat_half_life_s=30.0,
        )
        eng = client._engine
        filters = []
        for i in range(POP):
            bf = client.get_bloom_filter(f"t11-{i}")
            bf.try_init(10_000, 0.01)
            filters.append(bf)
        errors = 0
        from collections import deque
        futs = deque()
        t0 = time.perf_counter()
        for step, t in enumerate(stream):
            keys = rng.integers(0, 1 << 18, MIX_B).astype(np.uint64)
            if step % 3 == 0:
                futs.append(filters[t].add_all_async(keys))
            else:
                futs.append(filters[t].contains_all_async(keys))
            while futs and futs[0].done():
                try:
                    futs.popleft().result()
                except Exception:
                    errors += 1
        for fu in futs:
            try:
                fu.result(timeout=600.0)
            except Exception:
                errors += 1
        mixed_dt = time.perf_counter() - t0
        out["config11_tiered_mixed_ops_per_sec"] = round(
            MIX_STEPS * MIX_B / mixed_dt
        )
        out["config11_errors"] = errors
        # Let the ladder converge (the background thread is live too;
        # driving maintain() here bounds the bench's wall-clock
        # instead of sleeping on the interval).
        for _ in range(8):
            eng.residency.maintain()
        out["config11_tiered_hot_ops_per_sec"] = round(
            hot_pass([filters[i] for i in hot_ids])
        )
        st = eng.residency.stats()
        out["config11_residency"] = {
            k: st[k] for k in (
                "device_rows_used", "host_objects", "host_bytes",
                "disk_objects", "disk_bytes", "promotions",
                "demotions", "spills", "loads", "host_serves",
            )
        }
        out["config11_hot_device_resident"] = sum(
            1 for i in hot_ids
            if eng.registry.lookup(f"t11-{i}").row >= 0
        )
        client.shutdown()

        # -- all-resident arm: ONLY the hot set, no ladder ------------
        client = make_client(coalesce=True)
        res_filters = []
        for i in hot_ids:
            bf = client.get_bloom_filter(f"t11-{i}")
            bf.try_init(10_000, 0.01)
            res_filters.append(bf)
        out["config11_resident_hot_ops_per_sec"] = round(
            hot_pass(res_filters)
        )
        client.shutdown()
        out["config11_hot_ratio"] = round(
            out["config11_resident_hot_ops_per_sec"]
            / max(1, out["config11_tiered_hot_ops_per_sec"]), 3
        )
        out["config11_pass_link"] = measure_pass_link_sample()
    finally:
        shutil.rmtree(blob_dir, ignore_errors=True)
    return out


def bench_config12_loadmap(_make_client):
    """Config 12 — load-attribution plane (ISSUE 16): a zipf(1.1) key
    stream with a skewed tenant mix against 3 forked cluster nodes,
    full key sampling armed fleet-wide.

    Three claims, measured:
    - the fleet load map finds the TRUE hot slots: the measured
      top-5 slots (fleet_loadmap ranking by per-slot op counters) are
      compared against the stream's actual top-5 slots by op count
      (config12_loadmap_slot_rank_quality = intersection fraction; the
      slot counters are exact, so the bar is 1.0);
    - HOTKEYS finds the TRUE hot keys: recall of the fleet-merged
      hottest 10 against the zipf stream's actual head
      (config12_loadmap_hotkey_recall_at_10, acceptance >= 0.9);
    - accounting is near-free: interleaved passes of the same traffic
      with loadmap-enabled yes vs no
      (config12_loadmap_overhead_ratio, acceptance <= 1.05).

    Tenant device-time shares for the skewed CMS tenants travel in
    config12_loadmap_tenant_shares so the JSON shows attribution saw
    the skew, not just the slots."""
    from redisson_tpu.cluster.slots import key_slot
    from redisson_tpu.cluster.supervisor import ClusterSupervisor

    N_KEYS = 64                 # zipf key population
    STREAM = 1500               # SET ops over the population per pass
    AB_OPS = 800                # ops per overhead A/B pass
    AB_ROUNDS = 4               # interleaved on/off rounds
    TENANT_OPS = (120, 60, 20)  # skewed CMS tenant mix (60/30/10)
    rng = np.random.default_rng(12)
    stream = (rng.zipf(1.1, size=STREAM) % N_KEYS).astype(np.int64)
    counts = np.bincount(stream, minlength=N_KEYS)
    true_rank = np.argsort(counts)[::-1]
    # Tie-closed head: any key at least as hot as the 10th-ranked key
    # is a correct answer (a zipf tail ties at the cutoff — rng seed 12
    # puts a 4-way tie at ranks 9-12 — and the detector picking a
    # different member of the tie is not an error).
    tie_floor = counts[true_rank[9]]
    true_hot_keys = {
        f"lm-k{i}" for i in range(N_KEYS)
        if counts[i] >= tie_floor and counts[i] > 0
    }
    # Ground-truth slot loads include the tenant warmup traffic — the
    # slot counters account EVERY served command, so the truth must too.
    slot_ops: dict = {}
    for i in range(N_KEYS):
        if counts[i]:
            s = key_slot(f"lm-k{i}")
            slot_ops[s] = slot_ops.get(s, 0) + int(counts[i])
    for t, n in enumerate(TENANT_OPS):
        s = key_slot(f"lm-t{t}")
        slot_ops[s] = slot_ops.get(s, 0) + n + 1  # +1 INITBYDIM
    true_top_slots = set(
        sorted(slot_ops, key=slot_ops.get, reverse=True)[:5]
    )

    sup = ClusterSupervisor(n_nodes=3)
    sup.start()
    out = {}
    try:
        client = sup.client()
        for addr, r in client._fanout(
            [b"CONFIG", b"SET", b"loadmap-key-sample-rate", b"1"]
        ).items():
            assert r == b"OK", (addr, r)
        # Skewed tenant mix (60/30/10) on the engine path: device-time
        # attribution must see the skew.
        for t, n in enumerate(TENANT_OPS):
            client.execute("CMS.INITBYDIM", f"lm-t{t}", "64", "2")
            for _ in range(n):
                client.execute("CMS.INCRBY", f"lm-t{t}", "item", "1")
        # The zipf key stream (plain grid writes: slot + hot-key plane).
        for i in stream:
            client.execute("SET", f"lm-k{i}", "v")
        fl = client.fleet_loadmap(hot_keys=24)
        got_slots = set(fl["top_slots"][:5])
        out["config12_loadmap_slot_rank_quality"] = round(
            len(got_slots & true_top_slots) / max(1, len(true_top_slots)),
            3,
        )
        # Recall of the STREAM's head: the tenant keys are legitimately
        # hot too (the sketches saw every command), so rank the merged
        # hot list, keep the lm-k entries, and score its top 10 against
        # the tie-closed zipf head (every pick must be a truly-hot key).
        got_keys = [
            d["key"] for d in fl["hot_keys"]
            if d["key"].startswith("lm-k")
        ][:10]
        out["config12_loadmap_hotkey_recall_at_10"] = round(
            len(set(got_keys) & true_hot_keys) / 10.0, 3
        )
        shares = {
            t: d["share"] for t, d in fl["tenants"].items()
            if t.startswith("lm-t")
        }
        out["config12_loadmap_tenant_shares"] = shares
        out["config12_loadmap_nodes"] = {
            n: t.get("ops") for n, t in fl["nodes"].items()
        }
        # Overhead A/B: identical SET traffic, accounting armed vs off,
        # at the PRODUCTION sample rate (0.01 default — rate 1.0 above
        # was the detection-quality arm, not the cost claim).
        # Interleaved rounds + min of paired per-round ratios: RTT
        # noise on a loopback socket only inflates a single pass, so
        # the min-paired ratio is the noise-shedding overhead estimate
        # (the test_observability guard discipline).
        client._fanout(
            [b"CONFIG", b"SET", b"loadmap-key-sample-rate", b"0.01"]
        )

        def pass_cmds_per_sec():
            t0 = time.perf_counter()
            for i in range(AB_OPS):
                client.execute("SET", f"lm-k{i % N_KEYS}", "v")
            return AB_OPS / (time.perf_counter() - t0)

        pass_cmds_per_sec()  # warmup: connections + grid buckets hot
        on_rates, off_rates = [], []
        for _ in range(AB_ROUNDS):
            for arm, rates in (("no", off_rates), ("yes", on_rates)):
                client._fanout(
                    [b"CONFIG", b"SET", b"loadmap-enabled",
                     arm.encode()]
                )
                rates.append(pass_cmds_per_sec())
        on_med = float(np.median(on_rates))
        off_med = float(np.median(off_rates))
        out["config12_loadmap_on_cmds_per_sec"] = round(on_med)
        out["config12_loadmap_off_cmds_per_sec"] = round(off_med)
        out["config12_loadmap_overhead_ratio"] = round(
            min(off / on for off, on in zip(off_rates, on_rates)), 3
        )
    finally:
        sup.shutdown()
    return out


def bench_config13_multicore(_make_client):
    """Config 13 — per-core front door A/B (ISSUE 17 tentpole).

    (a) K=4 SO_REUSEPORT reactor worker processes vs ONE single-process
    front door, same closed-loop unpipelined client population in
    forked client processes (config8's client shape + config9's
    forked-server discipline).  Each client connection probes INFO
    frontdoor for the worker it landed on and pins its hot set to that
    worker's slot range via hash tags — the measured quantity is the
    door's per-core scaling, not the handoff path (the published
    handoff counters from the K=4 arm's INFO prove the forwarded
    fraction stayed ~0).  All arms live simultaneously, interleaved
    passes, per-arm 3-pass MEDIANS (the config8/config9 discipline).
    (b) native-tick mini A/B on the single-process arm: the identical
    workload against a second single-process door running with
    RTPU_NO_NATIVE_TICK=1 — isolates the C drain+frame+classify loop's
    contribution from the process-scaling story.

    Headline: config13_multicore_speedup.  The artifact carries
    config13_host_cores for attribution — on a 1-core bench box K
    worker processes timeshare one core and the >= 2.5x target
    (docs/performance.md) is only physical on >= 4 cores; the number
    published is the measured one, attributed, never extrapolated."""
    import multiprocessing as _mp
    import os as _os
    import signal as _signal
    import socket as _socket
    import subprocess as _subprocess
    import sys as _sys

    from redisson_tpu.serve import multicore as _mc
    from redisson_tpu.serve import wireutil as _wu

    K = 4
    PASS_S = 1.5
    N_PROCS = 8   # forked client processes...
    CONNS = 4     # ...each running this many closed-loop conn threads
    N_KEYS = 128  # per-connection hot set

    def _recv_frame(sock):
        buf = b""
        while True:
            chunk = sock.recv(1 << 16)
            if not chunk:
                raise OSError("peer closed mid-reply")
            buf += chunk
            try:
                _wu.skip_reply_frame(buf, 0)
                return buf
            except IndexError:
                continue

    def _landed(sock):
        """(nworkers, worker_index) from INFO frontdoor — (1, 0) on a
        door that predates the section."""
        sock.sendall(_wu.wire_command([b"INFO", b"frontdoor"]))
        body, _ = _wu.decode_reply(_recv_frame(sock), 0)
        nw, wi = 1, 0
        for ln in bytes(body or b"").splitlines():
            if ln.startswith(b"frontdoor_processes:"):
                nw = int(ln.split(b":", 1)[1])
            elif ln.startswith(b"frontdoor_worker_index:"):
                wi = int(ln.split(b":", 1)[1])
        return max(1, nw), wi

    def _client_proc(host, port, conns, stop_at, seed, q):
        """Closed-loop unpipelined clients, one thread per connection,
        in a FORKED process (the config8 rationale: the measurement
        must load the servers from outside the bench interpreter)."""
        counts = [0] * conns
        lats: list = [[] for _ in range(conns)]

        def worker(t):
            rng = np.random.default_rng(seed * 100 + t)
            sock = _socket.create_connection((host, port))
            sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
            try:
                nw, wi = _landed(sock)
                # Pin this connection's keyspace to the worker it
                # landed on: worker-local dispatch is the scaling path
                # this config measures (handoff cost is config13's
                # forwarded-fraction evidence, not its headline).
                tag = _mc.worker_tag(wi, nw)
                keys = [
                    ("{%s}c13-%d-%d-%d" % (tag, seed, t, i)).encode()
                    for i in range(N_KEYS)
                ]
                sock.sendall(b"".join(
                    _wu.wire_command([b"SET", k, b"v%d" % i])
                    for i, k in enumerate(keys)
                ))
                got = pos = 0
                buf = b""
                while got < len(keys):
                    chunk = sock.recv(1 << 16)
                    if not chunk:
                        raise OSError("closed during seed")
                    buf += chunk
                    while got < len(keys):
                        try:
                            pos = _wu.skip_reply_frame(buf, pos)
                            got += 1
                        except IndexError:
                            break
                while time.time() < stop_at:
                    hot = int((rng.zipf(1.2) - 1) % N_KEYS)
                    if rng.random() < 0.1:
                        cmd = [b"SET", keys[hot], b"v%d" % hot]
                    else:
                        cmd = [b"GET", keys[hot]]
                    t0 = time.perf_counter()
                    sock.sendall(_wu.wire_command(cmd))
                    data = b""
                    closed = False
                    while True:
                        chunk = sock.recv(1 << 16)
                        if not chunk:
                            closed = True  # teardown racing the clock
                            break
                        data += chunk
                        try:
                            _wu.skip_reply_frame(data, 0)
                            break
                        except IndexError:
                            continue
                    if closed:
                        break
                    lats[t].append(time.perf_counter() - t0)
                    counts[t] += 1
            except OSError:
                pass  # arm teardown racing the clock: keep the counts
            finally:
                sock.close()

        t0 = time.time()
        threads = [
            threading.Thread(target=worker, args=(t,))
            for t in range(conns)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        q.put((sum(counts), time.time() - t0,
               [x for la in lats for x in la]))

    def _measure(host, port, duration_s):
        ctx = _mp.get_context("fork")
        q = ctx.Queue()
        stop_at = time.time() + duration_s + 0.3
        procs = [
            ctx.Process(
                target=_client_proc,
                args=(host, port, CONNS, stop_at, i, q),
            )
            for i in range(N_PROCS)
        ]
        for p in procs:
            p.start()
        results = [q.get(timeout=duration_s + 120) for _ in procs]
        for p in procs:
            p.join(timeout=30)
        total = sum(r[0] for r in results)
        dt = float(np.median([r[1] for r in results]))
        all_lat = sorted(x for r in results for x in r[2])
        p99 = all_lat[int(len(all_lat) * 0.99)] if all_lat else 0.0
        return total / max(1e-9, dt), p99 * 1000

    def _spawn_single(env_extra=None):
        """One forked single-process door on the CPU backend (the
        config9 rationale: an in-process server would share the bench
        interpreter's GIL with everything else main() has running)."""
        port = _mc._free_port("127.0.0.1")
        env = dict(_os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(env_extra or {})
        proc = _subprocess.Popen(
            [_sys.executable, "-m", "redisson_tpu",
             "--host", "127.0.0.1", "--port", str(port),
             "--platform", "cpu", "--max-connections", "256"],
            stdout=_subprocess.DEVNULL, stderr=_subprocess.DEVNULL,
            env=env,
        )
        deadline = time.monotonic() + 120.0
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"single-door arm exited rc={proc.returncode}"
                )
            try:
                s = _socket.create_connection(("127.0.0.1", port),
                                              timeout=2.0)
                try:
                    if _wu.exchange(s, [[b"PING"]])[0] == b"PONG":
                        return proc, port
                finally:
                    s.close()
            except OSError:
                pass
            if time.monotonic() > deadline:
                proc.kill()
                raise TimeoutError("single-door arm not serving")
            time.sleep(0.2)

    out = {"config13_multicore_k": K,
           "config13_host_cores": len(_os.sched_getaffinity(0))}
    node = None
    singles: list = []
    try:
        node = _mc.MulticoreNode(K, platform="cpu")
        single_proc, single_port = _spawn_single()
        singles.append(single_proc)
        nat_off_proc, nat_off_port = _spawn_single(
            {"RTPU_NO_NATIVE_TICK": "1"}
        )
        singles.append(nat_off_proc)
        arms = {
            "multicore": (node.host, node.port),
            "single": ("127.0.0.1", single_port),
            "native_off": ("127.0.0.1", nat_off_port),
        }
        for addr in arms.values():  # warm (conn setup, first dispatch)
            _measure(*addr, 0.8)
        passes = {a: [] for a in arms}
        for _ in range(3):
            for a, addr in arms.items():
                passes[a].append(_measure(*addr, PASS_S))
        for a, label in (("multicore", "config13_multicore"),
                         ("single", "config13_single"),
                         ("native_off", "config13_native_off")):
            cps = sorted(p[0] for p in passes[a])[1]  # median of 3
            out[f"{label}_cmds_per_sec"] = round(cps)
            out[f"{label}_passes"] = [round(p[0]) for p in passes[a]]
            out[f"{label}_p99_ms"] = round(
                sorted(p[1] for p in passes[a])[1], 2
            )
        out["config13_multicore_speedup"] = round(
            out["config13_multicore_cmds_per_sec"]
            / max(1.0, out["config13_single_cmds_per_sec"]), 2
        )
        out["config13_native_tick_speedup"] = round(
            out["config13_single_cmds_per_sec"]
            / max(1.0, out["config13_native_off_cmds_per_sec"]), 2
        )
        # Arm-config + forwarded-fraction evidence off the K=4 arm's
        # own INFO: native tick live in the workers, handoffs ~0.
        s = _socket.create_connection((node.host, node.port))
        try:
            nworkers, _ = _landed(s)
            s.sendall(_wu.wire_command([b"INFO", b"frontdoor"]))
            body, _ = _wu.decode_reply(_recv_frame(s), 0)
            info = {}
            for ln in bytes(body or b"").splitlines():
                if b":" in ln and not ln.startswith(b"#"):
                    k, v = ln.split(b":", 1)
                    info[k.decode()] = v.decode()
        finally:
            s.close()
        out["config13_multicore_processes_live"] = nworkers
        out["config13_multicore_info"] = info
    finally:
        if node is not None:
            node.shutdown()
        for p in singles:
            if p.poll() is None:
                try:
                    p.send_signal(_signal.SIGTERM)
                    p.wait(timeout=10)
                except (OSError, _subprocess.TimeoutExpired):
                    p.kill()
    return out


def bench_config14_failover(_make_client):
    """Config 14 — failover drill (ISSUE 18 tentpole).

    3 journaled primaries × 1 replica each (node timeout 1s); forked
    closed-loop writers stream zipf-keyed acked SETs through the
    redirect-chasing ClusterClient, and mid-stream primary 0 dies by
    SIGKILL.  Published:

    - config14_time_to_recovered_goodput_s: wall time from the kill to
      the first half-second bucket whose ack rate recovers to >= 50%
      of the pre-kill median — detection + election + takeover +
      client reconvergence, measured as the CLIENT sees it.
    - config14_time_to_promotion_s: kill → the dead shard's replica
      reporting role:master (the server-side half of the window).
    - config14_acked_write_loss: acked writes that fail to read back
      after recovery, counted over the loss-guaranteed set (writes
      fenced by WAIT 1 before the kill + writes acked after it).
      MUST be 0 — the differential zero-acked-write-loss criterion.
    - config14_replica_staleness_lag_ops_{p50,p99,max}: replica-read
      staleness (slave_lag_ops) sampled across the surviving replicas
      under load — the bounded-staleness read gate's operating range.

    Nodes run on the CPU backend like config9/10/12/13 (N processes
    cannot share the one bench accelerator; this config measures the
    recovery plane, not kernel rate)."""
    import threading as _threading

    from redisson_tpu.cluster.supervisor import (
        ClusterSupervisor,
        _request,
    )

    PRE_S = 3.0
    POST_S = 12.0
    BUCKET_S = 0.5
    N_THREADS = 4
    out = {}
    sup = ClusterSupervisor(
        n_nodes=3, replicas_per_shard=1, node_timeout_ms=1000,
        startup_timeout_s=180.0,
    )
    try:
        sup.start()
        from redisson_tpu.cluster.client import ClusterClient

        stop_evt = _threading.Event()
        acked = [dict() for _ in range(N_THREADS)]  # seq -> ack time
        buckets: dict = {}
        blk = _threading.Lock()

        def writer(t):
            cc = ClusterClient(sup.addrs)
            rng = np.random.default_rng(t)
            seq = t * 10_000_000
            try:
                while not stop_evt.is_set():
                    seq += 1
                    hot = int((rng.zipf(1.2) - 1) % 4096)
                    key = "c14-%d-%d" % (hot, seq)
                    try:
                        r = cc.execute("SET", key, "v%d" % seq)
                    except Exception:
                        continue  # retry budget exhausted mid-failover
                    if r == b"OK":
                        now = time.time()
                        acked[t][key] = now
                        b = int(now / BUCKET_S)
                        with blk:
                            buckets[b] = buckets.get(b, 0) + 1
            finally:
                cc.close()

        lag_samples: list = []
        promoted_at: list = []

        def sampler(kill_at_box):
            raddr0 = sup.replica_addrs[0]
            survivors = sup.replica_addrs[1:]
            while not stop_evt.is_set():
                for addr in survivors:
                    try:
                        (info,) = _request(
                            addr, [("INFO", "replication")], timeout_s=2.0
                        )
                        for ln in info.decode().splitlines():
                            if ln.startswith("slave_lag_ops:"):
                                lag_samples.append(int(ln.split(":")[1]))
                    except (OSError, ValueError):
                        pass
                if kill_at_box and not promoted_at:
                    try:
                        (info,) = _request(
                            raddr0, [("INFO", "replication")],
                            timeout_s=2.0,
                        )
                        if b"role:master" in info:
                            promoted_at.append(time.time())
                    except OSError:
                        pass
                time.sleep(0.05)

        kill_at_box: list = []
        threads = [
            _threading.Thread(target=writer, args=(t,))
            for t in range(N_THREADS)
        ]
        st = _threading.Thread(target=sampler, args=(kill_at_box,))
        for th in threads:
            th.start()
        st.start()
        time.sleep(PRE_S)
        # Fence everything acked so far: WAIT 1 on every primary means
        # each shard's replica holds the prefix — the writes whose
        # survival the kill must not threaten.
        fence_t = time.time()  # BEFORE the fence: a write acked after a
        # primary's WAIT returned (while later primaries' WAITs run) is
        # not covered by that fence, so the cutoff is conservative.
        for addr in sup.addrs:
            (n,) = _request(addr, [("WAIT", "1", "8000")])
            assert n >= 1, f"{addr}: no replica ack for the fence"
        sup.kill_node(0)
        kill_t = time.time()
        kill_at_box.append(kill_t)
        time.sleep(POST_S)
        stop_evt.set()
        for th in threads:
            th.join(timeout=30)
        st.join(timeout=10)

        # Goodput timeline -> recovery point.
        kb = int(kill_t / BUCKET_S)
        pre = [v for b, v in buckets.items()
               if b < kb and (kb - b) * BUCKET_S <= PRE_S]
        pre_med = float(np.median(pre)) if pre else 0.0
        rec_b = next(
            (b for b in sorted(buckets) if b > kb
             and buckets[b] >= 0.5 * pre_med), None
        )
        out["config14_prekill_acked_per_sec"] = round(pre_med / BUCKET_S)
        out["config14_time_to_recovered_goodput_s"] = (
            None if rec_b is None
            else round(rec_b * BUCKET_S - kill_t, 2)
        )
        out["config14_time_to_promotion_s"] = (
            round(promoted_at[0] - kill_t, 2) if promoted_at else None
        )

        # Zero acked-write loss over the guaranteed set: fenced-before-
        # kill plus acked-after-promotion.  The fence->promotion window
        # holds acks the guarantee does NOT cover: the unfenced sliver
        # before the kill, and in-flight acks the dying primary sent
        # that its replica never received (client-side ack timestamps
        # can land just past kill_t for ops served just before it).
        post_t = promoted_at[0] if promoted_at else float("inf")
        guaranteed = [
            k for d in acked for k, ts in d.items()
            if ts <= fence_t or ts >= post_t
        ]
        cc = sup.client()
        lost = 0
        try:
            for i in range(0, len(guaranteed), 512):
                chunk = guaranteed[i:i + 512]
                got = cc.execute_many([("GET", k) for k in chunk])
                lost += sum(1 for g in got if g is None)
        finally:
            cc.close()
        out["config14_acked_writes_checked"] = len(guaranteed)
        out["config14_acked_write_loss"] = lost
        assert lost == 0, f"{lost} acked writes lost across failover"

        if lag_samples:
            lag = sorted(lag_samples)
            out["config14_replica_staleness_lag_ops_p50"] = int(
                lag[len(lag) // 2]
            )
            out["config14_replica_staleness_lag_ops_p99"] = int(
                lag[min(len(lag) - 1, int(len(lag) * 0.99))]
            )
            out["config14_replica_staleness_lag_ops_max"] = int(lag[-1])
            out["config14_replica_staleness_samples"] = len(lag)
    finally:
        sup.shutdown()
    return out


def bench_config15_rebalance(_make_client):
    """Config 15 — autonomous rebalancer A/B (ISSUE 19 tentpole).

    3 primaries with the rebalancer armed on every node (``--rebalance``);
    closed-loop writers stream acked zipf SETs whose hot-spot is a set of
    hash tags that all land on ONE node, and the hot-spot SHIFTS to a
    fresh single-owner tag set each round.  Rounds interleave an
    assigner-OFF pass (``CLUSTER REBALANCE PAUSE`` fleet-wide), a WAVE
    window (resume, shed runs to completion under live traffic), and an
    assigner-ON pass in the rebalanced steady state — the A/B is
    measured on the same fleet under the same churn.  Published:

    - config15_goodput_{off,on}_per_sec + config15_goodput_on_vs_off:
      acked SET rate with the hot-spot pinned vs shed.  The closed-loop
      goodput win needs >= (nodes + clients) host cores — on a 1-core
      box every process shares one CPU, so placement cannot change
      total throughput and the armed agent's scrape/plan ticks show up
      as pure overhead (the config13 situation: publish the measured
      ratio ATTRIBUTED via config15_host_cores, never extrapolated).
    - config15_imbalance_peak_post: per round ``[peak, post]`` of the
      coordinator's observed max/mean load ratio — the placement-plane
      win that holds on ANY host: peak must clear the 1.3 trigger (the
      planner saw the skew) and the round must end back inside the
      dead band under live traffic.
    - config15_set_p99_{off,on,wave}_ms: client-observed SET p99 per
      window; the WAVE number is p99-during-waves and must stay bounded
      (no multi-second stall while slots migrate under traffic).
    - config15_slots_moved / config15_keys_moved / config15_waves /
      config15_migration_seconds_{sum,count}: harvested from
      ``CLUSTER REBALANCE STATUS`` + the ``rtpu_rebalancer_*`` metric
      families — migration work attributed in the artifact itself.
    - config15_acked_write_loss: every acked write must read back after
      the final wave settles (zero-acked-write-loss differential, the
      config14 discipline under planned moves instead of failover).
    - config15_pass_link: [pre, post] link-probe brackets around each
      ON pass (the config4/headline phase-attribution discipline).

    Nodes run on the CPU backend like config9/10/12/13/14 (N processes
    cannot share the one bench accelerator; this config measures the
    placement plane, not kernel rate)."""
    import multiprocessing as _mp
    import os
    import threading as _threading
    import urllib.request as _urlreq

    from redisson_tpu.cluster.client import ClusterClient
    from redisson_tpu.cluster.slots import key_slot
    from redisson_tpu.cluster.supervisor import ClusterSupervisor

    PASS_S = 4.0
    WAVE_S = 8.0
    ROUNDS = 3
    N_PROCS = 6
    CONNS = 2
    out = {}
    sup = ClusterSupervisor(
        n_nodes=3, node_args=["--rebalance"], metrics=True,
        startup_timeout_s=180.0,
    )
    try:
        sup.start()
        ctl = ClusterClient(sup.addrs)
        # Bench cadence: fast ticks, short cooldown, no pacing — the
        # dead-band + cooldown damping is what keeps this honest, not a
        # slow clock.
        for addr, r in ctl._fanout(
            [b"CONFIG", b"SET",
             b"rebalance-interval-ms", b"250",
             b"rebalance-cooldown-ms", b"1500",
             b"rebalance-pace-ms", b"0",
             b"rebalance-threshold", b"1.3",
             b"rebalance-max-moves", b"8"]
        ).items():
            assert r == b"OK", (addr, r)
        assert ctl.rebalance_pause() == 3  # OFF is fleet-wide or it lies

        def hot_tags(rnd, avoid):
            """8 hash tags whose slots share ONE current owner (not
            ``avoid``) — a genuinely single-node hot-spot that shifts
            owner between rounds."""
            ctl.refresh_slots()
            by_owner: dict = {}
            i = 0
            while True:
                tag = "{c15r%d-%d}" % (rnd, i)
                i += 1
                owner = ctl.slot_addr(key_slot(tag))
                if owner == avoid:
                    continue
                grp = by_owner.setdefault(owner, [])
                grp.append(tag)
                if len(grp) >= 8:
                    return owner, grp

        def fleet_counter(field):
            return sum(
                st.get(field, 0)
                for st in ctl.rebalance_status().values()
                if "error" not in st
            )

        # FORKED closed-loop clients (the config13 discipline): writer
        # threads in the driver process share one GIL and never
        # saturate the hot node, so spreading slots can't show a
        # goodput win.  Forked processes make the single hot SERVER
        # process the bottleneck, which is the regime the rebalancer
        # exists for.
        ctx = _mp.get_context("fork")

        def _burst_proc(tags, stop_at, seed, q):
            counts = [0] * CONNS
            lats = [[] for _ in range(CONNS)]
            ackd = [set() for _ in range(CONNS)]

            def worker(c):
                cc = ClusterClient(sup.addrs)
                rng = np.random.default_rng(1000 * seed + c)
                wid = seed * CONNS + c
                seq = 0
                try:
                    while time.time() < stop_at:
                        seq += 1
                        # Flat-ish zipf over 8 tags: rank-1 must not
                        # dwarf the rest or the mega-slot rule pins it
                        # and the shed can never reach the dead band.
                        tag = tags[int(rng.zipf(1.1) - 1) % len(tags)]
                        # TIGHTLY bounded key space per (tag, worker):
                        # the pump is one MIGRATE round trip per key,
                        # so hot slots must stay small (~100 keys) for
                        # a wave to finish inside a window — heat is
                        # ops-driven, 12 keys are as hot as 12k.
                        key = "%s-%d-%d" % (tag, wid, seq % 12)
                        t0 = time.perf_counter()
                        try:
                            rep = cc.execute("SET", key, "v%d" % seq)
                        except Exception:
                            continue  # retry budget exhausted mid-wave
                        if rep == b"OK":
                            lats[c].append(
                                (time.perf_counter() - t0) * 1000.0
                            )
                            counts[c] += 1
                            ackd[c].add(key)
                finally:
                    cc.close()

            t0 = time.time()
            ths = [
                _threading.Thread(target=worker, args=(c,))
                for c in range(CONNS)
            ]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            q.put((
                sum(counts),
                time.time() - t0,
                [x for la in lats for x in la],
                sorted(set().union(*ackd)),
            ))

        acked_keys: set = set()

        def burst(tags, duration_s):
            """Run one measured traffic window via forked clients;
            returns (acked rate, p50 ms, p99 ms)."""
            q = ctx.Queue()
            stop_at = time.time() + duration_s + 0.3  # absorb fork
            procs = [
                ctx.Process(
                    target=_burst_proc, args=(tags, stop_at, i, q)
                )
                for i in range(N_PROCS)
            ]
            for p in procs:
                p.start()
            res = [q.get(timeout=duration_s + 120.0) for _ in procs]
            for p in procs:
                p.join(timeout=30)
            total = sum(r[0] for r in res)
            dt = float(np.median([r[1] for r in res]))
            lat = sorted(x for r in res for x in r[2])
            acked_keys.update(k for r in res for k in r[3])
            pct = (lambda f: round(
                lat[min(len(lat) - 1, int(len(lat) * f))], 2
            )) if lat else (lambda f: None)
            return total / max(dt, 1e-9), pct(0.5), pct(0.99)

        def settle_moves(floor, cap_s):
            """Poll the fleet slots_moved counter until it has been
            quiet for 1.5s (in-flight waves keep pumping after their
            heat source stops; counters land only on wave return)."""
            prev, stable_at = fleet_counter("slots_moved"), time.time()
            deadline = time.time() + cap_s
            while time.time() < deadline:
                time.sleep(0.5)
                cur = fleet_counter("slots_moved")
                if cur != prev:
                    prev, stable_at = cur, time.time()
                elif cur >= floor and time.time() - stable_at >= 1.5:
                    break

        arms: dict = {"off": [], "wave": [], "on": []}
        pass_link = []
        slots_moved_per_round = []
        imbalance_rounds = []
        hot_owner = None
        burst(hot_tags(0, None)[1], 1.0)  # warm path off the books
        for rnd in range(ROUNDS):
            # OFF: hot-spot pinned on one node, assigner frozen — the
            # baseline the rebalancer is supposed to beat.
            hot_owner, tags = hot_tags(rnd, hot_owner)
            arms["off"].append(burst(tags, PASS_S))
            moved0 = fleet_counter("slots_moved")
            bracket = measure_pass_link_sample()
            # Sample the coordinator's observed imbalance ratio across
            # the armed window: the PEAK is the skew the planner saw
            # (why it shed), the LAST sample is the rebalanced steady
            # state under live traffic — the placement-plane win that
            # holds regardless of host core count.
            ratio_samples: list = []
            samp_stop = _threading.Event()

            def sampler():
                sc = ClusterClient(sup.addrs)
                try:
                    while not samp_stop.is_set():
                        try:
                            vals = [
                                st.get("imbalance_ratio", 0.0)
                                for st in sc.rebalance_status().values()
                                if "error" not in st
                            ]
                            if vals:
                                ratio_samples.append(max(vals))
                        except Exception:
                            pass
                        time.sleep(0.3)
                finally:
                    sc.close()

            samp_th = _threading.Thread(target=sampler)
            samp_th.start()
            # WAVE: resume; the burst itself is the heat source and
            # this window IS "p99 during waves".
            assert ctl.rebalance_resume() >= 1
            arms["wave"].append(burst(tags, WAVE_S))
            # Generous cap: slots_moved lands only when the WHOLE wave
            # returns, and a wave can outlive the burst under CPU
            # contention — the ON pass must not start mid-pump.
            settle_moves(moved0 + 1, 60.0)
            # ON: the rebalanced steady state, assigner still armed —
            # the dead band keeps it quiet unless the fleet re-skews.
            arms["on"].append(burst(tags, PASS_S))
            assert ctl.rebalance_pause() >= 1
            samp_stop.set()
            samp_th.join(timeout=10)
            imbalance_rounds.append(
                [round(max(ratio_samples), 3),
                 round(ratio_samples[-1], 3)]
                if ratio_samples else [None, None]
            )
            post = measure_pass_link_sample()
            pass_link.append({
                k: [bracket[k], post[k]]
                for k in ("link_h2d_put_rt_ms", "link_resident_rt_ms")
            })
            slots_moved_per_round.append(
                fleet_counter("slots_moved") - moved0
            )
        # A wave armed during the last ON pass may still be pumping
        # past the pause — settle before the loss differential.
        settle_moves(0, 60.0)

        def arm(name):
            rates = [r for r, _, _ in arms[name]]
            p50s = [p for _, p, _ in arms[name] if p is not None]
            p99s = [p for _, _, p in arms[name] if p is not None]
            return (
                round(float(np.mean(rates))) if rates else 0,
                round(float(np.median(p50s)), 2) if p50s else None,
                round(float(max(p99s)), 2) if p99s else None,
            )

        off_rate, off_p50, off_p99 = arm("off")
        on_rate, on_p50, on_p99 = arm("on")
        wave_rate, wave_p50, wave_p99 = arm("wave")
        out["config15_rounds"] = ROUNDS
        out["config15_goodput_off_per_sec"] = off_rate
        out["config15_goodput_on_per_sec"] = on_rate
        out["config15_goodput_wave_per_sec"] = wave_rate
        out["config15_goodput_on_vs_off"] = (
            round(on_rate / off_rate, 3) if off_rate else None
        )
        out["config15_set_p50_off_ms"] = off_p50
        out["config15_set_p50_on_ms"] = on_p50
        out["config15_set_p99_off_ms"] = off_p99
        out["config15_set_p99_on_ms"] = on_p99
        out["config15_set_p99_wave_ms"] = wave_p99
        slots_moved = fleet_counter("slots_moved")
        out["config15_slots_moved_per_round"] = slots_moved_per_round
        out["config15_slots_moved"] = slots_moved
        out["config15_keys_moved"] = fleet_counter("keys_moved")
        out["config15_waves"] = fleet_counter("waves")
        out["config15_wave_failures"] = fleet_counter("failures")
        out["config15_imbalance_peak_post"] = imbalance_rounds
        out["config15_host_cores"] = len(os.sched_getaffinity(0))
        out["config15_pass_link"] = pass_link
        # The assigner must have actually moved the hot-spot, and the
        # p99 during waves must stay bounded (no multi-second stall).
        assert slots_moved > 0, "assigner never moved"
        assert wave_p99 is not None and wave_p99 < 5000.0, (
            f"p99 during waves unbounded: {wave_p99}ms"
        )
        # Placement-plane win, valid on ANY host: the planner must have
        # OBSERVED the skew (peak ratio past the trigger) and ended the
        # round back inside the dead band under live traffic.
        peaks = [p for p, _ in imbalance_rounds if p is not None]
        posts = [q for _, q in imbalance_rounds if q is not None]
        assert peaks and max(peaks) >= 1.3, (
            f"planner never observed the skew: {imbalance_rounds}"
        )
        assert posts and posts[-1] <= 1.3, (
            f"fleet still skewed after waves: {imbalance_rounds}"
        )

        # Migration-seconds from the coordinator's histogram family —
        # the rtpu_rebalancer_* plane feeding the artifact directly.
        mig_sum = mig_count = 0.0
        for host, port in sup.metrics_addrs:
            try:
                with _urlreq.urlopen(
                    "http://%s:%d/metrics" % (host, port), timeout=5.0
                ) as resp:
                    body = resp.read().decode()
            except OSError:
                continue
            for ln in body.splitlines():
                if ln.startswith("rtpu_rebalancer_migration_seconds_sum"):
                    mig_sum += float(ln.rsplit(" ", 1)[1])
                elif ln.startswith(
                    "rtpu_rebalancer_migration_seconds_count"
                ):
                    mig_count += float(ln.rsplit(" ", 1)[1])
        out["config15_migration_seconds_sum"] = round(mig_sum, 3)
        out["config15_migration_seconds_count"] = int(mig_count)

        # Zero acked-write loss + full slot coverage after the dust
        # settles: planned moves must strand neither keys nor slots.
        ctl.refresh_slots()
        unowned = sum(1 for a in ctl._slots if a is None)
        assert unowned == 0, f"{unowned} slots unowned after waves"
        guaranteed = sorted(acked_keys)
        lost = 0
        for i in range(0, len(guaranteed), 512):
            chunk = guaranteed[i:i + 512]
            got = ctl.execute_many([("GET", k) for k in chunk])
            lost += sum(1 for g in got if g is None)
        out["config15_acked_writes_checked"] = len(guaranteed)
        out["config15_acked_write_loss"] = lost
        assert lost == 0, f"{lost} acked writes lost across waves"
        ctl.close()
    finally:
        sup.shutdown()
    return out


def bench_config16_doctor(_make_client):
    """Config 16 — flight recorder + fleet doctor overhead (ISSUE 20):
    a 2-shard × 1-replica fleet with ``--doctor`` armed everywhere,
    measured under steady closed-loop SET traffic with the doctor
    SWEEPING (on arm) vs PAUSED (off arm), interleaved rounds.

    The claim: continuous invariant auditing is near-free for the data
    plane — the doctor probes over short-lived control connections and
    the flight recorder only writes on control-plane transitions, so
    steady-state data traffic never touches either.
    ``config16_doctor_overhead_ratio`` is the min of paired per-round
    off/on ratios (the config12 noise-shedding discipline), acceptance
    <= 1.05.  The sweeps must actually have run during the on arms
    (config16_doctor_sweeps), and a healthy fleet must finish with
    ZERO findings and ZERO canary failures — the bench doubles as the
    clean-soak false-positive gate."""
    import json as _json

    from redisson_tpu.cluster.supervisor import (
        ClusterSupervisor,
        _request,
    )

    AB_OPS = 1200              # ops per A/B pass (~1s: sweeps overlap)
    AB_ROUNDS = 4              # interleaved paused/sweeping rounds
    out = {}
    sup = ClusterSupervisor(
        n_nodes=2, replicas_per_shard=1, node_timeout_ms=2000,
        node_args=("--doctor",),
    )
    sup.start()
    try:
        client = sup.client()
        addr0 = sup.addrs[0]

        def doctor_status():
            (raw,) = _request(addr0, [("CLUSTER", "DOCTOR", "STATUS")])
            return _json.loads(raw)

        # Wait for the coordinator's first sweeps so both arms measure
        # a WORKING doctor, not its startup.
        deadline = time.monotonic() + 60.0
        st = {}
        while time.monotonic() < deadline:
            st = doctor_status()
            if st.get("enabled") and st.get("sweeps", 0) >= 2:
                break
            time.sleep(0.2)
        assert st.get("enabled"), f"doctor never armed: {st}"

        def pass_cmds_per_sec():
            t0 = time.perf_counter()
            for i in range(AB_OPS):
                client.execute("SET", f"dr-k{i % 64}", "v")
            return AB_OPS / (time.perf_counter() - t0)

        pass_cmds_per_sec()  # warmup: connections + grid buckets hot
        on_rates, off_rates = [], []
        for _ in range(AB_ROUNDS):
            for verb, rates in (("PAUSE", off_rates),
                                ("RESUME", on_rates)):
                _request(addr0, [("CLUSTER", "DOCTOR", verb)])
                rates.append(pass_cmds_per_sec())
        st = doctor_status()
        on_med = float(np.median(on_rates))
        off_med = float(np.median(off_rates))
        out["config16_doctor_on_cmds_per_sec"] = round(on_med)
        out["config16_doctor_off_cmds_per_sec"] = round(off_med)
        out["config16_doctor_overhead_ratio"] = round(
            min(off / on for off, on in zip(off_rates, on_rates)), 3
        )
        out["config16_doctor_sweeps"] = st.get("sweeps", 0)
        out["config16_doctor_findings_total"] = st.get(
            "findings_total", -1
        )
        out["config16_doctor_canary_failures"] = st.get(
            "canary_failures", -1
        )
        # The flight recorder saw the control plane (at minimum the
        # PAUSE/RESUME cycle ran against a live ring) and the fleet
        # timeline merges cleanly.
        tl = client.fleet_events()
        out["config16_fleet_events"] = len(tl["events"])
        out["config16_fleet_event_gaps"] = tl["gaps"]
        assert st.get("sweeps", 0) >= 2, f"doctor never swept: {st}"
        assert out["config16_doctor_findings_total"] == 0, (
            f"doctor raised findings on a healthy fleet: {st}"
        )
        assert out["config16_doctor_canary_failures"] == 0, st
        client.close()
    finally:
        sup.shutdown()
    return out


def bench_config3_bitset(client):
    """Config 3: 2^30-bit RBitSet, batched get/set (raw bitmap path).

    On the single bench chip the 128MB row is device-resident; the
    m-sharded multi-chip layout for the same object is exercised by the
    CPU-mesh suite (tests/test_mbit_sharded.py) and dryrun_multichip."""
    NBITS = 1 << 30
    bs = client.get_bit_set("bench-bs")
    bs.set(NBITS - 1)  # materialize the full row
    rng = np.random.default_rng(2)
    B = 1 << 21  # few, huge launches: fast in both link-RT regimes
    bs.set_many(rng.integers(0, NBITS, B).astype(np.uint32))  # warm compile
    bs.get_many(rng.integers(0, NBITS, B).astype(np.uint32))
    iters = 8
    t0 = time.perf_counter()
    futs = []
    with client.defer_fetch():  # one sync: the mailbox flush below
        for i in range(iters):
            idx = rng.integers(0, NBITS, B).astype(np.uint32)
            if i % 2 == 0:
                futs.append(bs.set_many_async(idx))
            else:
                futs.append(bs.get_many_async(idx))
    client.collect(futs)  # one mailbox flush for all passes
    dt = time.perf_counter() - t0
    return iters * B / dt


def bench_config5_stream_topk(client):
    """Config 5: streaming top-K over a topic→CMS pipe.

    Geometry scaled from the 100M-event spec to 16M events for bench
    wall-clock (same zipf shape, same pipe).  Events ride the real topic
    (publish → delivery pool → listener → coalescer → device) batched at
    the producer into 32k-event array messages — the Kafka-style shape;
    per-event Python dispatch caps near 200k events/s and is reported by
    the in-process listener path tests instead."""
    from redisson_tpu.serve import TopicCmsBridge

    cms = client.get_count_min_sketch("bench-cms")
    cms.try_init(5, 1 << 16, track_top_k=20)
    bridge = TopicCmsBridge(
        client, "bench-events", "bench-cms", batch_size=1 << 15,
        flush_interval_s=0.05,
    )
    topic = client.get_topic("bench-events")
    rng = np.random.default_rng(3)
    n_events = 16_000_000
    n_keys = 100_000
    chunk = 1 << 15
    stream = (rng.zipf(1.2, size=n_events) % n_keys).astype(np.uint64)
    topic.publish(stream[:chunk])  # warm the kernel shapes
    client._topic_bus.drain()
    bridge.flush()
    t0 = time.perf_counter()
    for i in range(chunk, n_events, chunk):
        topic.publish(stream[i : i + chunk])
    client._topic_bus.drain()
    bridge.close()
    dt = time.perf_counter() - t0
    true_counts = np.bincount(stream.astype(np.int64))
    true_top = set(np.argsort(-true_counts)[:10].tolist())
    got = {int(k) for k, _ in cms.top_k(10)}
    recall = len(got & true_top) / 10.0
    return (n_events - chunk) / dt, recall


def bench_full_geometry(make_client):
    """``--full`` mode (BASELINE configs 2 and 5 at their SPEC'd geometry
    — 10M-cardinality HLL stream, 100M-event CMS top-K): run once per
    round outside the driver's default bench, results appended to
    BASELINE.md.  Wall-clock heavy by design."""
    client = make_client(exact_add_semantics=False, coalesce=False)
    out = {}

    # Config 2 at 10M cardinality.
    h = client.get_hyper_log_log("full-hll")
    B = 1 << 19
    n = 10_000_000
    h.add_all_async(np.arange(B, dtype=np.uint64)).result()  # warm
    futs = []
    t0 = time.perf_counter()
    with client.defer_fetch():  # syncs happen only at the window flushes
        for i in range(0, n, B):
            futs.append(
                h.add_all_async(np.arange(i, min(i + B, n), dtype=np.uint64))
            )
            if len(futs) >= 16:
                client.collect(futs)  # one mailbox flush per window
                futs = []
        client.collect(futs)
    dt = time.perf_counter() - t0
    est = h.count()
    out["full_hll_pfadd_ops_per_sec"] = round(n / dt)
    out["full_hll_cardinality"] = n
    out["full_hll_estimate"] = est
    out["full_hll_rel_error"] = round(abs(est - n) / n, 5)

    # Config 5 at 100M events (zipf stream, chunked generation).
    from redisson_tpu.serve import TopicCmsBridge

    cms = client.get_count_min_sketch("full-cms")
    cms.try_init(5, 1 << 16, track_top_k=20)
    bridge = TopicCmsBridge(
        client, "full-events", "full-cms", batch_size=1 << 15,
        flush_interval_s=0.05,
    )
    topic = client.get_topic("full-events")
    rng = np.random.default_rng(13)
    n_events = 100_000_000
    n_keys = 100_000
    chunk = 1 << 18
    true_counts = np.zeros(n_keys, np.int64)
    warm = (rng.zipf(1.2, size=chunk) % n_keys).astype(np.uint64)
    topic.publish(warm)
    client._topic_bus.drain()
    bridge.flush()
    true_counts += np.bincount(warm.astype(np.int64), minlength=n_keys)
    t0 = time.perf_counter()
    done = chunk
    while done < n_events:
        stream = (rng.zipf(1.2, size=chunk) % n_keys).astype(np.uint64)
        topic.publish(stream)
        true_counts += np.bincount(stream.astype(np.int64), minlength=n_keys)
        done += chunk
    client._topic_bus.drain()
    bridge.close()
    dt = time.perf_counter() - t0
    true_top = set(np.argsort(-true_counts)[:10].tolist())
    got = {int(k) for k, _ in cms.top_k(10)}
    # CMS estimator error over the true top-10 (where estimates matter).
    signed = []
    for k in true_top:
        est = cms.estimate(np.uint64(k))
        signed.append((est - true_counts[k]) / max(1, true_counts[k]))
    out["full_cms_events"] = n_events
    out["full_cms_events_per_sec"] = round((done - chunk) / dt)
    out["full_cms_topk_recall_at_10"] = len(got & true_top) / 10.0
    out["full_cms_top10_max_rel_est_error"] = round(
        max(abs(s) for s in signed), 5
    )
    # CMS NEVER undercounts delivered events: a negative signed minimum
    # means the ingest pipe LOST events (diagnostic — separates pipeline
    # loss from the sketch's additive collision overcount).
    out["full_cms_top10_min_signed_error"] = round(min(signed), 5)
    client.shutdown()
    return out


def measure_device_kernel():
    """Engine attribution metric: the hot kernel timed with DEVICE-RESIDENT
    inputs (no H2D, no host round trip per iteration) — what the chip
    itself sustains.  The gap between this and the headline is, by
    construction, the link.

    Iterations are CHAINED (each step's inputs derive from the previous
    step's output) — repeated identical launches over the remote link of
    round 5 were memoized somewhere in the stack and reported fictional
    throughput (10 identical 1M-op launches "completed" in 0.4 ms);
    the data dependency forces genuine sequential execution.  Measured
    honestly the kernel is GATHER-bound (k random word reads into the
    38 MB row per key); the in-kernel murmur hash is nearly free."""
    import jax
    import jax.numpy as jnp

    from redisson_tpu.ops import bitops, bloom as bloom_ops

    B = 1 << 20
    m = 9_585_059  # config-1 geometry (1M keys @ 1% fpp)
    k = 7
    wpr = -(-m // 32)
    rng = np.random.default_rng(5)
    state = jax.device_put(jnp.zeros((wpr + 1,), jnp.uint32))
    rows = jax.device_put(jnp.zeros((B,), jnp.int32))
    h1 = jax.device_put(jnp.asarray(rng.integers(0, m, B).astype(np.uint32)))
    h2 = jax.device_put(jnp.asarray(rng.integers(0, m, B).astype(np.uint32)))

    @jax.jit
    def step(state, rows, h1, h2):
        out = bitops.pack_bool_u32(
            bloom_ops.bloom_contains(
                state, rows, h1, h2, m=m, k=k, words_per_row=wpr
            )
        )
        # Next inputs depend on THIS output: un-memoizable chain.
        bump = (out[0] & jnp.uint32(1)) + jnp.uint32(1)
        h1n = jnp.where(h1 + bump >= m, jnp.uint32(0), h1 + bump)
        h2n = jnp.where(h2 + jnp.uint32(1) >= m, jnp.uint32(1),
                        h2 + jnp.uint32(1))
        return out, h1n, h2n

    out, h1, h2 = step(state, rows, h1, h2)
    np.asarray(out)  # compile + settle (a FETCH forces real execution)
    rt0 = measure_rt_sample() / 1000.0
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out, h1, h2 = step(state, rows, h1, h2)
    # block_until_ready returned without real execution over that link
    # (even chained launches reported 38B ops/s) — only fetching result
    # BYTES forces materialization of the whole chain.  One fetch per
    # measurement; its round trip is subtracted using the same-window
    # RT sample (floored at half, in case the phase shifted mid-run).
    np.asarray(out)
    dt = time.perf_counter() - t0
    dt = max(dt - rt0, dt / 2)
    return round(iters * B / dt)


def measure_link_calibration():
    """Raw transport capability AT BENCH TIME, reported alongside the
    engine numbers so a BENCH_rN drop is attributable from the JSON alone
    (a shared remote link's throughput swung >2x — r4 measured 22-160 MB/s
    H2D and 0.2-360 ms resident round trips across phases on identical
    code).  ``h2d_MBps`` bounds key-shipping throughput (the headline
    ships ~8 bytes/key); ``resident_rt_ms`` bounds per-launch retirement."""
    import jax

    out = {}
    arr = np.zeros(8 << 20, np.uint8)
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        jax.device_put(arr).block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    out["link_h2d_MBps"] = round(8 / best)
    # Per-transfer RT: some phases charge ~a round trip for EVERY
    # device_put regardless of size (r5 measured 2 ms vs 325 ms for the
    # same 2 MB put minutes apart) — this sample tells a reader which
    # regime the capture ran in.
    small = np.ones(1024, np.uint32)
    t0 = time.perf_counter()
    for _ in range(4):
        jax.device_put(small).block_until_ready()
    out["link_h2d_put_rt_ms"] = round((time.perf_counter() - t0) * 250, 2)
    x = jax.device_put(np.ones(1024, np.uint32))
    f = jax.jit(lambda a: a.sum())
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        int(f(x))
    out["link_resident_rt_ms"] = round((time.perf_counter() - t0) * 100, 2)
    return out


def measure_host_baseline():
    """Honest comparison baseline (SURVEY.md §6): the configured bench env
    has NO redis-server binary, so the Redis-backed number cannot be
    measured here — ``vs_baseline`` is null.  What CAN be measured is the
    host golden engine (the NumPy stand-in for the Redis server's sketch
    math) driven through the identical client path; its contains()
    throughput is reported separately as ``host_engine_ops_per_sec``."""
    import shutil

    if shutil.which("redis-server"):
        return None  # future: drive real Redis through the client codec path
    import redisson_tpu
    from redisson_tpu import Config
    from redisson_tpu.codecs import LongCodec

    client = redisson_tpu.create(Config().set_codec(LongCodec()))
    bf = client.get_bloom_filter("host-bf")
    bf.try_init(1_000_000, 0.01)
    B = 1 << 16
    rng = np.random.default_rng(0)
    bf.add_all(np.arange(1 << 18, dtype=np.uint64))
    t0 = time.perf_counter()
    iters = 8
    for _ in range(iters):
        bf.contains_each(rng.integers(0, 1 << 19, B).astype(np.uint64))
    dt = time.perf_counter() - t0
    client.shutdown()
    return iters * B / dt


def main():
    import sys

    from redisson_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    import redisson_tpu
    from redisson_tpu import Config
    from redisson_tpu.codecs import LongCodec

    def make_client(**kw):
        cfg = Config().set_codec(LongCodec()).use_tpu_sketch(**kw)
        return redisson_tpu.create(cfg)

    if "--full" in sys.argv:
        # Spec'd-geometry validation pass (not part of the driver run).
        print(json.dumps({"full_geometry": bench_full_geometry(make_client)}))
        return

    if "--config12" in sys.argv:
        # CI smoke mode (ISSUE 16): the load-attribution pass alone,
        # written as a BENCH.json artifact so the workflow can assert
        # the published keys exist without paying for the full bench.
        stats = bench_config12_loadmap(make_client)
        result = {
            "metric": "config12_loadmap_smoke",
            "value": stats.get("config12_loadmap_hotkey_recall_at_10"),
            "unit": "recall@10",
            "vs_baseline": None,
            "extra": stats,
        }
        line = json.dumps(result)
        print(line)
        write_bench_artifact(result, line)
        return

    if "--config14" in sys.argv:
        # CI smoke mode (ISSUE 18): the failover drill alone — kill -9
        # a primary under acked zipf load, time-to-recovered-goodput,
        # zero acked-write loss, replica staleness histogram — written
        # as a BENCH.json artifact so the workflow can assert the
        # published keys exist without paying for the full bench.
        stats = bench_config14_failover(make_client)
        result = {
            "metric": "config14_failover_smoke",
            "value": stats.get("config14_time_to_recovered_goodput_s"),
            "unit": "s to recovered goodput",
            "vs_baseline": None,
            "extra": stats,
        }
        line = json.dumps(result)
        print(line)
        write_bench_artifact(result, line)
        return

    if "--config15" in sys.argv:
        # CI smoke mode (ISSUE 19): the rebalancer A/B alone — shifting
        # single-node zipf hot-spot, assigner paused vs running, zero
        # acked-write loss after the waves — written as a BENCH.json
        # artifact so the workflow can assert the published keys exist
        # without paying for the full bench.
        stats = bench_config15_rebalance(make_client)
        result = {
            "metric": "config15_rebalance_smoke",
            "value": stats.get("config15_goodput_on_vs_off"),
            "unit": "x goodput, assigner on vs off",
            "vs_baseline": None,
            "extra": stats,
        }
        line = json.dumps(result)
        print(line)
        write_bench_artifact(result, line)
        return

    if "--config16" in sys.argv:
        # CI smoke mode (ISSUE 20): the doctor-overhead A/B alone,
        # written as a BENCH.json artifact so the workflow can assert
        # the published keys exist without paying for the full bench.
        stats = bench_config16_doctor(make_client)
        result = {
            "metric": "config16_doctor_smoke",
            "value": stats.get("config16_doctor_overhead_ratio"),
            "unit": "x goodput, doctor paused vs sweeping",
            "vs_baseline": None,
            "extra": stats,
        }
        line = json.dumps(result)
        print(line)
        write_bench_artifact(result, line)
        return

    if "--config13" in sys.argv:
        # CI smoke mode (ISSUE 17): the per-core front door A/B alone,
        # written as a BENCH.json artifact so the workflow can assert
        # the published keys exist without paying for the full bench.
        stats = bench_config13_multicore(make_client)
        result = {
            "metric": "config13_multicore_smoke",
            "value": stats.get("config13_multicore_speedup"),
            "unit": "x vs single-process door",
            "vs_baseline": None,
            "extra": stats,
        }
        line = json.dumps(result)
        print(line)
        write_bench_artifact(result, line)
        return

    # Bulk single-tenant path: device-side hashing, no cross-call coalescing
    # (that serves the mixed multi-tenant QPS config below).
    link = measure_link_calibration()
    link["device_kernel_contains_ops_per_sec"] = measure_device_kernel()
    client = make_client(exact_add_semantics=False, coalesce=False)
    (
        contains_ops,
        fpp,
        headline_passes,
        headline_B,
        ops_per_sync,
        headline_pass_rt_ms,
        headline_pass_link,
    ) = bench_bloom_contains(client)
    hll_ops = bench_hll_pfadd(client)
    bitset_ops = bench_config3_bitset(client)
    stream_eps, topk_recall = bench_config5_stream_topk(client)
    # Config 4 runs THREE full passes and publishes the MEDIAN (ISSUE 4
    # satellite): r05's best-of-2 recorded [1105792, 9933] — a single
    # link-stall pass poisons a 2-sample aggregate, while a median of 3
    # sheds one stall.  Each pass travels with BOTH link probes sampled
    # in its bracketing windows (small-put RT for per-transfer-RT phases,
    # resident RT for fetch-RT phases), so a stalled pass is attributable
    # from the JSON alone.
    config4_runs = []
    bracket = measure_pass_link_sample()
    for _ in range(3):
        ops, m, cold = bench_config4_mixed(make_client)
        post = measure_pass_link_sample()
        config4_runs.append({
            "ops": ops, "metrics": m, "cold": cold,
            "link": {
                k: [bracket[k], post[k]]
                for k in ("link_h2d_put_rt_ms", "link_resident_rt_ms")
            },
        })
        bracket = post
    config4_passes = [round(r["ops"]) for r in config4_runs]
    config4_cold_passes = [round(r["cold"]) for r in config4_runs]
    config4_pass_link = [r["link"] for r in config4_runs]
    config4_pass_rt_ms = [
        round(sum(r["link"]["link_resident_rt_ms"]) / 2, 2)
        for r in config4_runs
    ]
    # Phase-conditional p99: the r3 target (<=25 ms at 1M QPS) is only
    # physical when the link RT is small in the SAME window — report the
    # p99 of any pass whose bracketing RT samples averaged < 5 ms.
    fast_p99s = [
        r["metrics"].get("p99_wait_ms")
        for r, rt in zip(config4_runs, config4_pass_rt_ms)
        if rt < 5.0 and r["metrics"].get("p99_wait_ms") is not None
    ]
    p99_fast_phase = min(fast_p99s) if fast_p99s else None
    # Published number = the median pass; its own metrics travel with it.
    median_run = sorted(config4_runs, key=lambda r: r["ops"])[1]
    mixed_ops, metrics = median_run["ops"], median_run["metrics"]
    # Near-cache hot-key pass (ISSUE 4 tentpole evidence): same traffic
    # with the tier on vs off + measured hit rate.
    nearcache_stats = bench_nearcache_hotkeys(make_client)
    # Front-door vectorization pass (ISSUE 6 tentpole evidence):
    # pipelined RESP cmds/s with fused runs on vs off, interleaved A/B.
    frontdoor_stats = bench_config6_frontdoor(make_client)
    # Overload A/B (ISSUE 7): graceful degradation past saturation —
    # shedding ON holds bounded accepted-op p99 + near-peak goodput at
    # 2x offered load; OFF shows the queue-wait collapse.  Plus the
    # tenant-fairness mini-pass.
    overload_stats = bench_config7_overload(make_client)
    # Reactor front door A/B (ISSUE 11): unpipelined-client cmds/s +
    # p99 with the epoll reactor vs thread-per-connection, plus the
    # idle-connection thread/fd census (reactor_* keys).
    reactor_stats = bench_config8_reactor(make_client)
    # Cluster-mode scaling A/B (ISSUE 12): 1 vs 3 forked server nodes
    # under the same client population + the live-migration
    # differential (cluster_* keys).  Isolated: a spawn failure on a
    # constrained box degrades to an attributed error key, never a
    # dead bench.
    try:
        cluster_stats = bench_config9_cluster(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        cluster_stats = {"cluster_error": repr(e)}
    # Durability tier A/B (ISSUE 10): journal off vs everysec vs always
    # on the acked-write path (journal_* keys).
    journal_stats = bench_journal_ab(make_client)
    # Fleet tracing A/B (ISSUE 13): 3-node scatter/gather cmds/s with
    # the distributed tracer off vs sampled-on at rate 1.0, plus one
    # exemplar multi-node trace embedded in the artifact.  Isolated
    # like config9 (subprocess spawn).
    try:
        trace_stats = bench_config10_trace(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        trace_stats = {"config10_trace_error": repr(e)}
    # Tiered residency (ISSUE 14): config11_tiered — a zipf(1.1)
    # population 100x the device-row budget through the ladder, hot-set
    # throughput vs an all-resident hot-set-only run, tier transition
    # counters.  Isolated: a failure degrades to an attributed error
    # key, never a dead bench.
    try:
        tiered_stats = bench_config11_tiered(make_client)
    except Exception as e:  # pragma: no cover - env-dependent
        tiered_stats = {"config11_tiered_error": repr(e)}
    # Load-attribution plane (ISSUE 16): config12_loadmap — zipf key
    # stream + skewed tenants against 3 forked nodes; hot-slot rank
    # quality, HOTKEYS recall, accounting-overhead A/B.  Isolated like
    # config9/10 (subprocess spawn).
    try:
        loadmap_stats = bench_config12_loadmap(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        loadmap_stats = {"config12_loadmap_error": repr(e)}
    # Per-core front door (ISSUE 17): config13_multicore — K=4
    # SO_REUSEPORT workers vs one single-process door under the same
    # forked closed-loop clients, plus the native-tick mini A/B.
    # Isolated like config9/10/12 (subprocess spawn).
    try:
        multicore_stats = bench_config13_multicore(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        multicore_stats = {"config13_multicore_error": repr(e)}
    # Failover drill (ISSUE 18): config14_failover — kill -9 a primary
    # under acked zipf load; time-to-recovered-goodput, zero
    # acked-write loss, replica staleness histogram.  Isolated like
    # config9/10/12/13 (subprocess spawn).
    try:
        failover_stats = bench_config14_failover(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        failover_stats = {"config14_failover_error": repr(e)}
    # Autonomous rebalancer (ISSUE 19): config15_rebalance — shifting
    # single-node zipf hot-spot, assigner-off vs assigner-on passes,
    # zero acked-write loss after the waves.  Isolated like
    # config9/10/12/13/14 (subprocess spawn).
    try:
        rebalance_stats = bench_config15_rebalance(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        rebalance_stats = {"config15_rebalance_error": repr(e)}
    # Flight recorder + fleet doctor (ISSUE 20): config16_doctor —
    # continuous invariant auditing's steady-state overhead A/B plus
    # the clean-fleet zero-findings gate.  Isolated like
    # config9/10/12/13/14/15 (subprocess spawn).
    try:
        doctor_stats = bench_config16_doctor(make_client)
    except Exception as e:  # pragma: no cover - env-dependent spawn
        doctor_stats = {"config16_doctor_error": repr(e)}
    host_ops = measure_host_baseline()

    # vs_baseline: the bench env ships no redis-server, so the Redis-backed
    # comparison cannot be MEASURED here — null, not assumed (BASELINE.md
    # comparison row).  vs_host_engine is a real measurement: the NumPy
    # golden engine (the Redis-server stand-in) through the same client.
    result = (
            {
                "metric": "bloom_contains_ops_per_sec_per_chip",
                "value": round(contains_ops),
                "unit": "ops/s",
                "vs_baseline": None,
                "extra": {
                    **link,
                    "headline_passes": [round(p) for p in headline_passes],
                    "headline_median": round(
                        float(np.median(headline_passes))
                    ),
                    "headline_batch_ops": headline_B,
                    "ops_per_sync": ops_per_sync,
                    "headline_pass_rt_ms": headline_pass_rt_ms,
                    # Headline phase brackets (ISSUE 14 satellite):
                    # [pre, post] link probes per measured pass — a
                    # slow-link regression is attributed to the
                    # environment phase, not the code, in the JSON
                    # itself (ROADMAP measurement-debt note).
                    "headline_pass_link": headline_pass_link,
                    "config4_passes": config4_passes,
                    # Warm/cold split (ISSUE 2): cold passes run while
                    # the AOT pre-warmer is still compiling; warm passes
                    # run behind the prewarm_wait barrier — the compile
                    # cliff is measured, not averaged away.
                    "config4_cold_passes": config4_cold_passes,
                    "config4_cold_pass": max(config4_cold_passes),
                    "config4_warm_pass": max(config4_passes),
                    "config4_pass_rt_ms": config4_pass_rt_ms,
                    # Per-pass bracketing link probes ([pre, post] per
                    # pass, both regimes): a stalled pass carries its
                    # own attribution (ISSUE 4 satellite).
                    "config4_pass_link": config4_pass_link,
                    "p99_batch_ms_fast_phase": p99_fast_phase,
                    "config4_median": round(
                        float(np.median(config4_passes))
                    ),
                    # Near cache (ISSUE 4): zipf hot-key pass, on vs off
                    # + epoch-aware hit rate — the host-tier win measured
                    # independently of link phase.
                    **nearcache_stats,
                    # Front door (ISSUE 6): config6_frontdoor — pipelined
                    # RESP throughput, fusion on vs off (interleaved),
                    # fusion ratio + response-cache hit rate + the
                    # phase-aware merge-cap mini A/B.
                    **frontdoor_stats,
                    # Overload control plane (ISSUE 7): config7_overload
                    # open-loop A/B + fairness soak keys (overload_*).
                    **overload_stats,
                    # Reactor front door (ISSUE 11): config8_reactor —
                    # unpipelined cmds/s + p99 reactor ON/OFF, cross-
                    # connection fused ops, 5k-idle thread/fd census.
                    **reactor_stats,
                    # Durability tier (ISSUE 10): journal-on overhead
                    # A/B — off vs everysec vs always on the acked
                    # bloom-add path, with fsync counts (journal_*).
                    **journal_stats,
                    # Cluster mode (ISSUE 12): config9_cluster — 1 vs 3
                    # forked nodes, same client population, per-arm
                    # 3-pass medians + speedup, and the zero-acked-
                    # write-loss live-migration differential.
                    **cluster_stats,
                    # Fleet telemetry (ISSUE 13): config10_trace —
                    # tracing-off vs sampled-on cmds/s across a 3-node
                    # scatter/gather population + one exemplar
                    # multi-node trace (client legs, per-node ingress,
                    # device-launch phases).
                    **trace_stats,
                    # Tiered residency (ISSUE 14): config11_tiered —
                    # population 100x device capacity, zero errors,
                    # hot-set ratio vs all-resident, tier counters.
                    **tiered_stats,
                    # Load attribution (ISSUE 16): config12_loadmap —
                    # hot-slot rank quality + HOTKEYS recall on a zipf
                    # stream, tenant device-time shares, accounting
                    # overhead A/B.
                    **loadmap_stats,
                    # Per-core front door (ISSUE 17):
                    # config13_multicore — K=4 reuseport workers vs one
                    # door (forked closed-loop clients, interleaved
                    # 3-pass medians), native-tick A/B, host-core
                    # attribution.
                    **multicore_stats,
                    # Failover drill (ISSUE 18): time-to-recovered-
                    # goodput, promotion time, zero acked-write loss,
                    # replica staleness percentiles.
                    **failover_stats,
                    # Autonomous rebalancer (ISSUE 19): assigner on/off
                    # goodput + p99, slots/keys moved, migration
                    # seconds, zero acked-write loss across waves.
                    **rebalance_stats,
                    # Flight recorder + fleet doctor (ISSUE 20):
                    # doctor sweeping vs paused goodput A/B, clean-
                    # fleet zero-findings gate, fleet-timeline size.
                    **doctor_stats,
                    "hll_pfadd_ops_per_sec": round(hll_ops),
                    "config3_bitset_ops_per_sec": round(bitset_ops),
                    "config4_mixed_ops_per_sec": round(mixed_ops),
                    "config5_stream_events_per_sec": round(stream_eps),
                    "config5_topk_recall_at_10": topk_recall,
                    "config5_path": "xla_vectorized",  # production path is
                    # the vectorized XLA add_all via TopicCmsBridge; the
                    # Pallas kernel serves add_all_seq's exact
                    # at-sequence-point semantics (ops/pallas_cms.py)
                    "p50_batch_ms": metrics.get("p50_wait_ms"),
                    "p99_batch_ms": metrics.get("p99_wait_ms"),
                    "p99_flush_ms": metrics.get("p99_flush_ms"),
                    # In-framework observability snapshot (ISSUE 1): the
                    # perf trajectory carries latency BREAKDOWNS, not
                    # just throughput — per-command p50/p99 from the
                    # lifecycle-span histograms plus batch occupancy, so
                    # a BENCH_rN drop is attributable to a specific op
                    # path from the JSON alone.
                    "metrics_snapshot": {
                        "per_command": metrics.get("ops"),
                        "mean_batch_occupancy": metrics.get(
                            "mean_batch_occupancy"
                        ),
                        "p50_wait_ms": metrics.get("p50_wait_ms"),
                        "p99_wait_ms": metrics.get("p99_wait_ms"),
                        "tenants_tracked": len(metrics.get("tenants", {})),
                        # Per-phase span histograms (coalesce_wait /
                        # host_stage / device_dispatch / d2h_fetch): the
                        # evidence view for WHERE warm-path time goes.
                        "phases": metrics.get("phases"),
                    },
                    "measured_fpp": round(fpp, 5),
                    "host_engine_ops_per_sec": (
                        None if host_ops is None else round(host_ops)
                    ),
                    "vs_host_engine": (
                        None
                        if host_ops is None
                        else round(contains_ops / host_ops, 2)
                    ),
                    "vs_baseline_note": "no redis-server in bench env; "
                    "vs_host_engine measures the NumPy golden engine "
                    "(Redis-server stand-in) through the same client path",
                },
            }
    )
    line = json.dumps(result)
    print(line)
    write_bench_artifact(result, line)


def write_bench_artifact(result: dict, line: str,
                         path: str = "BENCH.json") -> None:
    """ISSUE 12 satellite: the checked-in BENCH_r0*.json are DRIVER-side
    raw capture wrappers (n/cmd/rc/tail/parsed) — trajectory tooling
    had to unwrap ``parsed`` before diffing two runs.  The bench now
    also writes its own stable artifact with the parsed result dict as
    the TOP-LEVEL payload and the capture-wrapper-shaped metadata under
    a ``raw`` key, so ``jq .extra.cluster_speedup BENCH.json`` works on
    any run without knowing the wrapper."""
    import os
    import sys

    payload = dict(result)
    payload["raw"] = {
        "cmd": " ".join([sys.executable] + sys.argv),
        "rc": 0,
        "tail": line,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)  # readers never see a torn artifact


if __name__ == "__main__":
    main()
