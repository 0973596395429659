#!/usr/bin/env python3
"""Chip smoke: drive redisson_tpu's main path once on a TPU, end to end.

One process owns the chip.  It builds the device client through
``redisson_tpu.create(Config().set_codec(LongCodec()).use_tpu_sketch())``
(coalescer on), serves it over an in-process RESP front door the way
``python -m redisson_tpu`` does, and checks every answer against the host
golden engine (``redisson_tpu.create(Config())``) fed the same ops from
the same ``--seed``.  Phases run at the BASELINE geometries: Bloom config
1 (1M keys, 1%), config 4 (1000 stacked 10k/1% tenants, 8 threads), HLL
config 2 (10M distinct), bitset config 3 (2^30 bits), CMS config 5
(d=5, w=65536, top-K 20, Pallas streaming kernel), and RESP replies
byte-compared against a host-engine server.  After every phase the
engine must be healthy with every sketch served from the device.

``--chips 4`` runs only the sharded-executor path (tenant-sharded pools,
the m-sharded 2^30-bit bitmap, PFMERGE / BITOP OR across shards).

One JSON line per phase; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero at the device gate.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback

import numpy as np

# Sizes users of each config run (BASELINE.json).  A CPU rehearsal may
# shrink them by assigning these module globals; the script never does.
BLOOM_N = 1_000_000  # config 1: try_init(1M, 0.01), 1M adds
TENANTS = 1000  # config 4: stacked 10k/1% tenants
TENANT_THREADS = 8
TENANT_STEPS = 400  # per thread; 256-key chunks
HLL_N = 10_000_000  # config 2: distinct keys into one HLL
BITSET_BITS = 1 << 30  # config 3
BITSET_OPS = 1_000_000
CMS_EVENTS = 1 << 20  # config 5: >= 1M zipf(1.2) events
CMS_KEYS = 100_000
RESP_BATCH = 10_000  # members per pipelined RESP command
SHARDED_TENANTS = 64  # --chips 4: Bloom and HLL tenants each
WATCHDOG_S = 1100  # the driver's limit is 1200 s, compile included


def check(ok, detail) -> None:
    """A failed correctness check fails the run (kept under ``python -O``,
    unlike assert)."""
    if not ok:
        raise AssertionError(detail)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_gate(min_chips: int) -> dict:
    """The chip or nothing: no CPU fallback."""
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if info["platform"] != "tpu" or info["count"] < min_chips:
        raise SystemExit(
            f"chip_smoke: needs {min_chips} TPU device(s), JAX found {info}"
        )
    emit({"phase": "device", **info})
    return info


def device_bytes() -> list:
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append(st.get("bytes_in_use"))
    return out


def check_served_from_device(client) -> None:
    """Phase 8, after every phase: nothing degraded, nothing fell back
    to a host mirror or disk."""
    eng = client._engine
    h = eng.health.summary()
    check(h["state"] == "healthy", h)
    check(not h["degraded_kinds"], h)
    check(h["degrade_events"] == 0, h)
    r = eng.residency.stats()
    check(r["host_objects"] == 0, r)
    check(r["disk_objects"] == 0, r)
    check(r["host_serves"] == 0, r)


def run_phase(name, fn, dev, *args):
    t0 = time.perf_counter()
    out = fn(dev, *args)
    check_served_from_device(dev)
    emit({
        "phase": name,
        "seconds": time.perf_counter() - t0,
        "device_bytes_in_use": device_bytes(),
        **out,
    })


def _distinct_keys(rng, n: int, lo: int = 0) -> np.ndarray:
    """n distinct int64-range keys at or above ``lo``."""
    base = int(rng.integers(lo, lo + (1 << 40)))
    return rng.permutation(np.arange(base, base + n, dtype=np.uint64))


# -- one chip ----------------------------------------------------------------


def phase_bloom(dev, host, rng):
    """Config 1: one 1M-key 1% filter."""
    keys = _distinct_keys(rng, BLOOM_N)
    absent = _distinct_keys(rng, BLOOM_N, lo=1 << 50)
    res = []
    for c in (dev, host):
        bf = c.get_bloom_filter("cfg1")
        check(bf.try_init(BLOOM_N, 0.01), "cfg1 try_init")
        res.append((bf.add_all(keys), bf.contains_each(keys),
                    bf.contains_each(absent)))
    (added, hits, fp), (h_added, _, h_fp) = res
    false_neg = int(BLOOM_N - np.count_nonzero(hits))
    fpp, h_fpp = float(fp.mean()), float(h_fp.mean())
    # Every absent-key answer, not a sample: same (m, k, hash), same bits.
    mismatches = int(np.count_nonzero(fp != h_fp))
    check(added == h_added, (added, h_added))
    check(false_neg == 0, false_neg)
    check(abs(fpp - h_fpp) <= 0.02 * h_fpp, (fpp, h_fpp))
    check(mismatches == 0, mismatches)
    return {"keys": BLOOM_N, "added": added, "false_negatives": false_neg,
            "fpp": fpp, "host_fpp": h_fpp, "absent_mismatches": mismatches}


def phase_tenants(dev, host, rng):
    """Config 4: 1000 stacked tenants, mixed add/contains from 8 threads
    through the coalescer; thread t owns tenants t, t+8, ... so each
    tenant's op order is fixed and the host replays it exactly."""
    names = [f"cfg4:{t}" for t in range(TENANTS)]
    for c in (dev, host):
        for n in names:
            check(c.get_bloom_filter(n).try_init(10_000, 0.01), n)
    seeds = rng.integers(0, 1 << 31, TENANT_THREADS)
    logs = [None] * TENANT_THREADS
    errors = []

    def worker(t):
        try:
            trng = np.random.default_rng(int(seeds[t]))
            mine = names[t::TENANT_THREADS]
            ops = []
            for step in range(TENANT_STEPS):
                n = mine[int(trng.integers(len(mine)))]
                keys = trng.integers(0, 50_000, 256).astype(np.uint64)
                bf = dev.get_bloom_filter(n)
                add = step % 3 == 0
                fut = (bf.add_all_async(keys) if add
                       else bf.contains_all_async(keys))
                ops.append((n, add, keys, fut))
            logs[t] = [(n, add, keys, np.asarray(f.result()))
                       for n, add, keys, f in ops]
        except BaseException as e:  # re-raised by the main thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(TENANT_THREADS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    n_ops = mismatches = 0
    for log in logs:
        for n, add, keys, got in log:
            bf = host.get_bloom_filter(n)
            want = (bf.add_all_async(keys) if add
                    else bf.contains_all_async(keys)).result()
            mismatches += int(np.count_nonzero(got != np.asarray(want)))
            n_ops += len(keys)
    check(mismatches == 0, mismatches)
    return {"tenants": TENANTS, "threads": TENANT_THREADS, "ops": n_ops,
            "mismatches": mismatches}


# p=14 HLL: relative standard error 1.04/sqrt(2^14) = 0.8125 %.  The
# estimate must sit within 3 of them; the exact check is device == host.
HLL_REL_BOUND = 3 * 1.04 / 128


def phase_hll(dev, host, rng):
    """Config 2: 10M distinct keys into one HyperLogLog."""
    keys = _distinct_keys(rng, HLL_N)
    counts = []
    for c in (dev, host):
        h = c.get_hyper_log_log("cfg2")
        for i in range(0, HLL_N, 1 << 20):
            h.add_all(keys[i : i + (1 << 20)])
        counts.append(h.count())
    est, h_est = counts
    rel_err = abs(est - HLL_N) / HLL_N
    vs_host = abs(est - h_est) / h_est
    check(rel_err <= HLL_REL_BOUND, rel_err)
    check(vs_host <= 1e-4, (est, h_est))
    return {"keys": HLL_N, "count": est, "host_count": h_est,
            "rel_error": rel_err, "rel_vs_host": vs_host}


def phase_bitset(dev, host, rng):
    """Config 3: one 2^30-bit bitset (128 MiB on device)."""
    idx = rng.integers(0, BITSET_BITS, BITSET_OPS).astype(np.uint32)
    probe = np.concatenate([
        idx[: BITSET_OPS // 2],
        rng.integers(0, BITSET_BITS, BITSET_OPS // 2).astype(np.uint32),
    ])
    res = []
    for c in (dev, host):
        bs = c.get_bit_set("cfg3")
        bs.set(BITSET_BITS - 1)  # materialize the full row
        prev = np.asarray(bs.set_many(idx))
        got = np.asarray(bs.get_many(probe))
        res.append((prev, got, bs.cardinality()))
    (prev, got, card), (h_prev, h_got, h_card) = res
    check(np.array_equal(prev, h_prev), "prev bits")
    check(np.array_equal(got, h_got), "get answers")
    check(card == h_card, (card, h_card))
    return {"bits": BITSET_BITS, "ops": 2 * BITSET_OPS, "cardinality": card,
            "host_cardinality": h_card}


def phase_cms(dev, host, rng):
    """Config 5: streaming top-K through the Pallas sequential kernel."""
    from redisson_tpu.ops import pallas_cms
    from redisson_tpu.utils import hashing

    d, w = 5, 1 << 16
    stream = (rng.zipf(1.2, CMS_EVENTS) % CMS_KEYS).astype(np.uint64)
    cms = dev.get_count_min_sketch("cfg5")
    check(cms.try_init(d, w, track_top_k=20), "cms try_init")
    est = np.asarray(cms.add_all_seq(stream))
    h1, h2 = cms._hash128(stream)
    h1w, h2w = hashing.km_reduce_mod(h1, h2, w)
    _, want = pallas_cms.golden_seq(
        np.zeros((d, w), np.uint32), h1w, h2w,
        np.ones(CMS_EVENTS, np.uint32), d=d, w=w,
    )
    mismatches = int(np.count_nonzero(est != want))
    true_top = set(np.argsort(-np.bincount(stream.astype(np.int64)),
                              kind="stable")[:10].tolist())
    got_top = {int(k) for k, _ in cms.top_k(10)}
    recall = len(true_top & got_top) / 10.0
    pallas = any(k[0] == "cms_seq" for k in dev._engine.executor._jit_cache)
    check(mismatches == 0, mismatches)
    check(recall == 1.0, (sorted(true_top), sorted(got_top)))
    check(pallas, "the Pallas cms_seq executable was never built")
    return {"events": CMS_EVENTS, "depth": d, "width": w,
            "golden_mismatches": mismatches, "top10_recall": recall,
            "pallas_built": pallas}


def _resp_commands(rng) -> list:
    n = RESP_BATCH
    ints = lambda a: [str(int(x)).encode() for x in a]  # noqa: E731
    members = ints(rng.integers(0, 1 << 40, n))
    cmds = [[b"BF.RESERVE", b"resp:bf", b"0.01", b"100000"]]
    cmds += [[b"BF.MADD", b"resp:bf"] + members[i : i + n // 4]
             for i in range(0, n, n // 4)]
    cmds.append([b"BF.MEXISTS", b"resp:bf"] + members[: n // 2]
                + ints(rng.integers(1 << 41, 1 << 42, n // 2)))
    cmds += [[b"PFADD", b"resp:hll"] + ints(rng.integers(0, 1 << 40, n))
             for _ in range(4)]
    cmds.append([b"PFCOUNT", b"resp:hll"])
    bits = ints(rng.integers(0, 1 << 24, 500))
    cmds += [[b"SETBIT", b"resp:bits", o, b"1"] for o in bits]
    cmds += [[b"GETBIT", b"resp:bits", o]
             for o in bits[:250] + ints(rng.integers(0, 1 << 24, 250))]
    cmds.append([b"BITCOUNT", b"resp:bits"])
    cmds.append([b"CMS.INITBYDIM", b"resp:cms", b"65536", b"5"])
    hot = ints(rng.zipf(1.2, n) % 1000)
    for i in range(0, n, 1000):
        pairs = []
        for k in hot[i : i + 1000]:
            pairs += [k, b"1"]
        cmds.append([b"CMS.INCRBY", b"resp:cms"] + pairs)
    cmds.append([b"CMS.QUERY", b"resp:cms"] + ints(range(1000)))
    return cmds


def _raw_replies(port: int, cmds) -> list:
    """Ship the pipeline in one sendall; return each reply's raw bytes."""
    from redisson_tpu.serve.wireutil import skip_reply_frame, wire_command

    with socket.create_connection(("127.0.0.1", port), timeout=300) as s:
        s.sendall(b"".join(wire_command(c) for c in cmds))
        buf, pos, out = b"", 0, []
        while len(out) < len(cmds):
            chunk = s.recv(1 << 20)
            if not chunk:
                raise OSError("server closed mid-reply")
            buf += chunk
            while len(out) < len(cmds):
                try:
                    end = skip_reply_frame(buf, pos)
                except IndexError:
                    break  # frame not complete yet
                out.append(buf[pos:end])
                pos = end
    return out


def phase_resp(dev, host, rng):
    """RESP over loopback against the in-process front door, replies
    byte-compared with a RespServer over the host engine."""
    from redisson_tpu.serve.resp import RespServer

    cmds = _resp_commands(rng)
    replies = []
    for c in (dev, host):
        server = RespServer(c, host="127.0.0.1", port=0)
        try:
            replies.append(_raw_replies(server.port, cmds))
        finally:
            server.close()
    got, want = replies
    bad = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    errors = [i for i, r in enumerate(got) if r.startswith(b"-")]
    check(not errors, [got[i][:200] for i in errors[:3]])
    check(not bad, [(cmds[i][0], got[i][:120], want[i][:120])
                     for i in bad[:3]])
    return {"commands": len(cmds),
            "reply_bytes": sum(len(r) for r in got),
            "mismatched_replies": len(bad)}


# -- four chips --------------------------------------------------------------


def phase_sharded(dev, host, rng):
    """The sharded executor: tenant-sharded Bloom/HLL pools, the
    m-sharded 2^30-bit bitmap, PFMERGE and BITOP OR across shards."""
    T = SHARDED_TENANTS
    out = {"shards": dev._engine.executor.S}
    bloom_bad = hll_bad = 0
    keys = [rng.integers(0, 1 << 40, 5000).astype(np.uint64)
            for _ in range(T)]
    probes = [np.concatenate([k[:2500], rng.integers(1 << 41, 1 << 42, 2500)
                              .astype(np.uint64)]) for k in keys]
    hll_keys = [_distinct_keys(rng, 50_000) for _ in range(T)]
    answers = {}
    for c in (dev, host):
        a = answers[c is dev] = []
        for t in range(T):
            bf = c.get_bloom_filter(f"sh:bf{t}")
            check(bf.try_init(10_000, 0.01), "sharded try_init")
            a.append(np.asarray(bf.add_all_async(keys[t]).result()))
            a.append(np.asarray(bf.contains_each(probes[t])))
            h = c.get_hyper_log_log(f"sh:hll{t}")
            h.add_all(hll_keys[t])
            a.append(h.count())
        hll0 = c.get_hyper_log_log("sh:hll0")
        hll0.merge_with(*[f"sh:hll{t}" for t in range(1, 8)])  # PFMERGE
        a.append(hll0.count())
    for g, w in zip(answers[True], answers[False]):
        if np.ndim(g):
            bloom_bad += int(np.count_nonzero(g != w))
        else:
            hll_bad += int(g != w)
    out.update(tenants=T, bloom_mismatches=bloom_bad, hll_mismatches=hll_bad,
               pfmerge_count=int(answers[True][-1]),
               pfmerge_host_count=int(answers[False][-1]))
    check(bloom_bad == 0 and hll_bad == 0, out)

    idx = rng.integers(0, BITSET_BITS, BITSET_OPS).astype(np.uint32)
    small = [rng.integers(0, 1 << 20, 20_000).astype(np.uint32)
             for _ in range(4)]
    res = []
    for c in (dev, host):
        big = c.get_bit_set("sh:giant")
        big.set(BITSET_BITS - 1)
        prev = np.asarray(big.set_many(idx))
        got = np.asarray(big.get_many(idx[::-1]))
        parts = []
        for i, s in enumerate(small):
            bs = c.get_bit_set(f"sh:b{i}")
            bs.set((1 << 20) - 1)
            bs.set_many(s)
            parts.append(bs)
        parts[0].or_op("sh:b1", "sh:b2", "sh:b3")  # BITOP OR
        res.append((prev, got, big.cardinality(), parts[0].cardinality(),
                    parts[0].to_byte_array()))
    g, w = res
    check(np.array_equal(g[0], w[0]) and np.array_equal(g[1], w[1]),
          "giant bitset prev/get answers")
    check(g[2] == w[2], (g[2], w[2]))
    check(g[3] == w[3] and g[4] == w[4], (g[3], w[3]))
    from redisson_tpu.tenancy import PoolKind

    eng = dev._engine
    m_sharded = eng.executor._is_mbit(
        eng._require("sh:giant", PoolKind.BITSET).pool)
    check(m_sharded, "the 2^30-bit row is not m-sharded")
    out.update(giant_bits=BITSET_BITS, giant_cardinality=g[2],
               bitop_or_cardinality=g[3], giant_m_sharded=m_sharded)
    return out


# -- driver ------------------------------------------------------------------


def _watchdog() -> None:
    def fire():
        sys.stderr.write(f"chip_smoke: watchdog fired after {WATCHDOG_S}s\n")
        sys.stderr.flush()
        os._exit(3)

    t = threading.Timer(WATCHDOG_S, fire)
    t.daemon = True
    t.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded-executor path alone")
    args = ap.parse_args(argv)
    _watchdog()
    info = device_gate(args.chips)

    from redisson_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import redisson_tpu
    from redisson_tpu import Config
    from redisson_tpu.codecs import LongCodec

    kw = {"num_shards": 4} if args.chips == 4 else {}
    dev = redisson_tpu.create(
        Config().set_codec(LongCodec()).use_tpu_sketch(**kw))
    host = redisson_tpu.create(Config().set_codec(LongCodec()))
    phases = [("sharded", phase_sharded)] if args.chips == 4 else [
        ("bloom_cfg1", phase_bloom), ("bloom_cfg4", phase_tenants),
        ("hll_cfg2", phase_hll), ("bitset_cfg3", phase_bitset),
        ("cms_cfg5", phase_cms), ("resp", phase_resp),
    ]
    try:
        for i, (name, fn) in enumerate(phases):
            # One stream per phase: a phase's data never depends on what
            # the phases before it drew.
            rng = np.random.default_rng([args.seed, i])
            run_phase(name, fn, dev, host, rng)
    finally:
        dev.shutdown()
        host.shutdown()
    emit({"ok": True, "device": info})
    return 0


if __name__ == "__main__":
    # Any failure exits non-zero at once: os._exit, because a failed phase
    # can leave engine threads that would keep a normal exit waiting.
    try:
        rc = main()
    except SystemExit as e:  # the device gate, or argparse
        sys.stdout.flush()
        if not isinstance(e.code, int):
            print(e.code, file=sys.stderr, flush=True)
        os._exit(e.code if isinstance(e.code, int) else 1)
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    sys.exit(rc)
