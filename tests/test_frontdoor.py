"""Front-door command-stream vectorization (ISSUE 6).

The tentpole contract: fusing runs of adjacent pipelined commands into
single engine launches must be INVISIBLE on the wire — the reply stream
is byte-identical to sequential execution, whatever the parse-ahead batch
boundaries, including under chaos fault injection at the fused dispatch
points.  The randomized differential soak at the bottom enforces exactly
that against a ``resp_vectorize=False`` reference server.
"""

import random
import socket
import threading
import time

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.serve.resp import RespServer


# One shared wire-helper implementation (redisson_tpu/serve/wireutil.py)
# for bench + tests: a framing fix lands everywhere at once.
from redisson_tpu.serve.wireutil import (  # noqa: E402
    skip_reply_frame as _skip_frame,
    wire_command as _wire,
)


def _recv_replies(sock, n, timeout=60.0):
    """Read exactly ``n`` complete reply frames; returns (frames, rest)."""
    sock.settimeout(timeout)
    data = b""
    frames = []
    pos = 0
    deadline = time.monotonic() + timeout
    while len(frames) < n:
        try:
            while len(frames) < n:
                end = _skip_frame(data, pos)
                frames.append(data[pos:end])
                pos = end
        except (IndexError, ValueError):
            pass
        if len(frames) >= n:
            break
        if time.monotonic() > deadline:
            raise AssertionError(
                f"timeout with {len(frames)}/{n} replies"
            )
        chunk = sock.recv(1 << 16)
        if not chunk:
            raise AssertionError(
                f"connection closed with {len(frames)}/{n} replies"
            )
        data += chunk
    return frames, data[pos:]


def _mk_server(vectorize: bool, retry_attempts=None, **tpu_kw):
    cfg = Config().use_tpu_sketch(min_bucket=64, **tpu_kw)
    cfg.resp_vectorize = vectorize
    if retry_attempts is not None:
        cfg.retry_attempts = retry_attempts
    client = redisson_tpu.create(cfg)
    server = RespServer(client)
    return client, server


def _roundtrip(server, cmds, chunks=None, sock=None):
    """Send ``cmds`` pipelined (optionally split at ``chunks`` byte
    offsets) and return the reply frames."""
    own = sock is None
    if own:
        sock = socket.create_connection((server.host, server.port))
    try:
        payload = b"".join(_wire(c) for c in cmds)
        if chunks:
            pos = 0
            for cut in chunks:
                sock.sendall(payload[pos:cut])
                pos = cut
                time.sleep(0.001)
            sock.sendall(payload[pos:])
        else:
            sock.sendall(payload)
        frames, rest = _recv_replies(sock, len(cmds))
        assert rest == b""
        return frames
    finally:
        if own:
            sock.close()


@pytest.fixture(scope="module")
def vec():
    client, server = _mk_server(True)
    yield client, server
    server.close()
    client.shutdown()


class TestFusedRuns:
    def test_bf_mixed_run_exact_semantics(self, vec):
        client, server = vec
        cmds = [[b"BF.RESERVE", b"fd-f1", b"0.01", b"5000"]]
        cmds += [[b"BF.ADD", b"fd-f1", b"a"]]
        cmds += [[b"BF.EXISTS", b"fd-f1", b"a"]]   # added one cmd ago
        cmds += [[b"BF.ADD", b"fd-f1", b"a"]]      # duplicate: 0
        cmds += [[b"BF.EXISTS", b"fd-f1", b"zzz-never"]]
        cmds += [[b"BF.MADD", b"fd-f1", b"b", b"a", b"c"]]
        cmds += [[b"BF.MEXISTS", b"fd-f1", b"a", b"b", b"c", b"nope2"]]
        frames = _roundtrip(server, cmds)
        assert frames[0] == b"+OK\r\n"
        assert frames[1] == b":1\r\n"
        assert frames[2] == b":1\r\n"   # intra-run read-your-writes
        assert frames[3] == b":0\r\n"   # duplicate add
        assert frames[4] == b":0\r\n"
        assert frames[5] == b"*3\r\n:1\r\n:0\r\n:1\r\n"  # a already in
        assert frames[6] == b"*4\r\n:1\r\n:1\r\n:1\r\n:0\r\n"
        # The whole mixed span fused into runs.
        st = server.obs.resp_fused_cmds
        assert sum(int(c.value) for _, c in st.items()) >= 6

    def test_bitset_run_prev_values(self, vec):
        client, server = vec
        cmds = [
            [b"SETBIT", b"fd-bs", b"5", b"1"],
            [b"GETBIT", b"fd-bs", b"5"],
            [b"SETBIT", b"fd-bs", b"5", b"0"],  # prev 1
            [b"GETBIT", b"fd-bs", b"5"],
            [b"SETBIT", b"fd-bs", b"9", b"1"],
            [b"GETBIT", b"fd-bs", b"9"],
            [b"GETBIT", b"fd-bs", b"1000"],     # out of range: 0
        ]
        frames = _roundtrip(server, cmds)
        assert frames == [
            b":0\r\n", b":1\r\n", b":1\r\n", b":0\r\n",
            b":0\r\n", b":1\r\n", b":0\r\n",
        ]

    def test_get_run_and_response_cache(self, vec):
        client, server = vec
        cmds = [[b"SET", b"fd-k", b"v1"]]
        cmds += [[b"GET", b"fd-k"]] * 5
        cmds += [[b"SET", b"fd-k", b"v2"]]     # epoch bump mid-batch
        cmds += [[b"GET", b"fd-k"]] * 3
        cmds += [[b"MGET", b"fd-k", b"fd-missing"]]
        frames = _roundtrip(server, cmds)
        assert frames[0] == b"+OK\r\n"
        assert all(f == b"$2\r\nv1\r\n" for f in frames[1:6])
        assert frames[6] == b"+OK\r\n"
        # The cached v1 reply must NOT survive the write.
        assert all(f == b"$2\r\nv2\r\n" for f in frames[7:10])
        assert frames[10] == b"*2\r\n$2\r\nv2\r\n$-1\r\n"

    def test_mixed_run_read_frames_never_cached_stale(self, vec):
        # Review regression: a mixed fused run computes its read frames
        # in run order, so a GETBIT that PRECEDED a same-key SETBIT must
        # not be installed into the response cache — a later identical
        # GETBIT in the same window would serve the pre-write bit.
        client, server = vec
        cmds = [
            [b"GETBIT", b"fd-stale", b"5"],      # 0 (pre-write)
            [b"SETBIT", b"fd-stale", b"5", b"1"],
            [b"PING"],                            # barrier, no epoch bump
            [b"GETBIT", b"fd-stale", b"5"],      # must be 1, never cached 0
        ]
        frames = _roundtrip(server, cmds)
        assert frames == [b":0\r\n", b":0\r\n", b"+PONG\r\n", b":1\r\n"]

    def test_get_run_respects_reply_buffer_bound(self, vec):
        # Review regression: a fused GET run must stop buffering at the
        # 1 MB reply bound (the tail re-queues) — and every reply still
        # arrives, in order.
        client, server = vec
        big = b"x" * (300 << 10)
        setup = [[b"SET", b"fd-big", big]]
        reads = [[b"GET", b"fd-big"]] * 8
        frames = _roundtrip(server, setup + reads)
        assert frames[0] == b"+OK\r\n"
        want = b"$%d\r\n%s\r\n" % (len(big), big)
        assert all(f == want for f in frames[1:])

    def test_uninitialized_filter_errors_per_command(self, vec):
        client, server = vec
        cmds = [
            [b"BF.EXISTS", b"fd-missing-f", b"x"],
            [b"BF.ADD", b"fd-missing-f", b"y"],
            [b"BF.EXISTS", b"fd-missing-f", b"z"],
        ]
        frames = _roundtrip(server, cmds)
        # One fused call raised once; every command still gets its own
        # error frame — same bytes the sequential path produces.
        assert all(f.startswith(b"-ERR") for f in frames)
        assert len(set(frames)) == 1

    def test_multi_exec_inside_pipeline(self, vec):
        client, server = vec
        cmds = [
            [b"SET", b"fd-m", b"1"],
            [b"GET", b"fd-m"],
            [b"MULTI"],
            [b"GET", b"fd-m"],
            [b"SET", b"fd-m", b"2"],
            [b"EXEC"],
            [b"GET", b"fd-m"],
        ]
        frames = _roundtrip(server, cmds)
        assert frames[2] == b"+OK\r\n"
        assert frames[3] == frames[4] == b"+QUEUED\r\n"
        assert frames[5] == b"*2\r\n$1\r\n1\r\n+OK\r\n"
        assert frames[6] == b"$1\r\n2\r\n"

    def test_vectorize_off_still_correct(self):
        client, server = _mk_server(False)
        try:
            cmds = [[b"BF.RESERVE", b"nf", b"0.01", b"100"]]
            cmds += [[b"BF.ADD", b"nf", b"x"], [b"BF.EXISTS", b"nf", b"x"]]
            frames = _roundtrip(server, cmds)
            assert frames == [b"+OK\r\n", b":1\r\n", b":1\r\n"]
            fused = sum(
                int(c.value) for _, c in server.obs.resp_fused_cmds.items()
            )
            assert fused == 0
        finally:
            server.close()
            client.shutdown()


# -- randomized differential soak --------------------------------------------


def _gen_stream(rng: random.Random, n_cmds: int):
    """Interleaved pipelined command stream: fusable reads/writes,
    structural barriers, repeated reads (cache hits) — everything
    deterministic (no TTLs, no randomized replies)."""
    filters = [b"soak-f0", b"soak-f1"]
    bitsets = [b"soak-b0", b"soak-b1"]
    strkeys = [b"soak-s%d" % i for i in range(4)]
    cmds = [[b"BF.RESERVE", f, b"0.01", b"4000"] for f in filters]
    item = lambda: b"it%d" % rng.randrange(60)  # noqa: E731

    def one():
        r = rng.random()
        if r < 0.30:
            f = rng.choice(filters)
            k = rng.random()
            if k < 0.35:
                return [b"BF.ADD", f, item()]
            if k < 0.75:
                return [b"BF.EXISTS", f, item()]
            if k < 0.88:
                return [b"BF.MADD", f] + [item() for _ in range(
                    rng.randrange(1, 5))]
            return [b"BF.MEXISTS", f] + [item() for _ in range(
                rng.randrange(1, 5))]
        if r < 0.55:
            b = rng.choice(bitsets)
            off = b"%d" % rng.randrange(256)
            if rng.random() < 0.5:
                return [b"SETBIT", b, off, b"1" if rng.random() < 0.8
                        else b"0"]
            return [b"GETBIT", b, off]
        if r < 0.80:
            s = rng.choice(strkeys)
            k = rng.random()
            if k < 0.3:
                return [b"SET", s, b"v%d" % rng.randrange(1000)]
            if k < 0.8:
                return [b"GET", s]
            if k < 0.9:
                return [b"MGET"] + rng.sample(strkeys, 2)
            return [b"STRLEN", s]
        if r < 0.86:  # structural barriers
            k = rng.random()
            if k < 0.4:
                return [b"DEL", rng.choice(strkeys)]
            if k < 0.7:
                return [b"DEL", rng.choice(filters)]
            return [b"BF.RESERVE", rng.choice(filters), b"0.01", b"4000"]
        if r < 0.93:
            return [b"PFADD", b"soak-h", item()]
        if r < 0.97:
            return [b"PFCOUNT", b"soak-h"]
        return [b"APPEND", rng.choice(strkeys), b"x"]

    cmds += [one() for _ in range(n_cmds)]
    return cmds


def _run_stream(server, cmds, rng: random.Random):
    """Send the stream in random chunk splits (varying the parse-ahead
    batch boundaries) and return the concatenated reply bytes."""
    payload = b"".join(_wire(c) for c in cmds)
    cuts = sorted(
        rng.sample(range(1, len(payload)), min(12, len(payload) - 1))
    )
    frames = _roundtrip(server, cmds, chunks=cuts)
    return b"".join(frames)


class TestDifferentialSoak:
    def _pair(self, **kw):
        vec_c, vec_s = _mk_server(True, **kw)
        ref_c, ref_s = _mk_server(False, **kw)
        return (vec_c, vec_s), (ref_c, ref_s)

    def test_soak_byte_identical(self):
        (vc, vs), (rc, rs) = self._pair()
        try:
            for seed in (11, 23):
                rng = random.Random(seed)
                cmds = _gen_stream(rng, 400)
                got = _run_stream(vs, cmds, random.Random(seed + 1))
                want = _run_stream(rs, cmds, random.Random(seed + 2))
                assert got == want, f"reply streams diverged (seed {seed})"
                # Cleanup both keyspaces between rounds (same commands
                # on both → still comparable).
                for s_, c_ in ((vs, vc), (rs, rc)):
                    c_.get_keys().flushall()
        finally:
            vs.close()
            vc.shutdown()
            rs.close()
            rc.shutdown()

    def test_soak_byte_identical_under_chaos(self):
        """Fault injection at the fused dispatch points: the coalescer's
        retry discipline absorbs injected dispatch errors, so the fused
        and sequential servers still answer byte-identically."""
        from redisson_tpu import chaos

        # Deep retry budget: the soak asserts EQUALITY, so an exhausted
        # retry (different call counts → different fire sequences per
        # server) must be statistically impossible, not just rare.
        (vc, vs), (rc, rs) = self._pair(retry_attempts=8)
        try:
            for point in (
                "dispatch.bloom_mixed_keys",
                "dispatch.bloom_mixed_keys_runs",
                "dispatch.bloom_contains_keys_runs",
                "dispatch.bitset_mixed",
                "dispatch.bitset_mixed_runs",
            ):
                chaos.inject(point, kind="error", rate=0.04, seed=97)
            rng = random.Random(5)
            cmds = _gen_stream(rng, 300)
            got = _run_stream(vs, cmds, random.Random(6))
            want = _run_stream(rs, cmds, random.Random(7))
            assert got == want, "chaos soak diverged"
        finally:
            chaos.clear()
            vs.close()
            vc.shutdown()
            rs.close()
            rc.shutdown()
