"""Per-core front door (ISSUE 17): the in-node slot→process map, the
SO_REUSEPORT probe + fallback, device-slice pinning, cross-worker
handoff semantics (forward / split / fan-out / CROSSSLOT), MULTI and
pub/sub across workers, chaos at the handoff leg, and the forked-worker
MulticoreNode suite with the K=4 differential soak.

The in-process tests run TWO RespServers in one process sharing a TCP
port via SO_REUSEPORT (each with its own engine), which exercises the
identical code path the forked workers run — the slow-marked tests at
the bottom fork real `python -m redisson_tpu` workers and are what the
CI multicore-smoke job runs.
"""

import logging
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config, chaos
from redisson_tpu.serve import multicore, wireutil
from redisson_tpu.serve.multicore import (
    MulticoreNode,
    device_slice_for_worker,
    effective_processes,
    peer_sock_path,
    reuseport_available,
    worker_of_slot,
    worker_slot_range,
    worker_tag,
)
from redisson_tpu.cluster.slots import NSLOTS, key_slot
from redisson_tpu.serve.resp import RespServer

pytestmark = pytest.mark.skipif(
    not reuseport_available(), reason="SO_REUSEPORT unavailable"
)


def _key(w, nworkers, suffix):
    """A key pinned to worker ``w`` via its hash tag."""
    return ("{%s}%s" % (worker_tag(w, nworkers), suffix)).encode()


def _recv_frames(sock, n, timeout=30.0):
    """Read exactly ``n`` raw reply frames (byte-identical checks)."""
    sock.settimeout(timeout)
    data = b""
    frames = []
    pos = 0
    while len(frames) < n:
        try:
            while len(frames) < n:
                end = wireutil.skip_reply_frame(data, pos)
                frames.append(data[pos:end])
                pos = end
        except IndexError:
            pass
        if len(frames) >= n:
            break
        chunk = sock.recv(1 << 16)
        assert chunk, f"connection closed with {len(frames)}/{n} replies"
        data += chunk
    assert data[pos:] == b"", "trailing bytes after expected replies"
    return frames


def _ask(sock, cmds):
    sock.sendall(b"".join(wireutil.wire_command(c) for c in cmds))
    return _recv_frames(sock, len(cmds))


# -- the in-node slot→process map (pure units) --------------------------------


@pytest.mark.parametrize("nworkers", [2, 3, 4, 5])
def test_worker_of_slot_contiguous_partition(nworkers):
    owners = [worker_of_slot(s, nworkers) for s in range(NSLOTS)]
    assert owners[0] == 0 and owners[-1] == nworkers - 1
    assert owners == sorted(owners), "partition must be contiguous"
    assert set(owners) == set(range(nworkers)), "every worker owns slots"
    for w in range(nworkers):
        lo, hi = worker_slot_range(w, nworkers)
        assert worker_of_slot(lo, nworkers) == w
        assert worker_of_slot(hi, nworkers) == w
        if lo > 0:
            assert worker_of_slot(lo - 1, nworkers) == w - 1
        if hi < NSLOTS - 1:
            assert worker_of_slot(hi + 1, nworkers) == w + 1


@pytest.mark.parametrize("nworkers", [2, 4])
def test_worker_tag_pins_keys(nworkers):
    for w in range(nworkers):
        k = _key(w, nworkers, "anything")
        assert worker_of_slot(key_slot(k), nworkers) == w


def test_device_slice_for_worker_partitions_devices():
    slices = [device_slice_for_worker(i, 4, 8) for i in range(4)]
    assert slices == [[0, 1], [2, 3], [4, 5], [6, 7]]
    # Fewer devices than workers: no pinning (shared enumeration).
    assert device_slice_for_worker(0, 4, 1) is None
    # Uneven split still covers every device exactly once.
    got = [d for i in range(3) for d in device_slice_for_worker(i, 3, 8)]
    assert got == list(range(8))


def test_device_slice_for_worker_refuses_shared_chip():
    # A chip backend with fewer devices than workers refuses to start —
    # K processes cannot each open the one chip.
    with pytest.raises(RuntimeError, match=r"4 worker processes.*1 device"):
        device_slice_for_worker(0, 4, 1, "tpu")
    assert device_slice_for_worker(1, 4, 4, "tpu") == [1]


def test_supervisor_path_never_imports_jax():
    # The multicore parent only spawns the workers that own the devices;
    # loading jax there could take the chip they need.
    code = ("import sys, redisson_tpu.__main__, redisson_tpu.serve.multicore,"
            " redisson_tpu.obs.federate; print('jax' in sys.modules)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True, cwd=root)
    assert out.stdout.strip() == "False"


# -- device-slice pinning (ISSUE 17 satellite, ROADMAP carry-over) ------------


def test_resolve_device_slice_fake_devices():
    from redisson_tpu.executor.tpu_executor import resolve_device_slice

    fake = ["dev0", "dev1", "dev2", "dev3"]
    assert resolve_device_slice(None, devices=fake) == fake
    # Order is the caller's, not the enumeration's.
    assert resolve_device_slice([2, 0], devices=fake) == ["dev2", "dev0"]
    with pytest.raises(ValueError, match="out of range"):
        resolve_device_slice([4], devices=fake)
    with pytest.raises(ValueError, match="repeated"):
        resolve_device_slice([1, 1], devices=fake)
    with pytest.raises(ValueError, match="empty"):
        resolve_device_slice([], devices=fake)


def test_executor_pins_device_slice():
    """An executor built with device_indices uses exactly that slice of
    the (fake-8-device) enumeration as its pool devices."""
    import jax

    cfg = Config().use_tpu_sketch(min_bucket=64)
    cfg.tpu_sketch.device_indices = [1, 3]
    client = redisson_tpu.create(cfg)
    try:
        ex = client._engine.executor
        assert ex.devices is not None and len(ex.devices) == 2
        assert list(ex.devices) == [jax.devices()[1], jax.devices()[3]]
        # The pinned executor still serves traffic.
        bf = client.get_bloom_filter("pin-bf")
        bf.try_init(10_000, 0.01)
        keys = np.arange(64, dtype=np.uint64)
        bf.add_all(keys)
        assert bool(np.all(bf.contains_each(keys)))
    finally:
        client.shutdown()


# -- SO_REUSEPORT probe + fallback (ISSUE 17 satellite) -----------------------


def test_reuseport_probe_is_a_real_setsockopt():
    # On this platform (the skipif gate passed) the probe must agree.
    assert reuseport_available() is True


def test_effective_processes_fallback_logs_and_degrades(monkeypatch, caplog):
    monkeypatch.setattr(multicore, "reuseport_available", lambda: False)
    with caplog.at_level(logging.INFO, logger="redisson_tpu.frontdoor"):
        assert effective_processes(4) == 1
    msgs = [r for r in caplog.records if "SO_REUSEPORT" in r.getMessage()]
    assert msgs, "fallback must log an INFO frontdoor line"
    assert msgs[0].levelno == logging.INFO
    # K=1 is not a fallback: no probe, no log line.
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="redisson_tpu.frontdoor"):
        assert effective_processes(1) == 1
        assert effective_processes(None) == 1
    assert not caplog.records


# -- in-process worker pair ---------------------------------------------------


NW = 2


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """Two front-door workers in ONE process: same TCP port via
    SO_REUSEPORT, each with its own engine, handoff over the rundir's
    unix sockets."""
    rundir = str(tmp_path_factory.mktemp("frontdoor"))
    servers, clients = [], []
    port = 0
    try:
        for i in range(NW):
            cfg = Config().use_tpu_sketch(min_bucket=64)
            cfg.frontdoor_workers = NW
            cfg.frontdoor_index = i
            cfg.frontdoor_dir = rundir
            client = redisson_tpu.create(cfg)
            clients.append(client)
            server = RespServer(client, host="127.0.0.1", port=port)
            servers.append(server)
            port = server.port
        yield servers
    finally:
        for s in servers:
            s.close()
        for c in clients:
            c.shutdown()


def _tcp(pair):
    s = socket.create_connection(("127.0.0.1", pair[0].port))
    s.settimeout(30)
    return s


def _landed_index(sock):
    info = wireutil.exchange(sock, [[b"INFO", b"frontdoor"]])[0].decode()
    for line in info.splitlines():
        if line.startswith("frontdoor_worker_index:"):
            return int(line.split(":")[1])
    raise AssertionError(f"no frontdoor_worker_index in {info!r}")


def _peer_conn(pair, w):
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.connect(peer_sock_path(pair[w].multicore.rundir, w))
    s.settimeout(30)
    return s


def test_pair_serves_keyless_where_landed(pair):
    s = _tcp(pair)
    try:
        assert wireutil.exchange(s, [[b"PING"], [b"ECHO", b"hi"]]) == [
            b"PONG", b"hi",
        ]
        assert _landed_index(s) in range(NW)
    finally:
        s.close()


def test_pair_cross_worker_forward_and_local(pair):
    s = _tcp(pair)
    try:
        me = _landed_index(s)
        other = (me + 1) % NW
        mine = _key(me, NW, "fwd")
        theirs = _key(other, NW, "fwd")
        assert wireutil.exchange(
            s, [[b"SET", mine, b"local"], [b"SET", theirs, b"remote"]]
        ) == [b"OK", b"OK"]
        assert wireutil.exchange(
            s, [[b"GET", mine], [b"GET", theirs]]
        ) == [b"local", b"remote"]
        # The landed worker counted the forwards; the in-node map never
        # surfaced a -MOVED to the client.
        lines = dict(
            ln.split(":", 1)
            for ln in wireutil.exchange(s, [[b"INFO", b"frontdoor"]])[0]
            .decode().splitlines()
            if ":" in ln
        )
        assert int(lines["frontdoor_handoffs_forward"]) >= 1
        assert int(lines["frontdoor_processes"]) == NW
    finally:
        s.close()


def test_pair_split_commands_merge_byte_identically(pair):
    s = _tcp(pair)
    try:
        k0 = _key(0, NW, "sp0")
        k1 = _key(1, NW, "sp1")
        k2 = _key(0, NW, "sp2")
        assert wireutil.exchange(
            s, [[b"MSET", k0, b"a", k1, b"b", k2, b"c"]]
        ) == [b"OK"]
        assert wireutil.exchange(
            s, [[b"MGET", k0, k1, k2, b"{missing}nope"]]
        ) == [[b"a", b"b", b"c", None]]
        assert wireutil.exchange(
            s, [[b"EXISTS", k0, k1, k2], [b"DEL", k0, k1]]
        ) == [3, 2]
        assert wireutil.exchange(s, [[b"MGET", k0, k1, k2]]) == [
            [None, None, b"c"],
        ]
        assert wireutil.exchange(s, [[b"DEL", k2]]) == [1]
    finally:
        s.close()


def test_pair_fanout_dbsize_keys_flushall(pair):
    s = _tcp(pair)
    try:
        wireutil.exchange(s, [[b"FLUSHALL"]])
        k0 = _key(0, NW, "fan0")
        k1 = _key(1, NW, "fan1")
        wireutil.exchange(s, [[b"SET", k0, b"x"], [b"SET", k1, b"y"]])
        assert wireutil.exchange(s, [[b"DBSIZE"]]) == [2]
        got = wireutil.exchange(s, [[b"KEYS", b"*"]])[0]
        assert sorted(got) == sorted([k0, k1])
        assert wireutil.exchange(s, [[b"FLUSHALL"]]) == [b"OK"]
        assert wireutil.exchange(s, [[b"DBSIZE"]]) == [0]
    finally:
        s.close()


def test_pair_cross_worker_multikey_gets_crossslot(pair):
    s = _tcp(pair)
    try:
        k0 = _key(0, NW, "ren")
        k1 = _key(1, NW, "ren")
        wireutil.exchange(s, [[b"SET", k0, b"v"]])
        err = wireutil.exchange(s, [[b"RENAME", k0, k1]])[0]
        assert isinstance(err, wireutil.ReplyError)
        assert err.code == "CROSSSLOT"
        # Same-worker multikey RENAME is untouched by the map.
        k0b = _key(0, NW, "ren2")
        assert wireutil.exchange(s, [[b"RENAME", k0, k0b]]) == [b"OK"]
        wireutil.exchange(s, [[b"DEL", k0b]])
    finally:
        s.close()


def test_pair_multi_exec_across_handoff(pair):
    s = _tcp(pair)
    try:
        me = _landed_index(s)
        theirs = _key((me + 1) % NW, NW, "tx")
        frames = _ask(s, [
            [b"MULTI"],
            [b"SET", theirs, b"txv"],
            [b"GET", theirs],
            [b"EXEC"],
        ])
        assert frames[0] == b"+OK\r\n"
        assert frames[1] == frames[2] == b"+QUEUED\r\n"
        assert frames[3] == b"*2\r\n+OK\r\n$3\r\ntxv\r\n"
        wireutil.exchange(s, [[b"DEL", theirs]])
    finally:
        s.close()


def test_pair_publish_fans_out_to_both_workers(pair):
    # One subscriber parked on EACH worker (the unix door serves normal
    # dispatch and lets a test pick its worker); a TCP publisher's
    # PUBLISH fans out: the reply sums receivers across workers and
    # both buses deliver, in order.
    sub0 = _peer_conn(pair, 0)
    sub1 = _peer_conn(pair, 1)
    pub = _tcp(pair)
    try:
        for sub in (sub0, sub1):
            assert wireutil.exchange(sub, [[b"SUBSCRIBE", b"mc-chan"]]) == [
                [b"subscribe", b"mc-chan", 1],
            ]
        assert wireutil.exchange(pub, [[b"PUBLISH", b"mc-chan", b"m1"]]) == [2]
        assert wireutil.exchange(pub, [[b"PUBLISH", b"mc-chan", b"m2"]]) == [2]
        for sub in (sub0, sub1):
            got = _recv_frames(sub, 2)
            assert got[0] == (
                b"*3\r\n$7\r\nmessage\r\n$7\r\nmc-chan\r\n$2\r\nm1\r\n"
            )
            assert got[1] == (
                b"*3\r\n$7\r\nmessage\r\n$7\r\nmc-chan\r\n$2\r\nm2\r\n"
            )
        # Nobody listening on a foreign channel: the fan-out sum is 0.
        assert wireutil.exchange(pub, [[b"PUBLISH", b"mc-none", b"x"]]) == [0]
    finally:
        sub0.close()
        sub1.close()
        pub.close()


def test_pair_chaos_at_handoff_leg_surfaces_handoffbroken(pair):
    s = _tcp(pair)
    chaos.inject("handoff.leg", kind="error", rate=1.0, seed=3)
    try:
        me = _landed_index(s)
        theirs = _key((me + 1) % NW, NW, "chaos")
        err = wireutil.exchange(s, [[b"GET", theirs]])[0]
        assert isinstance(err, wireutil.ReplyError)
        assert err.code == "HANDOFFBROKEN"
        assert b"retry" in str(err).encode()
    finally:
        chaos.clear()
    try:
        # The failed leg was never repooled (RT013): the next handoff
        # rides a fresh socket and succeeds.
        me = _landed_index(s)
        theirs = _key((me + 1) % NW, NW, "chaos")
        assert wireutil.exchange(
            s, [[b"SET", theirs, b"ok"], [b"GET", theirs]]
        ) == [b"OK", b"ok"]
        wireutil.exchange(s, [[b"DEL", theirs]])
        lines = dict(
            ln.split(":", 1)
            for ln in wireutil.exchange(s, [[b"INFO", b"frontdoor"]])[0]
            .decode().splitlines()
            if ":" in ln
        )
        assert int(lines["frontdoor_handoff_errors"]) >= 1
    finally:
        s.close()


def test_pair_gauges_and_info(pair):
    for i, srv in enumerate(pair):
        reg = srv.obs.registry if hasattr(srv.obs, "registry") else None
        assert srv.multicore is not None
        assert srv.multicore.nworkers == NW
        assert srv.multicore.index == i
    # The gauge the fallback satellite pins to 1 reads K here.
    sample = pair[0].obs.frontdoor_processes
    assert sample is not None


# -- forked-worker suite (CI multicore-smoke job) -----------------------------


def _node_conn(node):
    s = socket.create_connection((node.host, node.port))
    s.settimeout(60)
    return s


@pytest.mark.slow
def test_multicore_node_k2_smoke():
    """The MulticoreNode parent forks K=2 real workers on one port,
    serves cross-worker traffic, and SIGTERM-reaps them cleanly (the
    pgrep no-orphans gate in CI counts the survivors)."""
    node = MulticoreNode(2, platform="cpu")
    try:
        s = _node_conn(node)
        k0 = _key(0, 2, "a")
        k1 = _key(1, 2, "b")
        assert wireutil.exchange(s, [[b"PING"]]) == [b"PONG"]
        assert wireutil.exchange(
            s, [[b"SET", k0, b"v0"], [b"SET", k1, b"v1"]]
        ) == [b"OK", b"OK"]
        assert wireutil.exchange(s, [[b"MGET", k0, k1]]) == [[b"v0", b"v1"]]
        assert wireutil.exchange(s, [[b"DBSIZE"]]) == [2]
        info = wireutil.exchange(s, [[b"INFO", b"frontdoor"]])[0].decode()
        assert "frontdoor_processes:2" in info
        assert "frontdoor_native_tick:1" in info
        s.close()
    finally:
        assert node.shutdown() is True, "workers must exit from SIGTERM"
    for p in node.procs:
        assert p.poll() is not None


def _rand_cmds(rng, conn_id, n_ops, nworkers):
    """A randomized per-connection command stream over a PRIVATE
    keyspace (disjoint across connections, so replies are independent
    of cross-connection interleaving), pinned across both doors."""
    cmds = []
    mine = [
        _key(w, nworkers, "c%d-k%d" % (conn_id, i))
        for w in range(nworkers) for i in range(4)
    ]
    in_multi = False
    for _ in range(n_ops):
        roll = int(rng.integers(10))
        k = mine[int(rng.integers(len(mine)))]
        if roll <= 3:
            cmds.append([b"SET", k, b"v%d" % int(rng.integers(1000))])
        elif roll <= 5:
            cmds.append([b"GET", k])
        elif roll == 6:
            ks = [mine[int(rng.integers(len(mine)))] for _ in range(3)]
            cmds.append([b"MGET"] + ks)
        elif roll == 7:
            cmds.append([b"INCR", _key(
                int(rng.integers(nworkers)), nworkers, "c%d-ctr" % conn_id
            )])
        elif roll == 8:
            cmds.append([b"DEL", k])
        elif not in_multi:
            cmds.append([b"MULTI"])
            in_multi = True
        else:
            cmds.append([b"EXEC"])
            in_multi = False
    if in_multi:
        cmds.append([b"EXEC"])
    return cmds


@pytest.mark.slow
def test_differential_soak_k4_byte_identical():
    """Satellite 4: K=4 multicore vs the single-process door — every
    connection's reply stream is byte-identical, including MULTI/EXEC
    spanning workers and ordered pub/sub delivery."""
    nworkers = 4
    cfg = Config().use_tpu_sketch(min_bucket=64)
    ref_client = redisson_tpu.create(cfg)
    ref = RespServer(ref_client, host="127.0.0.1", port=0)
    node = MulticoreNode(nworkers, platform="cpu")
    try:
        rng = np.random.default_rng(170)
        streams = [
            _rand_cmds(rng, c, 80, nworkers) for c in range(6)
        ]
        for conn_id, cmds in enumerate(streams):
            sm = _node_conn(node)
            sr = socket.create_connection((ref.host, ref.port))
            sr.settimeout(60)
            got_m = _ask(sm, cmds)
            got_r = _ask(sr, cmds)
            assert got_m == got_r, (
                f"conn {conn_id}: reply stream diverged\n"
                f"multicore: {got_m}\nreference: {got_r}"
            )
            sm.close()
            sr.close()
        # Ordered pub/sub across doors: N sequential publishes arrive
        # as N ordered pushes, byte-identical on both doors.
        for srv_kind, (host, port) in (
            ("multicore", (node.host, node.port)),
            ("reference", (ref.host, ref.port)),
        ):
            sub = socket.create_connection((host, port))
            pub = socket.create_connection((host, port))
            sub.settimeout(60)
            pub.settimeout(60)
            subf = _ask(sub, [[b"SUBSCRIBE", b"soak-chan"]])
            pushes = []
            for i in range(8):
                assert wireutil.exchange(
                    pub, [[b"PUBLISH", b"soak-chan", b"m%d" % i]]
                ) == [1], srv_kind
            pushes = _recv_frames(sub, 8)
            if srv_kind == "multicore":
                want_sub, want_pushes = subf, pushes
            else:
                assert subf == want_sub
                assert pushes == want_pushes, "pub/sub streams diverged"
            sub.close()
            pub.close()
    finally:
        node.shutdown()
        ref.close()
        ref_client.shutdown()


@pytest.mark.slow
def test_chaos_soak_handoff_legs_fail_clean():
    """Chaos armed at the handoff leg via env (the forked workers read
    RTPU_CHAOS_HANDOFF at router init): every reply is either the
    correct value or -HANDOFFBROKEN, the stream never desyncs, and the
    connection survives."""
    node = MulticoreNode(
        2, platform="cpu",
        env_extra={
            "RTPU_CHAOS_HANDOFF": "0.4",
            "RTPU_CHAOS_HANDOFF_SEED": "17",
        },
    )
    try:
        s = _node_conn(node)
        me = _landed_index(s)
        theirs = _key((me + 1) % 2, 2, "soak")
        ok = broken = 0
        for i in range(40):
            rep = wireutil.exchange(s, [[b"SET", theirs, b"v%d" % i]])[0]
            if isinstance(rep, wireutil.ReplyError):
                assert rep.code == "HANDOFFBROKEN", rep
                broken += 1
            else:
                assert rep == b"OK"
                ok += 1
        assert ok > 0, "some legs must survive at rate 0.4"
        assert broken > 0, "some legs must fail at rate 0.4"
        # The stream is still framed and the conn still serves.
        assert wireutil.exchange(s, [[b"PING"]]) == [b"PONG"]
        s.close()
    finally:
        node.shutdown()
