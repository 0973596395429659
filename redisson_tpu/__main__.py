"""Standalone server entry point — the redis-server-analog deployment
shape: ``python -m redisson_tpu [--port P] [--config cfg.yaml] ...``
boots the engine and serves RESP2/RESP3 over TCP until SIGINT/SIGTERM,
so foreign clients (redis-cli, redis-py, a stock Redisson) can use the
framework without any Python embedding.

The reference is a client library; its server is redis-server.  This
framework carries its own keyspace, so the server role collapses into
one process: engine + front door (SURVEY.md §2.4 comm row).
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def _serve_multicore(args, nworkers: int) -> int:
    """Per-core front-door parent (ISSUE 17): a pure supervisor — no
    engine, no RESP door of its own.  Spawns K worker processes sharing
    (host, port) via SO_REUSEPORT, optionally fronts their per-worker
    metrics endpoints with ONE federated exposition (worker labels ride
    the federation plane's node label), forwards SIGTERM/SIGINT, and
    reaps every child before exiting (the CI no-orphans gate)."""
    from redisson_tpu.serve.multicore import MulticoreNode

    extra = [
        "--max-connections", str(args.max_connections),
        "--idle-timeout-s", str(args.idle_timeout_s),
    ]
    if args.config:
        extra += ["--config", args.config]
    if args.snapshot_dir:
        extra += ["--snapshot-dir", args.snapshot_dir]
    if args.snapshot_interval_s:
        extra += ["--snapshot-interval-s", str(args.snapshot_interval_s)]
    if args.requirepass:
        extra += ["--requirepass", args.requirepass]
    if args.enable_python_scripts:
        extra += ["--enable-python-scripts"]
    if args.no_resp_vectorize:
        extra += ["--no-resp-vectorize"]
    if args.no_resp_reactor:
        extra += ["--no-resp-reactor"]
    if args.journal_dir:
        extra += ["--journal-dir", args.journal_dir]
    if args.replica_of:
        extra += ["--replica-of", args.replica_of]
    if args.resp_reactor_threads is not None:
        extra += ["--resp-reactor-threads", str(args.resp_reactor_threads)]
    if args.trace_sample_rate is not None:
        extra += ["--trace-sample-rate", str(args.trace_sample_rate)]
    if args.latency_monitor_threshold is not None:
        extra += [
            "--latency-monitor-threshold",
            str(args.latency_monitor_threshold),
        ]
    if args.cluster:
        extra += ["--cluster"]
    if args.rebalance:
        extra += ["--rebalance"]
    if args.doctor:
        extra += ["--doctor"]
    for val, flag in (
        (args.cluster_slots, "--cluster-slots"),
        (args.cluster_topology, "--cluster-topology"),
        (args.cluster_myid, "--cluster-myid"),
        (args.cluster_announce, "--cluster-announce"),
    ):
        if val is not None:
            extra += [flag, val]

    node = MulticoreNode(
        nworkers, host=args.host, port=args.port,
        platform=args.platform, metrics_port=args.metrics_port,
        extra_args=extra,
    )
    fed = None
    if args.metrics_port is not None:
        from redisson_tpu.obs.federate import start_federation_endpoint

        fed = start_federation_endpoint(
            [f"{args.host}:{mp}" for mp in node.metrics_ports],
            host=args.host, port=args.metrics_port,
        )
        print(
            f"federated worker metrics on "
            f"http://{fed.host}:{fed.port}/metrics",
            flush=True,
        )
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    print(
        f"redisson-tpu serving RESP on {node.host}:{node.port} "
        f"[{nworkers} SO_REUSEPORT front-door workers]",
        flush=True,
    )
    stop.wait()
    print("shutting down front-door workers", flush=True)
    if fed is not None:
        fed.close()
    clean = node.shutdown()
    return 0 if clean else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m redisson_tpu",
        description="redisson_tpu standalone RESP server",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=6379)
    p.add_argument(
        "--config", help="YAML/JSON config file (Config.from_yaml)"
    )
    p.add_argument(
        "--snapshot-dir",
        help="restore-on-boot + snapshot-on-shutdown directory",
    )
    p.add_argument(
        "--snapshot-interval-s", type=float, default=0.0,
        help="arm periodic snapshots (requires --snapshot-dir)",
    )
    p.add_argument(
        "--journal-dir",
        help="op-journal directory: tail-of-log durability between "
        "snapshots, and the replication stream's source on a primary "
        "(docs/robustness.md)",
    )
    p.add_argument(
        "--replica-of", default=None, metavar="HOST:PORT",
        help="boot as a read-only replica of this primary (ISSUE 18): "
        "full-resync bootstrap (snapshot + stream tail), then follow "
        "the replication stream; eligible for automatic failover in "
        "cluster mode (docs/clustering.md)",
    )
    p.add_argument(
        "--max-connections", type=int, default=256,
    )
    p.add_argument(
        "--idle-timeout-s", type=float, default=300.0,
    )
    p.add_argument(
        "--platform", default=None,
        help="jax platform for this server's engine (e.g. cpu for a "
        "host-only server); sets JAX_PLATFORMS before jax loads",
    )
    p.add_argument(
        "--requirepass", default=None,
        help="require AUTH before any command (also settable via the "
        "config file's requirepass key)",
    )
    p.add_argument(
        "--metrics-port", type=int, default=None,
        help="serve the Prometheus text exposition on this port at "
        "/metrics (docs/observability.md); omitted = no endpoint",
    )
    p.add_argument(
        "--federate", default=None, metavar="HOST:PORT,...",
        help="federation-only mode (ISSUE 13): no engine, no RESP "
        "door — scrape the listed member /metrics endpoints per "
        "request and serve ONE merged exposition (node label per "
        "member) on --metrics-port",
    )
    p.add_argument(
        "--trace-sample-rate", type=float, default=None,
        help="distributed-trace head-sampling probability in [0, 1] "
        "(ISSUE 13; default 0 = tracing off; live via CONFIG SET "
        "trace-sample-rate / TRACE SAMPLE)",
    )
    p.add_argument(
        "--latency-monitor-threshold", type=int, default=None,
        help="arm the LATENCY monitor at this many milliseconds "
        "(0 = off, the redis default; live via CONFIG SET)",
    )
    p.add_argument(
        "--enable-python-scripts", action="store_true",
        help="allow RESP EVAL/EVALSHA/SCRIPT/FUNCTION/FCALL (script "
        "bodies are Python — RCE for anyone who can reach the socket; "
        "refused unless --requirepass is set or the bind is loopback)",
    )
    p.add_argument(
        "--no-resp-vectorize", action="store_true",
        help="disable front-door pipeline vectorization (fused runs + "
        "per-connection response cache; docs/performance.md) — "
        "debugging escape hatch, semantics are identical either way",
    )
    p.add_argument(
        "--no-resp-reactor", action="store_true",
        help="serve thread-per-connection instead of the epoll reactor "
        "pool (ISSUE 11; docs/performance.md) — differential-testing "
        "escape hatch, per-connection semantics are identical either "
        "way but idle connections cost a thread each",
    )
    p.add_argument(
        "--resp-reactor-threads", type=int, default=None,
        help="reactor event-loop thread count (default from config, 1)",
    )
    p.add_argument(
        "--cluster", action="store_true",
        help="enable cluster mode (ISSUE 12; docs/clustering.md): the "
        "door speaks the 16384-slot redirect protocol (CLUSTER, "
        "-MOVED/-ASK, hash tags, live slot migration)",
    )
    p.add_argument(
        "--cluster-slots", default=None,
        help="slot range(s) this node owns when no topology file is "
        "given, e.g. '0-5461' or '0-99,200-299' (default: all 16384)",
    )
    p.add_argument(
        "--cluster-topology", default=None,
        help="JSON topology file ({'nodes': [{'id','host','port',"
        "'slots'}]}) shared by every node — the supervisor writes one",
    )
    p.add_argument(
        "--cluster-myid", default=None,
        help="this node's id in the topology (default: announce addr)",
    )
    p.add_argument(
        "--cluster-announce", default=None,
        help="host:port other nodes/clients are redirected to "
        "(default: the bind address; set when behind NAT/containers)",
    )
    p.add_argument(
        "--cluster-node-timeout-ms", type=int, default=None,
        help="failure-detection window for the cluster bus (ISSUE 18): "
        "a peer silent this long is marked failed; replicas of a "
        "failed primary start a failover election (default 1500)",
    )
    p.add_argument(
        "--rebalance", action="store_true",
        help="arm the autonomous rebalancer (ISSUE 19; docs/"
        "clustering.md 'Autonomous rebalancing'): the node scrapes the "
        "fleet's CLUSTER LOADMAPs into a smoothed per-slot heat model "
        "and, when coordinator, migrates slots to level the load; "
        "requires --cluster",
    )
    p.add_argument(
        "--doctor", action="store_true",
        help="arm the fleet doctor (ISSUE 20; docs/observability.md "
        "'Fleet doctor'): a continuous invariant sweep — slot "
        "ownership, replication monotonicity, stuck migrations — plus "
        "a black-box WAIT-fenced canary; the coordinator (lowest-id "
        "alive primary) audits, findings surface via CLUSTER DOCTOR; "
        "requires --cluster",
    )
    p.add_argument(
        "--frontdoor-processes", type=int, default=None,
        help="per-core front door (ISSUE 17): serve with this many "
        "reactor processes sharing the port via SO_REUSEPORT, each "
        "owning 1/K of the slot range behind an in-node handoff map "
        "(docs/performance.md); platforms without SO_REUSEPORT fall "
        "back to 1 with a logged INFO line",
    )
    # Internal worker-mode flags: the supervisor parent stamps these
    # into each spawned worker (serve/multicore.py MulticoreNode).
    p.add_argument("--frontdoor-workers", type=int, default=1,
                   help=argparse.SUPPRESS)
    p.add_argument("--frontdoor-index", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--frontdoor-dir", default=None,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.platform:
        # Before anything imports jax: the env var is read at import.
        import os

        os.environ["JAX_PLATFORMS"] = args.platform

    if args.federate:
        # Standalone federation mode: just the merged metrics endpoint
        # — no engine import, no jax initialization, no RESP door.
        if args.metrics_port is None:
            p.error("--federate requires --metrics-port")
        from redisson_tpu.obs.federate import start_federation_endpoint

        targets = [t.strip() for t in args.federate.split(",") if t.strip()]
        srv = start_federation_endpoint(
            targets, host=args.host, port=args.metrics_port
        )
        stop = threading.Event()

        def on_fed_signal(signum, frame):
            stop.set()

        signal.signal(signal.SIGINT, on_fed_signal)
        signal.signal(signal.SIGTERM, on_fed_signal)
        print(
            f"federated metrics on http://{srv.host}:{srv.port}/metrics "
            f"({len(targets)} member node(s))",
            flush=True,
        )
        stop.wait()
        srv.close()
        return 0

    import redisson_tpu
    from redisson_tpu import Config

    if args.config:
        import os

        if not os.path.exists(args.config):
            p.error(f"--config file not found: {args.config}")
        cfg = Config.from_yaml(args.config)
    else:
        cfg = Config().use_tpu_sketch()
    if args.snapshot_dir:
        cfg.snapshot_dir = args.snapshot_dir
    if args.snapshot_interval_s:
        # Applies to the EFFECTIVE dir (flag or config file) — silently
        # dropping the interval would fake-arm periodic snapshots.
        if not cfg.snapshot_dir:
            p.error("--snapshot-interval-s requires a snapshot dir "
                    "(--snapshot-dir or config file)")
        cfg.snapshot_interval_s = args.snapshot_interval_s
    if args.journal_dir:
        cfg.journal_dir = args.journal_dir
    if args.replica_of:
        cfg.replica_of = args.replica_of

    if args.trace_sample_rate is not None:
        if not 0.0 <= args.trace_sample_rate <= 1.0:
            p.error("--trace-sample-rate must be in [0, 1]")
        cfg.trace_sample_rate = args.trace_sample_rate
    if args.latency_monitor_threshold is not None:
        if args.latency_monitor_threshold < 0:
            p.error("--latency-monitor-threshold must be >= 0")
        cfg.latency_monitor_threshold_ms = args.latency_monitor_threshold
    if args.requirepass:
        cfg.requirepass = args.requirepass
    if args.enable_python_scripts:
        cfg.enable_python_scripts = True
    if args.no_resp_vectorize:
        cfg.resp_vectorize = False
    if args.no_resp_reactor:
        cfg.resp_reactor = False
    if args.resp_reactor_threads is not None:
        if args.resp_reactor_threads < 1:
            p.error("--resp-reactor-threads must be >= 1")
        cfg.resp_reactor_threads = args.resp_reactor_threads
    if args.cluster:
        cfg.cluster_enabled = True
    if args.rebalance:
        if not cfg.cluster_enabled:
            p.error("--rebalance requires --cluster (or a config file "
                    "with cluster_enabled: true)")
        cfg.rebalance_enabled = True
    if args.doctor:
        if not cfg.cluster_enabled:
            p.error("--doctor requires --cluster (or a config file "
                    "with cluster_enabled: true)")
        cfg.doctor_enabled = True
    for flag, key in (
        (args.cluster_slots, "cluster_slots"),
        (args.cluster_topology, "cluster_topology"),
        (args.cluster_myid, "cluster_node_id"),
        (args.cluster_announce, "cluster_announce"),
        (args.cluster_node_timeout_ms, "cluster_node_timeout_ms"),
    ):
        if flag is not None:
            if not cfg.cluster_enabled:
                p.error("--cluster-* flags require --cluster (or a "
                        "config file with cluster_enabled: true)")
            setattr(cfg, key, flag)

    # Per-core front door (ISSUE 17).  Parent shape: K > 1 and no
    # worker index — this process becomes a pure supervisor that spawns
    # K worker children sharing the port via SO_REUSEPORT (no engine of
    # its own).  Worker shape: the internal flags stamp this process as
    # worker i of K.  No-SO_REUSEPORT platforms degrade to K=1 here
    # (effective_processes logs the INFO frontdoor line).
    fd_req = (
        args.frontdoor_processes
        if args.frontdoor_processes is not None
        else getattr(cfg, "frontdoor_processes", 1)
    )
    if args.frontdoor_index is None and (fd_req or 1) > 1:
        from redisson_tpu.serve import multicore

        fd_k = multicore.effective_processes(fd_req)
        if fd_k > 1:
            return _serve_multicore(args, fd_k)
    # Past the supervisor branch: this process serves, so it may load
    # jax (the multicore parent never does — a chip is one process's).
    from redisson_tpu.serve.resp import RespServer
    from redisson_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.frontdoor_index is not None:
        import os

        cfg.frontdoor_workers = max(2, int(args.frontdoor_workers))
        cfg.frontdoor_index = args.frontdoor_index
        cfg.frontdoor_dir = args.frontdoor_dir
        # Durability dirs split per worker — K journals/snapshot sets,
        # one per slot-range owner, never one contended set.
        sub = f"worker{args.frontdoor_index}"
        if cfg.snapshot_dir:
            cfg.snapshot_dir = os.path.join(cfg.snapshot_dir, sub)
            os.makedirs(cfg.snapshot_dir, exist_ok=True)
        if getattr(cfg, "journal_dir", None):
            cfg.journal_dir = os.path.join(cfg.journal_dir, sub)
            os.makedirs(cfg.journal_dir, exist_ok=True)
        # Device pinning (satellite): each worker takes a contiguous
        # 1/K of the local devices; a chip backend with fewer devices
        # than workers refuses to start (raises, naming both counts).
        if cfg.tpu_sketch.device_indices is None:
            import jax

            from redisson_tpu.serve.multicore import device_slice_for_worker

            cfg.tpu_sketch.device_indices = device_slice_for_worker(
                args.frontdoor_index, cfg.frontdoor_workers,
                len(jax.devices()), jax.default_backend(),
            )

    repl_master = getattr(cfg, "replica_of", None)
    if repl_master:
        # Replica boot (ISSUE 18): pull the primary's snapshot and wipe
        # local durability state BEFORE the engine restores, so the
        # process always comes up at one consistent (replid, offset)
        # and never replays stale local segments over the primary's
        # snapshot.  Runs after the worker-subdir split above — the
        # extracted files land in the dirs the engine actually reads.
        host_m, _, port_m = str(repl_master).rpartition(":")
        if not host_m or not port_m.isdigit():
            p.error("--replica-of needs HOST:PORT")
        if not cfg.snapshot_dir:
            p.error("--replica-of requires a snapshot dir "
                    "(--snapshot-dir or config file)")
        from redisson_tpu.durability.replica import bootstrap_full_resync

        ident = (getattr(cfg, "cluster_node_id", None)
                 or f"{args.host}:{args.port}")
        replid, snap_seq = bootstrap_full_resync(
            host_m, int(port_m), cfg.snapshot_dir,
            getattr(cfg, "journal_dir", None), ident,
            listening_port=args.port,
        )
        # The RESP door hands this to the ReplicaLink so its first
        # PSYNC continues from the restored cut instead of re-shipping
        # the snapshot it was just built from.
        cfg._repl_bootstrap_id = replid
        print(
            f"replica of {repl_master}: FULLRESYNC {replid} "
            f"at seq {snap_seq}",
            flush=True,
        )

    client = redisson_tpu.create(cfg)
    server = RespServer(
        client,
        host=args.host,
        port=args.port,
        max_connections=args.max_connections,
        idle_timeout_s=args.idle_timeout_s,
    )
    if server.cluster is not None:
        # Automatic failover (ISSUE 18): every cluster node runs the
        # bus agent — primaries to vote, replicas to detect their
        # primary's death and run the election.  server.close() stops
        # it.
        from redisson_tpu.cluster.failover import FailoverAgent

        FailoverAgent(
            server,
            node_timeout_s=float(
                getattr(cfg, "cluster_node_timeout_ms", 1500) or 1500
            ) / 1000.0,
            ping_interval_s=float(
                getattr(cfg, "cluster_ping_interval_ms", 300) or 300
            ) / 1000.0,
        ).start()
        if getattr(cfg, "rebalance_enabled", False):
            # Autonomous rebalancer (ISSUE 19): observe everywhere,
            # execute on the coordinator.  server.close() stops it.
            from redisson_tpu.cluster.rebalancer import RebalanceAgent

            RebalanceAgent(
                server,
                interval_s=float(
                    getattr(cfg, "rebalance_interval_ms", 1000) or 1000
                ) / 1000.0,
                threshold=float(
                    getattr(cfg, "rebalance_threshold", 1.3) or 1.3
                ),
                max_moves=int(
                    getattr(cfg, "rebalance_max_moves", 8) or 8
                ),
                pace_s=float(
                    getattr(cfg, "rebalance_pace_ms", 50) or 0
                ) / 1000.0,
                cooldown_s=float(
                    getattr(cfg, "rebalance_cooldown_ms", 15000) or 0
                ) / 1000.0,
            ).start()
        if getattr(cfg, "doctor_enabled", False):
            # Fleet doctor (ISSUE 20): probe everywhere, audit on the
            # coordinator.  server.close() stops it.
            from redisson_tpu.obs.doctor import FleetDoctor

            FleetDoctor(
                server,
                interval_s=float(
                    getattr(cfg, "doctor_interval_ms", 1000) or 1000
                ) / 1000.0,
                stuck_slot_s=float(
                    getattr(cfg, "doctor_stuck_slot_ms", 30000) or 30000
                ) / 1000.0,
                lag_bound_ops=int(
                    getattr(cfg, "doctor_lag_bound_ops", 10000) or 10000
                ),
                canary=bool(getattr(cfg, "doctor_canary", True)),
            ).start()
    metrics_srv = None
    if args.metrics_port is not None:
        metrics_srv = client.start_metrics_endpoint(
            host=args.host, port=args.metrics_port
        )
        print(
            f"metrics on http://{metrics_srv.host}:{metrics_srv.port}/metrics",
            flush=True,
        )
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    mode = ""
    if server.cluster is not None:
        mode = (
            f" [cluster node {server.cluster.myid}, "
            f"{server.cluster.slotmap.owned_count(server.cluster.myid)}"
            f"/16384 slots]"
        )
    print(
        f"redisson-tpu serving RESP on {server.host}:{server.port} "
        f"(backend={client._engine.__class__.__name__}){mode}",
        flush=True,
    )
    stop.wait()
    print("shutting down (snapshot-on-shutdown if configured)", flush=True)
    server.close()
    client.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
