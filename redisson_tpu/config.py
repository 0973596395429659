"""Config system — parity with org/redisson/config/ (SURVEY.md §2.1 Config).

The reference exposes a programmatic builder ``Config`` plus YAML/JSON
loading (``Config.fromYAML`` via ConfigSupport,
→ org/redisson/config/ConfigSupport.java) with per-mode sections and ~50
tunables.  We mirror the shape: one dataclass-style ``Config`` with fluent
setters, ``from_yaml``/``from_dict``/``to_dict``, and the north-star
``use_tpu_sketch()`` switch that routes sketch objects through the
``TpuCommandExecutor`` instead of the host grid.

TPU-specific tunables replace netty/pool knobs (SURVEY.md §5 config row):
batch window, max batch size, bucketing, tenant capacity, shard axis size.
"""

from __future__ import annotations

import json
from typing import Any, Optional


class TpuSketchConfig:
    """Tunables for the TPU sketch backend (the analog of the netty/pool
    section of BaseConfig)."""

    def __init__(self):
        self.enabled = False
        # Coalescer (CommandBatchService-role) knobs.
        self.coalesce = True  # cross-call op coalescing via flush thread
        self.batch_window_us = 200  # flush deadline
        self.max_batch = 1 << 16  # flush size threshold
        self.min_bucket = 256  # smallest padded batch shape (floor 32: results travel bit-packed)
        # Dispatched-but-uncollected segment bound (coalescer pipelining;
        # keeps the transport in its fast retirement regime — tuned over
        # a remote link, where >12 un-synced dispatches degraded every
        # op; not yet measured on an attached chip).
        self.max_inflight = 8
        # Engine-side backpressure (the ConnectionPool#acquire role): a
        # producer's submit() BLOCKS once this many ops are queued ahead of
        # the flush thread — without it any unpaced client recreates the
        # unbounded-queue p99 catastrophe (round-2 postmortem).  0 → auto
        # (8 × max_batch).
        self.max_queued_ops = 0
        # Phase-aware merge cap (ISSUE 6 satellite, ROADMAP per-transfer-RT
        # lever): while the link's observed launch-retirement EWMA says
        # every transfer costs ~a round trip, merge-at-pop may combine
        # parked/queued segments PAST the static max_batch up to this
        # bound — fewer, larger launches exactly when each launch eats an
        # RT.  0 disables (cap stays max_batch); in the fast phase the
        # static cap always applies.
        self.max_batch_slow_phase = 0
        # Adaptive in-flight: shrink the dispatch window toward
        # min_inflight while observed launch retirement is slow (the
        # transport's >~12-launch cliff degrades EVERY op when the link
        # enters its slow phase), grow back toward max_inflight when
        # retirements are fast.
        self.adaptive_inflight = True
        self.min_inflight = 2
        # Adaptive flush window (warm-path dispatch): batch_window_us is
        # the BASE; an EWMA-of-arrival-rate + queue-pressure controller
        # moves the live window inside [min_window_us, max_window_us] —
        # small under light load (latency), toward the max under pressure
        # (segments fill toward max_batch).  0 → auto bounds
        # (base/2 .. base*8).
        self.adaptive_window = True
        self.min_window_us = 0
        self.max_window_us = 0
        # AOT bucket pre-warming: a background thread compiles the
        # (opcode, bucket) jit ladder up to max_batch on pool attach, so
        # no serving-path op pays a first-touch compile (the config-4
        # cold-pass cliff).  Off by default: every client would otherwise
        # spend background CPU compiling ladders it may never serve —
        # serving deployments and the bench turn it on.
        self.prewarm = False
        # Pools whose state exceeds this are not pre-warmed (a warm pass
        # needs a scratch state of the same shape on device).
        self.prewarm_max_state_bytes = 1 << 28
        # Self-healing dispatch (ISSUE 3): per-(shard, opcode) circuit
        # breakers over the coalescer's dispatch failures —
        # ``breaker_failure_threshold`` consecutive failures OPEN the
        # circuit (affected sketches fail over to the host golden
        # mirror); after ``breaker_open_ms`` a probe dispatch tests the
        # device and, on success, mirrored state reconciles back.
        self.breaker_failure_threshold = 5
        self.breaker_open_ms = 1000
        # Dispatch retry backoff: the coalescer re-enqueues a failed
        # segment with a jittered exponential deadline (base =
        # retry_interval doubling per attempt, capped here; jitter is a
        # uniform ±fraction) instead of sleeping the flush thread.
        self.retry_max_backoff_ms = 2000
        self.retry_jitter = 0.2
        # Near cache (ISSUE 4): the epoch-guarded host read tier — hot
        # single-key reads (contains/GETBIT/PFCOUNT/CMS estimate) answer
        # from host memory in microseconds regardless of link phase.
        # Coherence is host-side epoch bookkeeping (zero device traffic):
        # monotone positives (Bloom/bitset membership) cache until a
        # structural change; everything else is write-epoch-tagged and
        # served only while the tag matches.  Forced off under
        # multi-host (process_count > 1): a hit skips a device dispatch,
        # which would break multi-controller lockstep (same gate as
        # mailbox_collect).
        self.nearcache = True
        self.nearcache_max_bytes = 64 << 20
        # Per-tenant byte quota (fairness: one hot tenant can never
        # evict everyone).  0 → max_bytes / 8.
        self.nearcache_tenant_quota_bytes = 0
        self.nearcache_shards = 8
        # Batches larger than this bypass the cache entirely: bulk
        # passes belong to the three-transfer link path, and per-op key
        # materialization would tax them for nothing.
        self.nearcache_max_batch = 1024
        # Overload control plane (ISSUE 7) — the maxmemory/timeout/
        # client-output-buffer-limit analog for the batched dispatch
        # path.  ``op_deadline_ms``: default end-to-end deadline stamped
        # on every RESP command (0 = none; per-connection override via
        # CLIENT DEADLINE, direct-API via client.op_deadline(ms)).  Ops
        # whose deadline expires are shed strictly PRE-dispatch (fast
        # DeadlineExceededError / -BUSY reply) — acked writes are never
        # shed.
        self.op_deadline_ms = 0
        # Bound on a no-deadline blocking .result() wait (replaces the
        # old hardcoded 120 s in HintedFuture).  A fetch timeout records
        # a breaker failure like any other completion failure.
        self.fetch_timeout_ms = 120_000
        # RESP ingress shedding: once coalescer queue pressure
        # (queued_ops / max_queued_ops) crosses this watermark, every
        # non-exempt command is refused with a -BUSY error instead of
        # queueing.  The door is deliberately command-family-blind
        # (host-side ops are shed too — they share the process's grid
        # lock and threads, and classifying the backend of every
        # command is a maintenance trap); the exempt list covers the
        # handshake/admin/introspection surface an operator needs
        # during the incident.  1.0 effectively disables ingress
        # shedding (pressure rarely exceeds the bound); must be > 0.
        self.admission_watermark = 0.9
        # Per-tenant fairness: token-bucket rate limit (ops/sec, 0 =
        # unlimited), bucket burst size (0 → 2x the rate), and a
        # queued+in-flight op quota (0 = unlimited).  Over-quota tenants
        # are shed FIRST (TenantThrottledError / -BUSY), so a
        # well-behaved tenant keeps its throughput during another
        # tenant's burst.
        self.tenant_rate_limit = 0
        self.tenant_burst_ops = 0
        self.tenant_max_inflight = 0
        # Tiered sketch storage (ISSUE 14): the heat-based residency
        # ladder (storage/residency.py) — device rows become a CACHE
        # over host golden mirrors over per-object disk blobs, so the
        # addressable tenant population is bounded by host+disk, not
        # HBM.  ``residency_device_rows``: the fast-tier row budget
        # across all sketch pools (0 = unlimited, ladder passive —
        # every tenant stays device-resident, the pre-ISSUE-14
        # behavior; pay-for-use).  Cold rows demote to exact host
        # mirrors; frozen mirrors spill to ``residency_dir`` once host
        # bytes exceed ``residency_max_host_bytes`` (0 = never spill);
        # ``residency_max_disk_bytes`` caps the blob tier (0 =
        # unlimited); objects whose decayed access heat (half-life
        # ``residency_heat_half_life_s``) reaches
        # ``residency_promote_heat`` promote back through the prewarmed
        # pools, admission-aware.  All budgets live via CONFIG SET.
        self.residency_device_rows = 0
        self.residency_max_host_bytes = 0
        self.residency_max_disk_bytes = 0
        self.residency_promote_heat = 4.0
        self.residency_heat_half_life_s = 10.0
        self.residency_interval_ms = 200
        self.residency_dir: Optional[str] = None
        # Device-side result mailbox: the completer concatenates pending
        # launches' packed results on device and fetches them in ONE D2H
        # — over a remote link each host fetch cost a full round trip
        # regardless of size.
        self.mailbox_collect = True
        # Tenancy.
        self.initial_tenants_per_class = 8  # initial rows per size-class pool
        # Exact intra-batch sequential semantics for bloom add (sort-based
        # kernel).  False selects the fast single-tenant add whose
        # newly-added flags are computed vs pre-batch state (bit-level
        # results identical; see ops/fastpath.py).
        self.exact_add_semantics = True
        self.max_bloom_bits = 1 << 31
        # Sharding: 1 → single-device executor; S > 1 → the cluster-mode
        # analog (executor/sharded_executor.py): tenant row r lives on
        # shard r % S of a 1-D device mesh, batches replicate to every
        # shard, results combine via one ICI psum.  Requires >= S devices
        # (virtual CPU meshes via xla_force_host_platform_device_count
        # work for tests).
        self.num_shards = 1
        # Bitset rows at or above this many uint32 words shard along the
        # m-axis (contiguous word blocks per shard) instead of living on
        # one shard — config 3's 2^30-bit filter path (SURVEY.md §7-L4).
        # Only meaningful with num_shards > 1.
        self.mbit_threshold_words = 1 << 22
        # Explicit device pinning (ISSUE 17 satellite, ROADMAP
        # carry-over): the pool attach uses EXACTLY these local device
        # indices (in order) instead of first-come enumeration — each
        # front-door worker (and later each replica) owns a disjoint
        # slice of the node's devices.  None → all local devices, the
        # old behavior.  With num_shards > 1 the slice length must be
        # >= num_shards.
        self.device_indices: Optional[list] = None
        # Multi-host (DCN) — docs/MULTIHOST.md.  When coordinator_address
        # is set the engine joins the standard JAX distributed runtime
        # before device discovery; num_shards then counts GLOBAL shards.
        # Exercised across two real processes by tests/test_multihost.py;
        # multi-host PERFORMANCE stays unmeasurable in the single-chip
        # bench env.
        self.coordinator_address: Optional[str] = None
        self.num_processes = 1
        self.process_id = 0
        # HLL geometry is fixed to Redis parity (p=14) — not configurable,
        # matching Redis server behavior.

    def to_dict(self) -> dict:
        return dict(self.__dict__)

    def update(self, d: dict) -> None:
        for k, v in d.items():
            if not hasattr(self, k):
                raise ValueError(f"unknown tpuSketch config key: {k}")
            setattr(self, k, v)


class Config:
    """→ org/redisson/config/Config.java."""

    def __init__(self):
        from redisson_tpu.codecs import DEFAULT_CODEC

        self.codec = DEFAULT_CODEC
        self.threads = 4  # listener/executor pool (reference: `threads`)
        self.lock_watchdog_timeout_ms = 30_000  # reference default 30s
        self.retry_attempts = 3
        self.retry_interval_ms = 1500
        self.timeout_ms = 3000
        self.tpu_sketch = TpuSketchConfig()
        # Snapshot/restore (checkpoint row, SURVEY.md §5).
        self.snapshot_dir: Optional[str] = None
        self.snapshot_interval_s: float = 0.0  # 0 → no periodic snapshots
        # Crash-safe durability tier (ISSUE 10): the AOF analog.  With a
        # journal_dir set, every accepted sketch mutation appends a
        # CRC32-framed record (durability/journal.py); recovery =
        # restore_snapshot + deterministic tail replay through the host
        # golden engine.  ``journal_fsync`` maps to appendfsync
        # always|everysec|no (live-settable via CONFIG SET appendfsync):
        # under ``always`` an op's ack resolves only after its record is
        # fsynced.  Segments rotate at journal_max_segment_bytes; a
        # completed snapshot retires covered segments (the BGREWRITEAOF
        # analog).
        self.journal_dir: Optional[str] = None
        self.journal_fsync: str = "everysec"
        self.journal_max_segment_bytes: int = 64 << 20
        # Front-door auth (→ the reference server configs' `password`
        # key, org/redisson/config/BaseConfig#setPassword): when set,
        # every RESP connection must AUTH (or HELLO ... AUTH) before any
        # other command.  None = open, the redis-server default.
        self.requirepass: Optional[str] = None
        # RESP script execution watchdog (the busy-reply-threshold
        # analog): a script running longer than this makes the server
        # answer other connections with BUSY (SCRIPT KILL remains
        # available) instead of silently queueing them behind the grid
        # lock.  0 disables the BUSY surface (scripts may block forever).
        self.script_timeout_ms = 5000
        # RESP scripting (EVAL/EVALSHA/SCRIPT/FUNCTION/FCALL): script
        # bodies are arbitrary PYTHON, i.e. remote code execution for
        # anyone who can reach the socket — OFF by default, and the
        # RespServer refuses to enable it unless requirepass is set or
        # the bind is loopback.  (The in-process Python ScriptService is
        # unaffected: in-process callers can run code anyway.)
        self.enable_python_scripts = False
        # Front-door command-stream vectorization (ISSUE 6 tentpole):
        # fuse runs of adjacent pipelined commands that target the same
        # (object, opcode) family into single engine launches, demuxing
        # the packed result back into per-command replies in order.
        # Per-connection sequential semantics are preserved bit-for-bit
        # (non-fusable commands act as run barriers).
        self.resp_vectorize = True
        # Per-connection response cache for REPEATED IDENTICAL read
        # commands inside one pipeline window (one parsed-ahead batch):
        # entry count bound; 0 disables.  Entries are invalidated by any
        # write epoch bump (any non-read RESP command on any connection).
        self.resp_response_cache_size = 64
        # Reactor front door (ISSUE 11): replace thread-per-connection
        # serving with a small fixed pool of epoll/selector reactor
        # threads that drain recv buffers across ALL ready connections
        # per tick and feed one merged parse→vectorize→dispatch pass —
        # adjacent same-(object, family) ops from DIFFERENT connections
        # fuse into single engine launches, and idle connections cost a
        # file descriptor instead of a thread.  False restores the
        # legacy thread-per-connection accept loop (kept selectable for
        # differential testing; semantics are byte-identical per
        # connection either way).
        self.resp_reactor = True
        # Reactor thread-pool size.  ONE loop is the default (the
        # redis-server shape): the merged dispatch pass holds the GIL
        # anyway, so extra reactors buy no parse throughput — they
        # SPLIT the connection population and halve the cross-
        # connection fusion window (measured ~10% cmds/s regression at
        # 2 loops on the config8 bench).  Blocking commands never run
        # on the loop (worker handoff), so isolation is not the loop
        # count's job.  >1 remains available for experiments.
        self.resp_reactor_threads = 1
        # Slow-client protection (ISSUE 7): the client-output-buffer-
        # limit analog.  ``client_output_buffer_limit``: a reply frame
        # still holding more than this many unsent bytes after its
        # grace window (soft_seconds when set, else ~1 s) drops the
        # connection (0 = unlimited, the redis-server default for
        # normal clients) — time-gated so a fast reader of a large
        # reply is untouched while a trickler cannot ride byte-at-a-
        # time progress forever.  ``client_output_buffer_soft_seconds``:
        # a send making NO progress for this long is dropped regardless
        # of the byte bound (0 = fall back to the connection's idle
        # timeout).  Both live-settable via CONFIG SET.
        self.client_output_buffer_limit = 0
        self.client_output_buffer_soft_seconds = 0.0
        # Cluster mode (ISSUE 12): the 16384-slot CRC16 topology layer
        # (docs/clustering.md).  When enabled the RESP door routes every
        # keyed command by its keys' slot: wrong-slot keys get
        # -MOVED/-ASK redirects, hash tags {...} co-locate multi-key
        # ops, and live slot migration rides CLUSTER SETSLOT + MIGRATE.
        # ``cluster_topology`` is a dict (or path to a JSON file) of
        # {"nodes": [{"id", "host", "port", "slots": [[a, b], ...]}]};
        # without one this node is a single-node cluster owning
        # ``cluster_slots`` (e.g. "0-16383", default all).
        # ``cluster_node_id`` must name an entry in the topology;
        # ``cluster_announce`` ("host:port") is the address OTHER nodes
        # and clients are redirected to (defaults to the bind address).
        self.cluster_enabled = False
        self.cluster_node_id: Optional[str] = None
        self.cluster_topology = None
        self.cluster_slots: Optional[str] = None
        self.cluster_announce: Optional[str] = None
        # Fleet telemetry plane (ISSUE 13).  ``trace_sample_rate``:
        # head-based sampling probability for distributed request traces
        # (obs/trace.py) — 0.0 (default) disables tracing entirely; the
        # module-level guard makes the off path one attribute read per
        # hook.  Live-settable via CONFIG SET trace-sample-rate / TRACE
        # SAMPLE.  ``trace_max_spans``: the HARD per-process span-ring
        # bound (oldest spans evict — tracing is a recency window, never
        # a leak).  ``latency_monitor_threshold_ms``: the redis
        # latency-monitor-threshold analog — named latency events
        # (command, slow-launch, fsync-stall, breaker-open, migration,
        # reconcile) at or above this many ms are sampled into bounded
        # per-event histories served by LATENCY LATEST|HISTORY|DOCTOR;
        # 0 disables.
        self.trace_sample_rate = 0.0
        self.trace_max_spans = 2048
        self.latency_monitor_threshold_ms = 0
        # Load-attribution plane (ISSUE 16).  Probability that a served
        # command's keys are fed into the node's hot-key sketches
        # (decayed CMS + space-saving top-k in obs/loadmap.py) — the
        # per-slot load vectors are always maintained (O(1) array bumps);
        # only KEY sampling is probabilistic, since it takes the loadmap
        # lock.  Live-settable via CONFIG SET loadmap-key-sample-rate;
        # surfaced through HOTKEYS and INFO loadstats.
        self.loadmap_key_sample_rate = 0.01
        # Per-core front door (ISSUE 17).  ``frontdoor_processes``: K
        # reactor processes share this node's listen port via
        # SO_REUSEPORT, each owning a contiguous 1/K of the slot range
        # behind an in-node slot→process map (serve/multicore.py).
        # 1 (default) = the single-process door; >1 on a platform
        # without SO_REUSEPORT degrades to 1 with an INFO log line,
        # never a bind-time crash.  The ``frontdoor_workers`` /
        # ``frontdoor_index`` / ``frontdoor_dir`` triple is INTERNAL —
        # the supervisor parent stamps it into each worker child
        # (--frontdoor-workers/--frontdoor-index/--frontdoor-dir);
        # setting it by hand spawns one bare worker of a K-party door.
        self.frontdoor_processes = 1
        self.frontdoor_workers = 1
        self.frontdoor_index: Optional[int] = None
        self.frontdoor_dir: Optional[str] = None
        # Replication + automatic failover (ISSUE 18).  ``replica_of``
        # ("host:port") makes this node a READ replica: it bootstraps
        # via RTPU.PSYNC (snapshot tar + stream tail), applies the
        # primary's journal stream, and serves reads only (-READONLY on
        # writes).  ``repl_backlog_bytes`` bounds the primary-side
        # partial-resync ring; a replica whose offset falls off it (and
        # off the retired journal segments) full-resyncs.
        # ``repl_max_staleness_ops``: a replica more than this many ops
        # behind its primary refuses keyed reads with -STALEREAD
        # (0 = serve reads at any staleness — the Redis default).
        # ``cluster_node_timeout_ms`` / ``cluster_ping_interval_ms``:
        # the failover agent's failure-detection clock — a peer silent
        # for node-timeout is marked failed, and a failed primary's
        # replicas run the epoch election (docs/clustering.md
        # "Replication & failover").
        self.replica_of: Optional[str] = None
        self.repl_backlog_bytes = 4 << 20
        self.repl_max_staleness_ops = 0
        self.cluster_node_timeout_ms = 1500
        self.cluster_ping_interval_ms = 300
        # Autonomous rebalancer (ISSUE 19).  ``rebalance_enabled`` arms
        # the per-node control loop (cluster/rebalancer.py): every armed
        # node scrapes the fleet's CLUSTER LOADMAPs into a smoothed
        # per-slot heat EWMA; the coordinator (lowest-id alive primary)
        # additionally executes migration waves.  The damping knobs —
        # all live-settable via CONFIG SET rebalance-* — implement the
        # Memcache-at-Facebook churn lesson: ``rebalance_threshold`` is
        # the imbalance ratio (max node load / mean) that triggers a
        # wave, ``rebalance_max_moves`` caps migrations per wave,
        # ``rebalance_pace_ms`` breathes between consecutive pumps (the
        # p99 bound during a wave), and ``rebalance_cooldown_ms`` keeps
        # a just-moved slot untouchable so the loop can never ping-pong
        # one slot between two nodes.
        self.rebalance_enabled = False
        self.rebalance_interval_ms = 1000
        self.rebalance_threshold = 1.3
        self.rebalance_max_moves = 8
        self.rebalance_pace_ms = 50
        self.rebalance_cooldown_ms = 15000
        # Fleet doctor (ISSUE 20).  ``doctor_enabled`` arms the
        # continuous invariant sweep (obs/doctor.py): every armed node
        # probes the fleet, the coordinator (lowest-id alive primary)
        # audits — slot ownership, offset/epoch monotonicity, replica
        # lag, stuck migrations — and runs the black-box WAIT-fenced
        # canary.  ``doctor_stuck_slot_ms`` is how long a slot may sit
        # MIGRATING/IMPORTING before that reads as an abandoned
        # reshard; ``doctor_lag_bound_ops`` the replica-lag finding
        # threshold.
        self.doctor_enabled = False
        self.doctor_interval_ms = 1000
        self.doctor_stuck_slot_ms = 30000
        self.doctor_lag_bound_ops = 10000
        self.doctor_canary = True

    # -- fluent setters, mirroring the Java builder idiom ------------------

    def set_codec(self, codec) -> "Config":
        self.codec = codec
        return self

    def set_threads(self, n: int) -> "Config":
        self.threads = n
        return self

    def set_requirepass(self, password: Optional[str]) -> "Config":
        """→ BaseConfig#setPassword: require AUTH on the RESP front
        door."""
        self.requirepass = password
        return self

    def set_enable_python_scripts(self, enabled: bool) -> "Config":
        """Allow RESP EVAL/FUNCTION (Python bodies — RCE for anyone who
        can reach the socket; the server refuses unless requirepass is
        set or the bind is loopback)."""
        self.enable_python_scripts = enabled
        return self

    def use_tpu_sketch(self, **kwargs) -> "Config":
        """Enable the TPU execution backend for sketch objects — the
        north-star mode switch (BASELINE.json: `useTpuSketch()`)."""
        self.tpu_sketch.enabled = True
        self.tpu_sketch.update(kwargs)
        return self

    # -- serialization -----------------------------------------------------

    _SIMPLE_KEYS = (
        "threads",
        "lock_watchdog_timeout_ms",
        "retry_attempts",
        "retry_interval_ms",
        "timeout_ms",
        "snapshot_dir",
        "snapshot_interval_s",
        "journal_dir",
        "journal_fsync",
        "journal_max_segment_bytes",
        "requirepass",
        "enable_python_scripts",
        "script_timeout_ms",
        "resp_vectorize",
        "resp_response_cache_size",
        "resp_reactor",
        "resp_reactor_threads",
        "client_output_buffer_limit",
        "client_output_buffer_soft_seconds",
        "cluster_enabled",
        "cluster_node_id",
        "cluster_topology",
        "cluster_slots",
        "cluster_announce",
        "trace_sample_rate",
        "trace_max_spans",
        "latency_monitor_threshold_ms",
        "loadmap_key_sample_rate",
        "frontdoor_processes",
        "frontdoor_workers",
        "frontdoor_index",
        "frontdoor_dir",
        "replica_of",
        "repl_backlog_bytes",
        "repl_max_staleness_ops",
        "cluster_node_timeout_ms",
        "cluster_ping_interval_ms",
        "rebalance_enabled",
        "rebalance_interval_ms",
        "rebalance_threshold",
        "rebalance_max_moves",
        "rebalance_pace_ms",
        "rebalance_cooldown_ms",
        "doctor_enabled",
        "doctor_interval_ms",
        "doctor_stuck_slot_ms",
        "doctor_lag_bound_ops",
        "doctor_canary",
    )

    def to_dict(self) -> dict:
        d: dict[str, Any] = {k: getattr(self, k) for k in self._SIMPLE_KEYS}
        d["codec"] = type(self.codec).__name__
        d["tpu_sketch"] = self.tpu_sketch.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        cfg = cls()
        d = dict(d)
        codec_name = d.pop("codec", None)
        if codec_name:
            from redisson_tpu import codecs

            codec_cls = getattr(codecs, codec_name, None)
            if codec_cls is None:
                raise ValueError(f"unknown codec: {codec_name}")
            try:
                cfg.codec = codec_cls()
            except TypeError as e:
                raise ValueError(
                    f"codec {codec_name} takes constructor arguments and cannot "
                    f"be reconstructed from config; set it with set_codec()"
                ) from e
        tpu = d.pop("tpu_sketch", None)
        for k, v in d.items():
            if k not in cls._SIMPLE_KEYS:
                raise ValueError(f"unknown config key: {k}")
            setattr(cfg, k, v)
        if tpu:
            cfg.tpu_sketch.update(tpu)
        return cfg

    @classmethod
    def from_yaml(cls, text_or_path: str) -> "Config":
        """→ Config.fromYAML.  Accepts YAML text or a path to a file.
        Uses PyYAML if available, else a JSON fallback (YAML superset)."""
        import os

        text = text_or_path
        if os.path.exists(text_or_path):
            with open(text_or_path) as f:
                text = f.read()
        elif "\n" not in text_or_path and (
            text_or_path.endswith((".yml", ".yaml", ".json"))
            or "/" in text_or_path
        ):
            # Clearly a PATH that doesn't exist — feeding it to the YAML
            # parser produced a baffling dict-update ValueError.
            raise FileNotFoundError(f"config file not found: {text_or_path}")
        try:
            import yaml  # type: ignore

            data = yaml.safe_load(text)
        except ImportError:
            data = json.loads(text)
        if data is None:
            data = {}
        if not isinstance(data, dict):
            raise ValueError(
                f"config must parse to a mapping, got {type(data).__name__}"
            )
        return cls.from_dict(data)

    @classmethod
    def from_json(cls, text: str) -> "Config":
        return cls.from_dict(json.loads(text))

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)
