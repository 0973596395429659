"""Unified observability subsystem (ISSUE 1 tentpole).

One ``Observability`` bundle per engine/client ties together:

- ``registry`` — labeled counters / gauges / log2 histograms
  (obs/registry.py) rendered as typed Prometheus text;
- ``spans`` — per-launch lifecycle spans feeding phase histograms
  (obs/spans.py);
- ``slowlog`` — the SLOWLOG-compatible slow-op ring (obs/slowlog.py),
  surfaced over RESP by serve/resp.py.

The pre-built families below are the instrumentation points the rest of
the codebase uses; everything is lazy-cheap when nothing reads it.
"""

from __future__ import annotations

from redisson_tpu.obs.events import EventRing
from redisson_tpu.obs.latency import LatencyMonitor
from redisson_tpu.obs.loadmap import LoadMap
from redisson_tpu.obs.registry import Family, MetricsRegistry
from redisson_tpu.obs.slowlog import SlowLog, SlowLogEntry
from redisson_tpu.obs.spans import OpSpan, SpanRecorder, Timed, register_bundle
from redisson_tpu.obs.trace import Tracer


class Observability:
    def __init__(self, slowlog_max_len: int = 128,
                 slowlog_threshold_us: int = 10_000,
                 trace_sample_rate: float = 0.0,
                 trace_max_spans: int = 2048,
                 latency_threshold_ms: int = 0):
        r = MetricsRegistry()
        self.registry = r
        # Fleet telemetry plane (ISSUE 13): latency monitor + tracer
        # volume counters come FIRST so the recorders below can ride
        # them.
        self.latency_events = r.counter(
            "rtpu_latency_events",
            "latency-monitor samples recorded, by event "
            "(command | slow-launch | fsync-stall | breaker-open | "
            "migration | reconcile | election | rebalance-wave | "
            "full-resync)", ("event",))
        self.trace_sampled = r.counter(
            "rtpu_trace_sampled",
            "requests head-sampled into a distributed trace")
        self.trace_spans = r.counter(
            "rtpu_trace_spans",
            "trace spans recorded into the bounded per-process ring")
        self.latency = LatencyMonitor(
            latency_threshold_ms, counter=self.latency_events)
        self.trace = Tracer(
            trace_sample_rate, max_spans=trace_max_spans,
            sampled_counter=self.trace_sampled,
            span_counter=self.trace_spans,
        )
        self.spans = SpanRecorder(r, latency=self.latency)
        self.slowlog = SlowLog(slowlog_max_len, slowlog_threshold_us)
        # RESP front door (per-command dimension).
        self.resp_commands = r.counter(
            "rtpu_resp_commands", "RESP commands processed", ("cmd",))
        self.resp_errors = r.counter(
            "rtpu_resp_errors", "RESP commands that returned an error",
            ("cmd",))
        self.resp_latency = r.histogram(
            "rtpu_resp_command_seconds", "RESP command execution time",
            ("cmd",))
        # Engine submit (per-tenant / per-object-type dimensions).
        self.tenant_ops = r.counter(
            "rtpu_tenant_ops", "sketch ops submitted, by tenant and op",
            ("tenant", "op"), max_children=2048)
        self.tenant_calls = r.counter(
            "rtpu_tenant_calls", "sketch API calls, by tenant and kind",
            ("tenant", "kind"), max_children=2048)
        # Executor dispatch (per-method; per-shard in sharded mode).
        self.dispatches = r.counter(
            "rtpu_dispatches", "executor dispatches, by method", ("method",))
        self.dispatch_ops = r.counter(
            "rtpu_dispatch_ops", "ops dispatched, by executor method",
            ("method",))
        self.dispatch_seconds = r.histogram(
            "rtpu_dispatch_enqueue_seconds",
            "host-side dispatch enqueue time, by method", ("method",))
        self.shard_ops = r.counter(
            "rtpu_shard_ops", "ops routed to each mesh shard", ("shard",))
        # Launch-path timing (docs/observability.md "Host regions"): each
        # family pairs with a constant-named rtpu:* region of the same
        # work, one observation per call, launch or collection.
        self.encode_seconds = r.histogram(
            "rtpu_encode_seconds",
            "client-side key encoding time per call, by object kind",
            ("kind",))
        self.submit_seconds = r.histogram(
            "rtpu_submit_seconds",
            "engine submit time per call, engine entry to returned "
            "future (encode excluded), by op", ("op",))
        self.admit_wait_seconds = r.histogram(
            "rtpu_admit_wait_seconds",
            "time a submit waited on the coalescer's queue bound (one "
            "observation per submit that waited)")
        self.launch_slot_wait_seconds = r.histogram(
            "rtpu_launch_slot_wait_seconds",
            "flush-thread wait for an in-flight launch slot, per launch "
            "(part of the device_dispatch phase), by op", ("op",))
        self.h2d_bytes = r.counter(
            "rtpu_h2d_bytes",
            "bytes shipped host-to-device by the executor's staging "
            "helpers, by executor method", ("method",))
        self.h2d_puts = r.counter(
            "rtpu_h2d_puts",
            "host-to-device transfers (device_put calls) the executor's "
            "staging helpers make, by executor method", ("method",))
        self.bloom_scan_steps = r.counter(
            "rtpu_bloom_scan_steps",
            "steps of the Bloom add scan the executor launched, from each "
            "launch's static shape, by executor method and the pool form "
            "the scan carries (lanes: the 128-lane view; flat: the "
            "fallback)", ("method", "carry"))
        self.compiles = r.counter(
            "rtpu_compiles",
            "XLA backend compiles, by the executor method dispatching "
            "when each fired (other = outside any dispatch)", ("method",))
        self.compile_seconds = r.counter(
            "rtpu_compile_seconds", "seconds spent in XLA backend compiles")
        self.gc_pause_seconds = r.counter(
            "rtpu_gc_pause_seconds",
            "seconds the process spent in Python's cyclic collector")
        self.gc_collections = r.counter(
            "rtpu_gc_collections", "cyclic-collector runs, by generation",
            ("generation",))
        # Children the GC hook touches exist up front: the hook then
        # never takes a family lock from inside a collection.
        self.gc_pause_seconds.child(())
        for g in ("0", "1", "2"):
            self.gc_collections.child((g,))
        register_bundle(self)
        # Robustness (ISSUE 3): degraded-mode serving + chaos injection.
        # Breaker state itself is a render-time gauge (rtpu_breaker_state,
        # registered by the engine's health-gauge wiring).
        self.degraded_ops = r.counter(
            "rtpu_degraded_ops",
            "ops served from the host golden mirror while a breaker was "
            "open, by op kind", ("op",))
        self.faults_injected = r.counter(
            "rtpu_faults_injected",
            "chaos faults injected, by fault point and kind",
            ("point", "kind"))
        # Overload control plane (ISSUE 7): pre-dispatch shedding by
        # reason (deadline | admission | tenant | ingress), deadline
        # failures by stage (submit | admission | queue | fetch_wait),
        # per-tenant throttles, fetch timeouts (the breaker-feeding
        # kind), and slow-client disconnects.  The admission wait
        # estimate itself is a render-time gauge the engine registers
        # (rtpu_admission_est_wait_us).
        self.shed_ops = r.counter(
            "rtpu_shed_ops",
            "ops shed pre-dispatch by the overload control plane, "
            "by reason", ("reason",))
        self.deadline_exceeded = r.counter(
            "rtpu_deadline_exceeded",
            "ops that failed with DeadlineExceededError, by stage",
            ("stage",))
        self.tenant_throttled = r.counter(
            "rtpu_tenant_throttled",
            "ops shed by per-tenant quotas, by tenant",
            ("tenant",), max_children=2048)
        self.fetch_timeouts = r.counter(
            "rtpu_fetch_timeouts",
            "blocking result waits that hit the fetch timeout, by op",
            ("op",))
        self.slow_client_disconnects = r.counter(
            "rtpu_slow_client_disconnects",
            "connections dropped by the output-buffer limits, by cause",
            ("cause",))
        self.resp_ingress_shed = r.counter(
            "rtpu_resp_ingress_shed",
            "RESP commands (or transactions) refused at ingress, by "
            "reason (pressure = admission watermark, tenant = over-quota "
            "tenant peek) — COMMAND-denominated, unlike the "
            "ops-denominated rtpu_shed_ops", ("reason",))
        # Durability tier (ISSUE 10): the op journal's append volume,
        # group-commit fsync latency, and recovery replay count.  Lag
        # (appended-but-unfsynced records) and live segment count are
        # render-time gauges the engine registers
        # (rtpu_journal_lag_ops / rtpu_journal_segments).
        self.journal_records = r.counter(
            "rtpu_journal_records",
            "op records appended to the durability journal")
        self.journal_bytes = r.counter(
            "rtpu_journal_bytes",
            "bytes appended to the durability journal")
        self.journal_fsync_us = r.histogram(
            "rtpu_journal_fsync_us",
            "journal group-commit fsync latency")
        self.journal_replayed = r.counter(
            "rtpu_journal_replayed",
            "journal records replayed through the golden engine at "
            "recovery")
        # Near cache (ISSUE 4): hit/miss by result kind; evictions and
        # live byte occupancy are store-side (evictions inc'd via the
        # store's on_evict hook, bytes a render-time gauge registered by
        # the engine).
        self.nearcache_hits = r.counter(
            "rtpu_nearcache_hits",
            "reads answered from the host near cache, by object kind",
            ("kind",))
        self.nearcache_misses = r.counter(
            "rtpu_nearcache_misses",
            "near-cache probes that went to the device, by object kind",
            ("kind",))
        self.nearcache_evictions = r.counter(
            "rtpu_nearcache_evictions",
            "near-cache entries evicted (quota or budget pressure)")
        # Tiered residency (ISSUE 14): SWAPIN/SWAPOUT-style transition
        # volume for the heat-based ladder (storage/residency.py);
        # tier occupancy (device rows in use, host/disk bytes) is a
        # set of render-time gauges the engine registers.
        self.residency_promotions = r.counter(
            "rtpu_residency_promotions",
            "sketches promoted back to a device row (host/disk tier "
            "→ device, through the prewarmed size-class pools)")
        self.residency_demotions = r.counter(
            "rtpu_residency_demotions",
            "sketches demoted from a device row to an exact host "
            "golden mirror (demoted is NOT degraded)")
        self.residency_spills = r.counter(
            "rtpu_residency_spills",
            "host mirrors spilled to CRC-framed per-object disk blobs")
        self.residency_loads = r.counter(
            "rtpu_residency_loads",
            "disk blobs loaded back into host mirrors (first touch of "
            "a DISK-resident sketch)")
        # Front door vectorization (ISSUE 6): pipelined command runs fused
        # into single engine launches, plus the per-connection response
        # cache for repeated identical reads inside one pipeline window.
        self.resp_fused_cmds = r.counter(
            "rtpu_resp_fused_cmds",
            "RESP commands absorbed into fused front-door runs, by family",
            ("family",))
        self.resp_fused_ops = r.counter(
            "rtpu_resp_fused_ops",
            "engine ops carried by fused front-door runs, by family",
            ("family",))
        self.resp_fused_runs = r.counter(
            "rtpu_resp_fused_runs",
            "fused front-door runs dispatched, by family", ("family",))
        self.resp_cache_hits = r.counter(
            "rtpu_resp_response_cache_hits",
            "pipelined replies served from the per-connection response "
            "cache")
        self.resp_cache_misses = r.counter(
            "rtpu_resp_response_cache_misses",
            "response-cache probes that executed the command")
        # Reactor front door (ISSUE 11): epoll event-loop ticks, how many
        # connections each tick found ready, and ops that fused into an
        # engine launch TOGETHER WITH ops from other connections (the
        # cross-connection batch-economics headline — within-connection
        # fusion is already counted by rtpu_resp_fused_ops).
        self.reactor_ticks = r.counter(
            "rtpu_reactor_ticks",
            "reactor event-loop ticks that processed at least one event")
        self.reactor_ready_conns = r.counter(
            "rtpu_reactor_ready_conns",
            "connections found ready across reactor ticks (avg per tick "
            "= this / rtpu_reactor_ticks)")
        self.cross_conn_fused_ops = r.counter(
            "rtpu_cross_conn_fused_ops",
            "engine ops fused into a launch together with ops from OTHER "
            "connections, by family", ("family",))
        # Per-core front door (ISSUE 17): K SO_REUSEPORT reactor
        # processes per node, an in-node slot→process map, and loopback
        # handoff legs over unix-domain sockets.  Per-worker series
        # federate through the existing plane — each worker process
        # serves its own /metrics, the parent's federation endpoint
        # labels them.
        self.frontdoor_processes = r.gauge(
            "rtpu_frontdoor_processes",
            "front-door worker processes sharing this node's listen "
            "port (1 = single-process door, incl. the no-SO_REUSEPORT "
            "fallback)")
        self.frontdoor_worker_index = r.gauge(
            "rtpu_frontdoor_worker_index",
            "this worker's index in the node's in-node slot->process "
            "map (0 in single-process mode)")
        self.frontdoor_handoffs = r.counter(
            "rtpu_frontdoor_handoffs",
            "commands routed across the in-node worker boundary, by "
            "kind (forward = whole command to one sibling, split = "
            "per-key multi-key split, fanout = broadcast-and-merge)",
            ("kind",))
        self.frontdoor_handoff_errors = r.counter(
            "rtpu_frontdoor_handoff_errors",
            "in-node handoff legs that failed (peer gone / corrupt "
            "stream / injected fault) and surfaced -HANDOFFBROKEN",
            ("kind",))
        self.frontdoor_peer_accepts = r.counter(
            "rtpu_frontdoor_peer_accepts",
            "handoff legs accepted from sibling workers on the in-node "
            "unix-domain listener")
        # Cluster mode (ISSUE 12): redirect volume by kind (the door
        # counts moved/ask/tryagain/crossslot/asking_served as it emits
        # or honors them; the slot-aware client counts
        # client_moved/client_ask as it follows them), slot-ownership
        # handoffs this process finalized, and the scatter/gather
        # client's fan-out (legs / batches = average nodes touched per
        # multi-slot batch).
        self.cluster_redirects = r.counter(
            "rtpu_cluster_redirects",
            "cluster redirects emitted by the door or followed by the "
            "slot-aware client, by kind", ("kind",))
        self.cluster_slot_migrations = r.counter(
            "rtpu_cluster_slot_migrations",
            "slot ownership handoffs finalized on this node (SETSLOT "
            "NODE closing an IMPORTING/MIGRATING state)")
        self.cluster_scatter_fanout = r.counter(
            "rtpu_cluster_scatter_fanout",
            "scatter/gather batches and the per-node pipeline legs they "
            "fanned out to, by unit", ("unit",))
        # Load-attribution plane (ISSUE 16): billing-grade device-time
        # split per (tenant, op) — label cardinality is bounded TWICE
        # (the loadmap folds cold tenants into "other" before the bump,
        # max_children backstops it), and the per-slot planes export as
        # render-time gauges over the top-N busiest slots only (a
        # 16384-label family would melt any scrape).
        self.tenant_device_us = r.counter(
            "rtpu_tenant_device_us",
            "device-side launch microseconds attributed by tenant and "
            "op (top-N tenants, cold ones fold into 'other')",
            ("tenant", "op"), max_children=256)
        self.loadmap = LoadMap()
        self.loadmap.tenant_device_us_family = self.tenant_device_us
        self.spans.loadmap = self.loadmap
        _lm = self.loadmap
        r.gauge_callback(
            "rtpu_loadmap_slot_ops",
            "commands accounted to the busiest slots (top-8 by ops — "
            "bounded export of the 16384-slot load vector)",
            lambda: {(str(s),): float(v) for s, v in _lm.top_slots(8)},
            labelnames=("slot",))
        r.gauge_callback(
            "rtpu_loadmap_sampled_keys",
            "keys sampled into the hot-key sketches at RESP ingress",
            _lm.sampled_keys)
        r.gauge_callback(
            "rtpu_loadmap_tracked_keys",
            "candidate keys currently monitored by the space-saving "
            "top-k",
            _lm.tracked_keys)
        # Replication + failover plane (ISSUE 18): stream/ack volume
        # counters plus offset/lag gauges.  The gauges read through
        # source callables the RESP door wires once the replication hub
        # or replica link exists — 0.0 until then, so the families are
        # present (and doc-tabled) on every process regardless of role.
        self.repl_acks = r.counter(
            "rtpu_repl_acks",
            "REPLCONF ACK frames accepted from replicas (primary side)")
        self.repl_fullresyncs = r.counter(
            "rtpu_repl_fullresyncs",
            "full resynchronizations served (snapshot + stream tail "
            "bootstrap) or performed (replica side)")
        self.repl_partial_resyncs = r.counter(
            "rtpu_repl_partial_resyncs",
            "partial resynchronizations (PSYNC CONTINUE on a matching "
            "replication id + backlog-covered offset)")
        self.repl_stream_records = r.counter(
            "rtpu_repl_stream_records",
            "journal records applied from the replication stream "
            "(replica side)")
        self.failover_elections = r.counter(
            "rtpu_failover_elections",
            "failover elections this node started as a candidate")
        self.failover_takeovers = r.counter(
            "rtpu_failover_takeovers",
            "slot takeovers this node performed after winning an "
            "election (or via manual FAILOVER promotion)")
        # Autonomous rebalancer (ISSUE 19).  `decisions` kinds: planned
        # (moves a wave scheduled), moved, failed, and the last-moment
        # vetoes skip_busy / skip_stale / skip_failover.
        self.rebalancer_decisions = r.counter(
            "rtpu_rebalancer_decisions",
            "rebalancer planning/execution decisions by kind",
            ("kind",))
        self.rebalancer_keys_moved = r.counter(
            "rtpu_rebalancer_keys_moved",
            "keys migrated by rebalancer-driven slot moves")
        self.rebalancer_migration_seconds = r.histogram(
            "rtpu_rebalancer_migration_seconds",
            "wall seconds per rebalancer-driven slot migration")
        self.rebalancer_imbalance_source = None  # wired by the agent
        r.gauge_callback(
            "rtpu_rebalancer_imbalance_ratio",
            "fleet imbalance (max node load / mean) from the planner's "
            "smoothed heat model; 1.0 = perfectly level",
            lambda: float(self.rebalancer_imbalance_source())
            if self.rebalancer_imbalance_source is not None else 1.0)
        # Fleet flight recorder + invariant doctor (ISSUE 20): the
        # control planes' causal event record (obs/events.py) and the
        # continuous protocol auditor (obs/doctor.py).  Kind label
        # cardinality is bounded by the events.KINDS catalog (rtpulint
        # RT015 rejects unregistered kind literals at lint time).
        self.events_emitted = r.counter(
            "rtpu_events_emitted",
            "flight-recorder events emitted, by kind (bounded by the "
            "events.KINDS catalog)", ("kind",))
        self.events_evicted = r.counter(
            "rtpu_events_evicted",
            "flight-recorder events evicted from the bounded ring "
            "(visible downstream as per-node seq gaps)")
        self.events = EventRing(
            counter=self.events_emitted,
            evicted_counter=self.events_evicted)
        self.doctor_sweeps = r.counter(
            "rtpu_doctor_sweeps",
            "invariant-doctor sweeps completed on this node (only the "
            "elected coordinator sweeps)")
        self.doctor_findings = r.counter(
            "rtpu_doctor_findings",
            "invariant findings raised by the doctor, by kind",
            ("kind",))
        self.doctor_canary_rtt_us = r.histogram(
            "rtpu_doctor_canary_rtt_us",
            "black-box canary round trip (WAIT-fenced write-then-read "
            "through the real client path)")
        self.repl_offset_source = None  # wired by the RESP door
        self.repl_lag_source = None
        r.gauge_callback(
            "rtpu_repl_offset",
            "replication offset: journal head seq on a primary, last "
            "applied stream seq on a replica",
            lambda: float(self.repl_offset_source())
            if self.repl_offset_source is not None else 0.0)
        r.gauge_callback(
            "rtpu_repl_lag_ops",
            "replica staleness in journal records (master_offset - "
            "applied; 0 on a primary)",
            lambda: float(self.repl_lag_source())
            if self.repl_lag_source is not None else 0.0)

    # -- instrumentation helpers (one call per batch, never per op) --------

    def record_resp_command(self, cmd: str, duration_s: float,
                            error: bool) -> None:
        self.resp_commands.inc((cmd,))
        if error:
            self.resp_errors.inc((cmd,))
        self.resp_latency.observe((cmd,), duration_s)

    def record_dispatch(self, method: str, nops: int, dur_s: float,
                        h2d_bytes: int = 0, h2d_puts: int = 0) -> None:
        self.dispatches.inc((method,))
        self.dispatch_ops.inc((method,), nops)
        self.dispatch_seconds.observe((method,), dur_s)
        if h2d_bytes:
            self.h2d_bytes.inc((method,), h2d_bytes)
        if h2d_puts:
            self.h2d_puts.inc((method,), h2d_puts)

    def encode_timer(self, kind: str) -> Timed:
        """``rtpu_encode_seconds{kind}`` + region ``rtpu:encode``."""
        return Timed(self.encode_seconds.child((kind,)), "rtpu:encode")

    def submit_timer(self, op: str) -> Timed:
        """``rtpu_submit_seconds{op}`` + region ``rtpu:submit:<op>``."""
        return Timed(self.submit_seconds.child((op,)), "rtpu:submit:" + op)

    def record_shard_counts(self, counts) -> None:
        for s, c in enumerate(counts):
            if c:
                self.shard_ops.inc((str(s),), int(c))

    def reset_command_stats(self) -> None:
        """CONFIG RESETSTAT: zero the RESP per-command families."""
        self.resp_commands.reset()
        self.resp_errors.reset()
        self.resp_latency.reset()

    def reset_op_stats(self) -> None:
        """Zero the span-derived families — benches call this after
        warmup so compile-era samples don't pollute the warm-path
        evidence view (op_stats / phase_stats).  Delegates to the
        recorder's PUBLIC reset() (ISSUE 13 satellite: reaching into
        ``spans._phase_hist`` etc. from here coupled the bench lifecycle
        to SpanRecorder privates); the trace ring shares the same
        lifecycle call."""
        self.spans.reset()
        self.trace.reset()

    # -- snapshot views ----------------------------------------------------

    def command_stats(self) -> dict:
        """{cmd: {calls, errors, usec, usec_per_call}} for INFO
        commandstats and client.get_metrics()."""
        out = {}
        errs = {lv: c.value for lv, c in self.resp_errors.items()}
        lat = dict(self.resp_latency.items())
        for (cmd,), c in self.resp_commands.items():
            calls = int(c.value)
            h = lat.get((cmd,))
            usec = int((h.sum if h is not None else 0.0) * 1e6)
            out[cmd] = {
                "calls": calls,
                "errors": int(errs.get((cmd,), 0)),
                "usec": usec,
                "usec_per_call": round(usec / calls, 2) if calls else 0.0,
            }
        return out

    def latency_stats(self) -> dict:
        """{cmd: {p50_us, p99_us, p999_us}} for INFO latencystats."""
        out = {}
        for (cmd,), c in self.resp_latency.items():
            if c.count == 0:
                continue
            p50, p99, p999 = self.resp_latency.percentiles(
                (cmd,), (50, 99, 99.9))
            out[cmd] = {
                "p50_us": p50 * 1e6,
                "p99_us": p99 * 1e6,
                "p999_us": p999 * 1e6,
            }
        return out

    def op_stats(self) -> dict:
        """{op: {ops, launches, p50_ms, p99_ms}} from the span
        histograms — the per-command latency view of the ENGINE (bench
        snapshots report this one)."""
        out = {}
        ops = {lv: c.value for lv, c in self.spans._ops.items()}
        for (op,), c in self.spans._total_hist.items():
            if c.count == 0:
                continue
            p50, p99 = self.spans._total_hist.percentiles((op,), (50, 99))
            out[op] = {
                "ops": int(ops.get((op,), 0)),
                "launches": int(c.count),
                "p50_ms": p50 * 1e3,
                "p99_ms": p99 * 1e3,
            }
        return out

    def phase_stats(self) -> dict:
        """{op: {phase: {launches, p50_ms, p99_ms}}} from the
        lifecycle-span phase histograms (coalesce_wait / host_stage /
        device_dispatch / d2h_fetch) — the warm-path evidence view:
        BENCH snapshots embed it so a latency regression is attributable
        to a specific phase from the JSON alone."""
        out: dict = {}
        h = self.spans._phase_hist
        for (op, phase), c in h.items():
            if c.count == 0:
                continue
            p50, p99 = h.percentiles((op, phase), (50, 99))
            out.setdefault(op, {})[phase] = {
                "launches": int(c.count),
                "p50_ms": round(p50 * 1e3, 3),
                "p99_ms": round(p99 * 1e3, 3),
            }
        return out

    def tenant_stats(self) -> dict:
        """{tenant: ops} aggregated over op types."""
        out: dict = {}
        for (tenant, _op), c in self.tenant_ops.items():
            out[tenant] = out.get(tenant, 0) + int(c.value)
        return out


__all__ = [
    "EventRing",
    "Family",
    "LatencyMonitor",
    "LoadMap",
    "MetricsRegistry",
    "Observability",
    "OpSpan",
    "SlowLog",
    "SlowLogEntry",
    "SpanRecorder",
    "Tracer",
]
