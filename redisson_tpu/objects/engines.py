"""Sketch engines: the backend behind BloomFilter/HyperLogLog/BitSet/CMS.

Two implementations of one interface, selected by
``Config.use_tpu_sketch()`` — the north-star mode switch:

- ``TpuSketchEngine``: tenant registry + size-class device pools +
  TpuCommandExecutor (stacked arrays, batched kernels).
- ``HostSketchEngine``: the golden NumPy models, playing the role the Redis
  server plays for the reference (→ SURVEY.md §2.2: the sketch math the
  client never implements).  It is also the honest comparison baseline for
  the benchmark configs.

Both consume identical host-side hash material (the object layer hashes
once with the shared murmur twins), so FPP/estimates agree bit-for-bit
between modes — the ≤2% FPP-drift gate reduces to kernel correctness.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Optional

import numpy as np

from redisson_tpu import chaos as _chaos
from redisson_tpu import overload as _ovl
from redisson_tpu.analysis import witness as _witness
from redisson_tpu.executor import LazyResult, TpuCommandExecutor
from redisson_tpu.objects.durability import SketchDurabilityMixin
from redisson_tpu.obs.spans import WaitRegionLock
from redisson_tpu.ops import golden
from redisson_tpu.tenancy import PoolKind, TenantRegistry
from redisson_tpu.tenancy.registry import class_words_for_bits
from redisson_tpu.utils import hashing


class ImmediateResult(LazyResult):
    """Host-engine results are already materialized."""

    def __init__(self, value):
        super().__init__(value)


class TopKStore:
    """Engine-shared heavy-hitter candidate tables (BASELINE config 5).

    Name-addressed: every CountMinSketch handle for ``name`` — from any
    number of client facades — sees ONE table (round-2 review flagged the
    per-instance dict: two handles to the same sketch disagreed).  The
    table holds candidate keys with their last-seen estimates, max-merged
    and pruned; ``top_k()`` re-estimates candidates on device for
    exactness, so the table only needs to not LOSE heavy keys."""

    def __init__(self):
        self._lock = _witness.named(threading.Lock(), "engine.topk")
        self._tables: dict[str, dict] = {}

    def configure(self, name: str, k: int) -> None:
        with self._lock:
            t = self._tables.get(name)
            if t is None:
                self._tables[name] = {"k": int(k), "cands": {}}
            else:
                t["k"] = max(t["k"], int(k))

    def track(self, name: str) -> int:
        with self._lock:
            t = self._tables.get(name)
            return 0 if t is None else t["k"]

    def offer(self, name: str, keys, estimates) -> None:
        """Max-merge a batch's post-update estimates.  Only the batch's
        heaviest 4k candidates are offered by callers (argpartition over
        the estimate stream), so the table stays small under 100M-event
        ingest."""
        import heapq

        with self._lock:
            t = self._tables.get(name)
            if t is None:
                return
            cands = t["cands"]
            for key, est in zip(keys, estimates):
                e = int(est)
                if cands.get(key, 0) < e:
                    cands[key] = e
            cap = 4 * max(t["k"], 16)
            if len(cands) > 2 * cap:
                keep = heapq.nlargest(cap, cands.items(), key=lambda kv: kv[1])
                t["cands"] = dict(keep)

    def candidates(self, name: str) -> list:
        with self._lock:
            t = self._tables.get(name)
            return [] if t is None else list(t["cands"])

    def drop(self, name: str) -> None:
        with self._lock:
            self._tables.pop(name, None)

    def rename(self, old: str, new: str) -> None:
        with self._lock:
            t = self._tables.pop(old, None)
            if t is not None:
                self._tables[new] = t

    # -- durability (data-only: snapshots + CMS dump blobs carry the
    # candidate tables — losing them on restore would forget every heavy
    # hitter even though the counters survive) ----------------------------

    # Candidate keys must round-trip with their ORIGINAL scalar type —
    # the codec encodes np.uint64(5) and 5 to different bytes (see
    # count_min_sketch.py offer note), so a type-collapsing export would
    # make restored top_k() re-estimate the wrong cells.
    _KEY_TAGS = {
        int: ("i", int),
        np.uint64: ("u8", int),
        np.uint32: ("u4", int),
        np.int64: ("i8", int),
        np.int32: ("i4", int),
        str: ("s", str),
    }
    _TAG_DECODE = {
        "i": int,
        "u8": np.uint64,
        "u4": np.uint32,
        "i8": np.int64,
        "i4": np.int32,
        "s": str,
        "b": bytes.fromhex,
    }
    MAX_K = 1 << 20  # prune-cap sanity bound for imported tables

    @classmethod
    def _encode_cands(cls, name: str, t: dict) -> dict:
        cands = []
        skipped = set()
        for k_, v_ in t["cands"].items():
            enc = cls._KEY_TAGS.get(type(k_))
            if enc is not None:
                cands.append([enc[0], enc[1](k_), int(v_)])
            elif isinstance(k_, bytes):
                cands.append(["b", k_.hex(), int(v_)])
            else:
                skipped.add(type(k_).__name__)
        if skipped:
            import warnings

            warnings.warn(
                f"top-K candidates of {name!r} with non-serializable key "
                f"types {sorted(skipped)} were not exported; they will "
                f"re-enter the table from future traffic"
            )
        return {"k": int(t["k"]), "cands": cands}

    @classmethod
    def _decode_cands(cls, d: dict) -> dict:
        """Strict decode of an UNTRUSTED table blob: unknown tags or
        malformed values raise ValueError (callers validate BEFORE any
        state mutation); k is clamped to the prune-cap sanity bound."""
        cands = {}
        for entry in d.get("cands", []):
            if not isinstance(entry, (list, tuple)) or len(entry) != 3:
                raise ValueError(f"bad topk entry: {entry!r}")
            tag, val, est = entry
            dec = cls._TAG_DECODE.get(tag)
            if dec is None:
                raise ValueError(f"bad topk key tag: {tag!r}")
            cands[dec(val)] = int(est)
        k = int(d.get("k", 0))
        if not 0 <= k <= cls.MAX_K:
            raise ValueError(f"topk k={k} out of range")
        return {"k": k, "cands": cands}

    def export_state(self, name: Optional[str] = None):
        """JSON-safe copy of one table (or all) for snapshots/dumps."""
        with self._lock:
            if name is not None:
                t = self._tables.get(name)
                return None if t is None else self._encode_cands(name, t)
            return {
                n: self._encode_cands(n, t) for n, t in self._tables.items()
            }

    @classmethod
    def decode_state(cls, state, name: Optional[str] = None):
        """Validate+decode an untrusted blob WITHOUT touching the store —
        restore paths call this before any state mutation, then install
        the returned value via import_decoded."""
        if name is not None:
            return cls._decode_cands(state) if state else None
        return {n: cls._decode_cands(d) for n, d in (state or {}).items()}

    def import_decoded(self, decoded, name: Optional[str] = None) -> None:
        with self._lock:
            if name is not None:
                self._tables.pop(name, None)  # never keep a ghost table
                if decoded:
                    self._tables[name] = decoded
                return
            for n, d in (decoded or {}).items():
                self._tables[n] = d

    def import_state(self, state, name: Optional[str] = None) -> None:
        self.import_decoded(self.decode_state(state, name), name)


class _ConcatLazy:
    """LazyResult adapter concatenating per-group results in op order —
    used when a mid-segment migration split one coalesced launch into
    consecutive per-pool launches (futures slice by [start, start+n)
    against the concatenation, which preserves op order)."""

    def __init__(self, parts):
        self._parts = parts
        self._done = None

    def result(self, timeout=None):
        # ``timeout`` accepted for signature parity with HintedFuture /
        # LazyResult (callers treat the future types interchangeably);
        # the per-part fetches are synchronous, so it is ignored.
        if self._done is None:
            self._done = np.concatenate([p.result() for p in self._parts])
            self._parts = None
        return self._done

    def get(self):
        return self.result()

    def done(self) -> bool:
        return self._done is not None


class _EpochGuard:
    """Entry+exit write-epoch bump around a mutating engine call (see
    cache/nearcache.py module doc: the entry bump retires stale serving
    the moment the write is in flight; the exit bump retires installs
    whose reads were captured inside the entry→submit window)."""

    __slots__ = ("_bump", "_name")

    def __init__(self, bump, name):
        self._bump = bump
        self._name = name

    def __enter__(self):
        self._bump(self._name)
        return self

    def __exit__(self, *exc):
        self._bump(self._name)
        return False


class _MappedFuture:
    """Future adapter applying a transform on .result()."""

    def __init__(self, fut, transform):
        self._fut = fut
        self._transform = transform

    def result(self, *a, **kw):
        return self._transform(self._fut.result(*a, **kw))

    def get(self):
        return self.result()

    def done(self):
        return self._fut.done()


class _DurableResult:
    """Ack gate for ``appendfsync=always`` (ISSUE 10): the caller's
    ``.result()`` returns only after the op's journal record is fsynced
    — group commit batches the fsyncs, so a burst of writers amortizes
    one disk barrier.  Wraps any result-like (HintedFuture, LazyResult,
    ImmediateResult, _MappedFuture)."""

    __slots__ = ("_res", "_journal", "_seq")

    def __init__(self, res, journal, seq):
        self._res = res
        self._journal = journal
        self._seq = seq

    def result(self, timeout=None):
        v = self._res.result(timeout)
        if not self._journal.wait_durable(self._seq, timeout):
            # A timed-out durability wait must NOT ack: returning the
            # value here would report a write durable that a crash can
            # still lose — the one lie this class exists to prevent.
            raise TimeoutError(
                f"journal record {self._seq} not fsynced within "
                f"{timeout}s (appendfsync=always durability fence)"
            )
        return v

    def get(self):
        return self.result()

    def done(self):
        inner = getattr(self._res, "done", None)
        return (
            (inner() if inner is not None else True)
            and self._journal.is_durable(self._seq)
        )

    def add_done_callback(self, fn):
        # Delegated un-gated: quota releases etc. key off the DEVICE
        # resolution; the durability gate applies to the ack (result()).
        self._res.add_done_callback(fn)


class TpuSketchEngine(SketchDurabilityMixin):
    def __init__(self, config):
        from redisson_tpu.executor.coalescer import BatchCoalescer
        from redisson_tpu.serve.metrics import Metrics

        self.config = config
        self._dist_initialized = False
        if config.tpu_sketch.coordinator_address:
            # Multi-host: join the JAX distributed runtime BEFORE any
            # device discovery (docs/MULTIHOST.md) — after this,
            # jax.devices() spans every process's chips and the sharded
            # executor's mesh covers them transparently.  Guarded: a
            # second engine in the process (client restart) must not
            # re-initialize.
            import jax

            already = getattr(jax.distributed, "is_initialized", None)
            if not (already is not None and already()):
                jax.distributed.initialize(
                    config.tpu_sketch.coordinator_address,
                    num_processes=config.tpu_sketch.num_processes,
                    process_id=config.tpu_sketch.process_id,
                )
                self._dist_initialized = True
        if config.tpu_sketch.num_shards > 1:
            from redisson_tpu.executor.sharded_executor import (
                ShardedTpuCommandExecutor,
            )

            self.executor = ShardedTpuCommandExecutor(config)
        else:
            self.executor = TpuCommandExecutor(config)
        self.registry = TenantRegistry(
            self.executor,
            initial_capacity=config.tpu_sketch.initial_tenants_per_class,
            dispatch_lock=self.executor._dispatch_lock,
        )
        self.metrics = Metrics()
        # Labeled observability bundle (obs package): per-tenant/op
        # counters, lifecycle spans, slowlog, health gauges.  Shared by
        # the coalescer, the executor, the client facade, and any RESP
        # server fronting this client.
        from redisson_tpu.obs import Observability

        self.obs = Observability(
            trace_sample_rate=getattr(config, "trace_sample_rate", 0.0),
            trace_max_spans=getattr(config, "trace_max_spans", 2048),
            latency_threshold_ms=getattr(
                config, "latency_monitor_threshold_ms", 0
            ),
        )
        self.executor.obs = self.obs
        # Near cache (ISSUE 4): the epoch-guarded host read tier — hot
        # single-key reads answer from host memory regardless of link
        # phase.  Built even when disabled so the epoch bookkeeping is
        # already coherent when a live `CONFIG SET nearcache yes` lands.
        # Multi-controller lockstep gate (same rule as mailbox_collect):
        # a cache hit SKIPS a device dispatch, and eviction order depends
        # on per-process-randomized hash() sharding — controllers would
        # diverge in which reads dispatch, breaking SPMD program order.
        from redisson_tpu.cache import ShardedLRUStore, SketchNearCache

        import jax

        ncc = config.tpu_sketch
        self.nearcache = SketchNearCache(
            ShardedLRUStore(
                max_bytes=ncc.nearcache_max_bytes,
                nshards=ncc.nearcache_shards,
                tenant_quota_bytes=ncc.nearcache_tenant_quota_bytes,
                on_evict=lambda tenant, nbytes: (
                    self.obs.nearcache_evictions.inc()
                ),
            ),
            obs=self.obs,
            enabled=ncc.nearcache and jax.process_count() == 1,
            max_batch=ncc.nearcache_max_batch,
        )
        if jax.process_count() > 1:
            # Refuse live re-enables too (CONFIG SET nearcache yes):
            # one controller turning it on alone would desync the fleet.
            self.nearcache.locked_off = True
        # Self-healing dispatch (ISSUE 3): per-(shard, opcode) circuit
        # breakers + per-executor health machine.  When a breaker opens,
        # affected sketches fail over to host golden mirrors
        # (objects/degraded.py) and reconcile back on close.
        from redisson_tpu.executor.health import DispatchHealth

        self.health = DispatchHealth(
            failure_threshold=config.tpu_sketch.breaker_failure_threshold,
            open_s=config.tpu_sketch.breaker_open_ms / 1000.0,
        )
        # Per-tenant fair load shedding (ISSUE 7): token-bucket rate
        # limits + in-flight quotas enforced at the submit boundary.
        # Built even when both limits are 0 (inactive) so a live
        # CONFIG SET tenant-rate-limit lands on a running engine.
        from redisson_tpu.tenancy.registry import TenantGovernor

        self.governor = TenantGovernor(
            rate_limit=config.tpu_sketch.tenant_rate_limit,
            burst=config.tpu_sketch.tenant_burst_ops,
            max_inflight=config.tpu_sketch.tenant_max_inflight,
            obs=self.obs,
        )
        self.health.reconcile_cb = self._reconcile_kind
        self.health.obs = self.obs  # LATENCY breaker-open events
        self._mirrors: dict = {}  # name -> degraded-mode OR demoted mirror
        self._mirror_lock = _witness.named(
            threading.RLock(), "engine.mirror"
        )
        # Bumped (under the lock) whenever reconcile writes mirrors back
        # to the device: a seed row read before the bump may predate the
        # write-back and must be discarded (see _degraded).
        self._mirror_epoch = 0
        # Chaos-injection accounting lands in this engine's registry
        # (module-level engine: the most recent engine owns the counter).
        # The closure is remembered so shutdown() can unhook it — a
        # module-global observer would otherwise pin this engine (and
        # its device pools) past shutdown.
        self._chaos_observer = (
            lambda point, kind: self.obs.faults_injected.inc((point, kind))
        )
        _chaos.set_observer(self._chaos_observer)
        self.topk = TopKStore()
        # Wired by the client to the grid store's ``exists`` — one logical
        # keyspace across both backends (WRONGTYPE on cross-backend reuse).
        self.foreign_exists = None
        self.coalescer = None
        if config.tpu_sketch.coalesce:
            import jax

            # Mailbox drains group launches by each controller's OWN
            # completion timing — divergent concat programs across
            # processes would break multi-controller lockstep, same as
            # the periodic snapshotter below.
            self.coalescer = BatchCoalescer(
                batch_window_us=config.tpu_sketch.batch_window_us,
                max_batch=config.tpu_sketch.max_batch,
                metrics=self.metrics,
                max_inflight=config.tpu_sketch.max_inflight,
                retry_attempts=config.retry_attempts,
                retry_interval_s=config.retry_interval_ms / 1000.0,
                max_queued_ops=config.tpu_sketch.max_queued_ops,
                adaptive_inflight=config.tpu_sketch.adaptive_inflight,
                min_inflight=config.tpu_sketch.min_inflight,
                adaptive_window=config.tpu_sketch.adaptive_window,
                min_window_us=config.tpu_sketch.min_window_us,
                max_window_us=config.tpu_sketch.max_window_us,
                group_collect=(
                    self.executor.collect_group
                    if config.tpu_sketch.mailbox_collect
                    and jax.process_count() == 1
                    else None
                ),
                obs=self.obs,
                retry_max_backoff_s=(
                    config.tpu_sketch.retry_max_backoff_ms / 1000.0
                ),
                retry_jitter=config.tpu_sketch.retry_jitter,
                health=self.health,
                max_batch_slow_phase=(
                    config.tpu_sketch.max_batch_slow_phase
                ),
                fetch_timeout_s=(
                    config.tpu_sketch.fetch_timeout_ms / 1000.0
                ),
            )
        else:
            # Direct-dispatch mode: the executor is the only recorder of
            # ops_total/batches_total (with a coalescer in front, the
            # coalescer records them — both would double-count).  Fixes
            # sharded/coalesce=False runs reporting zero ops.
            self.executor.metrics = self.metrics
        # AOT bucket pre-warming (executor/prewarm.py): a background
        # thread compiles the (opcode, bucket) jit ladder on pool attach
        # so serving-path ops never pay a first-touch compile.
        self.prewarmer = None
        self._prewarm_seen: set = set()
        if config.tpu_sketch.prewarm:
            from redisson_tpu.executor.prewarm import BucketPrewarmer

            self.prewarmer = BucketPrewarmer(
                self.executor,
                max_batch=config.tpu_sketch.max_batch,
                max_state_bytes=config.tpu_sketch.prewarm_max_state_bytes,
                obs=self.obs,
            )
        # Crash-safe durability tier (ISSUE 10): append-only op journal
        # + point-in-time recovery (durability/journal.py).  The commit
        # GATE makes one mutation's journal-append + dispatch atomic
        # against the snapshot's drain → cut → capture sequence: without
        # it a record could land before the cut while its device effect
        # lands after the capture — truncated from the journal AND
        # missing from the snapshot (a lost acked write).  A plain RLock
        # (not witness-named) on purpose: it is strictly the OUTERMOST
        # lock of every path that takes it (public mutation entry points
        # and snapshot(), both entered lock-free), so it can never
        # participate in an ordering cycle, and naming it would flag the
        # drains/dispatches the gated bodies legitimately perform.
        # Tiered sketch storage (ISSUE 14): the heat-based residency
        # ladder — device rows are a CACHE over host golden mirrors
        # over disk blobs (storage/residency.py).  Built BEFORE the
        # restore/recovery block below so a snapshot can reinstate
        # HOST/DISK tenants; the alloc gate and the background thread
        # arm AFTER recovery (replay must see the pre-crash tiers, not
        # race a budget enforcer).
        from redisson_tpu.storage import ResidencyManager

        self.residency = ResidencyManager(
            self, config.tpu_sketch, obs=self.obs
        )
        self.journal = None
        self._journal_replaying = False
        # Contended acquisitions are the host region rtpu:lock_wait.
        self._journal_gate = WaitRegionLock(threading.RLock(),
                                            "rtpu:lock_wait")
        # Snapshot serialization: SAVE, BGSAVE's thread, the periodic
        # snapshotter, BGREWRITEAOF and shutdown may all call snapshot()
        # concurrently — without one writer at a time, an OLDER capture
        # can overwrite a newer one AFTER the newer one already retired
        # journal segments (mark_snapshot), losing the acked tail; the
        # shared tmp paths would also interleave.  Plain Lock, strictly
        # outermost (ordering: snapshot lock → journal gate → engine
        # locks; no mutation path ever takes it).
        self._snapshot_lock = threading.Lock()
        self._restored_journal_seq = 0
        self._last_save_ts = 0.0
        self._register_health_gauges()
        # Checkpoint/resume (SURVEY.md §5): restore device state from the
        # configured snapshot dir, then recover the journal tail, then
        # arm periodic snapshots (strictly in that order — the
        # snapshotter must never run concurrently with replay).
        if config.snapshot_dir:
            self.restore_snapshot(config.snapshot_dir)
        if getattr(config, "journal_dir", None):
            self._journal_attach(config.journal_dir, recover=True)
        # Residency ladder goes LIVE only after recovery: creates past
        # the device budget now birth HOST-resident, and the
        # maintenance thread starts once a budget is armed.
        self.registry.alloc_gate = self.residency.device_full
        if (
            config.tpu_sketch.residency_device_rows > 0
            or config.tpu_sketch.residency_max_host_bytes > 0
        ):
            self.residency.start()
        if config.snapshot_dir:
            if config.snapshot_interval_s > 0:
                import jax

                if jax.process_count() > 1:
                    # The timer thread fires at independent wall-clock
                    # times per controller, and snapshot() dispatches
                    # device work — that breaks multi-controller lockstep
                    # (docs/MULTIHOST.md "Lockstep discipline").  Explicit
                    # snapshot() calls, issued at the same program point
                    # on every controller, remain supported.
                    import warnings

                    warnings.warn(
                        "periodic snapshots are disabled under multi-host: "
                        "call snapshot() explicitly at a coordinated point "
                        "on every controller (docs/MULTIHOST.md)"
                    )
                else:
                    self._start_snapshotter(
                        config.snapshot_dir, config.snapshot_interval_s
                    )

    def _register_health_gauges(self) -> None:
        """Executor-health gauges, sampled at scrape/snapshot time (ISSUE
        1 tentpole part 4): queue depth, in-flight window, completion
        backlog, tenant/pool occupancy, per-device memory."""
        reg = self.obs.registry
        c = self.coalescer
        if c is not None:
            reg.gauge_callback(
                "rtpu_coalescer_queued_ops",
                "ops queued ahead of the flush thread",
                lambda: c._queued_ops,
            )
            reg.gauge_callback(
                "rtpu_inflight_launches",
                "dispatched-but-uncollected launches",
                lambda: c._uncollected,
            )
            reg.gauge_callback(
                "rtpu_inflight_limit",
                "adaptive (AIMD) in-flight launch window",
                lambda: c._inflight_limit,
            )
            reg.gauge_callback(
                "rtpu_completion_backlog",
                "launches awaiting the completer thread",
                lambda: c._completions.qsize(),
            )
            reg.gauge_callback(
                "rtpu_flush_window_us",
                "live adaptive flush window",
                lambda: c.window_s * 1e6,
            )
            reg.gauge_callback(
                "rtpu_flush_merge_cap",
                "live pop-time merge cap (max_batch, or "
                "max_batch_slow_phase while the link phase is slow)",
                c.merge_cap,
            )
            reg.gauge_callback(
                "rtpu_admission_est_wait_us",
                "last admission-control queue-wait estimate",
                lambda: c.last_est_wait_s * 1e6,
            )
        if self.prewarmer is not None:
            reg.gauge_callback(
                "rtpu_prewarm_pending",
                "bucket warm tasks not yet compiled",
                self.prewarmer.pending,
            )
        # Self-healing dispatch (ISSUE 3): breaker + degradation gauges.
        reg.gauge_callback(
            "rtpu_breaker_state",
            "circuit state by shard/op (0 closed, 1 open, 2 half-open)",
            self.health.board.state_codes,
            labelnames=("shard", "op"),
        )
        reg.gauge_callback(
            "rtpu_degraded_objects",
            "sketches currently serving from the host golden mirror "
            "because a breaker is open (demoted-tier mirrors are NOT "
            "degraded and count in rtpu_residency_host_bytes instead)",
            lambda: max(
                0, len(self._mirrors) - self.residency.host_objects()
            ),
        )
        # Tiered residency (ISSUE 14): fast-tier occupancy + the host/
        # disk tier footprints (SWAPIN/SWAPOUT-style observability; the
        # promotion/demotion/spill/load counters live in the obs
        # bundle).
        reg.gauge_callback(
            "rtpu_residency_device_rows",
            "device rows in use across all sketch pools (the residency "
            "ladder's fast tier; compare residency_device_rows budget)",
            self.residency.device_rows_used,
        )
        reg.gauge_callback(
            "rtpu_residency_host_bytes",
            "host bytes held by demoted-tier golden mirrors",
            self.residency.host_bytes,
        )
        reg.gauge_callback(
            "rtpu_residency_disk_bytes",
            "bytes held by spilled per-object disk blobs",
            self.residency.disk_bytes,
        )
        # Near cache (ISSUE 4): live occupancy (hits/misses/evictions
        # are counters registered by the obs bundle itself).
        reg.gauge_callback(
            "rtpu_nearcache_bytes",
            "host bytes resident in the sketch near cache",
            self.nearcache.store.bytes,
        )
        reg.gauge_callback(
            "rtpu_nearcache_entries",
            "entries resident in the sketch near cache",
            self.nearcache.store.entries,
        )
        # Durability tier (ISSUE 10): journal lag + segment count.
        # Registered unconditionally (0 while journaling is off) so a
        # live CONFIG SET appendonly yes is visible without re-wiring.
        reg.gauge_callback(
            "rtpu_journal_lag_ops",
            "journal records appended but not yet fsynced",
            lambda: (
                0 if self.journal is None else self.journal.lag_ops()
            ),
        )
        reg.gauge_callback(
            "rtpu_journal_segments",
            "live journal segment files",
            lambda: (
                0 if self.journal is None
                else self.journal.stats()["segments"]
            ),
        )

        # One registry.stats() snapshot serves BOTH gauges per scrape:
        # stats() holds the tenancy lock (contended by the serving
        # path's try_create/lookup) while building the full dict, so the
        # short-TTL memo halves the scrape-time lock hold.
        import time as _time

        stats_memo = {"t": -1.0, "v": None}

        def _stats():
            now = _time.monotonic()
            if stats_memo["v"] is None or now - stats_memo["t"] > 0.2:
                stats_memo["v"] = self.registry.stats()
                stats_memo["t"] = now
            return stats_memo["v"]

        def _tenant_counts():
            return {
                (k,): v for k, v in _stats()["tenants_by_kind"].items()
            }

        def _pool_rows():
            out = {}
            for key, st in _stats()["pools"].items():
                kind = key[0]
                cls = "x".join(str(x) for x in key[1:]) or "-"
                out[(kind, cls, "used")] = st["used_rows"]
                out[(kind, cls, "capacity")] = st["capacity"]
            return out

        def _devmem():
            from redisson_tpu.serve.metrics import Profiler

            out = {}
            for dev, stats in Profiler.device_memory().items():
                for stat, v in (stats or {}).items():
                    if v is not None:
                        out[(dev, stat)] = v
            return out

        reg.gauge_callback(
            "rtpu_tenants", "registered sketch tenants by kind",
            _tenant_counts, labelnames=("kind",),
        )
        reg.gauge_callback(
            "rtpu_pool_rows", "size-class pool rows by kind/class/state",
            _pool_rows, labelnames=("kind", "class", "state"),
        )
        reg.gauge_callback(
            "rtpu_device_memory_bytes", "per-device memory stats",
            _devmem, labelnames=("device", "stat"),
        )

    def shutdown(self) -> None:
        _chaos.unset_observer(self._chaos_observer)
        self.health.shutdown()
        self.residency.shutdown()
        self._stop_snapshotter()
        self._stop_sweeper()
        if self.config.snapshot_dir:
            try:
                self.snapshot(self.config.snapshot_dir)
            except Exception:  # pragma: no cover — best-effort persistence
                pass
        # Journal close AFTER the final snapshot (which cut+retired the
        # covered segments): drain pending records + final fsync, so a
        # clean shutdown leaves a zero-replay journal.
        j = self.journal
        if j is not None:
            self.journal = None
            if self.coalescer is not None:
                self.coalescer.journal_lag_s = None
            try:
                j.close()
            except Exception:  # pragma: no cover — best-effort persistence
                pass
        if self.prewarmer is not None:
            self.prewarmer.shutdown()
        if self.coalescer is not None:
            self.coalescer.shutdown()
        if self._dist_initialized:  # pair with jax.distributed.initialize
            import jax

            try:
                jax.distributed.shutdown()
            except Exception:  # pragma: no cover — runtime already gone
                pass
            self._dist_initialized = False

    def _drain(self) -> None:
        """Direct state reads must observe all queued coalesced ops."""
        if self.coalescer is not None:
            self.coalescer.drain()

    def _nc_mutate(self, name: str, structural: bool = False):
        """Near-cache write discipline for a mutating op on ``name``:
        bump the write epoch at entry AND exit (structural ops bump the
        structural epoch too — they retire monotone positives).  Every
        path that can change the object's readable state must cross this
        (or drop_object/invalidate_all) — mirror-degraded, replicated,
        and sharded writes included, which it gets for free by wrapping
        the ENGINE entry points those paths all flow through."""
        nc = self.nearcache
        return _EpochGuard(
            nc.note_structural if structural else nc.note_write, name
        )

    def prewarm_wait(self, timeout=None) -> bool:
        """Block until the AOT bucket pre-warmer has compiled every
        scheduled ladder (True on drained; trivially True when pre-warm
        is off)."""
        if self.prewarmer is None:
            return True
        return self.prewarmer.wait_idle(timeout)

    # -- crash-safe durability tier (ISSUE 10): op journal -----------------

    def _journal_attach(self, jdir: str, recover: bool,
                        fresh: bool = False) -> None:
        """Open (and optionally recover) the op journal.  ``recover``
        replays the post-snapshot tail through the host golden engine
        into device rows (durability/recovery.py); ``fresh`` wipes any
        existing segments first (the live-enable path: pre-enable state
        is covered by the coordinating snapshot, stale segments from an
        earlier lineage must not replay on the next boot)."""
        from redisson_tpu.durability import OpJournal, replay_journal

        cfg = self.config
        j = OpJournal(
            jdir,
            fsync_policy=getattr(cfg, "journal_fsync", "everysec"),
            max_segment_bytes=getattr(
                cfg, "journal_max_segment_bytes", 64 << 20
            ),
            obs=self.obs,
            fresh=fresh,
        )
        if recover:
            n = replay_journal(self, j, self._restored_journal_seq)
            if n:
                self.obs.journal_replayed.inc((), n)
        self.journal = j
        if self.coalescer is not None:
            # Journal lag rides the admission estimate under ``always``
            # (a slow disk sheds deadline-carrying load instead of
            # queueing it unboundedly) — see coalescer.estimate_wait_s.
            self.coalescer.journal_lag_s = j.lag_s

    def journal_set_enabled(self, enabled: bool) -> None:
        """Live ``CONFIG SET appendonly yes|no``.  Enabling starts a
        FRESH journal lineage and, when a snapshot dir is configured,
        takes a coordinating snapshot so recovery = snapshot + tail
        (the Redis enable-appendonly-triggers-rewrite behavior);
        without one, only post-enable mutations are recoverable.
        Disabling closes the journal after a final drain+fsync."""
        if enabled:
            jdir = getattr(self.config, "journal_dir", None)
            if not jdir:
                raise ValueError(
                    "journal_dir is not configured (set Config.journal_dir "
                    "before enabling appendonly)"
                )
            with self._journal_gate:
                # Idempotency re-checked INSIDE the gate: two racing
                # enables must not both attach — the loser's fresh=True
                # wipe would orphan the winner's live segments and leak
                # a second writer on the same directory.
                if self.journal is not None:
                    return
                self._journal_attach(jdir, recover=False, fresh=True)
            if self.config.snapshot_dir:
                self.snapshot(self.config.snapshot_dir)
        else:
            with self._journal_gate:
                j, self.journal = self.journal, None
                if self.coalescer is not None:
                    self.coalescer.journal_lag_s = None
            if j is not None:
                j.close()

    def journal_set_policy(self, policy: str) -> None:
        """Live ``CONFIG SET appendfsync always|everysec|no``."""
        self.config.journal_fsync = policy
        j = self.journal
        if j is not None:
            j.set_policy(policy)

    def journal_fence(self, timeout=None) -> bool:
        """The WAIT fence: force an fsync covering every record appended
        so far and block until it lands (True; False on timeout).
        Trivially True with journaling off."""
        j = self.journal
        if j is None:
            return True
        return j.wait_durable(timeout=timeout)

    def _journal_rec(self, op: str, name: str, **fields) -> Optional[int]:
        """Append one ACCEPTED-mutation record; returns its seq, or None
        when journaling is off (or this is recovery replay — a recovery
        must never journal its own replay)."""
        j = self.journal
        if j is None or self._journal_replaying:
            return None
        rec = {"op": op, "name": name}
        rec.update(fields)
        return j.append(rec)

    def _durable(self, res, seq: Optional[int]):
        """Gate a result-like's ack on record durability under
        ``appendfsync=always`` (no-op under the other policies: their
        durability window is the fsync cadence, not the ack)."""
        j = self.journal
        if seq is None or j is None or j.policy != "always":
            return res
        return _DurableResult(res, j, seq)

    def _ack(self, value, seq: Optional[int]):
        """Durability fence for synchronously-returning mutations
        (delete/rename/expire/merge/...): under ``always`` the method
        returns — acks — only after its record is fsynced."""
        j = self.journal
        if seq is not None and j is not None and j.policy == "always":
            j.wait_durable(seq)
        return value

    def _commit(self, res, op: str, name: str, **fields):
        """Journal an accepted mutation and gate its ack: the one-call
        form for result-returning engine methods."""
        return self._durable(res, self._journal_rec(op, name, **fields))

    # -- graceful degradation (ISSUE 3): host golden-mirror failover -------

    def _degraded(self, entry) -> bool:
        """True when ``entry`` must serve from its host mirror.  Healthy
        fast path is two attribute reads and a branch — no lock, no dict
        probe — until the first breaker ever opens.

        Seeding a missing mirror runs OUTSIDE the mirror lock: the seed's
        drain barrier can wait out parked-segment backoffs and its
        read_row retries traverse the failing dispatch path (seconds),
        and every degraded op of every kind serializes on the one mirror
        lock — seeding under it turned a single-op-path failure into an
        engine-wide stall.  The install re-checks under the lock: a
        racing seeder's mirror wins, a reconcile that cleared the kind
        mid-seed routes back to the device, and a reconcile that WROTE
        mirrors back mid-seed (epoch bump) discards the possibly-stale
        row and retries — installing it would resurrect pre-reconcile
        state and lose acked writes on the next write-back.

        Residency ladder (ISSUE 14): the same boundary serves DEMOTED
        sketches — a HOST-resident entry's mirror answers here (no
        breaker, no degraded flag), a DISK-resident or born-cold entry
        loads its mirror first.  The membership probe is lock-free
        (dict probe, GIL-atomic): a stale True is re-checked by
        _mirror_call under the lock, and a promote racing a stale
        False repoints entry.row to a fully-written device row BEFORE
        dropping the mirror."""
        if entry.row < 0 and entry.name not in self._mirrors:
            self._ensure_resident(entry)
        if entry.name in self._mirrors:
            return True
        if not self.health.any_degraded:
            return False
        for _ in range(4):
            with self._mirror_lock:
                if entry.name in self._mirrors:
                    return True
                if not self.health.degraded_kind(entry.kind):
                    return False
                epoch = self._mirror_epoch
            row = self._seed_row(entry)
            with self._mirror_lock:
                if entry.name in self._mirrors:
                    return True
                if not self.health.degraded_kind(entry.kind):
                    return False
                if self._mirror_epoch != epoch:
                    continue  # reconciled mid-seed: row may be stale
                if row is None:
                    return False
                self._install_mirror(entry, row)
                return True
        return False  # flapping hard: let the device surface the failure

    def _ensure_resident(self, entry) -> None:
        """Row-less entry (DISK-resident, or born cold past the device
        budget): install its HOST mirror — from the CRC-checked blob,
        or from zeros for a never-touched tenant.  A corrupt/missing
        blob raises (the op fails typed; serving garbage state is the
        one thing a tier must never do)."""
        self.residency.load(entry.name)

    def _tier_row(self, entry, row0: int) -> int:
        """Resolve the device row for a READ dispatch that captured
        ``row0`` BEFORE its residency check and then got no mirror
        result.  Readers do not hold the journal gate, so a transition
        can interleave with their check→dispatch window:

        - a PROMOTE racing the check leaves row0 at -1 while entry.row
          is already live (promote repoints the row before dropping
          the mirror) — re-read it;
        - a DEMOTE racing it leaves row0 pointing at the QUARANTINED
          row, whose contents stay bit-identical to the pre-demotion
          state until a later maintenance cycle's post-drain reclaim —
          dispatching against it is linearizable (the read began
          before the demotion completed).

        Every read site must capture entry.row before its
        _serve_degraded/_degraded check and resolve through this
        helper — reading entry.row AFTER the check races the demote's
        row retirement."""
        return entry.row if row0 < 0 else row0

    def _install_residency_mirror(self, entry, row=None, mirror=None):
        """Install ``entry`` as HOST-resident from a row array or a
        ready-made mirror — the snapshot-restore / journal-writeback
        install path (engine init, or under the journal gate).
        Delegates to the residency manager, which owns the mirror
        install + host-bytes accounting in one place."""
        self.residency.install_host(entry, row=row, mirror=mirror)

    def _seed_row(self, entry):
        """Fetch the entry's device row for mirror seeding (no lock
        held).  Seeding itself needs a working read dispatch; under a
        partial fault schedule a few retries ride it out — if the device
        is truly unreachable, returns None and the op proceeds to the
        device (surfacing the typed failure instead of silently serving
        empty state)."""
        try:
            self._drain()
        except Exception:
            pass  # queued segments fail typed on their own futures
        for _ in range(4):
            try:
                return self.executor.read_row(entry.pool, entry.row)
            except Exception:
                continue
        return None

    def _install_mirror(self, entry, row):
        """Install ``entry``'s mirror from ``row`` (under the mirror
        lock) and register the kind's recovery probe: a real read
        dispatch against the degraded pool (exercises the full _locked
        path, chaos points included), driven by the health monitor while
        the breaker is open."""
        from redisson_tpu.objects.degraded import mirror_for_entry

        self._mirrors[entry.name] = mirror_for_entry(entry, row)
        pool, prow = entry.pool, entry.row
        self.health.ensure_probe(
            entry.kind,
            lambda: self.executor.read_row(pool, prow),
        )

    def _mirror_call(self, entry, nops: int, fn):
        """Apply a degraded-mode or demoted-tier op to the entry's
        mirror (serialized by the mirror lock) and account it; returns
        an ImmediateResult.  Demoted is NOT degraded: a residency
        mirror's serves count to the host tier, never to
        rtpu_degraded_ops."""
        with self._mirror_lock:
            mirror = self._mirrors.get(entry.name)
            if mirror is None:  # reconciled/promoted between check+apply
                return None
            out = fn(mirror)
            demoted = getattr(mirror, "residency", None) is not None
            if demoted:
                # Under the mirror lock: += is a read-modify-write and
                # every demoted serve already holds this lock.
                self.residency.host_serves += nops
        if not demoted:
            self.obs.degraded_ops.inc((entry.kind,), nops)
        return ImmediateResult(out)

    def _serve_degraded(self, entry, nops: int, fn):
        """The failover boundary every engine method crosses: the
        mirror's ImmediateResult when ``entry`` serves degraded, else
        None (the op proceeds to the device).  One helper, so a missing
        failover is a greppable hole, not a silent one — every method
        that touches ``entry``'s row must call this (or _host_row) first
        or acked state diverges from what reconcile writes back."""
        if self._degraded(entry):
            return self._mirror_call(entry, nops, fn)
        return None

    def _host_row(self, entry) -> np.ndarray:
        """``entry``'s current truth in device-row layout: its mirror's
        encoding while one is live (the device row is stale during
        degradation), else the device row itself.  Serves merge sources
        and DUMP during degradation (and the demoted/spilled tiers —
        a DISK-resident entry loads its mirror first)."""
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        if row0 < 0 and entry.name not in self._mirrors:
            self._ensure_resident(entry)
        if self._mirrors:
            with self._mirror_lock:
                mirror = self._mirrors.get(entry.name)
                if mirror is not None:
                    return np.asarray(mirror.encode(entry.pool.row_units))
        self._drain()
        return np.asarray(
            self.executor.read_row(entry.pool, self._tier_row(entry, row0))
        )

    def _reconcile_kind(self, kind: str) -> bool:
        """Breaker-close hook (health.reconcile_cb): write every mirrored
        row of ``kind`` back to the device, then drop the mirrors — the
        device resumes from exactly the state the mirror served.  False
        (stay degraded, breaker re-opens) if any write fails."""
        t0 = time.monotonic()
        try:
            return self._reconcile_kind_inner(kind)
        finally:
            # LATENCY "reconcile" event (ISSUE 13): the write-back stall
            # every op of this kind rode out, visible next to
            # fsync-stall/breaker-open in LATENCY LATEST.
            lat = self.obs.latency
            if lat.threshold_ms > 0:
                lat.record(
                    "reconcile", (time.monotonic() - t0) * 1e3
                )

    def _reconcile_kind_inner(self, kind: str) -> bool:
        with self._mirror_lock:
            # Residency mirrors (ISSUE 14) are NOT breaker state: a
            # demoted sketch has no device row to write back to, and
            # its mirror stays the truth after the breaker closes.
            names = [
                n for n, m in self._mirrors.items()
                if m.kind == kind
                and getattr(m, "residency", None) is None
            ]
            for n in names:
                mirror = self._mirrors[n]
                entry = self.registry.lookup(n)
                if entry is None:  # deleted while degraded
                    del self._mirrors[n]
                    continue
                # Size to the entry's CURRENT pool: a degraded-window
                # bitset grow may have migrated it to a larger class.
                row = mirror.encode(entry.pool.row_units)
                try:
                    for r in self._entry_rows(entry):
                        # rtpulint: disable=RT001 write-back MUST hold the mirror lock: a degraded op interleaving between write-back and mirror drop would apply to a mirror about to be discarded (lost acked write); the degraded flag clears atomically with the mirrors below
                        self.executor.write_row(entry.pool, r, row)
                except Exception:
                    return False
                del self._mirrors[n]
            # Device rows changed under any in-flight seeder: its row
            # snapshot may predate the write-backs above (see _degraded).
            self._mirror_epoch += 1
            # Still under the mirror lock: drop the degraded flag
            # atomically with the mirrors, so no serving thread can see
            # "kind degraded, mirror missing" and seed an orphan mirror
            # that outlives the recovery (permanent split-brain).
            self.health.clear_degraded(kind)
        return True

    def _submit(self, key, dispatch, arrays, nops, pool_key=None, meta=None,
                tenant=None):
        from redisson_tpu.executor.coalescer import HintedFuture, _op_label

        # ``tenant`` rides the segment as an appended (tenant, nops)
        # tuple; the coalescer's COMPLETER thread turns it into the
        # per-tenant counters, so this producer path pays no counter
        # lock (the ≤10% submit-overhead guard in test_observability.py).
        #
        # Overload control plane (ISSUE 7): the ambient deadline (RESP
        # ingress stamp or client.op_deadline scope) rides the op into
        # the coalescer — admission control + queue shedding there, the
        # residual budget on the returned future's .result().  The
        # tenant governor sheds over-quota tenants HERE, before the op
        # can cost anyone else queue wait.
        deadline = _ovl.current_deadline()
        gov = self.governor
        governed = (
            gov is not None and tenant is not None and gov.active
        )
        if governed:
            gov.admit(tenant, nops)  # raises TenantThrottledError
        try:
            fut = self.coalescer.submit(
                key, dispatch, arrays, nops, pool_key=pool_key, meta=meta,
                tenant=tenant, deadline=deadline,
            )
        except BaseException:
            if governed:
                gov.release(tenant, nops)
            raise
        if governed and gov.max_inflight > 0:
            fut.add_done_callback(lambda _f: gov.release(tenant, nops))
        return HintedFuture(
            fut, self.coalescer, deadline=deadline, op=_op_label(key),
            nops=nops,
        )

    def _prewarm_keyed(self, pool, k: int, L: int, blocks, lengths) -> None:
        """Register device-hash warm ladders for an observed codec
        signature (lane count L + trim depth Lt + const-length flag are
        jit-key components only real key bytes reveal).  Called once per
        coarse (pool, k, L) signature — the caller's seen-set gate keeps
        the trim/const scans below off the per-submit hot path."""
        from redisson_tpu.executor import prewarm

        Lt = self.executor._trim_lanes(blocks)[0].shape[1]
        const = lengths.ndim == 0 or bool(np.all(lengths == lengths[0]))
        if getattr(self.executor, "supports_runs_metadata", False):
            self.prewarmer.register(
                pool, ("bloom_mixkr", k, L, Lt, const),
                prewarm.warm_bloom_mixed_keys_runs(k, L, Lt, const),
            )
        self.prewarmer.register(
            pool, ("bloom_mixk", k, L, Lt),
            prewarm.warm_bloom_mixed_keys(k, L, Lt),
        )

    # -- generic -----------------------------------------------------------

    def exists(self, name: str) -> bool:
        return self._live_lookup(name) is not None

    def delete(self, name: str) -> bool:
        import time as _time

        # detach-then-zero-then-free: only one concurrent deleter (user
        # call, expiry sweeper, or lazy-expiry reader) wins the pop, and
        # the row is reusable only after it is zeroed — a stale deleter
        # can never zero a row already reallocated to a new object.
        # Epoch BEFORE detach: a change_topology completing between
        # detach and the epoch read would return this entry's rows to the
        # rebuilt free list AND bump the epoch — reading the bumped value
        # would defeat _reap_rows' stale-topology guard and double-free.
        with self._journal_gate:
            pre_pool = self.registry.lookup(name)
            pre_epoch = pre_pool.pool.topology_epoch if pre_pool else 0
            entry = self.registry.detach(name)
            if entry is None:
                return False
            seq = self._journal_rec("obj.del", name)
            # An expired-but-unswept entry is already logically gone: free
            # the row, but report False (Redis DEL on an expired key).
            # Checked inline — _live_lookup would recurse through
            # _expire_if_due.
            was_expired = (
                entry.expire_at is not None
                and _time.time() >= entry.expire_at
            )
            epoch = pre_epoch if pre_pool and pre_pool.pool is entry.pool \
                else entry.pool.topology_epoch
            self._drain()
            self._reap_rows(entry.pool, self._entry_rows(entry), epoch)
            self.topk.drop(name)
            # Structural epoch advance + entry drop: a successor object
            # under this name continues the epoch sequence, so an
            # in-flight read of the OLD object can never install as fresh.
            self.nearcache.drop_object(name)
            if self._mirrors:
                with self._mirror_lock:
                    self._mirrors.pop(name, None)
            # Residency state dies with the object: heat, host-bytes
            # accounting, and the disk blob (retired into blob GC).
            self.residency.drop(name)
            result = not was_expired
        # Durability fence OUTSIDE the gate: blocking on the fsync while
        # holding it would serialize every writer behind one barrier
        # (group commit amortizes exactly because waiters overlap).
        return self._ack(result, seq)

    def rename(self, old: str, new: str) -> bool:
        with self._journal_gate:
            if old == new or self._live_lookup(old) is None:
                return False
            self._guard_foreign(new)
            self._drain()
            # Atomic rename FIRST: if the source vanished since the check
            # (expiry race), the destination must be left untouched.  The
            # displaced dest is zeroed before its row becomes reusable.
            ok, dest = self.registry.rename_detach_dest(old, new)
            if not ok:
                return False
            seq = self._journal_rec("obj.rename", old, new=new)
            if dest is not None:
                self._reap_rows(
                    dest.pool, self._entry_rows(dest),
                    dest.pool.topology_epoch,
                )
            self.topk.rename(old, new)
            # Both names change identity: drop entries + structural bumps.
            self.nearcache.drop_object(old)
            self.nearcache.drop_object(new)
            if self._mirrors:
                with self._mirror_lock:
                    self._mirrors.pop(new, None)
                    m = self._mirrors.pop(old, None)
                    if m is not None:
                        self._mirrors[new] = m
            # Residency state follows the rename (heat, host-bytes,
            # disk-blob index; the displaced dest's blob retires).
            self.residency.rename(old, new)
        return self._ack(True, seq)  # fence outside the gate (see delete)

    def names(self, kind=None):
        for e in self.registry.entries():
            if e.expire_at is not None:
                self._expire_if_due(e)
        return self.registry.names(kind)

    def params(self, name: str) -> Optional[dict]:
        entry = self._live_lookup(name)
        return None if entry is None else entry.params

    def _require(self, name: str, kind: str):
        entry = self._lookup_kind(name, kind)
        if entry is None:
            raise RuntimeError(f"{kind} object {name!r} is not initialized")
        # Per-tenant call counter: covers every op path (coalesced or
        # direct) at one inc per API call.
        self.obs.tenant_calls.inc((name, kind))
        return entry

    def _lookup_kind(self, name: str, kind: str):
        """None if absent/expired; TypeError (WRONGTYPE analog) on kind
        mismatch."""
        entry = self._live_lookup(name)
        if entry is not None and entry.kind != kind:
            raise TypeError(f"object {name!r} holds a {entry.kind}, not a {kind}")
        if entry is not None:
            # Residency heat feed (ISSUE 14): every read and write path
            # resolves its entry here (or via the ensure paths, which
            # also touch) — one decayed-counter bump per API call, the
            # same choke points the near-cache epoch hooks mark.
            self.residency.touch(name)
        return entry

    def _guard_foreign(self, name: str) -> None:
        """Cross-backend WRONGTYPE: creating a sketch under a name the data
        grid holds is an error, not a shadow object.  ``foreign_exists``
        is the grid's lock-free probe (see client.py wiring)."""
        if (
            self.foreign_exists is not None
            and self.registry.lookup(name) is None
            and self.foreign_exists(name)
        ):
            raise TypeError(
                f"object {name!r} is held by the data grid (WRONGTYPE)"
            )

    def probe(self, name: str) -> bool:
        """Lock-free-ish existence probe for the grid's guard: takes only
        the registry's leaf lock, never engine/store locks, and never
        mutates (no expiry reap)."""
        import time as _time

        entry = self.registry.lookup(name)
        return entry is not None and (
            entry.expire_at is None or _time.time() < entry.expire_at
        )

    # -- bloom read replication (SURVEY §2.4 replication row / the
    # ReadMode.SLAVE analog): a hot tenant's row copies to every shard;
    # reads spread round-robin across copies, writes broadcast to all ----

    def bloom_replicate(self, name: str) -> bool:
        """Replicate a bloom filter's row to every mesh shard.  No-op
        (False) on the single-device executor — there is nothing to
        spread reads across.

        Ordering vs concurrent writers (bloom bits only ever turn ON, so
        OR-merge makes this safe): the replica rows are published FIRST
        (new writers broadcast from then on, landing bits in the fresh
        rows), THEN queued primary-only writes drain, THEN the primary is
        OR-merged into each replica — a broadcast bit is never erased and
        a drained primary bit always reaches every copy.  The drain+merge
        runs twice, closing writers that captured the pre-publish state
        but had not yet submitted at the first drain."""
        S = getattr(self.executor, "S", 1)
        if S <= 1:
            return False
        entry = self._lookup_kind(name, PoolKind.BLOOM)
        if entry is None:
            raise RuntimeError(f"bloom filter {name!r} is not initialized")
        if entry.row < 0:
            # Replication spreads DEVICE rows across shards; promote
            # the demoted/spilled filter back to the fast tier first.
            if not self.residency.promote(name):
                raise RuntimeError(
                    f"bloom filter {name!r} could not promote to the "
                    f"device tier for replication"
                )
        # Topology change for this object's reads: defensively retire
        # every cached entry (structural bump) while replicas publish.
        self.nearcache.note_structural(name)
        with self.registry._lock:
            if entry.replica_rows:
                return True
            replicas = [None] * S
            replicas[entry.row % S] = entry.row
            for s in range(S):
                if replicas[s] is None:
                    replicas[s] = entry.pool.alloc_row_with_residue(s, S)
            entry.replica_rows = replicas  # published: writers broadcast now
        for _ in range(2):
            self._drain()
            for r in replicas:
                if r != entry.row:
                    # replica |= primary (device-side, serialized with all
                    # dispatches by the executor lock; rows are uint32
                    # bitmaps, so the bitset OR kernel applies verbatim).
                    self.executor.bitset_bitop(
                        entry.pool, r, [r, entry.row], "or"
                    )
        return True

    def bloom_is_replicated(self, name: str) -> bool:
        entry = self._lookup_kind(name, PoolKind.BLOOM)
        return bool(entry is not None and entry.replica_rows)

    def _bloom_expand_ops(self, entry, B: int, is_add):
        """(rows[B'], expand_idx[B'], primary_pos[B]) for a replicated
        entry: writes fan out to every replica (results identical on all
        copies — every write reaches every copy, so any one stands in);
        reads rotate across replicas.  ``expand_idx`` maps each expanded
        op back to its source op (for gathering the other columns)."""
        replicas = np.asarray(entry.replica_rows, np.int32)
        S = len(replicas)
        base = getattr(self, "_rr_counter", 0)
        self._rr_counter = base + B  # benign race: balance, not correctness
        is_add = np.asarray(is_add, bool)
        # Vectorized expansion (this is the dispatch hot path): each add
        # becomes S consecutive slots (replica 0..S-1), each read one slot.
        counts = np.where(is_add, S, 1)
        primary_pos = np.zeros(B, np.int64)
        np.cumsum(counts[:-1], out=primary_pos[1:])
        expand_idx = np.repeat(np.arange(B, dtype=np.int64), counts)
        ranks = np.arange(len(expand_idx), dtype=np.int64) - primary_pos[expand_idx]
        rows = np.where(
            is_add[expand_idx],
            replicas[ranks % S],
            replicas[(base + expand_idx) % S],
        ).astype(np.int32)
        return rows, expand_idx, primary_pos

    # -- bloom -------------------------------------------------------------

    def bloom_try_init(self, name, expected_insertions, false_probability) -> bool:
        m = golden.optimal_num_of_bits(
            expected_insertions, false_probability,
            max_bits=getattr(self.config.tpu_sketch, "max_bloom_bits",
                             golden.MAX_BLOOM_BITS),
        )
        k = golden.optimal_num_of_hash_functions(expected_insertions, m)
        params = {
            "size": m,
            "hash_iterations": k,
            "expected_insertions": expected_insertions,
            "false_probability": false_probability,
        }
        with self._journal_gate:
            self._live_lookup(name)  # reap an expired holder before tryInit
            self._guard_foreign(name)
            entry, created = self.registry.try_create(
                name, PoolKind.BLOOM, (class_words_for_bits(m),), params
            )
            # Journaled only when the create WON (replay of a lost race
            # must not re-parameterize the incumbent).
            seq = self._journal_rec(
                "bloom.init", name,
                ei=int(expected_insertions), fp=float(false_probability),
            ) if created else None
        if self.prewarmer is not None:
            from redisson_tpu.executor import prewarm

            # Pool attach → compile the hashed mixed-kernel ladder in the
            # background (the keyed/device-hash ladders register on first
            # sight of a codec signature, _bloom_submit_mixed_keys).
            self.prewarmer.register(
                entry.pool, ("bloom_mixed", k), prewarm.warm_bloom_mixed(k)
            )
        return self._ack(created, seq)

    def _bloom_reduce(self, entry, H1, H2):
        m = entry.params["size"]
        return hashing.km_reduce_mod(H1, H2, m)

    def _replication_fence(self, entry, saw_replicas, redispatch) -> None:
        """Close the writer-vs-set_replicated race: a writer that read
        ``replica_rows`` as unset and SUBMITTED before the publish is
        reached by bloom_replicate's drain+merge; a writer whose submit
        lands after the merge re-checks here (post-submit) and, seeing
        the publish, re-dispatches the same ops as a broadcast.  Bloom
        bits only turn ON, so the redundant re-write is idempotent and
        the original future's results stay valid."""
        if not saw_replicas and entry.replica_rows:
            # The primary write already applied: this broadcast
            # COMPLETES an acked write, so it must never shed on the
            # caller's deadline (neither the direct _locked shed nor
            # the coalescer's submit/queue shed) — a shed here leaves
            # replicas diverged from the primary and rotating reads
            # flapping.  The explicit None frame shadows any ambient
            # deadline for exactly this redispatch.
            with _ovl.deadline_scope(None):
                redispatch()

    def _bloom_dispatch_hashed(self, entry, h1m, h2m, is_add) -> LazyResult:
        """One mixed-kernel dispatch for hashed ops, honoring replication:
        replicated entries expand (writes fan to every copy, reads rotate)
        and results gather back to per-source-op shape."""
        m, k = entry.params["size"], entry.params["hash_iterations"]
        B = len(h1m)
        is_add = np.asarray(is_add, bool)
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(
            entry, B, lambda mir: mir.mixed(h1m, h2m, is_add)
        )
        if res is not None:
            return res
        orig = (h1m, h2m, is_add)
        saw_replicas = bool(entry.replica_rows)
        if saw_replicas:
            rows, eidx, ppos = self._bloom_expand_ops(entry, B, is_add)
            h1m, h2m, is_add = h1m[eidx], h2m[eidx], is_add[eidx]
            gather = lambda v: v[ppos]  # noqa: E731
        else:
            rows = np.full(B, self._tier_row(entry, row0), np.int32)
            gather = None
        m_arr = np.full(len(rows), m, np.uint32)
        pool = entry.pool
        if self.coalescer is not None:
            # Adds and contains share ONE segment per (pool, k) — the
            # combined kernel keeps exact arrival-order semantics while
            # mixed traffic coalesces instead of fragmenting (config 4).
            fut = self._submit(
                ("bloom_mix", id(pool), k),
                lambda cols: self.executor.bloom_mixed(
                    pool, cols[0], cols[1], k, cols[2], cols[3], cols[4]
                ),
                (rows, m_arr, h1m, h2m, is_add),
                len(rows),
                pool_key=id(pool),
                tenant=entry.name,
            )
        else:
            fut = self.executor.bloom_mixed(
                pool, rows, m_arr, k, h1m, h2m, is_add
            )
        if bool(np.any(orig[2])):
            self._replication_fence(
                entry,
                saw_replicas,
                lambda: self._bloom_dispatch_hashed(entry, *orig),
            )
        return fut if gather is None else _MappedFuture(fut, gather)

    def bloom_add(self, name, H1, H2) -> LazyResult:
        with self._nc_mutate(name), self._journal_gate:
            entry = self._require(name, PoolKind.BLOOM)
            h1m, h2m = self._bloom_reduce(entry, H1, H2)
            m, k = entry.params["size"], entry.params["hash_iterations"]
            if (
                not self.config.tpu_sketch.exact_add_semantics
                and not entry.replica_rows
                # Degraded: route through the hashed path's mirror
                # failover instead of hitting the dead device via the
                # fast-add st dispatch.
                and not self._degraded(entry)
            ):
                # Fast single-tenant bulk path dispatches immediately —
                # but only after queued coalesced ops flush, so a
                # contains submitted *before* this add can never observe
                # its writes (arrival-order contract of the coalescer
                # docstring).
                self._drain()
                res = self.executor.bloom_add_fast_st(
                    entry.pool, entry.row, m, k, h1m, h2m
                )
                self._replication_fence(
                    entry,
                    False,
                    lambda: self._bloom_dispatch_hashed(
                        entry, h1m, h2m, np.ones(len(H1), bool)
                    ),
                )
            else:
                res = self._bloom_dispatch_hashed(
                    entry, h1m, h2m, np.ones(len(H1), bool)
                )
            # Journaled PRE-reduce (raw twins): replay re-reduces against
            # the entry's params, same as the live path.
            return self._commit(
                res, "bloom.add", name,
                h1=np.asarray(H1), h2=np.asarray(H2),
            )

    def bloom_contains(self, name, H1, H2) -> LazyResult:
        # Epoch capture BEFORE entry resolution: a delete racing the
        # lookup bumps epochs in between, and a late capture would tag
        # the old object's results as fresh for its successor.
        nc = self.nearcache
        captured = nc.epochs(name)
        entry = self._require(name, PoolKind.BLOOM)
        if nc.active(len(H1)):
            H1a, H2a = np.asarray(H1), np.asarray(H2)
            return nc.lookup_batch(
                "bloom", name, nc.hashed_keys(H1a, H2a), np.bool_,
                lambda idx: self._bloom_contains_dispatch(
                    entry,
                    H1a if idx is None else H1a[idx],
                    H2a if idx is None else H2a[idx],
                ),
                monotone=True, captured=captured,
            )
        return self._bloom_contains_dispatch(entry, H1, H2)

    def _bloom_contains_dispatch(self, entry, H1, H2) -> LazyResult:
        h1m, h2m = self._bloom_reduce(entry, H1, H2)
        m, k = entry.params["size"], entry.params["hash_iterations"]
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        if (
            self.coalescer is not None
            or entry.replica_rows
            or self._degraded(entry)  # hashed path serves the mirror
        ):
            return self._bloom_dispatch_hashed(
                entry, h1m, h2m, np.zeros(len(H1), bool)
            )
        return self.executor.bloom_contains_st(
            entry.pool, self._tier_row(entry, row0), m, k, h1m, h2m
        )

    def bloom_count(self, name) -> LazyResult:
        nc = self.nearcache
        captured = nc.epochs(name)  # before entry resolution, see contains
        entry = self._require(name, PoolKind.BLOOM)
        if nc.active(1):
            return nc.lookup_scalar(
                "bloom", name, ("count",),
                lambda: self._bloom_count_dispatch(entry),
                captured=captured,
            )
        return self._bloom_count_dispatch(entry)

    def _bloom_count_dispatch(self, entry) -> LazyResult:
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(entry, 1, lambda mir: mir.count())
        if res is not None:
            return res
        self._drain()
        return self.executor.bloom_count(
            entry.pool, self._tier_row(entry, row0),
            entry.params["size"], entry.params["hash_iterations"]
        )

    # Encoded entry points: the object layer hands down raw codec lanes and
    # each engine decides where to hash.  On the direct single-device path
    # the hash + 64-bit mod run in-kernel (ops/fastpath.py device-hash
    # path, bit-identical to the host pipeline); coalesced/sharded paths
    # hash on the host as before.

    def _runs_dispatch(self, pool, k):
        """Flush-time dispatch for the run-length mixed path: folds the
        segment's per-chunk metas into per-RUN metadata arrays (row, m,
        is_add once per chunk + cumulative starts) and ships them with the
        concatenated key blocks (executor.bloom_mixed_keys_runs).  Key
        lengths collapse to one scalar when every chunk is const-length."""

        def dispatch(cols, metas):
            C = len(metas)
            run_rows = np.empty(C, np.int32)
            run_m = np.empty(C, np.uint32)
            run_flags = np.empty(C, np.bool_)
            starts = np.zeros(C + 1, np.int32)
            const_val = None
            all_const = True
            for i, (nops, (row, m, flag, ln)) in enumerate(metas):
                run_rows[i] = row
                run_m[i] = m
                run_flags[i] = flag
                starts[i + 1] = starts[i] + nops
                if isinstance(ln, (int, np.integer)):
                    if const_val is None:
                        const_val = int(ln)
                    elif const_val != int(ln):
                        all_const = False
                else:
                    all_const = False
            if all_const:
                lengths = np.uint32(0 if const_val is None else const_val)
            else:
                lengths = np.concatenate(
                    [
                        np.full(nops, ln, np.uint32)
                        if isinstance(ln, (int, np.integer))
                        else np.asarray(ln, np.uint32)
                        for nops, (_, _, _, ln) in metas
                    ]
                )
            if (
                not getattr(self.executor, "supports_runs_metadata", False)
                or C > 1024
            ):
                # Two reasons to expand the runs host-side and take the
                # per-op-array path: (1) the executor changed under a
                # queued segment (live change_topology swaps in a
                # sharded executor, which has no runs kernel) — rows are
                # topology-stable, so the queued ops stay valid
                # verbatim; (2) a degenerate many-tiny-chunk segment
                # with >1024 runs — capping C here pins the runs
                # kernel's compiled Cp space to exactly {1024}, which is
                # what the AOT pre-warmer compiles (a bigger Cp would be
                # a first-touch compile ON the serving path after
                # prewarm_wait reported a warmed cache).
                B = int(starts[-1])
                rows = np.repeat(run_rows, np.diff(starts))
                m_arr = np.repeat(run_m, np.diff(starts))
                flags = np.repeat(run_flags, np.diff(starts))
                if np.ndim(lengths) == 0:
                    lengths = np.full(B, lengths, np.uint32)
                return self.executor.bloom_mixed_keys(
                    pool, rows, m_arr, k, cols[0], lengths, flags
                )
            return self.executor.bloom_mixed_keys_runs(
                pool, k, cols[0], lengths, run_rows, run_m, run_flags, starts
            )

        return dispatch

    def _bloom_submit_mixed_keys(self, entry, blocks, lengths, is_add):
        """Device-hash path: raw codec lanes ride the mixed kernel;
        producer threads never hash (GIL relief under offered load).
        Replicated entries expand writes to every copy and rotate reads.
        Lane count is part of the segment key so concatenated chunks
        always agree on shape.

        ``is_add`` is a scalar for uniform batches or a per-op bool array
        for an ordered add/contains mix (the front-door fused runs of
        ISSUE 6) — the mixed kernel honors intra-batch order either way;
        only the runs-metadata compression requires a uniform flag."""
        m, k = entry.params["size"], entry.params["hash_iterations"]
        pool = entry.pool
        B = blocks.shape[0]
        L = blocks.shape[1]
        lengths = np.asarray(lengths, np.uint32)
        uniform = np.ndim(is_add) == 0
        orig_flags = (
            np.full(B, bool(is_add), bool)
            if uniform else np.asarray(is_add, bool)
        )
        any_add = bool(orig_flags.any())
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        if self._degraded(entry):
            # Degraded: hash host-side (the mirror consumes reduced
            # hashes) and serve from the golden mirror.
            lens = (
                np.full(B, lengths, np.uint32)
                if lengths.ndim == 0 else lengths
            )
            h1m, h2m = self._bloom_reduce(
                entry, *hashing.hash128_np(blocks, lens)
            )
            res = self._mirror_call(
                entry, B, lambda mir: mir.mixed(h1m, h2m, orig_flags)
            )
            if res is not None:
                return res
            # mirror reconciled mid-call: fall through to the device
        saw_replicas = bool(entry.replica_rows)
        if self.prewarmer is not None and B:
            # Keyed (codec-shaped) signatures can't be known at pool
            # attach — the lane count and trim depth come from real key
            # bytes.  First sight of a COARSE (pool, k, L) signature
            # schedules the whole bucket ladder in the background; the
            # coarse gate keeps the O(B) trim/const scans off every
            # subsequent submit (this producer path is the hot path).
            coarse = (id(pool), k, L)
            if coarse not in self._prewarm_seen:
                self._prewarm_seen.add(coarse)
                self._prewarm_keyed(pool, k, L, blocks, lengths)
        if (
            self.coalescer is not None
            and not saw_replicas
            and uniform
            and getattr(self.executor, "supports_runs_metadata", False)
        ):
            # Run-length path: row/m/is_add are constant across this call,
            # so they ride the segment as ONE meta tuple instead of B-long
            # arrays — ~22→~8 bytes/op on the wire and
            # no np.full per submit on the producer thread.
            if lengths.ndim == 0:
                len_meta = int(lengths)
            else:
                const = B > 0 and bool(np.all(lengths == lengths[0]))
                len_meta = int(lengths[0]) if const else lengths
            fut = self._submit(
                ("bloom_mixkr", id(pool), k, L),
                self._runs_dispatch(pool, k),
                (blocks,),
                B,
                pool_key=id(pool),
                meta=(self._tier_row(entry, row0), m, is_add, len_meta),
                tenant=entry.name,
            )
            if any_add:
                self._replication_fence(
                    entry,
                    saw_replicas,
                    # _bloom_submit_mixed_keys accepts scalar lengths, so
                    # the original (blocks, lengths) pair re-submits as-is.
                    lambda: self._bloom_submit_mixed_keys(
                        entry, blocks, lengths, True
                    ),
                )
            return fut
        if lengths.ndim == 0:
            lengths = np.full(B, lengths, np.uint32)
        flags = orig_flags
        orig = (blocks, lengths)
        if saw_replicas:
            rows, eidx, ppos = self._bloom_expand_ops(entry, B, flags)
            blocks, lengths, flags = blocks[eidx], lengths[eidx], flags[eidx]
            gather = lambda v: v[ppos]  # noqa: E731
        else:
            rows = np.full(B, self._tier_row(entry, row0), np.int32)
            gather = None
        if self.coalescer is not None:
            m_arr = np.full(len(rows), m, np.uint32)
            fut = self._submit(
                ("bloom_mixk", id(pool), k, L),
                lambda cols: self.executor.bloom_mixed_keys(
                    pool, cols[0], cols[1], k, cols[2], cols[3], cols[4]
                ),
                (rows, m_arr, blocks, lengths, flags),
                len(rows),
                pool_key=id(pool),
                tenant=entry.name,
            )
        else:
            m_arr = np.full(len(rows), m, np.uint32)
            fut = self.executor.bloom_mixed_keys(
                pool, rows, m_arr, k, blocks, lengths, flags
            )
        if any_add:
            # Fence re-applies WRITES only: for a mixed batch the add
            # subset re-broadcasts (contains ops have nothing to re-apply
            # and re-running them would waste a launch).
            if uniform:
                redo = lambda: self._bloom_submit_mixed_keys(  # noqa: E731
                    entry, *orig, True
                )
            else:
                sel = orig_flags
                redo = lambda: self._bloom_submit_mixed_keys(  # noqa: E731
                    entry, orig[0][sel], orig[1][sel], True
                )
            self._replication_fence(entry, saw_replicas, redo)
        return fut if gather is None else _MappedFuture(fut, gather)

    # The three encoded Bloom entries are the serving path's submits:
    # each is timed into rtpu_submit_seconds{op} and named
    # rtpu:submit:<op> in a capture, once per call (internal delegation
    # goes to the untimed bodies).

    def bloom_add_encoded(self, name, blocks, lengths) -> LazyResult:
        with self.obs.submit_timer("bloom_add"):
            return self._bloom_add_encoded(name, blocks, lengths)

    def bloom_contains_encoded(self, name, blocks, lengths) -> LazyResult:
        with self.obs.submit_timer("bloom_contains"):
            return self._bloom_contains_encoded(name, blocks, lengths)

    def bloom_mixed_encoded(self, name, blocks, lengths, flags) -> LazyResult:
        with self.obs.submit_timer("bloom_mixed"):
            return self._bloom_mixed_encoded(name, blocks, lengths, flags)

    def _bloom_add_encoded(self, name, blocks, lengths) -> LazyResult:
        if self.executor.supports_device_hash:
            with self._nc_mutate(name), self._journal_gate:
                entry = self._require(name, PoolKind.BLOOM)
                if (
                    self.coalescer is not None
                    and self.config.tpu_sketch.exact_add_semantics
                ) or entry.replica_rows or self._degraded(entry):
                    # The mixed-keys path owns the degraded-mirror failover.
                    res = self._bloom_submit_mixed_keys(
                        entry, blocks, lengths, True
                    )
                    # Journaled as raw key material (replay hashes
                    # host-side — bit-identical to the device hash).
                    return self._commit(
                        res, "bloom.addk", name,
                        blocks=np.asarray(blocks),
                        lengths=np.asarray(lengths),
                    )
                if not self.config.tpu_sketch.exact_add_semantics:
                    m, k = entry.params["size"], entry.params["hash_iterations"]
                    self._drain()
                    res = self.executor.bloom_add_keys_st(
                        entry.pool, entry.row, m, k, blocks, lengths
                    )
                    self._replication_fence(
                        entry,
                        False,
                        lambda: self._bloom_submit_mixed_keys(
                            entry, blocks, lengths, True
                        ),
                    )
                    return self._commit(
                        res, "bloom.addk", name,
                        blocks=np.asarray(blocks),
                        lengths=np.asarray(lengths),
                    )
        # Host-hash fallback journals inside bloom_add (one record per
        # accepted op — never two).
        return self.bloom_add(name, *hashing.hash128_np(blocks, lengths))

    def collect_results(self, lazies) -> None:
        """Engine-level mailbox collect (policy gate for the bulk APIs):
        honors ``mailbox_collect`` and never raises — a failed group
        fetch degrades to per-item ``.result()``, which recovers or
        attributes each launch individually."""
        if not self.config.tpu_sketch.mailbox_collect:
            return
        try:
            self.executor.collect_group(lazies)
        except Exception:
            pass

    def _bloom_contains_encoded(self, name, blocks, lengths) -> LazyResult:
        if not self.executor.supports_device_hash:
            return self.bloom_contains(name, *hashing.hash128_np(blocks, lengths))
        nc = self.nearcache
        captured = nc.epochs(name)  # before entry resolution, see contains
        entry = self._require(name, PoolKind.BLOOM)
        B = blocks.shape[0]
        if nc.active(B):
            lengths_arr = np.asarray(lengths)

            def fetch(idx):
                if idx is None:
                    return self._bloom_contains_encoded_dispatch(
                        entry, blocks, lengths
                    )
                sub_l = (
                    lengths if lengths_arr.ndim == 0 else lengths_arr[idx]
                )
                return self._bloom_contains_encoded_dispatch(
                    entry, blocks[idx], sub_l
                )

            return nc.lookup_batch(
                "bloom", name, nc.encoded_keys(blocks, lengths), np.bool_,
                fetch, monotone=True, captured=captured,
            )
        return self._bloom_contains_encoded_dispatch(entry, blocks, lengths)

    def _bloom_contains_encoded_dispatch(self, entry, blocks, lengths):
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        if (
            self.coalescer is not None
            or entry.replica_rows
            or self._degraded(entry)  # mixed-keys path serves mirror
        ):
            return self._bloom_submit_mixed_keys(entry, blocks, lengths, False)
        m, k = entry.params["size"], entry.params["hash_iterations"]
        return self.executor.bloom_contains_keys_st(
            entry.pool, self._tier_row(entry, row0), m, k, blocks, lengths
        )

    def _bloom_mixed_encoded(self, name, blocks, lengths, flags) -> LazyResult:
        """Front-door fused run (ISSUE 6): one ordered add/contains mix on
        one filter as ONE engine call — per-op results (newly-added for
        add ops, membership for contains ops) come back in command order.
        The mixed kernel already honors intra-batch sequencing (adds and
        contains of one pool share a coalescer segment today), so a run
        of 500 pipelined BF.ADD/BF.EXISTS costs one launch, not 500."""
        flags = np.asarray(flags, bool)
        if not flags.any():
            return self._bloom_contains_encoded(name, blocks, lengths)
        if flags.all():
            return self._bloom_add_encoded(name, blocks, lengths)
        with self._nc_mutate(name), self._journal_gate:
            entry = self._require(name, PoolKind.BLOOM)
            # Journal the ADD subset only (contains ops have no state
            # effect to recover); replay order within the batch is
            # preserved — adds of one call are order-independent.
            lens_arr = np.asarray(lengths, np.uint32)
            if lens_arr.ndim == 0:
                lens_arr = np.full(blocks.shape[0], lens_arr, np.uint32)
            if not self.executor.supports_device_hash:
                h1m, h2m = self._bloom_reduce(
                    entry, *hashing.hash128_np(blocks, lens_arr)
                )
                res = self._bloom_dispatch_hashed(entry, h1m, h2m, flags)
            else:
                res = self._bloom_submit_mixed_keys(
                    entry, blocks, lengths, flags
                )
            return self._commit(
                res, "bloom.addk", name,
                blocks=np.asarray(blocks)[flags],
                lengths=lens_arr[flags],
            )

    # -- hll ---------------------------------------------------------------

    def hll_ensure(self, name):
        self._live_lookup(name)  # reap an expired holder first
        self._guard_foreign(name)
        entry, _ = self.registry.try_create(name, PoolKind.HLL, (), {})
        self.residency.touch(name)  # heat feed (see _lookup_kind)
        if self.prewarmer is not None:
            # Seen-set gate: hll_ensure runs on EVERY op — the closure
            # build + prewarmer lock belong off the hot path (register
            # itself dedupes, but not for free).
            coarse = (id(entry.pool), "hll")
            if coarse not in self._prewarm_seen:
                self._prewarm_seen.add(coarse)
                from redisson_tpu.executor import prewarm

                self.prewarmer.register(
                    entry.pool, ("hll_add",), prewarm.warm_hll_add_changed()
                )
        return entry

    def hll_add(self, name, c0, c1, c2) -> LazyResult:
        with self._nc_mutate(name), self._journal_gate:
            res = self._hll_add_impl(name, c0, c1, c2)
            return self._commit(
                res, "hll.add", name,
                c0=np.asarray(c0, np.uint32),
                c1=np.asarray(c1, np.uint32),
                c2=np.asarray(c2, np.uint32),
            )

    def _hll_add_impl(self, name, c0, c1, c2) -> LazyResult:
        entry = self.hll_ensure(name)
        res = self._serve_degraded(
            entry, len(c0),
            lambda mir: bool(np.any(mir.add_changed(c0, c1, c2))),
        )
        if res is not None:
            return res
        if self.coalescer is not None:
            pool = entry.pool
            rows = np.full(len(c0), entry.row, np.int32)
            fut = self._submit(
                ("hll_add", id(pool)),
                lambda cols: self.executor.hll_add_changed(
                    pool, cols[0], cols[1], cols[2], cols[3]
                ),
                (rows, c0, c1, c2),
                len(c0),
                pool_key=id(pool),
                tenant=entry.name,
            )
            # addAll boolean: did anything change?
            return _MappedFuture(fut, lambda v: bool(np.any(v)))
        return self.executor.hll_add_single(entry.pool, entry.row, c0, c1, c2)

    def hll_add_encoded(self, name, blocks, lengths) -> LazyResult:
        if self.coalescer is None and self.executor.supports_device_hash:
            with self._nc_mutate(name), self._journal_gate:
                entry = self.hll_ensure(name)
                if not self._degraded(entry):
                    res = self.executor.hll_add_keys_single(
                        entry.pool, entry.row, blocks, lengths
                    )
                    # Raw key material; replay hashes host-side.
                    return self._commit(
                        res, "hll.addk", name,
                        blocks=np.asarray(blocks),
                        lengths=np.asarray(lengths),
                    )
        # Host-hash fallback journals inside hll_add.
        c0, c1, c2, _ = hashing.murmur3_x86_128(blocks, lengths)
        return self.hll_add(name, c0, c1, c2)

    def hll_count(self, name) -> LazyResult:
        nc = self.nearcache
        captured = nc.epochs(name)  # before entry resolution
        entry = self._lookup_kind(name, PoolKind.HLL)
        if entry is None:
            return ImmediateResult(0)
        if nc.active(1):
            return nc.lookup_scalar(
                "hll", name, ("count",),
                lambda: self._hll_count_dispatch(entry),
                captured=captured,
            )
        return self._hll_count_dispatch(entry)

    def _hll_count_dispatch(self, entry) -> LazyResult:
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(entry, 1, lambda mir: mir.count())
        if res is not None:
            return res
        self._drain()
        return self.executor.hll_count(
            entry.pool, self._tier_row(entry, row0)
        )

    def hll_count_with(self, name, other_names) -> int:
        """PFCOUNT over several keys = cardinality of the union: merge
        histogram-side via max of registers without mutating state."""
        entries = [self._lookup_kind(n, PoolKind.HLL) for n in (name, *other_names)]
        entries = [e for e in entries if e is not None]
        if not entries:
            return 0
        self._drain()
        # All HLL tenants share one pool; union via host max of rows is
        # small (16KB/row) — fine for a count call.  Degraded entries
        # contribute their MIRROR registers (the device row is stale
        # while a breaker is open).
        regs = None
        for e in entries:
            r = None
            row0 = e.row  # BEFORE the residency check (see _tier_row)
            if e.row < 0 and e.name not in self._mirrors:
                self._ensure_resident(e)  # DISK/born-cold union source
            if self._mirrors:
                # Snapshot under the mirror lock (degraded.py's
                # external-synchronization contract): a concurrent
                # add_changed or reconcile must not tear the read.
                with self._mirror_lock:
                    mir = self._mirrors.get(e.name)
                    if mir is not None and mir.kind == PoolKind.HLL:
                        r = mir.regs.copy()
            if r is None:
                r = self.executor.read_row(e.pool, self._tier_row(e, row0))
            regs = r if regs is None else np.maximum(regs, r)
        hist = np.bincount(regs, minlength=golden.HLL_Q + 2)
        return int(round(golden.ertl_estimate(hist)))

    def hll_merge_with(self, name, other_names) -> None:
        with self._nc_mutate(name), self._journal_gate:
            self._hll_merge_with_impl(name, other_names)
            seq = self._journal_rec(
                "hll.merge", name, srcs=[str(n) for n in other_names]
            )
        return self._ack(None, seq)  # fence outside the gate (see delete)

    def _hll_merge_with_impl(self, name, other_names) -> None:
        entry = self.hll_ensure(name)
        src_entries = [
            e
            for e in (self._lookup_kind(n, PoolKind.HLL) for n in other_names)
            if e is not None
        ]
        if not src_entries:
            return
        if self._degraded(entry):
            # Merge golden-side: each source contributes its CURRENT
            # truth (its own mirror if degraded, else its device row) —
            # source rows gathered before the dest's mirror lock is
            # taken (lock order: one _mirror_lock acquisition at a time).
            rows = [self._host_row(e) for e in src_entries]
            res = self._mirror_call(
                entry, 1, lambda mir: mir.merge_rows(rows)
            )
            if res is not None:
                return
        self._drain()
        self.executor.hll_merge(
            entry.pool, entry.row, [e.row for e in src_entries]
        )

    # -- bitset ------------------------------------------------------------

    def _bitset_entry_with_capacity(self, name, min_bits: int):
        """Physical placement only — create/migrate so the row can hold
        ``min_bits``, WITHOUT extending the logical bit length (bitop
        operands must keep their true lengths)."""
        self._live_lookup(name)  # reap an expired holder first
        self._guard_foreign(name)
        entry, created = self.registry.try_create(
            name, PoolKind.BITSET, (class_words_for_bits(min_bits),), {"nbits": 0}
        )
        self.residency.touch(name)  # heat feed (see _lookup_kind)
        if not created:
            self._bitset_grow(entry, min_bits)
        return entry

    def bitset_ensure(self, name, min_bits: int = 1):
        entry = self._bitset_entry_with_capacity(name, min_bits)
        # Logical size tracking = Redis string-length semantics (SETBIT
        # grows the value to cover the highest index ever touched).
        entry.params["nbits"] = max(entry.params.get("nbits", 0), int(min_bits))
        if self.prewarmer is not None:
            # Seen-set gate: bitset_ensure runs on EVERY op (see
            # hll_ensure) — register once per pool, off the hot path.
            coarse = (id(entry.pool), "bitset")
            if coarse not in self._prewarm_seen:
                self._prewarm_seen.add(coarse)
                from redisson_tpu.executor import prewarm

                if getattr(self.executor, "supports_runs_metadata", False):
                    self.prewarmer.register(
                        entry.pool, ("bs_mixed_runs",),
                        prewarm.warm_bitset_mixed_runs(),
                    )
                self.prewarmer.register(
                    entry.pool, ("bs_mixed",), prewarm.warm_bitset_mixed()
                )
        return entry

    def _bitset_grow(self, entry, min_bits: int) -> None:
        """Auto-grow semantics of Redis bitmaps: migrate the tenant to a
        larger size class, copying the row through the host (rare path).

        The commit (write new row, zero+free old, repoint the entry) runs
        under the dispatch lock with a topology-epoch check: if a live
        change_topology swapped layouts mid-migration, the swap's free-
        list rebuild already reclaimed the not-yet-attached new row — we
        retry against the fresh layout instead of committing stale state."""
        cur_words = entry.pool.row_units
        need_words = class_words_for_bits(min_bits)
        if need_words <= cur_words:
            return
        # Size-class migration is STRUCTURAL for the near cache (ISSUE
        # 4: clear/resize/migration bump unconditionally) — entry+exit
        # bumps bracket the whole commit so no read captured mid-
        # migration can install.
        with self._nc_mutate(entry.name, structural=True):
            if entry.row < 0:
                # HOST/DISK residency (ISSUE 14): no device row to
                # migrate — repoint the entry to the larger size class.
                # The mirror's golden model grows on demand, the blob
                # loader zero-pads, and promote/encode size to the
                # entry's CURRENT pool.  Mutating callers hold the
                # journal gate, so no transition can interleave.
                entry.pool = self.registry.pool_for(
                    PoolKind.BITSET, (need_words,)
                )
                return
            self._bitset_migrate(entry, need_words)

    def _bitset_migrate(self, entry, need_words: int) -> None:
        # Shrink the queue first (optional — flush-time row resolution in
        # _bitset_submit_mixed makes queued ops follow the repoint, so
        # correctness doesn't depend on this drain).
        self._drain()
        while True:
            old_pool, old_row = entry.pool, entry.row
            epoch_old = old_pool.topology_epoch
            new_pool = self.registry.pool_for(PoolKind.BITSET, (need_words,))
            with old_pool._dispatch_lock:
                if (
                    old_pool.topology_epoch != epoch_old
                    or entry.pool is not old_pool
                    or entry.row != old_row
                ):
                    # Stale view: a topology swap rebuilt layouts, or a
                    # CONCURRENT grow already migrated this entry (same
                    # destination class → committing here would copy the
                    # zeroed old row over live data and double-free it).
                    # Nothing allocated yet — safe to re-evaluate.
                    if entry.pool.row_units >= need_words:
                        return  # the other grow already got us there
                    continue
                # Allocate INSIDE the dispatch lock: change_topology holds
                # this lock for its swap, so no free-list rebuild can
                # interleave between this alloc and the commit below (the
                # old alloc-before-lock ordering leaked or double-freed
                # the new row depending on which side of the swap the
                # alloc landed).
                new_row = new_pool.alloc_row()
                # Read INSIDE the lock: the copy and the commit are atomic
                # vs concurrent flushes applying ops to the old row.
                # rtpulint: disable=RT001 migration copy-and-commit must be atomic vs concurrent flushes on the old row — releasing the dispatch lock between read and write would lose ops applied in the gap
                data = self.executor.read_row(old_pool, old_row)
                padded = np.zeros(need_words, dtype=np.uint32)
                padded[: len(data)] = data
                # rtpulint: disable=RT001 same atomic migration window as the read above
                self.executor.write_row(new_pool, new_row, padded)
                # rtpulint: disable=RT001 zero-then-free must be atomic vs reallocation (the _reap_rows discipline): releasing between would hand out a dirty row
                self.executor.zero_row(old_pool, old_row)
                old_pool.free_row(old_row)
                entry.pool, entry.row = new_pool, new_row
                return

    def bitset_capacity_bits(self, name) -> int:
        entry = self._lookup_kind(name, PoolKind.BITSET)
        return 0 if entry is None else entry.pool.row_units * 32

    def _bitset_dispatch_group(self, pool, gidx, runs):
        """One resolved-placement group of a mixed-bit segment → one
        device launch (runs-metadata form when the executor supports it;
        >1024 runs expand to per-op arrays so the runs kernel's Cp
        compile space stays the single pre-warmed 1024 bucket)."""
        if (
            getattr(self.executor, "supports_runs_metadata", False)
            and len(runs) <= 1024
        ):
            run_rows = np.array([r for _, r, _ in runs], np.int32)
            run_ops = np.array([o for _, _, o in runs], np.uint32)
            starts = np.zeros(len(runs) + 1, np.int32)
            starts[1:] = np.cumsum([n for n, _, _ in runs])
            return self.executor.bitset_mixed_runs(
                pool, gidx, run_rows, run_ops, starts
            )
        rows = np.concatenate(
            [np.full(n, r, np.int32) for n, r, _ in runs]
        )
        ops_col = np.concatenate(
            [np.full(n, o, np.uint32) for n, _, o in runs]
        )
        return self.executor.bitset_mixed(pool, rows, gidx, ops_col)

    def _bitset_submit_mixed(self, entry, idx, opcode: int):
        """Coalesced path: every single-bit opcode rides ONE segment per
        pool through the unified affine kernel (exact sequential
        semantics), so interleaved set/clear/flip/get never fragment.

        Placement (entry.pool/row) resolves at FLUSH time, under the
        dispatch lock, from per-chunk metas — not at submit: a size-class
        migration (_bitset_grow) or live change_topology committing while
        ops sit queued repoints the entry, and baked-at-submit rows would
        land writes in the old, freed row (lost updates).  Flush-time
        resolution linearizes queued ops AFTER the commit, onto the row
        that now holds the data."""

        def dispatch(cols, metas):
            offs = [0]
            for nops, _m in metas:
                offs.append(offs[-1] + nops)
            # Residency stragglers (ISSUE 14): a chunk whose entry
            # DEMOTED between submit and flush serves from the mirror —
            # flush-time residency resolution, the same discipline as
            # the flush-time row resolution below.  Applied OUTSIDE the
            # dispatch lock: mirror→dispatch is the engine-wide lock
            # order (snapshot capture, reconcile, promote); inverting
            # it here would be an AB-BA.  A None from _mirror_call
            # means the entry promoted mid-flight — its row is live
            # again and the group pass re-reads it under the lock.
            mirror_parts = {}
            for mi, (nops, (e, op)) in enumerate(metas):
                if e.row >= 0:
                    continue
                gidx = np.asarray(cols[0][offs[mi]:offs[mi + 1]])
                ops_col = np.full(nops, op, np.uint32)
                res = None
                for _ in range(4):
                    res = self._mirror_call(
                        e, nops,
                        lambda mir, g=gidx, o=ops_col: mir.mixed(g, o),
                    )
                    if res is not None or e.row >= 0:
                        break
                    # Row-less with no mirror: the entry SPILLED
                    # between this chunk queueing and the flush (spill
                    # drains first, but readers enqueue gate-free) —
                    # reload the mirror and re-apply.  Falling through
                    # to the device branch would dispatch row -1 into
                    # another tenant's row.  load_nowait, never load:
                    # the gate holder may be draining on THIS flush
                    # (blocking would be flush→gate vs gate→drain).
                    if not self.residency.load_nowait(e):
                        time.sleep(0.001)
                if res is not None:
                    mirror_parts[mi] = res
                elif e.row < 0:  # pragma: no cover — load kept failing
                    from redisson_tpu.executor.failures import (
                        NonRetryableDispatchError,
                    )

                    raise NonRetryableDispatchError(
                        f"bitset chunk for {e.name!r} has neither a "
                        f"device row nor a loadable mirror"
                    )
            with self.executor._dispatch_lock:  # atomic vs migration commit
                # Group CONSECUTIVE device chunks by their resolved pool
                # (op order is preserved — groups split at chunk
                # boundaries and at mirror-served chunks).  More than one
                # group only when a migration or demotion committed
                # mid-segment.
                groups = []  # ("dev", pool, runs, lo, hi) | ("mir", res,...)
                off = 0
                for mi, (nops, (e, op)) in enumerate(metas):
                    part = mirror_parts.get(mi)
                    if part is not None:
                        groups.append(("mir", part, None, off, off + nops))
                    else:
                        pool, row = e.pool, e.row
                        if (
                            groups and groups[-1][0] == "dev"
                            and groups[-1][1] is pool
                        ):
                            groups[-1][2].append((nops, row, op))
                            groups[-1][4] = off + nops
                        else:
                            groups.append(
                                ["dev", pool, [(nops, row, op)],
                                 off, off + nops]
                            )
                    off += nops
                results = []
                # Mirror parts already applied: any later failure must
                # not blind-retry the whole segment (re-applying them).
                applied = bool(mirror_parts)
                for tag, pool, runs, lo, hi in groups:
                    if tag == "mir":
                        results.append(pool)  # the ImmediateResult
                        continue
                    gidx = cols[0][lo:hi]
                    if applied:
                        # Earlier groups/mirror parts already mutated
                        # state: a failure from here on must NOT be
                        # blind-retried (double-applying OP_FLIP/OP_SET).
                        try:
                            results.append(
                                self._bitset_dispatch_group(
                                    pool, gidx, runs
                                )
                            )
                        except Exception as exc:
                            from redisson_tpu.executor.failures import (
                                NonRetryableDispatchError,
                            )

                            raise NonRetryableDispatchError(
                                "a later group of a split mixed-bit "
                                "launch failed after earlier groups "
                                "applied"
                            ) from exc
                        continue
                    results.append(self._bitset_dispatch_group(pool, gidx, runs))
                    applied = True
                return results[0] if len(results) == 1 else _ConcatLazy(results)

        return self._submit(
            ("bs_mix", id(entry.pool)),
            dispatch,
            (np.asarray(idx, np.uint32),),
            len(idx),
            pool_key=id(entry.pool),
            meta=(entry, opcode),
            tenant=entry.name,
        )

    def _bitset_rw(self, opcode: int, method, entry, idx):
        res = self._serve_degraded(
            entry, len(idx), lambda mir: mir.mixed(
                idx, np.full(len(idx), opcode, np.uint32)
            )
        )
        if res is not None:
            return res
        if self.coalescer is not None:
            return self._bitset_submit_mixed(entry, idx, opcode)
        # Resolve placement and dispatch atomically vs a concurrent
        # size-class migration (same lock its commit holds).
        with self.executor._dispatch_lock:
            rows = np.full(len(idx), entry.row, np.int32)
            return method(entry.pool, rows, idx)

    def bitset_set(self, name, idx, value: bool) -> LazyResult:
        from redisson_tpu.ops import bitset as bitset_ops

        idx = np.asarray(idx, np.uint32)
        # Clearing bits retires monotone positives → structural bump;
        # setting bits is an ordinary (monotone-safe) write.
        with self._nc_mutate(name, structural=not value), \
                self._journal_gate:
            entry = self.bitset_ensure(
                name, int(idx.max()) + 1 if idx.size else 1
            )
            if value:
                res = self._bitset_rw(
                    bitset_ops.OP_SET, self.executor.bitset_set, entry, idx
                )
            else:
                res = self._bitset_rw(
                    bitset_ops.OP_CLEAR, self.executor.bitset_clear_bits,
                    entry, idx,
                )
            return self._commit(
                res, "bitset.set", name, idx=idx, value=bool(value)
            )

    def bitset_flip(self, name, idx) -> LazyResult:
        from redisson_tpu.ops import bitset as bitset_ops

        idx = np.asarray(idx, np.uint32)
        with self._nc_mutate(name, structural=True), \
                self._journal_gate:  # flips clear bits
            entry = self.bitset_ensure(
                name, int(idx.max()) + 1 if idx.size else 1
            )
            res = self._bitset_rw(
                bitset_ops.OP_FLIP, self.executor.bitset_flip, entry, idx
            )
            return self._commit(res, "bitset.flip", name, idx=idx)

    def bitset_get(self, name, idx) -> LazyResult:
        idx = np.asarray(idx, np.uint32)
        nc = self.nearcache
        captured = nc.epochs(name)  # before entry resolution
        entry = self._lookup_kind(name, PoolKind.BITSET)
        if entry is None:
            return ImmediateResult(np.zeros(len(idx), bool))
        if nc.active(len(idx)):
            return nc.lookup_batch(
                "bitset", name, [int(i) for i in idx], np.bool_,
                lambda midx: self._bitset_get_dispatch(
                    entry, idx if midx is None else idx[midx]
                ),
                monotone=True,  # OP_CLEAR/OP_FLIP/replace are structural
                captured=captured,
            )
        return self._bitset_get_dispatch(entry, idx)

    def _bitset_get_dispatch(self, entry, idx) -> LazyResult:
        from redisson_tpu.ops import bitset as bitset_ops

        cap = entry.pool.row_units * 32
        in_range = idx < cap
        safe_idx = np.where(in_range, idx, 0).astype(np.uint32)
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(
            entry, len(idx), lambda mir: mir.mixed(
                safe_idx, np.full(len(idx), bitset_ops.OP_GET, np.uint32)
            ) & in_range
        )
        if res is not None:
            return res
        if self.coalescer is not None:
            fut = self._bitset_submit_mixed(entry, safe_idx, bitset_ops.OP_GET)
            return _MappedFuture(fut, lambda v: v & in_range)
        rows = np.full(len(idx), self._tier_row(entry, row0), np.int32)
        res = self.executor.bitset_get(entry.pool, rows, safe_idx)
        return _MappedFuture(res, lambda v: v & in_range)

    def bitset_set_range(self, name, from_bit, to_bit, value: bool) -> LazyResult:
        with self._nc_mutate(name, structural=not value), \
                self._journal_gate:
            entry = self.bitset_ensure(name, int(to_bit))
            res = self._serve_degraded(
                entry, 1,
                lambda mir: mir.set_range(int(from_bit), int(to_bit), bool(value)),
            )
            if res is None:
                self._drain()
                res = self.executor.bitset_set_range(
                    entry.pool, entry.row, int(from_bit), int(to_bit), value
                )
            return self._commit(
                res, "bitset.range", name,
                frm=int(from_bit), to=int(to_bit), value=bool(value),
            )

    def _nc_scalar(self, kind, name, key, dispatch, captured):
        """Near-cache plumbing shared by every scalar read-through
        (bitset cardinality/length/bitpos, CMS total): epoch-tagged,
        single host int.  ``captured``: epoch pair sampled before entry
        resolution."""
        nc = self.nearcache
        if nc.active(1):
            return int(
                nc.lookup_scalar(
                    kind, name, key, dispatch, captured=captured
                ).result()
            )
        return int(dispatch().result())

    def bitset_cardinality(self, name) -> int:
        captured = self.nearcache.epochs(name)
        entry = self._lookup_kind(name, PoolKind.BITSET)
        if entry is None:
            return 0

        def dispatch():
            row0 = entry.row  # BEFORE the residency check
            res = self._serve_degraded(entry, 1, lambda mir: mir.cardinality())
            if res is not None:
                return res
            self._drain()
            return self.executor.bitset_cardinality(
                entry.pool, self._tier_row(entry, row0)
            )

        return self._nc_scalar("bitset", name, ("card",), dispatch, captured)

    def bitset_length(self, name) -> int:
        captured = self.nearcache.epochs(name)
        entry = self._lookup_kind(name, PoolKind.BITSET)
        if entry is None:
            return 0

        def dispatch():
            row0 = entry.row  # BEFORE the residency check
            res = self._serve_degraded(entry, 1, lambda mir: mir.length())
            if res is not None:
                return res
            self._drain()
            return self.executor.bitset_length(
                entry.pool, self._tier_row(entry, row0)
            )

        return self._nc_scalar("bitset", name, ("len",), dispatch, captured)

    def bitset_bitpos(self, name, target_bit: int) -> int:
        captured = self.nearcache.epochs(name)
        entry = self._lookup_kind(name, PoolKind.BITSET)
        if entry is None:
            return -1 if target_bit else 0

        def dispatch():
            row0 = entry.row  # BEFORE the residency check
            res = self._serve_degraded(
                entry, 1, lambda mir: mir.bitpos(int(target_bit))
            )
            if res is not None:
                return res
            self._drain()
            return self.executor.bitset_bitpos(
                entry.pool, self._tier_row(entry, row0), target_bit
            )

        return self._nc_scalar(
            "bitset", name, ("bitpos", int(target_bit)), dispatch, captured
        )

    def bitset_bitop(self, dest: str, src_names, op: str) -> None:
        """BITOP dest = op(srcs).  All operands (dest included) are grown
        into one size class first so their rows co-reside in a single pool
        (the TPU answer to the reference's same-slot requirement for
        cross-key BITOP, SURVEY.md §2.2).

        Redis semantics: dest is *replaced* (its prior value never leaks
        into the result), and the result length is the max source length.
        Unary NOT complements the source's full *byte-aligned* string
        (Redis values are byte strings, so BITOP NOT flips padding bits up
        to the byte boundary too) and is masked there so tail bits of the
        size-class row stay 0.
        """
        with self._nc_mutate(dest, structural=True), \
                self._journal_gate:  # dest is REPLACED
            self._bitset_bitop_impl(dest, src_names, op)
            seq = self._journal_rec(
                "bitset.bitop", dest,
                srcs=[str(n) for n in src_names], bop=str(op),
            )
        return self._ack(None, seq)  # fence outside the gate (see delete)

    def _bitset_bitop_impl(self, dest: str, src_names, op: str) -> None:
        max_bits = max(
            (self.bitset_capacity_bits(n) for n in (dest, *src_names)),
            default=0,
        ) or 32 * 32
        dst = self._bitset_entry_with_capacity(dest, max_bits)
        srcs, src_nbits, src_entries = [], [], []
        for n in src_names:
            e = self._bitset_entry_with_capacity(n, max_bits)
            srcs.append(e.row)
            src_nbits.append(e.params.get("nbits", 0))
            src_entries.append(e)
        nbits = (
            -(-src_nbits[0] // 8) * 8 if op == "not" else max(src_nbits, default=0)
        )
        if self._degraded(dst):
            # Golden-side BITOP: decode every source's current truth
            # (mirror or device row — all operands were grown into one
            # size class above, so rows share one physical width),
            # combine host-side, and REPLACE the dest mirror (Redis
            # semantics: dest's prior value never leaks into the result).
            from redisson_tpu.objects.degraded import _bits_from_words

            nb_phys = dst.pool.row_units * 32
            srcs_bits = [
                _bits_from_words(self._host_row(e), nb_phys)
                for e in src_entries
            ]
            if op == "not":
                out = np.zeros(nb_phys, bool)
                out[:nbits] = ~srcs_bits[0][:nbits]
            else:
                fn = {
                    "and": np.logical_and,
                    "or": np.logical_or,
                    "xor": np.logical_xor,
                }[op]
                out = srcs_bits[0].copy()
                for b in srcs_bits[1:]:
                    out = fn(out, b)
            res = self._mirror_call(
                dst, 1, lambda mir: mir.replace_bits(out)
            )
            if res is not None:
                dst.params["nbits"] = nbits
                return
        self._drain()
        self.executor.bitset_bitop(
            dst.pool, dst.row, srcs, op,
            limit_bits=nbits if op == "not" else None,
        )
        dst.params["nbits"] = nbits

    def bitset_to_bytes(self, name) -> bytes:
        """Dump trimmed to the logical length (Redis STRLEN semantics) so
        both engines return identical bytes for the same object."""
        entry = self._lookup_kind(name, PoolKind.BITSET)
        if entry is None:
            return b""
        nbytes = -(-entry.params.get("nbits", 0) // 8)
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(
            entry, 1,
            lambda mir: np.packbits(
                mir.bits, bitorder="little"
            ).tobytes()[:nbytes],
        )
        if res is not None:
            return res.result()
        self._drain()
        return self.executor.read_row(
            entry.pool, self._tier_row(entry, row0)
        ).tobytes()[:nbytes]

    # -- cms ---------------------------------------------------------------

    def cms_try_init(self, name, depth: int, width: int) -> bool:
        params = {"depth": depth, "width": width}
        with self._journal_gate:
            self._live_lookup(name)  # reap an expired holder before tryInit
            self._guard_foreign(name)
            entry, created = self.registry.try_create(
                name, PoolKind.CMS, (depth, width), params
            )
            seq = self._journal_rec(
                "cms.init", name, depth=int(depth), width=int(width)
            ) if created else None
        if self.prewarmer is not None:
            from redisson_tpu.executor import prewarm

            self.prewarmer.register(
                entry.pool, ("cms_updest", depth, width),
                prewarm.warm_cms_update_estimate(depth, width),
            )
        return self._ack(created, seq)

    def cms_total(self, name) -> int:
        """Total inserted weight (CMS.INFO 'count'): every increment adds
        its weight to exactly one cell per depth row, so row 0's sum is
        the total."""
        captured = self.nearcache.epochs(name)  # before entry resolution
        entry = self._require(name, PoolKind.CMS)
        w = entry.params["width"]

        def dispatch():
            row0 = entry.row  # BEFORE the residency check
            res = self._serve_degraded(entry, 1, lambda mir: mir.total())
            if res is not None:
                return res
            self._drain()
            row = self.executor.read_row(
                entry.pool, self._tier_row(entry, row0)
            )
            return ImmediateResult(int(np.asarray(row[:w], np.uint64).sum()))

        return self._nc_scalar("cms", name, ("total",), dispatch, captured)

    def cms_reset(self, name) -> None:
        """Zero a CMS's counters in place (CMS.MERGE overwrite semantics)
        — the registry entry and any top-K configuration survive."""
        with self._nc_mutate(name, structural=True), \
                self._journal_gate:  # counters REPLACED
            entry = self._require(name, PoolKind.CMS)
            res = self._serve_degraded(entry, 1, lambda mir: mir.reset())
            if res is None:
                self._drain()
                self.executor.zero_row(entry.pool, entry.row)
            seq = self._journal_rec("cms.reset", name)
        self._ack(None, seq)  # fence outside the gate (see delete)

    def cms_add(self, name, H1, H2, weights) -> LazyResult:
        with self._nc_mutate(name), self._journal_gate:
            res = self._cms_add_impl(name, H1, H2, weights)
            return self._commit(
                res, "cms.add", name,
                h1=np.asarray(H1), h2=np.asarray(H2),
                w=np.asarray(weights, np.uint32),
            )

    def _cms_add_impl(self, name, H1, H2, weights) -> LazyResult:
        entry = self._require(name, PoolKind.CMS)
        d, w = entry.params["depth"], entry.params["width"]
        h1w, h2w = hashing.km_reduce_mod(H1, H2, w)
        rows = np.full(len(H1), entry.row, np.int32)
        wts = np.asarray(weights, np.uint32)
        res = self._serve_degraded(
            entry, len(H1),
            lambda mir: mir.update_estimate(h1w, h2w, wts),
        )
        if res is not None:
            return res
        if self.coalescer is not None:
            # Updates and estimates share one segment per (pool, d, w):
            # estimate ops ride with weight 0 (the scatter-add identity).
            # Estimates in a flush window may observe adds coalesced into
            # the same batch — CMS stays an upper bound either way.
            pool = entry.pool
            return self._submit(
                ("cms_mix", id(pool), d, w),
                lambda cols: self.executor.cms_update_estimate(
                    pool, cols[0], cols[1], cols[2], cols[3], d, w
                ),
                (rows, h1w, h2w, wts),
                len(H1),
                pool_key=id(pool),
                tenant=entry.name,
            )
        return self.executor.cms_update_estimate(
            entry.pool, rows, h1w, h2w, wts, d, w
        )

    def cms_estimate(self, name, H1, H2) -> LazyResult:
        nc = self.nearcache
        captured = nc.epochs(name)  # before entry resolution
        entry = self._require(name, PoolKind.CMS)
        if nc.active(len(H1)):
            H1a, H2a = np.asarray(H1), np.asarray(H2)
            return nc.lookup_batch(
                "cms", name, nc.hashed_keys(H1a, H2a), np.uint32,
                lambda idx: self._cms_estimate_dispatch(
                    entry,
                    H1a if idx is None else H1a[idx],
                    H2a if idx is None else H2a[idx],
                ),
                monotone=False,  # any add can raise an estimate
                captured=captured,
            )
        return self._cms_estimate_dispatch(entry, H1, H2)

    def _cms_estimate_dispatch(self, entry, H1, H2) -> LazyResult:
        d, w = entry.params["depth"], entry.params["width"]
        h1w, h2w = hashing.km_reduce_mod(H1, H2, w)
        row0 = entry.row  # BEFORE the residency check (see _tier_row)
        res = self._serve_degraded(
            entry, len(H1),
            lambda mir: mir.update_estimate(
                h1w, h2w, np.zeros(len(H1), np.uint32)
            ),
        )
        if res is not None:
            return res
        rows = np.full(len(H1), self._tier_row(entry, row0), np.int32)
        if self.coalescer is not None:
            pool = entry.pool
            zeros = np.zeros(len(H1), np.uint32)
            return self._submit(
                ("cms_mix", id(pool), d, w),
                lambda cols: self.executor.cms_update_estimate(
                    pool, cols[0], cols[1], cols[2], cols[3], d, w
                ),
                (rows, h1w, h2w, zeros),
                len(H1),
                pool_key=id(pool),
                tenant=entry.name,
            )
        return self.executor.cms_estimate(entry.pool, rows, h1w, h2w, d, w)

    # Per-launch op cap for the Pallas path: 4 uint32[B] operands must
    # share VMEM with the table; bigger batches chunk (state carries
    # across chunks, so sequential semantics are preserved exactly).
    _SEQ_CHUNK = 1 << 15

    def cms_add_seq(self, name, H1, H2, weights) -> LazyResult:
        """Streaming add+estimate via the Pallas heavy-hitter kernel
        (BASELINE config 5): op j's estimate is its AT-SEQUENCE-POINT
        value — ops ≤ j applied (its own update included), later ops
        excluded.  Falls back to the vectorized XLA path where the kernel
        isn't available (sharded mode) or the geometry doesn't fit VMEM
        lane blocks; the fallback's estimates include the whole batch."""
        with self._nc_mutate(name), self._journal_gate:
            res = self._cms_add_seq_impl(name, H1, H2, weights)
            # Same record as cms_add: the STATE effect of seq vs
            # vectorized add is identical (only the returned estimates'
            # sequence point differs), so replay shares one path.
            return self._commit(
                res, "cms.add", name,
                h1=np.asarray(H1), h2=np.asarray(H2),
                w=np.asarray(weights, np.uint32),
            )

    def _cms_add_seq_impl(self, name, H1, H2, weights) -> LazyResult:
        entry = self._require(name, PoolKind.CMS)
        d, w = entry.params["depth"], entry.params["width"]
        if self._degraded(entry):
            # Mirror fallback has whole-batch (vectorized) semantics,
            # like the non-Pallas fallback below.
            # _cms_add_impl, not cms_add: the public wrapper already
            # journals this call once (one record per accepted op).
            return self._cms_add_impl(name, H1, H2, weights)
        if (
            not getattr(self.executor, "supports_pallas_cms", False)
            or (d * w) % 128 != 0  # VMEM lane-block geometry
            or d * w * 4 > (8 << 20)  # table must fit VMEM
            or len(H1) == 0
        ):
            # _cms_add_impl, not cms_add: the public wrapper already
            # journals this call once (one record per accepted op).
            return self._cms_add_impl(name, H1, H2, weights)
        h1w, h2w = hashing.km_reduce_mod(H1, H2, w)
        weights = np.asarray(weights, np.uint32)
        self._drain()  # sequential semantics: all queued ops land first
        B = len(h1w)
        if B <= self._SEQ_CHUNK:
            return self.executor.cms_update_estimate_seq(
                entry.pool, entry.row, h1w, h2w, weights, d, w
            )
        parts = [
            self.executor.cms_update_estimate_seq(
                entry.pool, entry.row,
                h1w[i : i + self._SEQ_CHUNK],
                h2w[i : i + self._SEQ_CHUNK],
                weights[i : i + self._SEQ_CHUNK],
                d, w,
            )
            for i in range(0, B, self._SEQ_CHUNK)
        ]
        return ImmediateResult(
            np.concatenate([np.asarray(p.result()) for p in parts])
        )

    def cms_merge(self, name, other_names) -> None:
        with self._nc_mutate(name), self._journal_gate:
            self._cms_merge_impl(name, other_names)
            seq = self._journal_rec(
                "cms.merge", name, srcs=[str(n) for n in other_names]
            )
        return self._ack(None, seq)  # fence outside the gate (see delete)

    def _cms_merge_impl(self, name, other_names) -> None:
        entry = self._require(name, PoolKind.CMS)
        src_entries = []
        for n in other_names:
            e = self._require(n, PoolKind.CMS)
            if (
                e.params["depth"] != entry.params["depth"]
                or e.params["width"] != entry.params["width"]
            ):
                raise ValueError("cannot merge CMS with different geometry")
            src_entries.append(e)
        if not src_entries:
            return
        if self._degraded(entry):
            # Golden-side CMS.MERGE: sum each source's current truth
            # (its mirror if degraded, else its device row) into the
            # dest mirror — see hll_merge_with.
            rows = [self._host_row(e) for e in src_entries]
            res = self._mirror_call(
                entry, 1, lambda mir: mir.merge_rows(rows)
            )
            if res is not None:
                return
        self._drain()
        self.executor.cms_merge(
            entry.pool, entry.row, [e.row for e in src_entries]
        )


class HostSketchEngine:
    """Golden-model backend — the 'Redis server on the host' analog and the
    benchmark baseline.  Same hash material, same formulas; same
    TTL/dump/restore surface as the TPU engine."""

    def __init__(self, config):
        from redisson_tpu.obs import Observability

        self.config = config
        self._lock = _witness.named(threading.RLock(), "engine.host")
        self._objects: dict[str, dict] = {}
        # Same observability surface as the TPU engine (so a RESP server
        # or client fronting either backend finds one bundle to record
        # into); the host engine has no coalescer/executor to instrument.
        self.obs = Observability(
            trace_sample_rate=getattr(config, "trace_sample_rate", 0.0),
            trace_max_spans=getattr(config, "trace_max_spans", 2048),
            latency_threshold_ms=getattr(
                config, "latency_monitor_threshold_ms", 0
            ),
        )
        self.topk = TopKStore()
        # Wired by the client to the grid store's lock-free ``probe`` (one
        # logical keyspace — same contract as TpuSketchEngine).  Called
        # while holding self._lock, so it MUST NOT take the grid's lock.
        self.foreign_exists = None

    def _guard_foreign(self, name: str) -> None:
        if (
            self.foreign_exists is not None
            and name not in self._objects
            and self.foreign_exists(name)
        ):
            raise TypeError(
                f"object {name!r} is held by the data grid (WRONGTYPE)"
            )

    def probe(self, name: str) -> bool:
        """Lock-free existence probe for the grid's guard."""
        import time as _time

        o = self._objects.get(name)
        if o is None:
            return False
        exp = o.get("expire_at")
        return exp is None or _time.time() < exp

    def shutdown(self) -> None:
        pass

    # -- generic -----------------------------------------------------------

    def _live(self, name):
        """Lazy expiry (Redis-style): an overdue object vanishes on touch."""
        import time as _time

        o = self._objects.get(name)
        if o is not None and o.get("expire_at") is not None:
            if _time.time() >= o["expire_at"]:
                del self._objects[name]
                self.topk.drop(name)
                return None
        return o

    def exists(self, name) -> bool:
        with self._lock:
            return self._live(name) is not None

    def delete(self, name) -> bool:
        with self._lock:
            live = self._live(name) is not None
            self._objects.pop(name, None)
            self.topk.drop(name)
            return live

    def rename(self, old, new) -> bool:
        with self._lock:
            if old == new or self._live(old) is None:
                return False
            self._guard_foreign(new)  # one keyspace: RENAME can't shadow grid
            self._objects[new] = self._objects.pop(old)
            self.topk.rename(old, new)
            return True

    def names(self, kind=None):
        with self._lock:
            return [
                n
                for n in list(self._objects)
                if self._live(n) is not None
                and (kind is None or self._objects[n]["kind"] == kind)
            ]

    def params(self, name):
        with self._lock:
            o = self._live(name)
            return None if o is None else o["params"]

    def _require(self, name, kind):
        o = self._lookup_kind(name, kind)
        if o is None:
            raise RuntimeError(f"{kind} object {name!r} is not initialized")
        return o

    def _lookup_kind(self, name, kind):
        with self._lock:
            o = self._live(name)
            if o is not None and o["kind"] != kind:
                raise TypeError(f"object {name!r} holds a {o['kind']}, not a {kind}")
            return o

    # -- TTL / dump parity with the TPU engine -----------------------------

    def expire(self, name, ttl_s: float) -> bool:
        import time as _time

        return self.expire_at(name, _time.time() + ttl_s)

    def expire_at(self, name, ts: float) -> bool:
        with self._lock:
            o = self._live(name)
            if o is None:
                return False
            o["expire_at"] = float(ts)
            return True

    def clear_expire(self, name) -> bool:
        with self._lock:
            o = self._live(name)
            if o is None or o.get("expire_at") is None:
                return False
            o["expire_at"] = None
            return True

    def remain_ttl_ms(self, name) -> int:
        import time as _time

        with self._lock:
            o = self._live(name)
            if o is None:
                return -2
            if o.get("expire_at") is None:
                return -1
            return max(0, int((o["expire_at"] - _time.time()) * 1000))

    # Data-only dump wire format (no pickle — dump blobs may cross trust
    # boundaries; the reference's DUMP/RESTORE payload is data-only,
    # ADVICE r3): RTPH | u32 header_len | json header | npy arrays.
    # The header records the golden-model class by NAME and its int
    # scalars; arrays ride as concatenated .npy blobs in header order.
    _DUMP_MAGIC = b"RTPH"

    def dump(self, name):
        import io
        import json
        import struct

        with self._lock:
            o = self._live(name)
            if o is None:
                return None
            m = o["model"]
            scalars, arrays = {}, []
            for k_, v_ in vars(m).items():
                if isinstance(v_, np.ndarray):
                    arrays.append(k_)
                elif isinstance(v_, (int, np.integer)):
                    scalars[k_] = int(v_)
                else:  # pragma: no cover — golden models hold ints+arrays
                    raise TypeError(f"non-serializable model field {k_!r}")
            header = json.dumps(
                {
                    "v": 2,
                    "kind": o["kind"],
                    "params": dict(o["params"]),
                    "model_cls": type(m).__name__,
                    "scalars": scalars,
                    "arrays": arrays,
                    "topk": self.topk.export_state(name),
                }
            ).encode("utf-8")
            buf = io.BytesIO()
            for k_ in arrays:
                np.save(buf, getattr(m, k_), allow_pickle=False)
            return (
                self._DUMP_MAGIC
                + struct.pack("<I", len(header))
                + header
                + buf.getvalue()
            )

    # Per-class schemas for restore-time validation: dumps cross trust
    # boundaries, so field names, dtypes, shapes, and bounds are all
    # checked before a model is built (a forged blob must not create a
    # corrupt object or a giant allocation).
    _RESTORE_SCHEMAS = {
        "GoldenBloomFilter": {
            "scalars": {"size": (1, 1 << 33), "hash_iterations": (1, 64)},
            "arrays": {"bits": (np.bool_, lambda s: (s["size"],))},
        },
        "GoldenHyperLogLog": {
            "scalars": {},
            "arrays": {"regs": (np.uint8, lambda s: (golden.HLL_M,))},
        },
        "GoldenCountMinSketch": {
            "scalars": {"depth": (1, 64), "width": (1, 1 << 27)},
            "arrays": {
                "counts": (np.uint32, lambda s: (s["depth"], s["width"]))
            },
        },
        "GoldenBitSet": {
            "scalars": {},
            "arrays": {"bits": (np.bool_, None)},  # any 1-D length ≤ cap
        },
    }

    def restore(self, name, data: bytes, replace: bool = False) -> None:
        import io
        import json
        import struct

        from redisson_tpu.objects.durability import safe_load_npy

        if len(data) < 8 or data[:4] != self._DUMP_MAGIC:
            raise ValueError("not a host-sketch dump (bad magic)")
        (hlen,) = struct.unpack("<I", data[4:8])
        if hlen > 1 << 16:
            raise ValueError("dump header too large")
        d = json.loads(data[8 : 8 + hlen].decode("utf-8"))
        if d.get("v") != 2:
            raise ValueError(f"unsupported dump version: {d.get('v')}")
        cls_name = d.get("model_cls")
        schema = self._RESTORE_SCHEMAS.get(cls_name)
        if schema is None:
            raise ValueError(f"unknown model class {cls_name!r}")
        # kind must agree with the model class — a forged blob pairing
        # kind='cms' with a bloom model would create an object whose every
        # later op feeds the wrong model the wrong arguments.
        expected_kind = {
            "GoldenBloomFilter": PoolKind.BLOOM,
            "GoldenHyperLogLog": PoolKind.HLL,
            "GoldenCountMinSketch": PoolKind.CMS,
            "GoldenBitSet": PoolKind.BITSET,
        }[cls_name]
        if d.get("kind") != expected_kind:
            raise ValueError(
                f"dump kind {d.get('kind')!r} does not match {cls_name}"
            )
        if not isinstance(d.get("params"), dict):
            raise ValueError("dump params must be a dict")
        # Untrusted candidate table: validate BEFORE any mutation.
        topk_decoded = TopKStore.decode_state(d.get("topk"), name)
        cls = getattr(golden, cls_name)
        scalars = d.get("scalars", {})
        if set(scalars) != set(schema["scalars"]):
            raise ValueError(f"dump scalar fields {sorted(scalars)} do not "
                             f"match {cls_name}")
        for k_, (lo, hi) in schema["scalars"].items():
            v_ = int(scalars[k_])
            if not lo <= v_ <= hi:
                raise ValueError(f"dump field {k_}={v_} out of range")
            scalars[k_] = v_
        if list(d.get("arrays", [])) != list(schema["arrays"]):
            raise ValueError(f"dump array fields {d.get('arrays')} do not "
                             f"match {cls_name}")
        model = object.__new__(cls)
        for k_, v_ in scalars.items():
            setattr(model, k_, v_)
        buf = io.BytesIO(data[8 + hlen :])
        for k_, (want_dtype, want_shape) in schema["arrays"].items():
            arr = safe_load_npy(buf)
            if arr.dtype != want_dtype:
                raise ValueError(f"dump array {k_} has dtype {arr.dtype}")
            if want_shape is not None and arr.shape != want_shape(scalars):
                raise ValueError(f"dump array {k_} has shape {arr.shape}")
            if want_shape is None and (arr.ndim != 1 or arr.size > 1 << 33):
                raise ValueError(f"dump array {k_} has bad geometry")
            setattr(model, k_, arr.copy())  # writable (frombuffer is RO)
        with self._lock:
            if self._live(name) is not None:
                if not replace:
                    raise ValueError(f"BUSYKEY: {name!r} already exists")
                del self._objects[name]
            self._guard_foreign(name)
            self._objects[name] = {
                "kind": d["kind"],
                "model": model,
                "params": d["params"],
            }
        # Unconditional: replaces (or clears) any previous object's table
        # so a ghost heavy-hitter set never survives a replace.
        self.topk.import_decoded(topk_decoded, name)

    # -- bloom -------------------------------------------------------------

    def bloom_try_init(self, name, expected_insertions, false_probability) -> bool:
        m = golden.optimal_num_of_bits(
            expected_insertions, false_probability,
            max_bits=getattr(self.config.tpu_sketch, "max_bloom_bits",
                             golden.MAX_BLOOM_BITS),
        )
        k = golden.optimal_num_of_hash_functions(expected_insertions, m)
        with self._lock:
            if self._lookup_kind(name, PoolKind.BLOOM) is not None:
                return False
            self._guard_foreign(name)
            self._objects[name] = {
                "kind": PoolKind.BLOOM,
                "model": golden.GoldenBloomFilter(m, k),
                "params": {
                    "size": m,
                    "hash_iterations": k,
                    "expected_insertions": expected_insertions,
                    "false_probability": false_probability,
                },
            }
            return True

    def bloom_add(self, name, H1, H2):
        o = self._require(name, PoolKind.BLOOM)
        model: golden.GoldenBloomFilter = o["model"]
        h1m, h2m = hashing.km_reduce_mod(H1, H2, model.size)
        with self._lock:
            return ImmediateResult(model.add_hashed(h1m, h2m))

    def bloom_contains(self, name, H1, H2):
        o = self._require(name, PoolKind.BLOOM)
        model = o["model"]
        h1m, h2m = hashing.km_reduce_mod(H1, H2, model.size)
        with self._lock:
            return ImmediateResult(model.contains_hashed(h1m, h2m))

    def bloom_count(self, name):
        o = self._require(name, PoolKind.BLOOM)
        with self._lock:
            return ImmediateResult(o["model"].cardinality_estimate())

    def bloom_add_encoded(self, name, blocks, lengths):
        return self.bloom_add(name, *hashing.hash128_np(blocks, lengths))

    def bloom_contains_encoded(self, name, blocks, lengths):
        return self.bloom_contains(name, *hashing.hash128_np(blocks, lengths))

    def bloom_mixed_encoded(self, name, blocks, lengths, flags):
        """Ordered add/contains mix on one filter (front-door fused runs):
        consecutive same-flag spans apply in order under one lock hold,
        so results are bit-identical to the sequential command stream."""
        o = self._require(name, PoolKind.BLOOM)
        model = o["model"]
        H1, H2 = hashing.hash128_np(blocks, lengths)
        h1m, h2m = hashing.km_reduce_mod(H1, H2, model.size)
        flags = np.asarray(flags, bool)
        n = len(flags)
        out = np.empty(n, bool)
        with self._lock:
            i = 0
            while i < n:
                j = i + 1
                while j < n and flags[j] == flags[i]:
                    j += 1
                if flags[i]:
                    out[i:j] = model.add_hashed(h1m[i:j], h2m[i:j])
                else:
                    out[i:j] = model.contains_hashed(h1m[i:j], h2m[i:j])
                i = j
        return ImmediateResult(out)

    def bloom_replicate(self, name) -> bool:
        return False  # one host copy; nothing to spread reads across

    def bloom_is_replicated(self, name) -> bool:
        return False

    # -- hll ---------------------------------------------------------------

    def _hll(self, name):
        with self._lock:
            o = self._lookup_kind(name, PoolKind.HLL)
            if o is None:
                self._guard_foreign(name)
                o = {
                    "kind": PoolKind.HLL,
                    "model": golden.GoldenHyperLogLog(),
                    "params": {},
                }
                self._objects[name] = o
            return o

    def hll_add(self, name, c0, c1, c2):
        o = self._hll(name)
        with self._lock:
            model = o["model"]
            before = int(model.regs.sum())
            model.add_hashed(c0, c1, c2)
            return ImmediateResult(int(model.regs.sum()) != before)

    def hll_add_encoded(self, name, blocks, lengths):
        c0, c1, c2, _ = hashing.murmur3_x86_128(blocks, lengths)
        return self.hll_add(name, c0, c1, c2)

    def hll_count(self, name):
        o = self._lookup_kind(name, PoolKind.HLL)
        with self._lock:
            return ImmediateResult(0 if o is None else o["model"].count())

    def hll_count_with(self, name, other_names) -> int:
        with self._lock:
            regs = None
            for n in (name, *other_names):
                o = self._lookup_kind(n, PoolKind.HLL)
                if o is not None:
                    r = o["model"].regs
                    regs = r.copy() if regs is None else np.maximum(regs, r)
            if regs is None:
                return 0
            hist = np.bincount(regs, minlength=golden.HLL_Q + 2)
            return int(round(golden.ertl_estimate(hist)))

    def hll_merge_with(self, name, other_names) -> None:
        o = self._hll(name)
        with self._lock:
            for n in other_names:
                src = self._lookup_kind(n, PoolKind.HLL)
                if src is not None:
                    o["model"].merge(src["model"])

    # -- bitset ------------------------------------------------------------

    def _bitset(self, name):
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            if o is None:
                self._guard_foreign(name)
                o = {
                    "kind": PoolKind.BITSET,
                    "model": golden.GoldenBitSet(),
                    "params": {},
                }
                self._objects[name] = o
            return o

    def bitset_capacity_bits(self, name) -> int:
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            return 0 if o is None else o["model"].bits.size

    def bitset_set(self, name, idx, value: bool):
        o = self._bitset(name)
        with self._lock:
            return ImmediateResult(o["model"].set(np.asarray(idx, np.int64), value))

    def bitset_flip(self, name, idx):
        o = self._bitset(name)
        with self._lock:
            model = o["model"]
            idx = np.asarray(idx, np.int64)
            model._grow(int(idx.max()) + 1 if idx.size else 1)
            prev = np.empty(len(idx), bool)
            for j, ix in enumerate(idx):
                prev[j] = model.bits[ix]
                model.bits[ix] = not model.bits[ix]
            return ImmediateResult(prev)

    def bitset_get(self, name, idx):
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            if o is None:
                return ImmediateResult(np.zeros(len(idx), bool))
            return ImmediateResult(o["model"].get(np.asarray(idx, np.int64)))

    def bitset_set_range(self, name, from_bit, to_bit, value: bool):
        o = self._bitset(name)
        with self._lock:
            model = o["model"]
            model._grow(int(to_bit))
            model.bits[int(from_bit) : int(to_bit)] = value
            return ImmediateResult(None)

    def bitset_cardinality(self, name) -> int:
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            return 0 if o is None else o["model"].cardinality()

    def bitset_length(self, name) -> int:
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            return 0 if o is None else o["model"].length()

    def bitset_bitpos(self, name, target_bit: int) -> int:
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            if o is None:
                return -1 if target_bit else 0
            bits = o["model"].bits
            matches = np.nonzero(bits == bool(target_bit))[0]
            return int(matches[0]) if matches.size else (-1 if target_bit else bits.size)

    def bitset_bitop(self, dest, src_names, op: str) -> None:
        """Redis BITOP: sources are zero-padded to the max source length
        (without mutating them), dest is replaced entirely; NOT complements
        its single source's byte-aligned string (padding bits up to the
        byte boundary flip to 1, as on a real Redis value) — mirrors
        TpuSketchEngine."""
        with self._lock:
            srcs = [self._bitset(n)["model"] for n in src_names]
            if op == "not":
                size = -(-srcs[0].bits.size // 8) * 8
                res = np.ones(size, dtype=bool)
                res[: srcs[0].bits.size] = ~srcs[0].bits
            else:
                size = max((s.bits.size for s in srcs), default=0)

                def padded(b):
                    if b.size == size:
                        return b
                    p = np.zeros(size, dtype=bool)
                    p[: b.size] = b
                    return p

                fn = {"and": np.logical_and, "or": np.logical_or, "xor": np.logical_xor}[op]
                res = padded(srcs[0].bits).copy()
                for s in srcs[1:]:
                    res = fn(res, padded(s.bits))
            d = self._bitset(dest)["model"]
            d.bits = np.array(res, dtype=bool)

    def bitset_to_bytes(self, name) -> bytes:
        with self._lock:
            o = self._lookup_kind(name, PoolKind.BITSET)
            if o is None:
                return b""
            return np.packbits(o["model"].bits, bitorder="little").tobytes()

    # -- cms ---------------------------------------------------------------

    def cms_try_init(self, name, depth, width) -> bool:
        with self._lock:
            if self._lookup_kind(name, PoolKind.CMS) is not None:
                return False
            self._guard_foreign(name)
            self._objects[name] = {
                "kind": PoolKind.CMS,
                "model": golden.GoldenCountMinSketch(depth, width),
                "params": {"depth": depth, "width": width},
            }
            return True

    def cms_total(self, name) -> int:
        o = self._require(name, PoolKind.CMS)
        with self._lock:
            return int(np.asarray(o["model"].counts[0], np.uint64).sum())

    def cms_reset(self, name) -> None:
        o = self._require(name, PoolKind.CMS)
        with self._lock:
            o["model"].counts[:] = 0

    def cms_add(self, name, H1, H2, weights):
        o = self._require(name, PoolKind.CMS)
        model: golden.GoldenCountMinSketch = o["model"]
        h1w, h2w = hashing.km_reduce_mod(H1, H2, model.width)
        with self._lock:
            model.add_hashed(h1w, h2w, weights)
            return ImmediateResult(
                model.estimate_hashed(h1w, h2w).astype(np.uint32)
            )

    def cms_estimate(self, name, H1, H2):
        o = self._require(name, PoolKind.CMS)
        model = o["model"]
        h1w, h2w = hashing.km_reduce_mod(H1, H2, model.width)
        with self._lock:
            return ImmediateResult(model.estimate_hashed(h1w, h2w).astype(np.uint32))

    def cms_add_seq(self, name, H1, H2, weights):
        """Exact-streaming semantics (parity with the TPU Pallas path):
        one-op-at-a-time through the golden model."""
        o = self._require(name, PoolKind.CMS)
        model = o["model"]
        h1w, h2w = hashing.km_reduce_mod(H1, H2, model.width)
        weights = np.asarray(weights, np.uint32)
        with self._lock:
            est = np.zeros(len(h1w), np.uint32)
            for j in range(len(h1w)):
                model.add_hashed(h1w[j : j + 1], h2w[j : j + 1], weights[j : j + 1])
                est[j] = model.estimate_hashed(h1w[j : j + 1], h2w[j : j + 1])[0]
            return ImmediateResult(est)

    def cms_merge(self, name, other_names) -> None:
        o = self._require(name, PoolKind.CMS)
        with self._lock:
            for n in other_names:
                src = self._require(n, PoolKind.CMS)
                if (
                    src["params"]["depth"] != o["params"]["depth"]
                    or src["params"]["width"] != o["params"]["width"]
                ):
                    raise ValueError("cannot merge CMS with different geometry")
                o["model"].merge(src["model"])
