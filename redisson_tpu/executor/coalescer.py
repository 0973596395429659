"""BatchCoalescer — cross-call op coalescing (the CommandBatchService role).

The reference collects N commands per *explicit* batch
(→ org/redisson/command/CommandBatchService.java) and pipelines them in one
network round trip.  Here coalescing is *implicit and cross-thread*: every
async sketch op lands in a multi-producer queue; a single flush thread
(SURVEY.md §5 race row: one coalescer thread keeps host threading trivial)
drains it into per-(pool, opcode, k) segments and dispatches each segment
as ONE multi-tenant device batch through the exact kernels.

Flush policy (SURVEY.md §7 hard part #1 — latency vs throughput):
- a segment flushes when it reaches ``max_batch`` ops, or
- when its oldest op exceeds the ``batch_window_us`` deadline, or
- immediately when a caller blocks on a result (``flush_hint``).

Pipelining (tuned over a remote link in round 3; not yet measured on an
attached chip): a dispatch whose
result is synced promptly completes in ~10-40 ms wall-clock, but letting
more than ~12 dispatches accumulate un-synced degrades EVERY in-flight op
to ~100 ms (the transport falls back to a slow retirement path).  Two
rules keep the fast regime:
- ``max_inflight`` bounds dispatched-but-uncollected segments (a
  semaphore acquired before dispatch, released by the completer), and
- consecutive same-key segments are merged at pop time, so a backlog
  collapses into fewer, larger launches instead of a deep queue.

Ordering: segments of one pool flush FIFO, so a read submitted after a
write observes it (per-thread read-your-writes at flush granularity);
cross-thread order is arrival order, same as concurrent Redisson clients.

Results resolve through ``concurrent.futures.Future``s carrying slices of
the batch's LazyResult.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import queue
import random
import threading
import time
from collections import deque

from redisson_tpu.executor.tpu_executor import defer_host_fetch
from concurrent.futures import Future
from typing import Callable, Optional

import numpy as np

from redisson_tpu import chaos as _chaos
from redisson_tpu.analysis import witness as _witness
from redisson_tpu.obs import trace as _trace
from redisson_tpu.obs.spans import region as _region
from redisson_tpu.executor.failures import (
    DeadlineExceededError,
    DispatchTimeoutError,
    KernelExecutionError,
    NonRetryableDispatchError,
    RetryExhaustedError,
)


def _op_label(key) -> str:
    """Human label for a segment key (keys are tuples whose first element
    names the op path, e.g. ("bloom_mix", id(pool), k))."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return "op"


class _Segment:
    __slots__ = (
        "key", "pool_key", "dispatch", "chunks", "metas", "futures",
        "nops", "born", "span", "not_before", "attempts",
    )

    def __init__(self, key, pool_key, dispatch):
        self.key = key
        self.pool_key = pool_key
        self.dispatch = dispatch  # fn(list_of_chunk_arrays) -> LazyResult
        # Retry state (self-healing dispatch, ISSUE 3): a segment whose
        # dispatch failed transiently is PARKED — re-enqueued with a
        # ``not_before`` deadline (jittered exponential backoff) instead
        # of sleeping the flush thread, so healthy pools keep flushing
        # while this one backs off.
        self.not_before = None
        self.attempts = 0
        self.chunks: list[tuple] = []  # per-submit tuples of op arrays
        # Per-submit metadata (parallel to chunks) for run-length dispatch:
        # values constant across one submit (tenant row, m, op flag, const
        # key length) travel ONCE per chunk instead of once per op — the
        # dispatch expands them device-side.  None for plain segments.
        self.metas: Optional[list] = None
        # (future, start, n, tenant): tenant rides the tuple the submit
        # path already appends — zero extra hot-path work; the completer
        # turns it into per-tenant counters (obs.tenant_ops).
        self.futures: list[tuple] = []
        self.nops = 0
        self.born = time.monotonic()
        # Lifecycle span (obs/spans.py): one per LAUNCH, not per op, so
        # the producer-side submit path pays one object per segment.
        self.span = None


class HintedFuture:
    """Future adapter: a blocking .result() nudges the coalescer to flush
    immediately instead of waiting out the batch window (the sync-bridge
    behavior of CommandAsyncService#get).  Optional ``transform`` maps the
    raw result slice (mirrors LazyResult's transform kwarg).

    Timeout resolution (ISSUE 7): an explicit ``timeout`` argument wins;
    otherwise the wait is bounded by the op's residual DEADLINE (when one
    rode the submit) capped at the coalescer's config-derived
    ``fetch_timeout_s`` (the old hardcoded 120 s, now ``fetch_timeout_ms``).
    A deadline-bounded miss raises :class:`DeadlineExceededError`
    (overload — the device is not implicated); a fetch-timeout miss
    raises :class:`DispatchTimeoutError` AND records a breaker failure +
    ``rtpu_fetch_timeouts``, like any other completion failure."""

    def __init__(self, fut: Future, coalescer: "BatchCoalescer",
                 transform=None, deadline: Optional[float] = None,
                 op: Optional[str] = None, nops: int = 1):
        self._fut = fut
        self._c = coalescer
        self._transform = transform
        self._deadline = deadline
        self._op = op
        self._nops = nops

    @property
    def deadline(self) -> Optional[float]:
        return self._deadline

    def result(self, timeout: Optional[float] = None):
        deadline_bound = False
        if timeout is None:
            # Default generous enough to absorb a first-compile of a
            # large bucket on a remote device; steady state resolves
            # in milliseconds.
            timeout = getattr(self._c, "fetch_timeout_s", 120.0)
            if self._deadline is not None:
                rem = self._deadline - time.monotonic()
                if rem < timeout:
                    timeout = max(0.0, rem)
                    deadline_bound = True
        if not self._fut.done():
            self._c.flush_hint()
        try:
            v = self._fut.result(timeout)
        except concurrent.futures.TimeoutError as e:
            if deadline_bound:
                self._c.note_deadline_wait(self._op, self._nops)
                raise DeadlineExceededError(
                    f"op deadline expired waiting for "
                    f"{self._op or 'result'} (residual budget "
                    f"{timeout * 1e3:.1f} ms)", stage="fetch_wait",
                ) from e
            err = DispatchTimeoutError(
                f"result not ready within {timeout}s"
            )
            self._c.note_fetch_timeout(self._op, err)
            raise err from e
        return v if self._transform is None else self._transform(v)

    def get(self):
        return self.result()

    def done(self) -> bool:
        return self._fut.done()

    def add_done_callback(self, fn) -> None:
        self._fut.add_done_callback(fn)


class BatchCoalescer:
    def __init__(self, *, batch_window_us: int, max_batch: int, metrics=None,
                 max_inflight: int = 8, retry_attempts: int = 3,
                 retry_interval_s: float = 0.05, max_queued_ops: int = 0,
                 adaptive_inflight: bool = True, min_inflight: int = 2,
                 adaptive_window: bool = True, min_window_us: int = 0,
                 max_window_us: int = 0,
                 group_collect: Optional[Callable] = None, obs=None,
                 retry_max_backoff_s: float = 2.0,
                 retry_jitter: float = 0.2, health=None,
                 max_batch_slow_phase: int = 0,
                 fetch_timeout_s: float = 120.0):
        self.window_s = batch_window_us / 1e6
        self.max_batch = max_batch
        # Phase-aware merge cap (ISSUE 6 satellite, the ROADMAP
        # per-transfer-RT lever): in the link regime where EVERY launch
        # eats ~a round trip, a backlog of queued/parked segments should
        # collapse into FEWER, LARGER launches than the static max_batch
        # allows — merge-at-pop may combine segments up to this bound
        # while the put-RT EWMA says the slow phase holds.  0 disables;
        # values <= max_batch are inert.  Only the POP-TIME merge is
        # affected: submit-side segment fill keeps the static cap, so
        # producer latency is untouched in either phase.
        self.max_batch_slow_phase = max(0, int(max_batch_slow_phase))
        # EWMA of observed launch retirement latency — the link model's
        # put-RT signal.  Genuine samples only for FAST readings (a
        # backlogged completer's near-zero collect time proves nothing);
        # slow readings always count (the result really took that long).
        self._put_rt_ewma = 0.0
        # Adaptive flush window: ``batch_window_us`` is the BASE; an
        # EWMA-of-arrival-rate + queue-pressure controller moves the live
        # window inside [min_window, max_window] — shrinking it under
        # light load (nothing to coalesce: flush for latency) and growing
        # it toward max_window under pressure (let segments approach
        # max_batch: throughput), which bounds p99 batch wait on both
        # sides.  0 → auto bounds derived from the base window.
        self.base_window_s = self.window_s
        self._adaptive_window = adaptive_window
        self.min_window_s = (
            min_window_us if min_window_us > 0 else batch_window_us / 2
        ) / 1e6
        self.max_window_s = (
            max_window_us if max_window_us > 0 else batch_window_us * 8
        ) / 1e6
        self._rate_ewma = 0.0
        self._ops_seen = 0  # monotonic submitted-op counter (under _lock)
        self._rate_mark = (time.monotonic(), 0)
        self.metrics = metrics
        # Observability bundle (obs/__init__.py): per-launch lifecycle
        # spans (submit -> coalesce-wait -> device-dispatch -> D2H-fetch)
        # and the TraceAnnotation that correlates them with device traces.
        self.obs = obs
        # RedisExecutor-style retry budget for dispatch-time failures
        # (executor/failures.py): state is not consumed when the executor
        # method raises synchronously, so re-dispatch is safe.  Retries
        # back off EXPONENTIALLY with jitter and park the segment in the
        # queue (not the flush thread) — see _flush / _next_locked.
        self.retry_attempts = max(1, retry_attempts)
        self.retry_interval_s = retry_interval_s
        self.retry_max_backoff_s = max(retry_interval_s, retry_max_backoff_s)
        self.retry_jitter = max(0.0, min(1.0, retry_jitter))
        self._rng = random.Random(0x5EEDBACC)  # jitter only — not fairness
        # Optional DispatchHealth (executor/health.py): per-(shard, op)
        # circuit breakers.  None → standalone coalescer, retry-only.
        self._health = health
        # Overload control plane (ISSUE 7).  ``fetch_timeout_s`` bounds a
        # no-deadline blocking .result() (the old hardcoded 120 s, now
        # config fetch_timeout_ms).  The admission estimator keeps an
        # EWMA of flush-to-retire latency and ops-per-launch; a submit
        # carrying a deadline is shed FAST when the estimated queue wait
        # exceeds its residual budget (blocking at the queue bound stays
        # the no-deadline default).
        self.fetch_timeout_s = max(0.001, float(fetch_timeout_s))
        # Durability tier (ISSUE 10): under appendfsync=always the
        # engine points this at OpJournal.lag_s — the estimated wait
        # until a NEW record fsyncs rides the admission estimate, so a
        # slow journal disk sheds deadline-carrying load at the door
        # instead of queueing acks unboundedly behind the fsync barrier.
        self.journal_lag_s: Optional[Callable[[], float]] = None
        self._service_ewma_s = 0.0
        self._ops_per_launch_ewma = 0.0
        self.last_est_wait_s = 0.0  # rtpu_admission_est_wait_us gauge
        # Engine-side backpressure (the pooled-acquire role): submit()
        # blocks while this many ops sit queued ahead of the flush thread.
        self.max_queued_ops = max_queued_ops if max_queued_ops > 0 else 8 * max_batch
        self._queued_ops = 0
        # Bounds dispatched-but-uncollected segments (see module docstring).
        # A counter + condition instead of a semaphore so the limit can
        # ADAPT: when a launch retires slowly (the transport's slow phase)
        # the window shrinks multiplicatively toward min_inflight; fast
        # retirements grow it back additively (AIMD).
        self._max_inflight_cfg = max(1, max_inflight)
        self._min_inflight = max(1, min(min_inflight, self._max_inflight_cfg))
        self._adaptive = adaptive_inflight
        self._inflight_limit = self._max_inflight_cfg
        self._uncollected = 0
        self._inflight_cv = threading.Condition(
            _witness.named(threading.Lock(), "coalescer.inflight")
        )
        self._good_streak = 0
        # Retirement thresholds (s): tuned over a remote link, not yet
        # measured on an attached chip — there pipelined launches
        # retired in 10-50 ms in the fast regime; >250 ms signalled the
        # slow phase / cliff.
        self.slow_launch_s = 0.25
        self.fast_launch_s = 0.08
        # Queued segments in creation order (the flush order).  A segment
        # stays JOINABLE while queued: ``_open`` maps segment key -> the
        # segment new ops of that key append to, and ``_pool_tail`` maps a
        # pool identity -> its most recently created segment.  An op may
        # only join a segment that is still its pool's tail — per-pool
        # strict arrival order (the slot-FIFO behavior of one Redis
        # connection) with cross-pool coalescing in between.
        self._order: deque[_Segment] = deque()
        self._open: dict = {}
        self._pool_tail: dict = {}
        self._hurry = False  # a caller is blocking: drain the queue now
        # Witness-named (analysis/witness.py): lock-order + blocking
        # discipline on the queue lock is checked at test time under
        # RTPU_LOCK_WITNESS=1; named() is identity when it is off.
        self._lock = _witness.named(threading.Lock(), "coalescer.queue")
        self._wake = threading.Condition(self._lock)
        # Producers blocked on the queue bound wait here; notified as
        # segments pop for dispatch.  FIFO tickets: without ordering, a
        # bulk submit larger than the bound only admits at an EMPTY
        # queue, and a stream of small submits can refill it forever
        # (livelock); with tickets, later submits queue behind it.
        self._admit = threading.Condition(self._lock)
        self._admit_q: deque = deque()
        self._inflight = 0  # popped but not yet dispatched
        self._closed = False
        # Device-side result mailbox (executor.collect_group): when the
        # completer finds several launches pending, their packed results
        # concatenate on device and come home in ONE D2H instead of one
        # fetch per launch — over a remote link each host fetch cost a
        # full round trip, whatever its size.
        self._group_collect = group_collect
        # Dispatch and completion are decoupled: the flush thread only
        # enqueues device work (cheap), while this thread blocks on result
        # transfers and resolves futures.  Without it every segment's D2H
        # round trip would serialize the flush loop — one link latency per
        # segment instead of a deep async pipeline.
        self._completions: "queue.Queue" = queue.Queue()
        self._completer = threading.Thread(
            target=self._complete_loop, name="rtpu-completer", daemon=True
        )
        self._completer.start()
        self._thread = threading.Thread(
            target=self._run, name="rtpu-coalescer", daemon=True
        )
        self._thread.start()

    # -- producer side -----------------------------------------------------

    def submit(self, key, dispatch: Callable, arrays: tuple, nops: int,
               pool_key=None, meta=None, tenant=None,
               deadline: Optional[float] = None) -> Future:
        """Queue ``nops`` ops (column arrays in ``arrays``) for the segment
        identified by ``key``; returns a Future of the per-op result slice.

        ``pool_key`` identifies the state the ops touch (defaults to
        ``key``): an op joins an existing queued segment of its key only
        while that segment is still the pool's most recent — otherwise a
        fresh segment is created, preserving per-pool arrival order.

        ``meta``: per-chunk run-length metadata; when present the segment's
        dispatch is called as ``dispatch(cols, metas)`` where ``metas`` is
        the list of (nops, meta) per chunk in order.  All submits of one
        key must agree on using meta or not (keys embed the path).

        ``deadline``: absolute monotonic instant after which the ops are
        worthless (ISSUE 7).  With one set, submit FAILS FAST with
        DeadlineExceededError instead of blocking: already expired, the
        admission estimate says the queue wait alone exceeds the residual
        budget, or the backpressure wait outlives it.  Ops shed here (and
        by the expired-segment sweep at flush) were never dispatched —
        no acked write is ever shed."""
        if pool_key is None:
            pool_key = key
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("coalescer is shut down")
            if deadline is not None:
                now = time.monotonic()
                if now >= deadline:
                    self._count_shed("deadline", "submit", nops)
                    raise DeadlineExceededError(
                        f"op deadline already expired at submit "
                        f"({_op_label(key)}, {nops} ops)", stage="submit",
                    )
                est = self.estimate_wait_s()
                if est > deadline - now:
                    self._count_shed("admission", "admission", nops)
                    raise DeadlineExceededError(
                        f"admission control: estimated queue wait "
                        f"{est * 1e3:.1f} ms exceeds residual deadline "
                        f"{(deadline - now) * 1e3:.1f} ms "
                        f"({_op_label(key)}, {nops} ops)",
                        stage="admission",
                    )
            # Backpressure: block while the queue is at capacity (an
            # oversize single submit is admitted when the queue is empty,
            # so it can never deadlock).  FIFO: later submits wait behind
            # an already-blocked one, so sustained small traffic cannot
            # starve a bulk submit.  The flush thread only ever REMOVES
            # queued ops, so this wait cannot starve globally.  An op
            # carrying a deadline waits only out its residual budget.
            def _full() -> bool:
                return (
                    self._queued_ops > 0
                    and self._queued_ops + nops > self.max_queued_ops
                )

            if _full() and not self._closed:
                ticket = object()
                self._admit_q.append(ticket)
                t_admit = time.monotonic()
                try:
                    with _region("rtpu:admit_wait"):
                        while not self._closed and (
                            self._admit_q[0] is not ticket or _full()
                        ):
                            wait_s = 1.0
                            if deadline is not None:
                                wait_s = deadline - time.monotonic()
                                if wait_s <= 0:
                                    self._count_shed("deadline", "queue", nops)
                                    raise DeadlineExceededError(
                                        f"queue full past op deadline "
                                        f"({_op_label(key)}, {nops} ops)",
                                        stage="queue",
                                    )
                                wait_s = min(wait_s, 1.0)
                            self._wake.notify()
                            self._admit.wait(timeout=wait_s)
                finally:
                    try:
                        self._admit_q.remove(ticket)
                    except ValueError:  # pragma: no cover
                        pass
                    self._admit.notify_all()  # next ticket re-checks
                    if self.obs is not None:
                        self.obs.admit_wait_seconds.observe(
                            (), time.monotonic() - t_admit
                        )
            if self._closed:
                raise RuntimeError("coalescer is shut down")
            seg = self._open.get(key)
            if (
                seg is None
                or self._pool_tail.get(seg.pool_key) is not seg
                or seg.nops + nops > self.max_batch
            ):
                seg = _Segment(key, pool_key, dispatch)
                if self.obs is not None:
                    seg.span = self.obs.spans.start(_op_label(key))
                if meta is not None:
                    seg.metas = []
                self._open[key] = seg
                self._pool_tail[pool_key] = seg
                self._order.append(seg)
                # Wake the flush thread so the window deadline is armed from
                # the segment's birth, not from the next idle-poll tick.
                self._wake.notify()
            seg.chunks.append(arrays)
            if meta is not None:
                seg.metas.append((nops, meta))
            if _trace.ENABLED and seg.span is not None:
                # Distributed tracing (ISSUE 13): a sampled request's
                # ambient context parents this launch — the span's
                # finish hook records the launch (with its phase
                # breakdown) into every linked trace.  One attr read +
                # branch when tracing is off.
                tctx = _trace.current()
                if tctx is not None:
                    seg.span.link(tctx)
            seg.futures.append((fut, seg.nops, nops, tenant, deadline))
            seg.nops += nops
            self._queued_ops += nops
            self._ops_seen += nops  # feeds the adaptive-window EWMA
            if seg.nops >= self.max_batch:
                self._wake.notify()
        return fut

    def flush_hint(self) -> None:
        """A caller is about to block on a Future — flush eagerly."""
        with self._lock:
            self._hurry = True
            self._wake.notify()

    # -- overload control plane (ISSUE 7) ----------------------------------

    def pressure(self) -> float:
        """Queue pressure in ~[0, 1]: queued ops over the admission
        bound (can exceed 1.0 transiently — an oversize single submit is
        admitted at an empty queue).  The RESP front door sheds at
        ingress once this crosses its watermark."""
        return self._queued_ops / max(1, self.max_queued_ops)

    def _phase_service_s(self) -> float:
        """Per-launch service estimate with the link-phase correction
        (ROADMAP overload item (a)): the flush-to-retire EWMA is the
        admission base, but its ~5-sample constant trails a link-phase
        flip, so for the first seconds after one the estimator
        under-admitted (stale-fast base in the new slow phase) or
        over-admitted nothing and SHED healthy traffic (stale-slow base
        in the new fast phase).  ``merge_cap()``'s put-RT EWMA is the
        faster phase signal — slow samples always count and its ~4-
        sample constant flips within a couple of launches — so it
        corrects the base in BOTH directions: a slow put-RT FLOORS the
        service estimate (a launch cannot retire faster than the link
        round trip it now costs), a fast put-RT under a stale-slow base
        CAPS it near the fast-phase bound."""
        svc = self._service_ewma_s
        rt = self._put_rt_ewma
        if svc <= 0.0 or rt <= 0.0:
            return svc
        if rt > self.slow_launch_s:
            return max(svc, rt)
        if rt < self.fast_launch_s and svc > self.slow_launch_s:
            return max(rt, self.fast_launch_s)
        return svc

    def estimate_wait_s(self) -> float:
        """Admission-control estimate of the queue wait a NEW op faces:
        launches ahead of it (queued ops at the observed ops-per-launch,
        plus dispatched-but-uncollected) times the phase-corrected
        flush-to-retire EWMA (see _phase_service_s), divided by the
        live pipelining window.  Zero until the first launch retires
        (an idle engine admits everything).  The ``overload.pressure``
        chaos point inflates the estimate deterministically
        (chaos.bias) so shedding is drivable in tests without real
        load."""
        svc = self._phase_service_s()
        if svc <= 0.0:
            est = 0.0
        else:
            opl = max(1.0, self._ops_per_launch_ewma)
            launches_ahead = self._queued_ops / opl + self._uncollected
            est = svc * launches_ahead / max(1, self._inflight_limit)
        jl = self.journal_lag_s
        if jl is not None:
            try:
                est += jl()
            except Exception:  # pragma: no cover — broken journal
                pass
        if _chaos.ENABLED:
            est += _chaos.bias("overload.pressure")
        self.last_est_wait_s = est
        return est

    def _count_shed(self, reason: str, stage: str, nops: int) -> None:
        if self.obs is not None:
            self.obs.shed_ops.inc((reason,), nops)
            self.obs.deadline_exceeded.inc((stage,), nops)

    def note_fetch_timeout(self, op: Optional[str], exc) -> None:
        """A blocking result wait hit the config fetch timeout: treat it
        like any other completion failure — it feeds the breaker (a
        device whose results never arrive must eventually open the
        circuit) and the rtpu_fetch_timeouts counter."""
        if self._health is not None:
            self._health.record_failure(op or "fetch", exc)
        if self.obs is not None:
            self.obs.fetch_timeouts.inc((op or "fetch",))

    def note_deadline_wait(self, op: Optional[str], nops: int = 1) -> None:
        """A result wait was cut short by the op's own deadline: overload
        accounting only (ops-denominated, like every other stage) — the
        device is not implicated, so no breaker failure is recorded."""
        if self.obs is not None:
            self.obs.deadline_exceeded.inc(("fetch_wait",), nops)

    @staticmethod
    def _all_expired(seg: _Segment, now: float) -> bool:
        """True when EVERY op in the segment carries a deadline and all
        of them have passed — the segment is pure waste: shed it before
        it costs a device launch (or before its parked backoff matures)."""
        return bool(seg.futures) and all(
            dl is not None and dl <= now
            for _f, _s, _n, _t, dl in seg.futures
        )

    def _shed_segment(self, seg: _Segment) -> None:
        """Resolve every future of a fully-expired segment with
        DeadlineExceededError — strictly pre-dispatch, so nothing in it
        was ever applied (retry segments were dispatched but FAILED:
        equally unapplied)."""
        if seg.span is not None:
            seg.span.nops = seg.nops
            seg.span.stamp("device_dispatch")
            seg.span.finish(error=True)
        self._count_shed("deadline", "queue", seg.nops)
        e = DeadlineExceededError(
            f"op deadline expired while queued "
            f"({_op_label(seg.key)}, {seg.nops} ops, "
            f"attempts={seg.attempts})", stage="queue",
        )
        for fut, _start, _n, _tenant, _dl in seg.futures:
            if fut.set_running_or_notify_cancel():
                fut.set_exception(e)

    # -- flush thread ------------------------------------------------------

    def _detach_locked(self, seg: _Segment) -> None:
        """Remove a segment from the queue bookkeeping (it is no longer
        joinable and no longer counts toward backpressure)."""
        if self._open.get(seg.key) is seg:
            del self._open[seg.key]
        if self._pool_tail.get(seg.pool_key) is seg:
            del self._pool_tail[seg.pool_key]
        if seg.nops:
            self._queued_ops -= seg.nops
            self._admit.notify_all()

    def _pop_seg_locked(self, seg: _Segment) -> _Segment:
        self._order.remove(seg)
        self._detach_locked(seg)
        seg.not_before = None
        if not self._order:
            self._hurry = False
        self._inflight += 1
        return seg

    def _requeue_locked(self, seg: _Segment, not_before: float) -> None:
        """Park a transiently-failed segment back at the FRONT of the
        queue with a backoff deadline.  Front keeps it ahead of every
        later segment of its own pool (arrival order); other pools skip
        past it via the parked-pool scan in _next_locked, so one failing
        pool never stalls healthy traffic (ISSUE 3 satellite: the old
        in-place ``time.sleep`` blocked EVERY queue)."""
        seg.not_before = not_before
        self._inflight -= 1
        if seg.nops:
            self._queued_ops += seg.nops
        self._order.appendleft(seg)
        self._wake.notify()

    def merge_cap(self) -> int:
        """Live pop-time merge bound: the static ``max_batch`` in the
        fast phase, ``max_batch_slow_phase`` while the put-RT EWMA says
        each launch costs ~a round trip (fewer, larger launches are the
        only lever left there — the per-op near cache already dodges the
        link, and transfer count per launch is fixed)."""
        cap = self.max_batch_slow_phase
        if cap > self.max_batch and self._put_rt_ewma > self.slow_launch_s:
            return cap
        return self.max_batch

    def _merge_consecutive_locked(self, head: _Segment, i: int) -> _Segment:
        """Fold queued segments with the same key immediately FOLLOWING
        ``head``'s old position into it (up to the live merge cap — see
        merge_cap): a backlog becomes one larger launch instead of a deep
        dispatch queue.  Only the consecutive run is merged — a
        different-key segment (possibly the same pool on another op path)
        acts as an order fence, so per-pool arrival order is preserved."""
        cap = self.merge_cap()
        while i < len(self._order):
            nxt = self._order[i]
            if (
                nxt.key != head.key
                or head.nops + nxt.nops > cap
                or nxt.not_before is not None
            ):
                break
            del self._order[i]
            self._detach_locked(nxt)
            if nxt.span is not None:
                # Its ops ride the head's span; trace parent links move
                # with them (a merged launch still reports to every
                # sampled request it serves).
                nxt.span.abandon(into=head.span)
            head.chunks.extend(nxt.chunks)
            if head.metas is not None:
                head.metas.extend(nxt.metas)
            for fut, start, n, tenant, dl in nxt.futures:
                head.futures.append((fut, head.nops + start, n, tenant, dl))
            head.nops += nxt.nops
        if not self._order:
            self._hurry = False
        return head

    def _next_locked(self, now: float):
        """(segment, index, deadline): the next dispatchable segment
        honoring per-pool FIFO around PARKED (retry-backoff) segments.
        A parked segment blocks its own pool's later segments (read-your-
        writes) but nothing else; a barrier never overtakes a parked
        segment submitted before it.  Returns (None, -1, deadline) when
        nothing is ready — ``deadline`` is the earliest instant something
        becomes actionable (backoff expiry or flush-window maturity)."""
        parked: set = set()
        deadline = None
        for i, seg in enumerate(self._order):
            if seg.dispatch is None:  # barrier
                if parked:
                    break  # waits for parked segments ahead of it
                return seg, i, None
            if seg.pool_key in parked:
                continue
            nb = seg.not_before
            if nb is not None and nb > now and not self._closed:
                if self._all_expired(seg, now):
                    # Every op in the parked segment is past its
                    # deadline: don't wait out the backoff — pop it now
                    # so the flush loop sheds it (futures resolve fast,
                    # its pool's later segments unblock).
                    return seg, i, None
                parked.add(seg.pool_key)
                deadline = nb if deadline is None else min(deadline, nb)
                continue
            if (
                seg.nops >= self.max_batch
                or seg.attempts > 0
                or self._closed
                or self._hurry
                or now - seg.born >= self.window_s
            ):
                return seg, i, None
            # Young and small: it keeps absorbing ops until the window
            # matures.  Later segments are younger still — stop scanning.
            d = seg.born + self.window_s
            deadline = d if deadline is None else min(deadline, d)
            break
        return None, -1, deadline

    def _update_window_locked(self) -> None:
        """Adaptive flush window (called from the flush loop, under the
        lock): EWMA the arrival rate (~50 ms time constant), map rate +
        queue backlog to a pressure score in [0, 1], and set the live
        window inside [min_window, max_window].  Light load → min window
        (an op that won't be joined should not wait); pressure → max
        window (let segments fill toward max_batch)."""
        if not self._adaptive_window:
            return
        now = time.monotonic()
        t0, seen0 = self._rate_mark
        dt = now - t0
        if dt < 0.002:  # sub-controller-tick: keep the current estimate
            return
        inst = (self._ops_seen - seen0) / dt
        self._rate_mark = (now, self._ops_seen)
        a = min(1.0, dt / 0.05)
        self._rate_ewma += a * (inst - self._rate_ewma)
        # Pressure: how much of max_batch the current rate would supply
        # within the max window, plus admission-queue backlog (a backlog
        # means dispatch is the bottleneck — bigger launches help).
        fill = self._rate_ewma * self.max_window_s / self.max_batch
        backlog = self._queued_ops / max(1, self.max_queued_ops)
        p = min(1.0, fill + backlog)
        self.window_s = (
            self.min_window_s + (self.max_window_s - self.min_window_s) * p
        )

    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._order and not self._closed:
                    self._hurry = False
                    self._wake.wait(timeout=0.05)
                if self._closed and not self._order:
                    return
                if not self._order:
                    continue
                self._update_window_locked()
                now = time.monotonic()
                seg, idx, deadline = self._next_locked(now)
                if seg is None:
                    # Everything queued is parked (backoff) or young:
                    # wait until the earliest deadline or a notify from a
                    # full batch / a blocking caller's hint.
                    if deadline is None:
                        self._wake.wait(timeout=0.05)
                        continue
                    # A young segment's window (or a parked backoff)
                    # holds the next launch back.
                    with _region("rtpu:coalesce:window"):
                        self._wake.wait(
                            timeout=min(max(deadline - now, 0.0005), 0.05)
                        )
                    continue
                self._pop_seg_locked(seg)
                if seg.dispatch is not None and not self._all_expired(
                    seg, now
                ):
                    seg = self._merge_consecutive_locked(seg, idx)
            # Expired-segment sweep (ISSUE 7): a segment whose EVERY op
            # is past its deadline is shed here — before staging, before
            # a launch slot, before the device sees it.  (Merging is
            # skipped for an expired head so a fresh same-key segment
            # behind it is not dragged into the shed.)
            if seg.dispatch is not None and self._all_expired(
                seg, time.monotonic()
            ):
                with self._lock:
                    self._inflight -= 1
                self._shed_segment(seg)
                continue
            cols = stage_exc = None
            if seg.dispatch is not None:
                # Stage FIRST (host-side pad/concat of the segment's
                # chunks), THEN wait for a launch slot: while prior
                # launches execute on device, this thread is packing the
                # next block — H2D staging and device compute pipeline
                # instead of serializing.  The slot wait still precedes
                # dispatch, keeping the transport's in-flight window
                # shallow and letting the queue behind us keep merging.
                try:
                    with _region("rtpu:coalesce:stage"):
                        cols = self._stage(seg)
                except Exception as e:
                    stage_exc = e
                if stage_exc is None:
                    self._acquire_launch_slot(_op_label(seg.key))
            self._flush(seg, cols, stage_exc)

    def _stage(self, seg: _Segment) -> list:
        """Host staging: concatenate the segment's per-submit chunks into
        flush columns.  Runs BEFORE the launch-slot wait (see _run) so it
        overlaps with in-flight device execution; the span's host_stage
        phase measures exactly this work."""
        if seg.span is not None:
            seg.span.stamp("coalesce_wait")  # queue time ends here
        cols = [
            c[0] if len(c) == 1 else np.concatenate(c)
            for c in zip(*seg.chunks)
        ]
        if seg.span is not None:
            seg.span.stamp("host_stage")
        return cols

    def _acquire_launch_slot(self, op: str) -> None:
        """Wait for an in-flight slot; the wait is recorded per launch in
        ``rtpu_launch_slot_wait_seconds{op}`` (it stays inside the span's
        device_dispatch phase)."""
        t0 = time.monotonic()
        with _region("rtpu:coalesce:slot_wait"):
            with self._inflight_cv:
                while self._uncollected >= self._inflight_limit:
                    self._inflight_cv.wait(timeout=0.5)
                self._uncollected += 1
        if self.obs is not None:
            self.obs.launch_slot_wait_seconds.observe(
                (op,), time.monotonic() - t0
            )

    def _release_launch_slot(self, collect_s: Optional[float],
                             genuine: bool = True) -> None:
        """Free a dispatched-launch slot; ``collect_s`` (the observed
        retirement latency of the launch, None on error paths) drives the
        AIMD window: halve on a slow retirement, +1 after a streak of
        fast ones.  ``genuine``: False when the completer was backlogged
        when it picked this launch up — such launches retired while the
        completer was blocked elsewhere, so a near-zero collect time says
        nothing about link health and must NOT feed the grow streak
        (slow measurements stay valid either way: the result really did
        take that long to arrive)."""
        with self._inflight_cv:
            self._uncollected = max(0, self._uncollected - 1)
            if collect_s is not None and (
                genuine or collect_s > self.slow_launch_s
            ):
                # Link-phase EWMA (feeds merge_cap): ~4-sample constant —
                # fast enough to catch a phase flip, slow enough that one
                # stall doesn't flap the cap.
                self._put_rt_ewma += 0.25 * (collect_s - self._put_rt_ewma)
            if self._adaptive and collect_s is not None:
                if collect_s > self.slow_launch_s:
                    self._inflight_limit = max(
                        self._min_inflight, self._inflight_limit // 2
                    )
                    self._good_streak = 0
                elif genuine and collect_s < self.fast_launch_s:
                    self._good_streak += 1
                    if (
                        self._good_streak >= 4
                        and self._inflight_limit < self._max_inflight_cfg
                    ):
                        self._inflight_limit += 1
                        self._good_streak = 0
            self._inflight_cv.notify_all()

    def _backoff_s(self, attempts: int) -> float:
        """Jittered exponential backoff for dispatch retries: base grows
        2x per attempt, capped at retry_max_backoff_s, scaled by a
        uniform ±retry_jitter factor (decorrelates a fleet of retrying
        segments so they never thundering-herd the device)."""
        base = min(
            self.retry_interval_s * (2 ** max(0, attempts - 1)),
            self.retry_max_backoff_s,
        )
        if self.retry_jitter:
            base *= 1.0 + self.retry_jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, base)

    def _fail_futures(self, seg: _Segment, e: BaseException) -> None:
        if seg.span is not None:
            seg.span.nops = seg.nops
            seg.span.stamp("device_dispatch")
            seg.span.finish(error=True)
        for fut, start, n, _, _dl in seg.futures:
            if fut.set_running_or_notify_cancel():
                fut.set_exception(
                    e
                    if isinstance(e, RetryExhaustedError)
                    else KernelExecutionError(seg.key, start, n, seg.nops, e)
                )

    def _flush(self, seg: _Segment, cols=None, stage_exc=None) -> None:
        t0 = time.monotonic()
        try:
            if seg.dispatch is None:  # barrier segment (drain)
                with self._lock:
                    self._inflight -= 1
                for fut, _, _, _, _ in seg.futures:
                    if fut.set_running_or_notify_cancel():
                        fut.set_result(None)
                return
            if stage_exc is not None:
                # Staging failed before a launch slot was taken: surface
                # through the shared error path below, which skips the
                # slot release for this case.
                raise stage_exc
            # Mailbox engines: skip the per-launch eager D2H prefetch
            # when a completion BACKLOG exists (the completer will scoop
            # a group and fetch once) — each extra host-bound transfer
            # costs a full round trip in slow phases.  With an empty
            # completion queue no group will form, and the eager copy is
            # exactly the overlap that hides the fetch RT for the lone
            # result, so keep it then.
            fetch_ctx = (
                defer_host_fetch()
                if (
                    self._group_collect is not None
                    and self._completions.qsize() > 0
                )
                else contextlib.nullcontext()
            )
            op = _op_label(seg.key)
            h = self._health
            if h is not None and not h.allow_dispatch(op):
                # Circuit OPEN for this op path: fail fast — the device
                # is not touched, callers get the typed retry surface
                # with the breaker as cause (the engine's degraded-mode
                # failover keeps NEW ops off this path entirely).
                from redisson_tpu.executor.health import CircuitOpenError

                raise RetryExhaustedError(
                    seg.attempts + 1, CircuitOpenError(0, op)
                )
            lazy = None
            try:
                # Correlates the host span's device-dispatch phase with
                # the device trace: the host region rtpu:dispatch:<op> in
                # a jax.profiler capture (docs/observability.md).
                with fetch_ctx, _region("rtpu:dispatch:" + op):
                    if seg.metas is not None:
                        lazy = seg.dispatch(cols, seg.metas)
                    else:
                        lazy = seg.dispatch(cols)
            except NonRetryableDispatchError as e:
                # Part of the launch already applied (compound dispatch
                # split by a mid-segment migration): re-dispatch would
                # double-apply the committed part.
                if h is not None:
                    h.record_failure(op, e)
                raise RetryExhaustedError(seg.attempts + 1, e)
            except Exception as e:
                # Dispatch-time failure: pool state not consumed (the
                # executor method raised before returning) — safe to
                # re-dispatch.  Instead of sleeping HERE (which would
                # stall every queue behind one failing segment), park the
                # segment with a jittered-exponential-backoff deadline
                # and return the flush thread to healthy traffic.
                if h is not None:
                    h.record_failure(op, e)
                seg.attempts += 1
                if seg.attempts >= self.retry_attempts or (
                    h is not None and not h.allow_dispatch(op)
                ):
                    raise RetryExhaustedError(seg.attempts, e)
                backoff = self._backoff_s(seg.attempts)
                with self._lock:
                    self._requeue_locked(seg, time.monotonic() + backoff)
                self._release_launch_slot(None)
                return
            # NOTE: no record_success here — a dispatch enqueue proving
            # anything would let a device whose every RESULT fetch fails
            # reset the breaker's consecutive-failure count each launch
            # (enqueue-ok/fetch-fail alternation never opens the
            # circuit).  Success is only proven at COMPLETION; the
            # completer records it.
            if seg.span is not None:
                seg.span.stamp("device_dispatch")  # enqueue done, async
            with self._lock:
                # Dispatched (device-ordered): drain() may proceed even
                # though result transfer is still in flight.
                self._inflight -= 1
            self._completions.put((seg, lazy, t0))
        except Exception as e:
            with self._lock:
                if self._inflight > 0:
                    self._inflight -= 1
            if stage_exc is None:
                # A slot was acquired in _run only when staging succeeded;
                # releasing one that was never taken would hand another
                # launch's slot back early.
                self._release_launch_slot(None)
            self._fail_futures(seg, e)

    def _complete_loop(self) -> None:
        stop = False
        while not stop:
            item = self._completions.get()
            if item is None:
                return
            # Mailbox drain: scoop everything already queued behind this
            # completion so the whole group comes home in one D2H
            # (collect_group).  A backlog here means those launches
            # retired while we were busy — their individual collect times
            # are not genuine link samples either way.
            # Scoop bound: max_inflight caps pending completions well
            # below this; collect_group's multi-round concat tree makes
            # ANY group size one fetch, so bigger scoops only help.
            group = [item]
            while self._group_collect is not None and len(group) < 64:
                try:
                    nxt = self._completions.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                group.append(nxt)
            genuine = len(group) == 1 and self._completions.qsize() == 0
            t_collect = time.monotonic()
            if len(group) > 1:
                try:
                    with _region("rtpu:complete:fetch"):
                        self._group_collect(
                            [lazy for _, lazy, _ in group if lazy is not None]
                        )
                except Exception:
                    pass  # per-item .result() below surfaces the failure
            first = True
            for seg, lazy, t0 in group:
                try:
                    with _region("rtpu:complete:fetch"):
                        res = lazy.result() if lazy is not None else None
                    self._release_launch_slot(
                        time.monotonic() - t_collect if first else None,
                        genuine=genuine,
                    )
                    first = False
                    if self._health is not None:
                        self._health.record_success(_op_label(seg.key))
                    # Admission estimator (ISSUE 7): flush-to-retire
                    # latency + ops-per-launch EWMAs (~5-sample time
                    # constant) — the service model behind
                    # estimate_wait_s.  GIL-atomic float stores; exact
                    # interleaving doesn't matter for an estimator.
                    retire_s = time.monotonic() - t0
                    self._service_ewma_s += 0.2 * (
                        retire_s - self._service_ewma_s
                    )
                    self._ops_per_launch_ewma += 0.2 * (
                        seg.nops - self._ops_per_launch_ewma
                    )
                    if seg.span is not None:
                        seg.span.nops = seg.nops
                        # Load attribution (ISSUE 16): stash the
                        # (tenant, nops) composition so the recorder can
                        # split the launch's device time per tenant.
                        # Only when a loadmap is armed — the common path
                        # allocates nothing extra.
                        if (self.obs is not None
                                and self.obs.spans.loadmap is not None
                                and self.obs.spans.loadmap.enabled):
                            seg.span.tenants = [
                                (t, n) for _, _, n, t, _ in seg.futures
                                if t is not None
                            ] or None
                        seg.span.stamp("d2h_fetch")
                        seg.span.finish()
                    if self.obs is not None:
                        # Per-tenant accounting, deferred from submit to
                        # HERE so producers never pay the counter lock.
                        op = _op_label(seg.key)
                        for _, _, n, tenant, _dl in seg.futures:
                            if tenant is not None:
                                self.obs.tenant_ops.inc((tenant, op), n)
                    with _region("rtpu:complete:resolve"):
                        for fut, start, n, _, _dl in seg.futures:
                            if fut.set_running_or_notify_cancel():
                                fut.set_result(
                                    None if res is None
                                    else res[start : start + n]
                                )
                except Exception as e:
                    # Completion-time failure: the device batch died after
                    # donation — NOT retryable; attribute each caller's op
                    # range within the failed launch (partial-batch surface).
                    if self._health is not None:
                        self._health.record_failure(_op_label(seg.key), e)
                    self._release_launch_slot(None)
                    first = False
                    if seg.span is not None:
                        seg.span.nops = seg.nops
                        seg.span.stamp("d2h_fetch")
                        seg.span.finish(error=True)
                    for fut, start, n, _, _dl in seg.futures:
                        if fut.set_running_or_notify_cancel():
                            fut.set_exception(
                                KernelExecutionError(
                                    seg.key, start, n, seg.nops, e
                                )
                            )
                if self.metrics is not None:
                    self.metrics.record_batch(
                        nops=seg.nops,
                        wait_s=t0 - seg.born,
                        flush_s=time.monotonic() - t0,
                    )

    def drain(self, timeout: float = 30.0) -> None:
        """Barrier: block until every segment submitted BEFORE this call has
        dispatched — used by direct state reads (count/bitop/merge/snapshot)
        so they observe all prior ops.  Implemented as a sentinel segment,
        so sustained producers appending behind the barrier cannot starve
        it."""
        fut: Future = Future()
        with self._lock:
            if self._closed:
                return
            if not self._order and self._inflight == 0:
                return
            barrier = object()  # unique key: never merged into
            seg = _Segment(barrier, barrier, None)
            seg.futures.append((fut, 0, 0, None, None))
            self._order.append(seg)
            self._hurry = True  # the caller is about to block on it
            self._wake.notify()
        fut.result(timeout)

    # -- lifecycle ---------------------------------------------------------

    def shutdown(self, timeout: Optional[float] = 5.0) -> None:
        with self._lock:
            self._closed = True
            self._wake.notify_all()
        self._thread.join(timeout=timeout)
        if not self._thread.is_alive():
            # Flush thread fully drained: safe to stop the completer after
            # the work already queued.  If the join timed out (e.g. a slow
            # first-compile inside dispatch), leave the daemon completer
            # running so late completions still resolve their futures.
            self._completions.put(None)
            self._completer.join(timeout=timeout)
