"""AOT bucket pre-warming — kill the first-touch compile cliff.

BENCH_r05 showed the config-4 serving path at 9,933 ops/s on its first
pass and 1,105,792 on its second: a 111x spread caused entirely by jit
first-touch compiles landing INSIDE the serving window.  This module
moves those compiles to a background thread: on pool attach (and on
every pool growth, which changes the state shape and thus every jit
key), the engine registers the pool's hot dispatch signatures here, and
the pre-warm thread drives each one through the REAL executor methods at
every padded bucket of the (min_bucket → max_batch) ladder.

Design constraints that shaped this:

- ``jax.jit(f).lower(...).compile()`` does NOT populate the jit call
  cache (measured on jax 0.4.37: the first real call recompiles), so
  warming must CALL the jitted functions with concrete arrays.
- Calling the wrapped executor methods would hold the dispatch lock for
  the whole compile (30-60s per shape on a remote TPU) and stall
  serving.  Warm calls therefore go through the UNWRAPPED methods
  (``_locked`` keeps the original behind ``__wrapped__``) against a
  private scratch pool of the same state shape: the jit cache and its
  compiled executables are shared (keys include only shapes/params),
  while the scratch state makes the calls race-free without the lock —
  op content is irrelevant, only avals reach the compile cache.
- Warm batches are harmless by construction anyway (contains-only /
  OP_GET / weight-0), but they run against scratch state, so even
  mutating signatures (HLL adds) cannot perturb tenant data.

A process-wide ``jax.monitoring`` listener counts XLA backend compiles;
tests and the bench use :func:`compile_count` to assert that NO compile
happens on the serving path after :meth:`BucketPrewarmer.wait_idle`.
"""

from __future__ import annotations

import atexit
import queue
import threading
from typing import Callable, Optional

from redisson_tpu import chaos as _chaos

import numpy as np

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_count = 0
_listener_lock = threading.Lock()
_listener_on = False


def _ensure_listener() -> None:
    global _listener_on
    with _listener_lock:
        if _listener_on:
            return
        import jax

        def on_duration(name, secs, **kw):
            global _compile_count
            if name == _COMPILE_EVENT:
                _compile_count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _listener_on = True


def compile_count() -> int:
    """Process-wide XLA backend-compile counter (monotonic).  Snapshot it
    around a workload window to prove the warm path compiles nothing."""
    _ensure_listener()
    return _compile_count


class _WarmPool:
    """Scratch stand-in for a SizeClassPool: same row_units / state shape
    (so jit keys match the real pool) but private state — warm dispatches
    mutate it freely without the dispatch lock."""

    __slots__ = ("spec", "state", "capacity", "row_units")

    def __init__(self, pool, executor):
        # Snapshot capacity ONCE: the real pool can grow concurrently
        # (its dispatch lock is exactly what warm calls avoid), and a
        # torn read here would mix two layouts in one scratch state.
        cap = pool.capacity
        self.spec = pool.spec
        self.capacity = cap
        self.row_units = pool.row_units
        self.state = executor.make_pool_state(
            cap, pool.row_units, pool.spec.dtype, kind=pool.spec.kind
        )


def _raw(executor, name: str) -> Callable:
    """The unwrapped (lock-free) executor method — see module docstring."""
    return getattr(type(executor), name).__wrapped__


# -- warm-batch builders ------------------------------------------------------
#
# Each returns fn(executor, warm_pool, bucket) that drives ONE real
# dispatch method with a bucket-sized batch whose avals match serving
# traffic exactly (dtypes and shapes are what the jit cache keys on).


def warm_bloom_mixed_keys(k: int, L: int, Lt: int) -> Callable:
    def fn(ex, wpool, B):
        blocks = np.zeros((B, L), np.uint32)
        blocks[:, :Lt] = 1  # trim keeps exactly Lt lanes
        rows = (np.arange(B, dtype=np.int64) % max(1, wpool.capacity)).astype(np.int32)
        _raw(ex, "bloom_mixed_keys")(
            ex, wpool, rows, np.ones(B, np.uint32), k, blocks,
            np.full(B, Lt * 4, np.uint32), np.zeros(B, bool),
        )
    return fn


def warm_bloom_mixed_keys_runs(k: int, L: int, Lt: int, const_len: bool) -> Callable:
    """Both run-length keyed kernels at each bucket: the contains-only
    gather (bloom_contains_keys_runs) and the scan that segments holding
    an add take (bloom_mixed_keys_runs, which scans whatever its flags)."""
    def fn(ex, wpool, B):
        if not ex.supports_runs_metadata:
            return  # rebound to a sharded successor: no runs kernel
        blocks = np.zeros((B, L), np.uint32)
        blocks[:, :Lt] = 1
        lengths = (
            np.uint32(Lt * 4) if const_len else np.full(B, Lt * 4, np.uint32)
        )
        runs = (np.zeros(1, np.int32), np.ones(1, np.uint32))
        starts = np.array([0, B], np.int32)
        _raw(ex, "bloom_contains_keys_runs")(
            ex, wpool, k, blocks, lengths, *runs, starts)
        _raw(ex, "bloom_mixed_keys_runs")(
            ex, wpool, k, blocks, lengths, *runs, np.zeros(1, bool), starts)
    return fn


def warm_bitset_mixed() -> Callable:
    def fn(ex, wpool, B):
        from redisson_tpu.ops import bitset as bitset_ops

        rows = (np.arange(B, dtype=np.int64) % max(1, wpool.capacity)).astype(np.int32)
        _raw(ex, "bitset_mixed")(
            ex, wpool, rows, np.zeros(B, np.uint32),
            np.full(B, bitset_ops.OP_GET, np.uint32),
        )
    return fn


def warm_bitset_mixed_runs() -> Callable:
    def fn(ex, wpool, B):
        from redisson_tpu.ops import bitset as bitset_ops

        if not ex.supports_runs_metadata:
            return  # rebound to a sharded successor: no runs kernel
        _raw(ex, "bitset_mixed_runs")(
            ex, wpool, np.zeros(B, np.uint32),
            np.zeros(1, np.int32),
            np.full(1, bitset_ops.OP_GET, np.uint32),
            np.array([0, B], np.int32),
        )
    return fn


def warm_hll_add_changed() -> Callable:
    def fn(ex, wpool, B):
        rows = (np.arange(B, dtype=np.int64) % max(1, wpool.capacity)).astype(np.int32)
        z = np.zeros(B, np.uint32)
        _raw(ex, "hll_add_changed")(ex, wpool, rows, z, z, z)
    return fn


def warm_cms_update_estimate(d: int, w: int) -> Callable:
    def fn(ex, wpool, B):
        rows = (np.arange(B, dtype=np.int64) % max(1, wpool.capacity)).astype(np.int32)
        z = np.zeros(B, np.uint32)
        _raw(ex, "cms_update_estimate")(ex, wpool, rows, z, z, z, d, w)
    return fn


class BucketPrewarmer:
    """Background compile thread: one daemon pops (pool, signature,
    bucket) tasks and runs the signature's warm builder at that bucket.

    ``register(pool, sig, warm_fn)`` is idempotent per signature and
    enqueues the whole bucket ladder on first sight; pool growth
    re-enqueues every signature of that pool (state shape changed →
    fresh jit keys).  ``wait_idle`` blocks until the queue drains — the
    bench and the no-compile-after-prewarm guard call it before their
    measured windows."""

    def __init__(self, executor, *, max_batch: int,
                 max_state_bytes: int = 1 << 28, obs=None):
        _ensure_listener()
        self._executor = executor
        self.max_batch = max_batch
        self.max_state_bytes = max_state_bytes
        self._q: "queue.Queue" = queue.Queue()
        self._sigs: dict = {}  # id(pool) -> {sig: warm_fn}
        self._pools: dict = {}  # id(pool) -> pool (keeps registration alive)
        self._warm_pools: dict = {}  # id(pool) -> (capacity, _WarmPool)
        self._lock = threading.Lock()
        self._outstanding = 0
        self._idle = threading.Condition(self._lock)
        self._closed = False
        self.warmed = 0  # completed warm tasks (test/bench introspection)
        self.errors = 0
        self._thread = threading.Thread(
            target=self._run, name="rtpu-prewarm", daemon=True
        )
        self._thread.start()
        # Interpreter teardown while the daemon worker sits INSIDE an XLA
        # compile segfaults the process ("terminate called without an
        # active exception"): join the worker out of its current compile
        # before Python starts dying.  Unregistered by a clean shutdown.
        atexit.register(self._join_at_exit)

    # -- registration ------------------------------------------------------

    def ladder(self) -> list:
        """Every padded bucket a serving batch can hit, floor → max_batch."""
        out, n = [], 1
        while n <= self.max_batch:
            b = self._executor._bucket(n)
            if not out or b != out[-1]:
                out.append(b)
            n *= 2
        return out

    def _pool_too_big(self, pool) -> bool:
        itemsize = np.dtype(pool.spec.dtype).itemsize
        return pool.capacity * pool.row_units * itemsize > self.max_state_bytes

    def register(self, pool, sig, warm_fn: Callable) -> bool:
        """Idempotently attach a warm signature to a pool and enqueue its
        bucket ladder.  Returns True when the signature was new."""
        if self._closed or self._pool_too_big(pool):
            return False
        with self._lock:
            sigs = self._sigs.setdefault(id(pool), {})
            if sig in sigs:
                return False
            sigs[sig] = warm_fn
            self._pools[id(pool)] = pool
            # Growth changes state shape -> every jit key of this pool:
            # re-warm the ladder against the new layout.
            pool.on_grow = self.on_pool_grow
            self._enqueue_locked(pool, warm_fn)
        return True

    def _enqueue_locked(self, pool, warm_fn) -> None:
        for b in self.ladder():
            self._outstanding += 1
            self._q.put((pool, warm_fn, b))

    def on_pool_grow(self, pool) -> None:
        if self._closed or self._pool_too_big(pool):
            return
        with self._lock:
            self._warm_pools.pop(id(pool), None)  # stale shape
            for warm_fn in self._sigs.get(id(pool), {}).values():
                self._enqueue_locked(pool, warm_fn)

    def rebind_executor(self, executor) -> None:
        """A live change_topology retired the executor this warmer was
        built around: adopt the successor, drop every scratch state (the
        layout changed), and re-run all registered ladders against the
        new jit keys."""
        if self._closed:
            return
        with self._lock:
            self._executor = executor
            self._warm_pools.clear()
            for pid, sigs in self._sigs.items():
                pool = self._pools.get(pid)
                if pool is None or self._pool_too_big(pool):
                    continue
                for warm_fn in sigs.values():
                    self._enqueue_locked(pool, warm_fn)

    # -- worker ------------------------------------------------------------

    def _warm_pool_for(self, pool) -> _WarmPool:
        cached = self._warm_pools.get(id(pool))
        if cached is not None and cached[0] == pool.capacity:
            return cached[1]
        wp = _WarmPool(pool, self._executor)
        # Tag the cache with the capacity the scratch state was ACTUALLY
        # built at (wp.capacity), not a re-read of pool.capacity: a
        # growth landing between the two reads would tag a stale-shape
        # pool as current, and every later task — including the re-warm
        # ladder the growth itself enqueued — would cache-hit the old
        # layout and never compile the new jit keys (measured: 1-in-~20
        # interleavings under a warm compile cache).
        self._warm_pools[id(pool)] = (wp.capacity, wp)
        return wp

    def _run(self) -> None:
        while True:
            task = self._q.get()
            if task is None:
                return
            pool, warm_fn, bucket = task
            try:
                if _chaos.ENABLED:  # prewarm-compile fault point (ISSUE 3)
                    _chaos.fire("prewarm")
                if not getattr(self._executor, "_retired", False):
                    warm_fn(self._executor, self._warm_pool_for(pool), bucket)
                    self.warmed += 1
            except Exception:
                self.errors += 1
            finally:
                with self._lock:
                    # max(0): shutdown may have zeroed the counter while
                    # this task was in flight.
                    self._outstanding = max(0, self._outstanding - 1)
                    if self._outstanding == 0:
                        # Ladder drained: drop the scratch states (a warm
                        # pool can be hundreds of MB of device memory).
                        self._warm_pools.clear()
                        self._idle.notify_all()

    def pending(self) -> int:
        with self._lock:
            return self._outstanding

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until every enqueued warm task has run; True on drained."""
        with self._idle:
            if self._outstanding == 0:
                return True
            self._idle.wait_for(lambda: self._outstanding == 0, timeout)
            return self._outstanding == 0

    def _discard_pending_locked_free(self) -> None:
        """Drop every queued (not yet started) warm task: only the
        in-flight compile remains to wait out."""
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        with self._lock:
            self._outstanding = 0
            self._idle.notify_all()

    def shutdown(self, timeout: float = 5.0) -> None:
        self._closed = True
        self._discard_pending_locked_free()
        self._q.put(None)
        self._thread.join(timeout=timeout)
        if not self._thread.is_alive():
            atexit.unregister(self._join_at_exit)
        self._warm_pools.clear()

    def _join_at_exit(self) -> None:
        """atexit hook: the worker must not be inside an XLA compile when
        the interpreter tears down (segfault).  Bounded join — compiles
        finish in ≤~60s even on a remote device."""
        self._closed = True
        self._discard_pending_locked_free()
        self._q.put(None)
        self._thread.join(timeout=300.0)
