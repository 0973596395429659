"""TpuCommandExecutor — the north-star intercept point.

BASELINE.json: sketch objects "acquire a TpuCommandExecutor that intercepts
their hash/bit-manipulation ops at the CommandAsyncService boundary,
coalesces them via CommandBatchService, and ships the batched bit-tests and
register-merges to a co-located JAX process".  This module is that executor:

- one jit cache keyed by (opcode, pool class, state length, padded batch),
  so steady-state traffic never recompiles;
- op batches padded to power-of-two buckets (≥ config.min_bucket) with a
  validity mask — padding routes to the pool's scratch slot (ops/bitops.py);
- pool state buffers are donated to write kernels (no copy per batch);
- results come back as ``LazyResult`` (the RFuture analog,
  → org/redisson/api/RFuture.java): device dispatch is async, the caller
  only blocks when reading a value.

The coalescer (executor/coalescer.py) feeds multi-tenant batches through
the same dispatch methods.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from redisson_tpu import chaos as _chaos
from redisson_tpu.ops import bitops
from redisson_tpu.ops import bitset as bitset_ops
from redisson_tpu.ops import bloom as bloom_ops
from redisson_tpu.ops import cms as cms_ops
from redisson_tpu.ops import fastpath
from redisson_tpu.ops import golden
from redisson_tpu.ops import hll as hll_ops
from redisson_tpu.obs import spans as _spans


# rtpulint: disable=RT006 keyed by Mesh topology (a handful per process, meshes hash by content), not by object/tenant name — bounded by construction
_REPLICATORS: dict = {}

# Device-side scan chunking: ONE launch for arbitrarily large batches
# with bounded kernel intermediates.  XLA's fused device-hash contains
# path materializes a ~(B*k, 128)-lane u32 buffer — an 8M-op launch
# failed compile with a 30 GB allocation on 16 GB HBM — so huge batches
# lax.scan the same per-chunk kernel sequentially on device: one H2D,
# one launch, one mailbox fetch, whatever the batch size.  This is what
# keeps the client path a-handful-of-round-trips per tens of millions
# of ops in link phases that charge ~an RT per TRANSFER.
_SCAN_CHUNK = 1 << 20

# Per-thread switch suppressing LazyResult's eager per-launch D2H
# prefetch inside a bulk dispatch region whose results come home
# through the mailbox (collect_group).  Over the remote link this was
# tuned on (not yet measured on an attached chip) every host-bound
# transfer cost a full round trip regardless of size, so a
# group of G launches each issuing its own fire-and-forget
# copy_to_host_async can serialize into G round trips in slow phases —
# the exact cost the mailbox's single grouped fetch exists to avoid.
_fetch_ctl = threading.local()


class defer_host_fetch:
    """Context manager: LazyResults created inside skip their eager
    copy_to_host_async (their values resolve via collect_group's ONE
    grouped fetch, or a synchronous np.asarray at .result())."""

    def __enter__(self):
        self._prev = getattr(_fetch_ctl, "defer", False)
        _fetch_ctl.defer = True
        return self

    def __exit__(self, *exc):
        _fetch_ctl.defer = self._prev
        return False


def ensure_addressable(arr):
    """Multi-host (docs/MULTIHOST.md): a result sharded over a mesh that
    spans other processes cannot be fetched host-side directly — replicate
    it first (XLA lowers the gather to DCN collectives).  Single-process
    arrays pass through untouched; result blocks are bit-packed, so the
    replicated copy is tiny."""
    if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
        return arr
    mesh = arr.sharding.mesh  # Mesh hashes by content: equal meshes share
    rep = _REPLICATORS.get(mesh)  # one cached replicator across engines
    if rep is None:
        from jax.sharding import NamedSharding, PartitionSpec

        def replicate(a):
            return a

        rep = jax.jit(
            replicate, out_shardings=NamedSharding(mesh, PartitionSpec())
        )
        _REPLICATORS[mesh] = rep
    return rep(arr)


class LazyResult:
    """Async result handle (RFuture analog): holds device arrays; transfers
    to host (and slices off padding) only on .result()."""

    def __init__(self, value, n: Optional[int] = None, transform=None):
        if isinstance(value, jax.Array):
            value = ensure_addressable(value)
        self._value = value
        self._n = n
        self._transform = transform
        self._done = None
        if isinstance(value, jax.Array) and not getattr(
            _fetch_ctl, "defer", False
        ):
            # Start the D2H transfer immediately so .result() overlaps with
            # subsequent dispatches (hides the per-roundtrip link latency).
            # Suppressed inside defer_host_fetch regions — bulk groups
            # resolve through ONE mailbox fetch instead.
            try:
                value.copy_to_host_async()
            except Exception:
                pass

    def result(self, timeout=None):
        # ``timeout`` accepted (and ignored) for signature parity with
        # the coalescer's HintedFuture: callers treat the two
        # interchangeably, and a LazyResult's fetch is synchronous — by
        # the time it could "time out" it has already completed.
        if self._done is None:
            v = self._value
            if isinstance(v, jax.Array):
                # Completion/D2H fault point (ISSUE 3): only a REAL
                # device fetch can fault here — host-materialized
                # results (ImmediateResult, degraded-mirror answers)
                # have no transfer to break.
                if _chaos.ENABLED:
                    _chaos.fire("fetch")
                v = np.asarray(v)
            self.resolve_from(v)
        return self._done

    def resolve_from(self, host):
        """Resolve with an ALREADY-FETCHED host copy of the device value —
        the mailbox path (collect_group) fetches many results in one D2H
        and hands each LazyResult its slice."""
        if self._done is None:
            v = host
            if self._n is not None:
                v = v[: self._n]
            if self._transform is not None:
                v = self._transform(v)
            self._done = v
            self._value = None
        return self._done

    # concurrent.futures-ish aliases
    def get(self):
        return self.result()

    def done(self) -> bool:
        return self._done is not None


def _pow2ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# -- pinned host staging (warm-path dispatch) -------------------------------
#
# Every flush used to allocate fresh np.full arrays per column and ship
# them as 4-6 separate jnp.asarray H2D transfers.  Both costs scale with
# flush RATE, not op count, and over the remote link this was tuned on
# some phases charged a full round trip per TRANSFER.  The staging rings below keep reusable
# pinned host buffers per (layout key); the hot coalesced methods pack a
# whole op batch into ONE contiguous uint32 block and ship it with a
# single jax.device_put, slicing columns back out INSIDE the jit (free —
# XLA fuses the slices into the kernel).
#
# Reuse safety: device_put's host buffer is immutable-until-transfer-
# completes, and the transfer may be async.  Each slot remembers the
# device array it last fed; re-acquiring the slot waits on that array
# (a no-op once the transfer retired — with ring depth 8 the wait is
# almost never hit in steady state) before the buffer is overwritten.
#
# CPU-backend caveat: there device_put ZERO-COPIES a suitably aligned
# numpy buffer — the jax.Array WRAPS the staging memory instead of
# copying it, so ring reuse would corrupt in-flight launches (measured:
# 20/20 aliased for 64-byte-aligned buffers).  On that backend the ship
# helpers hand jax a private copy of the packed block; the pinned
# buffers still serve as the packing arena (one allocation+transfer per
# flush instead of one np.full + transfer per column).

_STAGING_DEPTH = 8

_HOST_MAY_ALIAS = None


def _host_may_alias() -> bool:
    global _HOST_MAY_ALIAS
    if _HOST_MAY_ALIAS is None:
        _HOST_MAY_ALIAS = jax.default_backend() == "cpu"
    return _HOST_MAY_ALIAS


def _put_staged(slot: "_StagingSlot", view, sharding=None):
    """Ship a packed staging view: direct (pinned, pending-tracked) on
    accelerators; via a private copy on the zero-copy CPU backend.
    ``sharding`` places the view committed, each device's slice straight
    from the host; None puts it on the default device, uncommitted."""
    if _chaos.ENABLED:  # staged-H2D fault point (ISSUE 3)
        _chaos.fire("h2d.staging", data=view)
    # rtpu_h2d_bytes / rtpu_h2d_puts: the dispatching method records the
    # sums once.
    d = _spans.DISPATCH
    d.h2d_bytes += view.nbytes
    d.h2d_puts += 1
    if _host_may_alias():
        return jax.device_put(view.copy(), sharding)
    dev = jax.device_put(view, sharding)
    slot.pending = dev
    return dev


class _StagingSlot:
    __slots__ = ("buf", "pending")

    def __init__(self):
        self.buf = None
        self.pending = None


class _StagingRings(threading.local):
    """Per-thread staging-buffer rings (thread-local: the coalescer flush
    thread, direct-dispatch callers, and the pre-warm thread each get
    private buffers, so no cross-thread write races on reused memory)."""

    def __init__(self):
        self.rings: dict = {}

    def acquire(self, key, nwords: int, depth: int = _STAGING_DEPTH) -> _StagingSlot:
        ring = self.rings.get(key)
        if ring is None:
            ring = self.rings[key] = [0, [_StagingSlot() for _ in range(depth)]]
        slots = ring[1]
        slot = slots[ring[0]]
        ring[0] = (ring[0] + 1) % len(slots)
        if slot.pending is not None:
            try:
                slot.pending.block_until_ready()
            except Exception:
                pass
            slot.pending = None
        if slot.buf is None or slot.buf.shape[0] < nwords:
            slot.buf = np.empty(_pow2ceil(max(64, nwords)), np.uint32)
        return slot


def _expand_runs(starts, n: int, *cols):
    """Per-op values of run-length columns, inside a jit: op i < n takes
    run j's value where ``starts[j] <= i < starts[j + 1]``; ops at or
    past the last start take the last run's.  ``starts``: int32[C + 1]
    non-decreasing, ``cols``: [C] each.  Each run start adds the step
    from the previous run's value and a prefix sum spreads it (modular
    integer steps, so exact); a searchsorted in its place was a while
    loop of element gathers, about 60 ms per 1M-op launch on v5e."""
    pos = starts[1:-1]
    out = []
    for col in cols:
        z = jnp.zeros((n,), col.dtype).at[pos].add(col[1:] - col[:-1],
                                                   mode="drop")
        out.append(col[0] + jnp.cumsum(z, dtype=col.dtype))
    return out


def _fill_words(buf, off: int, n_pad: int, arr, dtype, fill=0) -> int:
    """Write ``arr`` into buf[off:off+n_pad] viewed as a 4-byte ``dtype``,
    padding the tail with ``fill``; returns the next offset."""
    view = buf[off : off + n_pad].view(dtype)
    n = arr.shape[0]
    view[:n] = arr
    if n < n_pad:
        view[n:] = fill
    return off + n_pad


def _fill_bits(buf, off: int, n_pad: int, flags) -> int:
    """Pack a bool column into buf[off : off + n_pad//32] at 1 bit/op
    (little-endian, the device unpacks with bitops.unpack_bool_u32_dev);
    returns the next offset."""
    nw = n_pad >> 5
    words = bitops.host_pack_bool_u32(np.asarray(flags, bool))
    view = buf[off : off + nw]
    view[: words.shape[0]] = words
    view[words.shape[0]:] = 0
    return off + nw


def _fill_blocks(buf, off: int, n_pad: int, blocks) -> int:
    """Write a [B, L] uint32 lane block into buf, zero-padding to
    [n_pad, L]; returns the next offset."""
    B, L = blocks.shape
    view = buf[off : off + n_pad * L].reshape(n_pad, L)
    view[:B] = blocks
    view[B:] = 0
    return off + n_pad * L


def bloom_count_from_bitcount(x, m: int, k: int) -> int:
    """BITCOUNT inversion n ≈ -m/k·ln(1 - X/m) (→ RedissonBloomFilter#count);
    shared by the single-device and sharded executors."""
    import math

    x = int(x)
    if x >= m:
        return m
    return int(round(-m / k * math.log(1 - x / m)))


def resolve_device_slice(indices, devices=None) -> list:
    """Map ``device_indices`` config to actual device objects (ISSUE 17
    satellite, ROADMAP carry-over): an explicit, ordered, duplicate-free
    slice of the local device enumeration, so each front-door worker
    (and later each replica) pins its own devices instead of first-come
    allocation.  ``devices`` overrides the enumeration for tests (fake
    multi-device lists)."""
    if devices is None:
        import jax as _jax

        devices = _jax.devices()
    if indices is None:
        return list(devices)
    out = []
    seen = set()
    for i in indices:
        i = int(i)
        if not (0 <= i < len(devices)):
            raise ValueError(
                f"device_indices entry {i} out of range: "
                f"{len(devices)} local devices"
            )
        if i in seen:
            raise ValueError(f"device_indices entry {i} repeated")
        seen.add(i)
        out.append(devices[i])
    if not out:
        raise ValueError("device_indices must not be empty")
    return out


class TpuCommandExecutor:
    """All dispatch methods are serialized by a global lock (see module
    docstring): pool.state buffers are donated, so two concurrent dispatches
    racing on the same state would hand XLA an already-consumed buffer.
    Device execution itself stays async — the lock only covers enqueue."""

    # Observability wiring (engine sets these): ``metrics`` is the legacy
    # Metrics aggregate, set ONLY when no coalescer fronts this executor
    # (the coalescer records the same ops_total/batches_total itself —
    # both recording would double-count); ``obs`` is the labeled
    # registry bundle, always set, recording per-method dispatch
    # counts/latency that are distinct from the coalescer's series.
    metrics = None
    obs = None
    # Run-length segment metadata (bloom_mixed_keys_runs): single-device
    # only — the sharded executor's partition-by-owner dispatch reorders
    # ops before expansion, so it keeps the per-op-array path.
    supports_runs_metadata = True

    def __init__(self, config):
        self._cfg = config.tpu_sketch
        self._jit_cache: dict[tuple, object] = {}
        self._lock = threading.Lock()
        self._dispatch_lock = threading.RLock()
        # Pinned host staging buffers (per-thread rings, see module
        # comment): the hot coalesced methods pack whole batches into one
        # block here; everything else pads into reusable column buffers.
        self._staging = _StagingRings()
        _spans.install_compile_listener()  # rtpu_compiles{method}
        # Explicit device pinning (ISSUE 17 satellite): when the config
        # names a device slice, every allocation this executor makes —
        # pool-state factory jnp.zeros, staging device_puts — lands on
        # its FIRST device via the process default-device, instead of
        # whatever device 0 happens to be.  Each front-door worker is
        # its own process, so a process-wide default is exactly the
        # per-worker pin the slot→process map wants.
        self.devices = None
        idx = getattr(self._cfg, "device_indices", None)
        if idx is not None:
            self.devices = resolve_device_slice(idx)
            jax.config.update("jax_default_device", self.devices[0])

    # -- pool-state factory (the executor owns array layout; pools only
    # hand out row numbers) ------------------------------------------------

    def round_capacity(self, capacity: int, row_units: int = 0, kind: str = "") -> int:
        # Giant rows (config-3 scale bitmaps): don't pre-allocate the
        # default 8 tenants' worth — cap the initial footprint at ~512MB
        # and let doubling growth take over.
        if row_units and capacity * row_units > (1 << 27):
            return max(1, (1 << 27) // row_units)
        return capacity

    def make_pool_state(self, capacity: int, row_units: int, dtype, kind: str = ""):
        """Flat [capacity*row_units + 1]; trailing scratch element."""
        return jnp.zeros((capacity * row_units + 1,), dtype)

    def grow_pool_state(self, state, old_cap: int, new_cap: int, row_units: int, dtype, kind: str = ""):
        extra = jnp.zeros(((new_cap - old_cap) * row_units + 1,), dtype)
        # state[:-1] drops the old scratch element; extra brings the new one.
        return jnp.concatenate([state[:-1], extra])

    # Snapshot transport (SURVEY.md §5 checkpoint row): full-pool D2H/H2D.

    def state_to_host(self, pool) -> np.ndarray:
        st = pool.state
        if isinstance(st, jax.Array) and not st.is_fully_addressable:
            # Multi-host: replicate one shard block at a time — peak extra
            # device memory is one block, not the whole pool (a sharded
            # pool can exceed a single device).  Must run in lockstep on
            # every controller, like any dispatch (docs/MULTIHOST.md).
            return np.stack(
                [
                    np.asarray(ensure_addressable(st[s]))
                    for s in range(st.shape[0])
                ]
            )
        return np.asarray(st)

    def state_from_host(self, pool, arr: np.ndarray) -> None:
        dev = jnp.asarray(arr)
        if _host_may_alias():
            # CPU backend: jnp.asarray ZERO-COPIES a suitably aligned
            # numpy buffer — the jax.Array WRAPS host memory (verified:
            # writes through the numpy array appear in the device view).
            # Pool state is consumed by DONATING kernels, so it must be
            # an XLA-owned buffer: a snapshot-restored state that aliased
            # the np.load scratch produced wholesale garbage rows on the
            # first donated dispatch (flaky pre-ISSUE-3; timing-dependent
            # via the host allocator).  jnp.copy materializes ownership.
            dev = jnp.copy(dev)
        pool.state = dev

    # -- jit plumbing ------------------------------------------------------

    def _bucket(self, n: int) -> int:
        # 32-divisibility: boolean results leave the device packed
        # 32-per-word (bitops.pack_bool_u32), so both the floor and a
        # user-set min_bucket (e.g. 48) round up to a multiple of 32.
        mb = -(-max(32, self._cfg.min_bucket) // 32) * 32
        return max(mb, _pow2ceil(max(1, n)))

    def _jit(self, key: tuple, build, donate: bool):
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    f = build()
                    # The XLA module, and so every device op in a trace,
                    # is named jit_<op> after the key's op name.
                    f.__name__ = f.__qualname__ = key[0]
                    fn = jax.jit(f, donate_argnums=(0,) if donate else ())
                    self._jit_cache[key] = fn
        return fn

    def collect_group(self, lazies) -> None:
        """Device-side result mailbox (the CommandBatchService
        one-reply-flush role): concatenate a group of launches' packed
        results ON DEVICE and fetch with ONE D2H, then resolve every
        LazyResult from its slice.  Over the remote link of rounds 3-4
        each host fetch cost a full round trip whatever its size (0.2
        ms–2.5 s across phases), so G results for one fetch was a direct
        G-fold cut of collection round trips (+12% to +30% on
        interleaved A/B there; not yet measured on an attached chip).

        Falls back silently per-item for results that are not device
        arrays (host engine, None payloads).

        Note on eager prefetches: a LazyResult created OUTSIDE a
        defer_host_fetch region issued its own fire-and-forget
        ``copy_to_host_async`` at creation (redundant but harmless
        here); one created INSIDE such a region deferred it — grouped
        members resolve via the single fetch below, and singleton-sig
        stragglers get their async copy kicked off in the loop so they
        overlap instead of serializing one round trip each."""
        d = _spans.DISPATCH  # label the mailbox concat's compiles
        prev = d.method, d.obs
        d.method, d.obs = "collect_group", self.obs
        try:
            return self._collect_group(lazies)
        finally:
            d.method, d.obs = prev

    def _collect_group(self, lazies) -> None:
        by_sig: dict = {}
        for l in lazies:
            # Unwrap MappedFuture-style adapters (objects/base.py): the
            # underlying LazyResult carries the device value; the
            # wrapper's transform runs at ITS .result() as usual.
            seen = 0
            while l is not None and not hasattr(l, "_value") and hasattr(l, "_fut"):
                l = l._fut
                seen += 1
                if seen > 4:  # defensive: no adapter nests this deep
                    break
            if (
                l is not None
                and getattr(l, "_done", 1) is None
                and isinstance(getattr(l, "_value", None), jax.Array)
            ):
                # Group by EXACT (dtype, shape): results are bucketed to
                # pow-2 sizes already, so same-sig groups are the common
                # case, and the concat program's cache key stays a small
                # (dtype, shape, count) space — a per-ordered-shape-tuple
                # key would compile combinatorially many executables
                # (30-60s each over a remote link, never evicted).
                by_sig.setdefault((l._value.dtype, l._value.shape), []).append(l)
        for (dtype, shape), group in by_sig.items():
            if len(group) < 2:
                # A lone result fetches itself at .result() time — but
                # its eager D2H may have been SUPPRESSED (defer_host_
                # fetch), so start the transfer now: with several
                # singleton sigs in one collect call, the async copies
                # overlap instead of serializing one round trip each.
                for l in group:
                    try:
                        l._value.copy_to_host_async()
                    except Exception:
                        pass
                continue
            # Multi-round device-side concat tree: rounds of ≤8-ary
            # concats collapse the WHOLE group to one flat array, so a
            # group of ANY size costs exactly ONE D2H fetch — ops-per-
            # sync scales with the caller's group, not with a fixed
            # concat arity (a 32-launch pass used to take 4 fetches;
            # at 263 ms/fetch RT that alone capped the headline).
            # Compile-key discipline: a round longer than 8 pads itself
            # to a MULTIPLE of 8 by repeating the last value, so every
            # non-final concat is exactly 8-ary over one uniform shape —
            # the cached-program space is (dtype, level_shape, 8) plus a
            # ≤7-ary final concat per level, NOT one program per
            # ordered-shape-tuple (those compiled 30-60s each over a
            # remote link, never evicted).  Duplicated pad results are
            # sliced off at resolution.
            vals = [l._value for l in group]
            while len(vals) > 1:
                if len(vals) > 8 and len(vals) % 8:
                    vals = vals + [vals[-1]] * (8 - len(vals) % 8)
                nxt = []
                for start in range(0, len(vals), 8):
                    chunk = vals[start : start + 8]
                    if len(chunk) == 1:
                        nxt.append(chunk[0])
                        continue
                    key = (
                        "mailbox",
                        dtype.name,
                        tuple(map(int, chunk[0].shape)),
                        len(chunk),
                    )

                    def build():
                        def f(*xs):
                            return jnp.concatenate([x.reshape(-1) for x in xs])

                        return f

                    nxt.append(self._jit(key, build, donate=False)(*chunk))
                vals = nxt
            flat = np.asarray(ensure_addressable(vals[0]))
            off = 0
            n = int(np.prod(shape))
            for l in group:
                # .copy(): a view would pin the whole group's concat
                # buffer for as long as any ONE result is retained.
                l.resolve_from(flat[off : off + n].reshape(shape).copy())
                off += n

    @staticmethod
    def _pad(arr: np.ndarray, n_pad: int, fill=0):
        out = np.full((n_pad,), fill, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return out

    def _ship(self, slot: _StagingSlot, nwords: int):
        """One fused H2D for a packed staging block; the slot remembers
        the device array so a later reuse waits out the transfer."""
        return _put_staged(slot, slot.buf[:nwords])

    def _staged_put(self, arr, n_pad: int, fill=0, dtype=None, depth=_STAGING_DEPTH):
        """Pad a column into a reusable pinned staging buffer and ship it
        (replaces the per-flush np.full + jnp.asarray allocation pair for
        methods that keep per-column transfers)."""
        arr = np.asarray(arr) if dtype is None else np.asarray(arr, dtype)
        dt = arr.dtype
        nwords = -(-n_pad * dt.itemsize // 4)
        slot = self._staging.acquire(("pad", dt.str, n_pad), nwords, depth)
        view = slot.buf[:nwords].view(dt)[:n_pad]
        n = arr.shape[0]
        view[:n] = arr
        if n < n_pad:
            view[n:] = fill
        return _put_staged(slot, view)

    def _staged_blocks(self, blocks, n_pad: int):
        """[B, L] uint32 lane block padded to [n_pad, L] in a reusable
        staging buffer (the big per-call np.zeros on the *_keys paths)."""
        B, L = blocks.shape
        nwords = n_pad * L
        # Depth 2: key blocks can be tens of MB (8M-op launches); a deep
        # ring would pin 8x that in host RAM for no extra overlap.
        slot = self._staging.acquire(("blocks", L, n_pad), nwords, depth=2)
        view = slot.buf[:nwords].reshape(n_pad, L)
        view[:B] = blocks
        view[B:] = 0
        return _put_staged(slot, view)

    def _staged_valid(self, n: int, n_pad: int):
        slot = self._staging.acquire(("valid", n_pad), -(-n_pad // 4))
        view = slot.buf[: -(-n_pad // 4)].view(bool)[:n_pad]
        view[:n] = True
        view[n:] = False
        return _put_staged(slot, view)

    def _pad_ops(self, n_pad: int, *arrays):
        padded = [self._staged_put(a, n_pad) for a in arrays]
        return padded, self._staged_valid(arrays[0].shape[0], n_pad)

    @staticmethod
    def _trim_lanes(blocks):
        """Drop trailing all-zero lane columns before H2D (the kernel
        rebuilds them, fastpath.pad_lanes); returns (trimmed, orig_lanes).
        Halves link bytes for 8-byte keys in 16-byte blocks."""
        L = blocks.shape[1]
        used = L
        while used > 1 and not np.any(blocks[:, used - 1]):
            used -= 1
        return blocks[:, :used], L

    # -- bloom -------------------------------------------------------------

    def bloom_mixed_keys(self, pool, rows, m_arr, k: int, blocks, lengths, is_add) -> LazyResult:
        """Combined add+contains from raw codec lanes with exact
        sequential semantics — device-side murmur + 64-bit mod
        (ops/fastpath.py), multi-tenant rows/m as arrays.

        Fused H2D: the whole batch (rows, lengths, m, bit-packed is_add,
        key lanes, real-op count in word 0) ships as ONE contiguous
        staging block; the jit slices the columns back out (XLA fuses the
        slices into the kernel) and rebuilds valid as ``iota < n``.

        A batch over ``_SCAN_CHUNK`` ops (a direct multi-million-key add)
        lax.scans ``_SCAN_CHUNK``-op chunks in order, each seeing the
        earlier chunks' writes, so answers stay exact while the device
        hash's intermediates stay bounded."""
        B = blocks.shape[0]
        Bp = self._bucket(B)
        if Bp > _SCAN_CHUNK and Bp % _SCAN_CHUNK:
            # Round huge buckets UP to a chunk multiple (a custom
            # min_bucket need not be a power of two): un-chunked
            # multi-million-op device-hash kernels fail compile on HBM.
            Bp = ((Bp // _SCAN_CHUNK) + 1) * _SCAN_CHUNK
        blocks, L = self._trim_lanes(blocks)
        Lt = blocks.shape[1]
        wpr = pool.row_units
        Wb = Bp >> 5
        chunk = min(Bp, _SCAN_CHUNK)
        key = ("bloom_mixed_keys", wpr, pool.state.shape[0], Bp, k, L, Lt,
               chunk)

        def build():
            def run(state, rows, lengths, m_arr, is_add, blocks, valid):
                new, res = fastpath.bloom_mixed_keys(
                    state, rows, blocks, lengths, m_arr, is_add, valid,
                    k=k, words_per_row=wpr, target_lanes=L,
                )
                return new, bitops.pack_bool_u32(res)

            def f(state, packed):
                n = jax.lax.bitcast_convert_type(packed[0], jnp.int32)
                o = 1
                rows = jax.lax.bitcast_convert_type(
                    packed[o : o + Bp], jnp.int32)
                o += Bp
                lengths = packed[o : o + Bp]
                o += Bp
                m_arr = packed[o : o + Bp]
                o += Bp
                is_add = bitops.unpack_bool_u32_dev(packed[o : o + Wb], Bp)
                o += Wb
                blocks = packed[o : o + Bp * Lt].reshape(Bp, Lt)
                valid = jnp.arange(Bp, dtype=jnp.int32) < n
                cols = (rows, lengths, m_arr, is_add, blocks, valid)
                if chunk == Bp:
                    return run(state, *cols)
                new, outs = jax.lax.scan(
                    lambda st, c: run(st, *c), state,
                    tuple(c.reshape((Bp // chunk, chunk) + c.shape[1:])
                          for c in cols),
                )
                return new, outs.reshape(-1)
            return f

        fn = self._jit(key, build, donate=True)
        total = 1 + 3 * Bp + Wb + Bp * Lt
        slot = self._staging.acquire(("bloom_mixed_keys", Bp, Lt), total)
        buf = slot.buf
        buf[0] = B
        o = _fill_words(buf, 1, Bp, np.asarray(rows, np.int32), np.int32)
        o = _fill_words(buf, o, Bp, np.broadcast_to(
            np.asarray(lengths, np.uint32), (B,)), np.uint32)
        # Padded m must be nonzero (mod arithmetic); 1 is harmless.
        o = _fill_words(
            buf, o, Bp, np.asarray(m_arr, np.uint32), np.uint32, 1)
        o = _fill_bits(buf, o, Bp, is_add)
        _fill_blocks(buf, o, Bp, blocks)
        pool.state, res = fn(pool.state, self._ship(slot, total))
        return LazyResult(res, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bloom_mixed_keys_runs(self, pool, k: int, blocks, lengths, run_rows, run_m, run_flags, run_starts) -> LazyResult:
        """Coalesced mixed path with RUN-LENGTH metadata: per-op rows/m/is_add/valid are constant within
        each submitted chunk, so they ship once per run (C entries + C+1
        cumulative starts) and expand to per-op arrays ON DEVICE
        (_expand_runs) — cutting link bytes/op from ~22-30 to ~8-12 on the
        config-4 mixed path.  ``lengths``: uint32 scalar when every op in
        the launch shares one key length (the common codec case), else a
        per-op array.  ``run_starts[i]``: first op index of run i;
        ``run_starts[C]`` = total real ops (ops beyond it are padding)."""
        return self._keys_runs_launch(
            pool, k, blocks, lengths, run_rows, run_m, run_flags, run_starts)

    def bloom_contains_keys_runs(self, pool, k: int, blocks, lengths, run_rows, run_m, run_starts) -> LazyResult:
        """Contains-only twin of bloom_mixed_keys_runs (same run-length
        layout, no flag words): with no add in the launch every op sees
        the pre-launch state, so one hashed gather
        (fastpath.bloom_contains_keys) answers exactly what the
        sort-chunked scan would.  Reads the state; donates nothing."""
        return self._keys_runs_launch(
            pool, k, blocks, lengths, run_rows, run_m, None, run_starts)

    def _keys_runs_program(self, writes: bool, shape: tuple):
        """(jitted program, packed words, jit key) of one run-length keyed
        Bloom kernel: the scan (``writes``: fastpath.bloom_mixed_keys,
        donates the state, returns (new state, packed results)) or the
        contains gather (fastpath.bloom_contains_keys, reads the state,
        returns packed results).  ``shape`` = (words per row, state
        length, Bp, k, L, Lt, Cp, const_len) fixes the packed layout."""
        wpr, _, Bp, k, L, Lt, Cp, const_len = shape
        Wc = Cp >> 5 if writes else 0
        key = ("bloom_mixk_runs" if writes else "bloom_conk_runs",) + shape

        def build():
            def f(state, packed):
                # Packed layout (one fused H2D per flush): [0]=n_ops,
                # [1]=const key length, then starts/rr/rm[/rf-bits]
                # [/lengths]/blocks at the static offsets below.
                n_ops = jax.lax.bitcast_convert_type(packed[0], jnp.int32)
                o = 2
                starts = jax.lax.bitcast_convert_type(
                    packed[o : o + Cp + 1], jnp.int32)
                o += Cp + 1
                rr = jax.lax.bitcast_convert_type(
                    packed[o : o + Cp], jnp.int32)
                o += Cp
                rm = packed[o : o + Cp]
                o += Cp
                if writes:
                    rf = bitops.unpack_bool_u32_dev(packed[o : o + Wc], Cp)
                    o += Wc
                if const_len:
                    lengths = packed[1]
                else:
                    lengths = packed[o : o + Bp]
                    o += Bp
                blocks = packed[o : o + Bp * Lt].reshape(Bp, Lt)
                # Padded run starts equal n_ops, so tail ops take run
                # Cp - 1's row and m; the scan routes them to scratch
                # (valid=False), the gather's results for them drop.
                if not writes:
                    rows, m = _expand_runs(starts, Bp, rr, rm)
                    return bitops.pack_bool_u32(fastpath.bloom_contains_keys(
                        state, rows, blocks, lengths, m,
                        k=k, words_per_row=wpr, target_lanes=L,
                    ))
                rows, m, is_add = _expand_runs(
                    starts, Bp, rr, rm, rf.astype(jnp.int32))
                new, res = fastpath.bloom_mixed_keys(
                    state, rows, blocks, lengths, m, is_add.astype(bool),
                    jax.lax.iota(jnp.int32, Bp) < n_ops,
                    k=k, words_per_row=wpr, target_lanes=L,
                )
                return new, bitops.pack_bool_u32(res)
            return f

        total = 2 + (Cp + 1) + 2 * Cp + Wc + (0 if const_len else Bp) + Bp * Lt
        return self._jit(key, build, donate=writes), total, key

    def _keys_runs_launch(self, pool, k: int, blocks, lengths, run_rows, run_m, run_flags, run_starts) -> LazyResult:
        """Host packing of the run-length keyed Bloom launches: the scan,
        or the contains gather when ``run_flags`` is None (no flag words
        ship)."""
        B = int(run_starts[-1])
        Bp = self._bucket(B)
        blocks, L = self._trim_lanes(blocks)
        Lt = blocks.shape[1]
        C = len(run_rows)
        # One compiled shape for any C ≤ 1024 (the padded runs cost ~13KB
        # on the wire — noise); degenerate many-tiny-chunk segments grow
        # the bucket rather than fail.
        Cp = max(1024, _pow2ceil(C))
        const_len = np.ndim(lengths) == 0
        writes = run_flags is not None
        shape = (pool.row_units, pool.state.shape[0], Bp, k, L, Lt, Cp, const_len)
        fn, total, key = self._keys_runs_program(writes, shape)
        slot = self._staging.acquire((key[0], Bp, Lt, Cp, const_len), total)
        buf = slot.buf
        buf[0] = B
        buf[1] = np.uint32(lengths) if const_len else 0
        o = 2
        sview = buf[o : o + Cp + 1].view(np.int32)
        sview[: C + 1] = run_starts
        sview[C + 1 :] = B
        o += Cp + 1
        o = _fill_words(buf, o, Cp, np.asarray(run_rows, np.int32), np.int32)
        o = _fill_words(buf, o, Cp, np.asarray(run_m, np.uint32), np.uint32, 1)
        if writes:
            o = _fill_bits(buf, o, Cp, run_flags)
        if not const_len:
            o = _fill_words(
                buf, o, Bp, np.asarray(lengths, np.uint32), np.uint32)
        _fill_blocks(buf, o, Bp, blocks)
        res = fn(pool.state, self._ship(slot, total))
        if writes:
            pool.state, res = res
        return LazyResult(res, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bitset_mixed_runs(self, pool, idx, run_rows, run_ops, run_starts) -> LazyResult:
        """bitset_mixed with RUN-LENGTH metadata (row + opcode constant per
        submitted chunk, expanded on device) — same scheme as
        bloom_mixed_keys_runs; cuts the coalesced bitset path from ~13 to
        ~4 bytes/op on the wire."""
        B = int(run_starts[-1])
        Bp = self._bucket(B)
        C = len(run_rows)
        Cp = max(1024, _pow2ceil(C))
        wpr = pool.row_units
        key = ("bs_mixed_runs", wpr, pool.state.shape[0], Bp, Cp)

        def build():
            def f(state, packed):
                # Packed layout: [0]=n_ops, idx, starts, rr, ro.
                n_ops = jax.lax.bitcast_convert_type(packed[0], jnp.int32)
                o = 1
                idx = packed[o : o + Bp]
                o += Bp
                starts = jax.lax.bitcast_convert_type(
                    packed[o : o + Cp + 1], jnp.int32)
                o += Cp + 1
                rr = jax.lax.bitcast_convert_type(
                    packed[o : o + Cp], jnp.int32)
                o += Cp
                ro = packed[o : o + Cp]
                rows, ops = _expand_runs(starts, Bp, rr, ro)
                new, obs = bitset_ops.bitset_mixed(
                    state, rows, idx, ops, words_per_row=wpr,
                    valid=jax.lax.iota(jnp.int32, Bp) < n_ops,
                )
                return new, bitops.pack_bool_u32(obs)
            return f

        fn = self._jit(key, build, donate=True)
        total = 1 + Bp + (Cp + 1) + 2 * Cp
        slot = self._staging.acquire(("bs_mixed_runs", Bp, Cp), total)
        buf = slot.buf
        buf[0] = B
        o = _fill_words(buf, 1, Bp, np.asarray(idx, np.uint32), np.uint32)
        sview = buf[o : o + Cp + 1].view(np.int32)
        sview[: len(run_starts)] = run_starts
        sview[len(run_starts) :] = B
        o += Cp + 1
        o = _fill_words(buf, o, Cp, np.asarray(run_rows, np.int32), np.int32)
        _fill_words(buf, o, Cp, np.asarray(run_ops, np.uint32), np.uint32,
                    bitset_ops.OP_GET)
        pool.state, obs = fn(pool.state, self._ship(slot, total))
        return LazyResult(obs, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bitset_mixed(self, pool, rows, idx, opcodes) -> LazyResult:
        """Unified set/clear/flip/get batch (ops/bitset.bitset_mixed) —
        one segment per bitset pool under interleaved opcodes.  Fused
        H2D: one packed staging block per flush (see bloom_mixed_keys)."""
        B = idx.shape[0]
        Bp = self._bucket(B)
        wpr = pool.row_units
        key = ("bs_mixed", wpr, pool.state.shape[0], Bp)

        def build():
            def f(state, packed):
                n = jax.lax.bitcast_convert_type(packed[0], jnp.int32)
                o = 1
                rows = jax.lax.bitcast_convert_type(
                    packed[o : o + Bp], jnp.int32)
                o += Bp
                idx = packed[o : o + Bp]
                o += Bp
                opcodes = packed[o : o + Bp]
                valid = jnp.arange(Bp, dtype=jnp.int32) < n
                new, obs = bitset_ops.bitset_mixed(
                    state, rows, idx, opcodes, words_per_row=wpr, valid=valid
                )
                return new, bitops.pack_bool_u32(obs)
            return f

        fn = self._jit(key, build, donate=True)
        total = 1 + 3 * Bp
        slot = self._staging.acquire(("bs_mixed", Bp), total)
        buf = slot.buf
        buf[0] = B
        o = _fill_words(buf, 1, Bp, np.asarray(rows, np.int32), np.int32)
        o = _fill_words(buf, o, Bp, np.asarray(idx, np.uint32), np.uint32)
        # Padded ops are routed to scratch; OP_GET keeps them write-free.
        _fill_words(buf, o, Bp, np.asarray(opcodes, np.uint32), np.uint32,
                    bitset_ops.OP_GET)
        pool.state, obs = fn(pool.state, self._ship(slot, total))
        return LazyResult(obs, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bloom_contains_keys_st(self, pool, row: int, m: int, k: int, blocks, lengths) -> LazyResult:
        """Single-tenant contains from raw codec lanes (device-side hash)."""
        B = blocks.shape[0]
        Bp = self._bucket(B)
        blocks, L = self._trim_lanes(blocks)
        Lt = blocks.shape[1]
        wpr = pool.row_units
        const_len = bool(B == 0 or np.all(lengths == lengths[0]))
        if Bp > _SCAN_CHUNK and Bp % _SCAN_CHUNK:
            # Round huge buckets UP to a chunk multiple (a custom
            # min_bucket need not be a power of two): the scan guarantee
            # must hold for EVERY huge launch — un-chunked multi-million
            # -op device-hash kernels fail compile on HBM.
            Bp = ((Bp // _SCAN_CHUNK) + 1) * _SCAN_CHUNK
        key = ("bloom_contains_keys", wpr, pool.state.shape[0], Bp, k, L, Lt, const_len)

        def build():
            def one(state, row, blocks, lengths, m):
                return bitops.pack_bool_u32(fastpath.bloom_contains_keys_st(
                    state, row, blocks, lengths, m,
                    k=k, words_per_row=wpr, target_lanes=L,
                ))

            if Bp <= _SCAN_CHUNK:
                return one

            nc = Bp // _SCAN_CHUNK

            def f(state, row, blocks, lengths, m):
                blocks_c = blocks.reshape(nc, _SCAN_CHUNK, blocks.shape[1])
                if const_len:
                    def body(c, bl):
                        return c, one(state, row, bl, lengths, m)

                    _, outs = jax.lax.scan(body, 0, blocks_c)
                else:
                    def body(c, xs):
                        return c, one(state, row, xs[0], xs[1], m)

                    _, outs = jax.lax.scan(
                        body, 0,
                        (blocks_c, lengths.reshape(nc, _SCAN_CHUNK)),
                    )
                return outs.reshape(-1)

            return f

        fn = self._jit(key, build, donate=False)
        len_arg = (
            np.uint32(lengths[0] if B else 0)
            if const_len
            else self._staged_put(lengths, Bp, dtype=np.uint32)
        )
        out = fn(
            pool.state, np.int32(row), self._staged_blocks(blocks, Bp),
            len_arg, np.uint32(m)
        )
        return LazyResult(out, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def hll_add_keys_single(self, pool, row: int, blocks, lengths) -> LazyResult:
        """Single-tenant PFADD from raw codec lanes (device-side hash)."""
        B = blocks.shape[0]
        Bp = self._bucket(B)
        blocks, L = self._trim_lanes(blocks)
        Lt = blocks.shape[1]
        const_len = bool(B == 0 or np.all(lengths == lengths[0]))
        if Bp > _SCAN_CHUNK and Bp % _SCAN_CHUNK:
            # Round huge buckets UP to a chunk multiple (a custom
            # min_bucket need not be a power of two): the scan guarantee
            # must hold for EVERY huge launch — un-chunked multi-million
            # -op device-hash kernels fail compile on HBM.
            Bp = ((Bp // _SCAN_CHUNK) + 1) * _SCAN_CHUNK
        key = ("hll_add_keys", pool.state.shape[0], Bp, L, Lt, const_len)

        def build():
            def one(state, row, blocks, lengths, valid):
                return fastpath.hll_add_keys_single(
                    state, row, blocks, lengths, valid, target_lanes=L
                )

            if Bp <= _SCAN_CHUNK:
                return one

            nc = Bp // _SCAN_CHUNK

            def f(state, row, blocks, lengths, valid):
                blocks_c = blocks.reshape(nc, _SCAN_CHUNK, blocks.shape[1])
                valid_c = valid.reshape(nc, _SCAN_CHUNK)
                if const_len:
                    def body(st, xs):
                        return one(st, row, xs[0], lengths, xs[1])

                    new_state, ch = jax.lax.scan(
                        body, state, (blocks_c, valid_c)
                    )
                else:
                    def body(st, xs):
                        return one(st, row, xs[0], xs[2], xs[1])

                    new_state, ch = jax.lax.scan(
                        body, state,
                        (blocks_c, valid_c,
                         lengths.reshape(nc, _SCAN_CHUNK)),
                    )
                return new_state, ch.any()

            return f

        fn = self._jit(key, build, donate=True)
        len_arg = (
            np.uint32(lengths[0] if B else 0)
            if const_len
            else self._staged_put(lengths, Bp, dtype=np.uint32)
        )
        pool.state, changed = fn(
            pool.state,
            np.int32(row),
            self._staged_blocks(blocks, Bp),
            len_arg,
            self._staged_valid(B, Bp),
        )
        return LazyResult(changed, transform=bool)

    def bloom_count(self, pool, row: int, m: int, k: int) -> LazyResult:
        wpr = pool.row_units
        key = ("bloom_card", wpr, pool.state.shape[0])

        def build():
            def f(state, row):
                return bloom_ops.bloom_cardinality(
                    state, row, m=0, k=0, words_per_row=wpr
                )
            return f

        fn = self._jit(key, build, donate=False)
        x = fn(pool.state, row)
        return LazyResult(x, transform=lambda xv: bloom_count_from_bitcount(xv, m, k))

    # -- hll ---------------------------------------------------------------

    def hll_add(self, pool, rows, c0, c1, c2) -> LazyResult:
        B = c0.shape[0]
        Bp = self._bucket(B)
        key = ("hll_add", pool.state.shape[0], Bp)

        def build():
            def f(state, rows, c0, c1, c2, valid):
                return hll_ops.hll_add(state, rows, c0, c1, c2, valid=valid)
            return f

        fn = self._jit(key, build, donate=True)
        (rows_p, c0p, c1p, c2p), valid = self._pad_ops(Bp, rows, c0, c1, c2)
        pool.state = fn(pool.state, rows_p, c0p, c1p, c2p, valid)
        return LazyResult(True)

    def hll_add_changed(self, pool, rows, c0, c1, c2) -> LazyResult:
        """Multi-tenant PFADD with exact per-op changed flags (coalesced
        path).  Fused H2D: one packed staging block per flush."""
        B = c0.shape[0]
        Bp = self._bucket(B)
        key = ("hll_add_changed", pool.state.shape[0], Bp)

        def build():
            def f(state, packed):
                n = jax.lax.bitcast_convert_type(packed[0], jnp.int32)
                o = 1
                rows = jax.lax.bitcast_convert_type(
                    packed[o : o + Bp], jnp.int32)
                o += Bp
                c0 = packed[o : o + Bp]
                o += Bp
                c1 = packed[o : o + Bp]
                o += Bp
                c2 = packed[o : o + Bp]
                valid = jnp.arange(Bp, dtype=jnp.int32) < n
                new, changed = hll_ops.hll_add_changed(
                    state, rows, c0, c1, c2, valid=valid)
                return new, bitops.pack_bool_u32(changed)
            return f

        fn = self._jit(key, build, donate=True)
        total = 1 + 4 * Bp
        slot = self._staging.acquire(("hll_add_changed", Bp), total)
        buf = slot.buf
        buf[0] = B
        o = _fill_words(buf, 1, Bp, np.asarray(rows, np.int32), np.int32)
        o = _fill_words(buf, o, Bp, np.asarray(c0, np.uint32), np.uint32)
        o = _fill_words(buf, o, Bp, np.asarray(c1, np.uint32), np.uint32)
        _fill_words(buf, o, Bp, np.asarray(c2, np.uint32), np.uint32)
        pool.state, changed = fn(pool.state, self._ship(slot, total))
        return LazyResult(changed, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def hll_add_single(self, pool, row: int, c0, c1, c2) -> LazyResult:
        """Single-tenant PFADD returning the 'changed' boolean."""
        B = c0.shape[0]
        Bp = self._bucket(B)
        key = ("hll_add_single", pool.state.shape[0], Bp)

        def build():
            def f(state, row, c0, c1, c2, valid):
                return hll_ops.hll_add_single(state, row, c0, c1, c2, valid=valid)
            return f

        fn = self._jit(key, build, donate=True)
        (c0p, c1p, c2p), valid = self._pad_ops(Bp, c0, c1, c2)
        pool.state, changed = fn(pool.state, row, c0p, c1p, c2p, valid)
        return LazyResult(changed, transform=bool)

    def hll_count(self, pool, row: int) -> LazyResult:
        key = ("hll_hist", pool.state.shape[0])

        def build():
            def f(state, row):
                return hll_ops.hll_histogram(state, row)
            return f

        fn = self._jit(key, build, donate=False)
        hist = fn(pool.state, row)
        return LazyResult(
            hist, transform=lambda h: int(round(golden.ertl_estimate(h)))
        )

    def hll_merge(self, pool, dst_row: int, src_rows) -> LazyResult:
        S = len(src_rows)
        key = ("hll_merge", pool.state.shape[0], S)

        def build():
            def f(state, dst, srcs):
                return hll_ops.hll_merge(state, dst, srcs)
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(pool.state, dst_row, jnp.asarray(np.asarray(src_rows, np.int32)))
        return LazyResult(None)

    # -- bitset ------------------------------------------------------------

    def _bitset_rw(self, opname, kernel, pool, rows, idx):
        B = idx.shape[0]
        Bp = self._bucket(B)
        wpr = pool.row_units
        key = (opname, wpr, pool.state.shape[0], Bp)

        def build():
            def f(state, rows, idx, valid):
                new, prev = kernel(state, rows, idx, words_per_row=wpr, valid=valid)
                return new, bitops.pack_bool_u32(prev)
            return f

        fn = self._jit(key, build, donate=True)
        (rows_p, idx_p), valid = self._pad_ops(Bp, rows, idx)
        pool.state, prev = fn(pool.state, rows_p, idx_p, valid)
        return LazyResult(prev, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bitset_set(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_set", bitset_ops.bitset_set, pool, rows, idx)

    def bitset_clear_bits(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_clear", bitset_ops.bitset_clear, pool, rows, idx)

    def bitset_flip(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_flip", bitset_ops.bitset_flip, pool, rows, idx)

    def bitset_get(self, pool, rows, idx) -> LazyResult:
        B = idx.shape[0]
        Bp = self._bucket(B)
        wpr = pool.row_units
        key = ("bs_get", wpr, pool.state.shape[0], Bp)

        def build():
            def f(state, rows, idx):
                return bitops.pack_bool_u32(
                    bitset_ops.bitset_get(state, rows, idx, words_per_row=wpr)
                )
            return f

        fn = self._jit(key, build, donate=False)
        (rows_p, idx_p), _ = self._pad_ops(Bp, rows, idx)
        out = fn(pool.state, rows_p, idx_p)
        return LazyResult(out, transform=lambda v: bitops.unpack_bool_u32(v, B))

    def bitset_set_range(self, pool, row: int, from_bit: int, to_bit: int, value: bool) -> LazyResult:
        wpr = pool.row_units
        key = ("bs_setrange", wpr, pool.state.shape[0], bool(value))

        def build():
            def f(state, row, fb, tb):
                return bitset_ops.bitset_set_range(
                    state, row, fb, tb, words_per_row=wpr, value=value
                )
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(pool.state, row, from_bit, to_bit)
        return LazyResult(None)

    def _bitset_row_scalar(self, opname, kernel, pool, row):
        wpr = pool.row_units
        key = (opname, wpr, pool.state.shape[0])

        def build():
            def f(state, row):
                return kernel(state, row, words_per_row=wpr)
            return f

        fn = self._jit(key, build, donate=False)
        return LazyResult(fn(pool.state, row), transform=int)

    def bitset_cardinality(self, pool, row) -> LazyResult:
        return self._bitset_row_scalar(
            "bs_card", bitset_ops.bitset_cardinality, pool, row
        )

    def bitset_length(self, pool, row) -> LazyResult:
        return self._bitset_row_scalar("bs_len", bitset_ops.bitset_length, pool, row)

    def bitset_bitpos(self, pool, row, target_bit: int) -> LazyResult:
        wpr = pool.row_units
        key = ("bs_pos", wpr, pool.state.shape[0], target_bit)

        def build():
            def f(state, row):
                return bitset_ops.bitset_bitpos(
                    state, row, words_per_row=wpr, target_bit=target_bit
                )
            return f

        fn = self._jit(key, build, donate=False)
        return LazyResult(fn(pool.state, row), transform=int)

    def bitset_bitop(self, pool, dst_row: int, src_rows, op: str, limit_bits=None) -> LazyResult:
        wpr = pool.row_units
        S = len(src_rows)
        masked = limit_bits is not None  # NOT path: mask to logical length
        key = ("bs_bitop", wpr, pool.state.shape[0], S, op, masked)

        def build():
            def f(state, dst, srcs, limit):
                return bitset_ops.bitset_bitop_rows(
                    state, dst, srcs, words_per_row=wpr, op=op, n_src=S,
                    limit_bits=limit if masked else None,
                )
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(
            pool.state,
            dst_row,
            jnp.asarray(np.asarray(src_rows, np.int32)),
            np.int64(limit_bits if masked else 0),
        )
        return LazyResult(None)

    def bitset_get_row(self, pool, row) -> LazyResult:
        wpr = pool.row_units
        key = ("bs_getrow", wpr, pool.state.shape[0])

        def build():
            def f(state, row):
                return bitset_ops.bitset_get_row(state, row, words_per_row=wpr)
            return f

        fn = self._jit(key, build, donate=False)
        return LazyResult(fn(pool.state, row))

    # -- cms ---------------------------------------------------------------

    def cms_update(self, pool, rows, h1w, h2w, weights, d: int, w: int) -> LazyResult:
        B = h1w.shape[0]
        Bp = self._bucket(B)
        u = pool.row_units
        key = ("cms_upd", pool.state.shape[0], Bp, d, w)

        def build():
            def f(state, rows, h1w, h2w, weights):
                return cms_ops.cms_update(
                    state, rows, h1w, h2w, weights, d=d, w=w, cells_per_row=u
                )
            return f

        fn = self._jit(key, build, donate=True)
        # Padded weights are 0 → scatter-add no-ops; no scratch needed.
        (rows_p, h1p, h2p, w_p), _ = self._pad_ops(Bp, rows, h1w, h2w, weights)
        pool.state = fn(pool.state, rows_p, h1p, h2p, w_p)
        return LazyResult(None)

    def cms_estimate(self, pool, rows, h1w, h2w, d: int, w: int) -> LazyResult:
        B = h1w.shape[0]
        Bp = self._bucket(B)
        u = pool.row_units
        key = ("cms_est", pool.state.shape[0], Bp, d, w)

        def build():
            def f(state, rows, h1w, h2w):
                return cms_ops.cms_estimate(
                    state, rows, h1w, h2w, d=d, w=w, cells_per_row=u
                )
            return f

        fn = self._jit(key, build, donate=False)
        (rows_p, h1p, h2p), _ = self._pad_ops(Bp, rows, h1w, h2w)
        out = fn(pool.state, rows_p, h1p, h2p)
        return LazyResult(out, B)

    def cms_update_estimate(self, pool, rows, h1w, h2w, weights, d: int, w: int) -> LazyResult:
        """Coalesced CMS path (updates + estimates share one segment).
        Fused H2D: one packed staging block per flush — padded ops carry
        weight 0 (the scatter-add identity), so no valid mask ships."""
        B = h1w.shape[0]
        Bp = self._bucket(B)
        u = pool.row_units
        key = ("cms_updest", pool.state.shape[0], Bp, d, w)

        def build():
            def f(state, packed):
                o = 0
                rows = jax.lax.bitcast_convert_type(
                    packed[o : o + Bp], jnp.int32)
                o += Bp
                h1w = packed[o : o + Bp]
                o += Bp
                h2w = packed[o : o + Bp]
                o += Bp
                weights = packed[o : o + Bp]
                return cms_ops.cms_update_and_estimate(
                    state, rows, h1w, h2w, weights, d=d, w=w, cells_per_row=u
                )
            return f

        fn = self._jit(key, build, donate=True)
        total = 4 * Bp
        slot = self._staging.acquire(("cms_updest", Bp), total)
        buf = slot.buf
        o = _fill_words(buf, 0, Bp, np.asarray(rows, np.int32), np.int32)
        o = _fill_words(buf, o, Bp, np.asarray(h1w, np.uint32), np.uint32)
        o = _fill_words(buf, o, Bp, np.asarray(h2w, np.uint32), np.uint32)
        _fill_words(buf, o, Bp, np.asarray(weights, np.uint32), np.uint32)
        pool.state, est = fn(pool.state, self._ship(slot, total))
        return LazyResult(est, B)

    # Pallas heavy-hitter path (BASELINE config 5): SEQUENTIAL streaming
    # semantics — op j's estimate is its at-sequence-point value (ops ≤ j
    # applied, later ops excluded), which the vectorized XLA path cannot
    # express (it applies the whole batch before estimating).  The counter
    # table is VMEM-resident for the launch.  Single-device only; the
    # sharded executor falls back.
    supports_pallas_cms = True

    def cms_update_estimate_seq(self, pool, row: int, h1w, h2w, weights, d: int, w: int) -> LazyResult:
        from redisson_tpu.ops import pallas_cms

        B = h1w.shape[0]
        # Pad BEFORE the jit boundary so varying batch sizes share one
        # compiled executable per 128-block bucket (padding inside the
        # trace would respecialize per raw B).  Padded ops carry weight 0
        # — the scatter-add identity.
        Bp = -(-B // 128) * 128
        u = pool.row_units
        interpret = jax.default_backend() == "cpu"
        key = ("cms_seq", pool.state.shape[0], u, d, w, Bp)

        def build():
            def f(state, row, h1, h2, wt):
                rowdata = bitops.row_slice(state, row, u)
                table = rowdata[: d * w].reshape(d, w)
                new_table, est = pallas_cms.cms_update_estimate_seq(
                    table, h1, h2, wt, d=d, w=w, interpret=interpret
                )
                newrow = jnp.concatenate(
                    [new_table.reshape(-1), rowdata[d * w :]]
                )
                return bitops.row_update(state, row, newrow, u), est
            return f

        fn = self._jit(key, build, donate=True)
        pool.state, est = fn(
            pool.state,
            np.int32(row),
            self._staged_put(h1w, Bp, dtype=np.uint32),
            self._staged_put(h2w, Bp, dtype=np.uint32),
            self._staged_put(weights, Bp, dtype=np.uint32),
        )
        return LazyResult(est, B)

    def cms_merge(self, pool, dst_row: int, src_rows) -> LazyResult:
        S = len(src_rows)
        u = pool.row_units
        key = ("cms_merge", pool.state.shape[0], S, u)

        def build():
            def f(state, dst, srcs):
                return cms_ops.cms_merge(state, dst, srcs, cells_per_row=u)
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(
            pool.state, dst_row, jnp.asarray(np.asarray(src_rows, np.int32))
        )
        return LazyResult(None)

    # -- generic -----------------------------------------------------------

    def zero_row(self, pool, row: int) -> None:
        """Clear a tenant row (delete / clear() support).  Synchronous."""
        u = pool.row_units
        key = ("zero_row", pool.state.shape[0], u, str(pool.spec.dtype))

        def build():
            def f(state, row):
                import jax.numpy as jnp
                from redisson_tpu.ops import bitops

                zeros = jnp.zeros((u,), state.dtype)
                return bitops.row_update(state, row, zeros, u)
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(pool.state, row)

    def read_row(self, pool, row: int) -> np.ndarray:
        """Host copy of one tenant row (migration / snapshot / dump)."""
        u = pool.row_units
        return np.asarray(pool.state[row * u : (row + 1) * u])

    def write_row(self, pool, row: int, data: np.ndarray) -> None:
        u = pool.row_units
        key = ("write_row", pool.state.shape[0], u, str(pool.spec.dtype))

        def build():
            def f(state, row, data):
                from redisson_tpu.ops import bitops

                return bitops.row_update(state, row, data, u)
            return f

        fn = self._jit(key, build, donate=True)
        pool.state = fn(pool.state, row, jnp.asarray(data))


def _nops_of(name: str, args) -> int:
    """Best-effort op count of a dispatch call: the longest sized
    operand after the pool (the per-op column — rows for multi-tenant
    methods, the key block for single-row methods such as
    bloom_contains_keys_st whose args[1] is a scalar row).  str/bytes
    args (opcode names) never count, and write_row's data payload is a
    row image, not an op batch."""
    if name == "write_row":
        return 1
    best = 1
    for a in args[1:]:
        if isinstance(a, (str, bytes)):
            continue
        try:
            n = len(a)
        except TypeError:
            continue
        if n > best:
            best = n
    return best


# Row-maintenance methods EXEMPT from the direct-dispatch deadline shed:
# they run inside compound engine operations (delete's detach→zero,
# migration's read→write→zero, reconcile's write-back, snapshots) where
# an abort between steps would tear state — a detached-but-unzeroed row
# could be reallocated carrying stale bits.  Serving-path ops (the
# bloom/hll/bitset/cms dispatch families) all shed.
_DEADLINE_EXEMPT = frozenset(("read_row", "write_row", "zero_row"))


def _locked(fn):
    import functools

    from redisson_tpu import overload as _ovl
    from redisson_tpu.executor.failures import (
        DeadlineExceededError,
        ExecutorRetiredError,
    )

    name = fn.__name__
    annotation = "rtpu:" + name  # host-region label (one str, not per call)
    # Chaos fault point, one interned string per method (zero per-call
    # allocation): rules can target one method ("dispatch.bloom_mixed_keys")
    # or the whole boundary ("dispatch").
    fault_point = "dispatch." + name
    sheddable = name not in _DEADLINE_EXEMPT

    def _shed_expired(self, args, stage: str) -> None:
        """Direct-dispatch deadline shed (ROADMAP overload item (c)):
        with no coalescer in front, the dispatch lock IS the queue — an
        op whose deadline lapsed must shed before the device sees it,
        exactly like the coalescer's pre-dispatch sweep.  Strictly
        pre-dispatch, so no acked write is ever shed."""
        nops = _nops_of(name, args)
        obs = self.obs
        if obs is not None:
            obs.shed_ops.inc(("deadline",), nops)
            obs.deadline_exceeded.inc(("direct",), nops)
        raise DeadlineExceededError(
            f"op deadline expired {stage} direct dispatch "
            f"({name}, {nops} ops)", stage="direct",
        )

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        deadline = _ovl.current_deadline() if sheddable else None
        if deadline is not None and time.monotonic() >= deadline:
            _shed_expired(self, args, "before")
        with self._dispatch_lock:
            if _chaos.ENABLED:
                _chaos.fire(fault_point)
            # Re-check after the lock wait: a long queue behind another
            # thread's dispatches may have outlived the budget.  Nested
            # wrapped calls (_dispatch_recording) are mid-compound-op
            # and never shed — the outermost check governed admission.
            if (
                deadline is not None
                and not getattr(self, "_dispatch_recording", False)
                and time.monotonic() >= deadline
            ):
                _shed_expired(self, args, "waiting for the lock of")
            # A live change_topology may have swapped this executor out
            # while the caller was blocked on the lock (callers read
            # ``engine.executor`` BEFORE acquiring it).  Running the old
            # kernel against the re-laid-out pool.state would corrupt or
            # crash.  FORWARD to the successor executor instead (same
            # lock object, reentrant) so direct non-coalesced callers
            # never see a spurious failure — except the *_runs methods
            # when the successor doesn't support runs metadata (its
            # inherited implementation would be layout-wrong): those
            # raise retryable and the coalescer's retry loop re-binds,
            # re-checking supports_runs_metadata at the engine level.
            if getattr(self, "_retired", False):
                succ = getattr(self, "_successor", None)
                if succ is not None and not (
                    name.endswith("_runs")
                    and not getattr(succ, "supports_runs_metadata", False)
                ):
                    # The successor's own wrapper records its metrics.
                    return getattr(succ, name)(*args, **kwargs)
                raise ExecutorRetiredError(
                    f"{type(self).__name__} was retired by a topology change"
                )
            obs, metrics = self.obs, self.metrics
            if obs is None and metrics is None:
                return fn(self, *args, **kwargs)
            if getattr(self, "_dispatch_recording", False):
                # Nested wrapped call (a sharded *_st method delegating
                # to bloom_mixed_keys, zero_row -> write_row, ...): the
                # OUTERMOST wrapper records; recording here too would
                # double-count launches and ops.  Safe as a plain
                # attribute — we hold the reentrant dispatch lock on
                # this thread.
                return fn(self, *args, **kwargs)
            self._dispatch_recording = True
            # Compiles firing inside are labelled with this method
            # (rtpu_compiles); the staging helpers add the H2D bytes and
            # transfers.
            d = _spans.DISPATCH
            d.method, d.obs, d.h2d_bytes, d.h2d_puts = name, obs, 0, 0
            t0 = time.monotonic()
            try:
                # Host region in a jax.profiler capture, named by method.
                with _spans.region(annotation):
                    out = fn(self, *args, **kwargs)
            finally:
                self._dispatch_recording = False
                d.method = d.obs = None
            dur = time.monotonic() - t0
            nops = _nops_of(name, args)
            if metrics is not None:
                # Direct-dispatch path (no coalescer in front): this is
                # the only recorder, so sharded/coalesce=False runs no
                # longer report zero ops (ISSUE 1 satellite).
                metrics.record_dispatch(nops=nops, enqueue_s=dur)
            if obs is not None:
                obs.record_dispatch(name, nops, dur, d.h2d_bytes, d.h2d_puts)
            return out

    return wrapper


# Every method that reads or swaps pool.state (donated buffers + concurrent
# threads would otherwise race, see class docstring).  Shared with the
# sharded executor so the two wrap lists cannot drift.
DISPATCH_METHODS = (
    "bloom_mixed_keys",
    "bloom_mixed_keys_runs",
    "bloom_contains_keys_runs",
    "bitset_mixed",
    "bitset_mixed_runs",
    "bloom_contains_keys_st",
    "hll_add_keys_single",
    "bloom_count",
    "hll_add",
    "hll_add_changed",
    "hll_add_single",
    "hll_count",
    "hll_merge",
    "bitset_set",
    "bitset_clear_bits",
    "bitset_flip",
    "bitset_get",
    "bitset_set_range",
    "bitset_cardinality",
    "bitset_length",
    "bitset_bitpos",
    "bitset_bitop",
    "bitset_get_row",
    "cms_update",
    "cms_estimate",
    "cms_update_estimate",
    "cms_update_estimate_seq",
    "cms_merge",
    "zero_row",
    "read_row",
    "write_row",
)

for _name in DISPATCH_METHODS:
    setattr(TpuCommandExecutor, _name, _locked(getattr(TpuCommandExecutor, _name)))
