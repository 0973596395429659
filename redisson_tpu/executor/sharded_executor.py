"""ShardedTpuCommandExecutor — the multi-chip command executor.

The cluster-mode analog of the reference's ClusterConnectionManager +
CommandExecutor pair (→ org/redisson/cluster/ClusterConnectionManager.java,
SURVEY.md §2.4 cluster-sharding row): instead of CRC16 slots and MOVED
redirects, tenant row ``r`` lives on shard ``r % S`` of a 1-D device mesh.

Dispatch model (round 3 — partition-by-owner): the host splits every op
batch by owner shard into ``[S, Bp]`` blocks — the role
CommandBatchService#executeAsync plays when it groups commands per
MasterSlaveEntry (SURVEY.md §3.2) — and ``shard_map`` with
``in_specs=P("shard")`` hands each shard ONLY its ops.  Total device work
is B (round 2 replicated every batch to every shard: S×B), writes are
shard-local, and results come back ``[S, Bp]`` bit-packed with no
collective.  Collectives remain only for genuinely cross-shard ops
(BITOP/PFMERGE, m-sharded bitmaps — parallel/mesh.py).  Every partitioned
input is placed with the state's sharding, so each chip receives its own
slice from the host; a Bloom launch packs all its columns into one
``[S, W, Bp]`` block, so it is one transfer.

Every Bloom launch hashes on the device: raw codec lanes ride the
partition and murmur runs in-kernel, so sharded traffic ships key bytes,
not 16-byte host hashes.

Pool state: ``[S, local_len]`` arrays block-sharded along axis 0
(NamedSharding over a ``jax.sharding.Mesh``); each shard's local block is a
flat ``[rows_local * row_units + 1]`` array with its own trailing scratch
element, so every kernel from ops/ runs unchanged inside ``shard_map``.

Exposes the exact method surface of TpuCommandExecutor, so the engine and
coalescer are shard-agnostic: ``Config.use_tpu_sketch(num_shards=S)`` is
the only switch.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from redisson_tpu import chaos as _chaos
from redisson_tpu.ops import bitops
from redisson_tpu.ops import bitset as bitset_ops
from redisson_tpu.ops import bloom as bloom_ops
from redisson_tpu.ops import golden
from redisson_tpu.executor.tpu_executor import (
    DISPATCH_METHODS,
    LazyResult,
    TpuCommandExecutor,
    _locked,
    _put_staged,
    bloom_count_from_bitcount,
    ensure_addressable,
)
from redisson_tpu.parallel import mesh as pm


class _Partition:
    """Host-side owner-shard split of one op batch: builds the [S, Bp]
    scatter layout and the inverse mapping that restores per-op results to
    arrival order.  ``shard`` may be any per-op owner assignment — row % S
    for tenant-sharded pools (see ``from_rows``), word-block for m-sharded
    bitmaps."""

    __slots__ = ("S", "B", "Bp", "order", "sh_sorted", "slot", "lrows",
                 "counts")

    @classmethod
    def from_rows(cls, S: int, rows, bucket_fn) -> "_Partition":
        rows = np.asarray(rows, np.int64)
        p = cls(S, rows % S, bucket_fn)
        p.lrows = (rows // S).astype(np.int32)
        return p

    def __init__(self, S: int, shard, bucket_fn):
        shard = np.asarray(shard, np.int64)
        self.S = S
        self.B = int(shard.shape[0])
        self.lrows = None
        self.order = np.argsort(shard, kind="stable")
        counts = np.bincount(shard, minlength=S)
        self.counts = counts  # per-shard op counts (obs shard dimension)
        self.Bp = bucket_fn(int(counts.max()) if self.B else 1)
        self.sh_sorted = shard[self.order]
        offsets = np.zeros(S, np.int64)
        np.cumsum(counts[:-1], out=offsets[1:])
        self.slot = np.arange(self.B, dtype=np.int64) - offsets[self.sh_sorted]

    def unpack_bools(self, packed: np.ndarray) -> np.ndarray:
        """[S, Bp/32] packed results -> bool[B] in arrival order."""
        un = bitops.unpack_bool_u32(
            np.ascontiguousarray(packed).reshape(-1), self.S * self.Bp
        ).reshape(self.S, self.Bp)
        res = np.empty(self.B, bool)
        res[self.order] = un[self.sh_sorted, self.slot]
        return res

    def gather_vals(self, block: np.ndarray) -> np.ndarray:
        """[S, Bp] per-op values -> [B] in arrival order."""
        res = np.empty(self.B, block.dtype)
        res[self.order] = block[self.sh_sorted, self.slot]
        return res


class ShardedTpuCommandExecutor(TpuCommandExecutor):
    # The Pallas sequential kernel operates on a single-device VMEM table;
    # sharded CMS traffic uses the partitioned XLA path instead.
    supports_pallas_cms = False
    # Partition-by-owner reorders ops host-side before dispatch, so runs
    # metadata can't describe the shipped order — per-op-array path.
    supports_runs_metadata = False

    def __init__(self, config):
        super().__init__(config)
        n = config.tpu_sketch.num_shards
        # Device pinning (ISSUE 17 satellite): an explicit device_indices
        # slice builds the mesh from EXACTLY those devices (order kept);
        # otherwise the enumeration order, as before.
        self.ctx = pm.MeshContext(devices=self.devices, n_shards=n)
        if self.ctx.n_shards < n:
            raise RuntimeError(
                f"num_shards={n} but only {self.ctx.n_shards} devices are "
                f"available (set XLA_FLAGS=--xla_force_host_platform_device_"
                f"count=N with JAX_PLATFORMS=cpu for a virtual mesh)"
            )
        self.S = self.ctx.n_shards
        # A partitioned launch's inputs go committed to their owning
        # chips, as the shard_map expects them: no hop through the
        # default device and no re-slice at dispatch.  A mesh spanning
        # other processes cannot be placed from this host alone, so
        # there each input goes to the default device and the jit
        # places it.
        sh = self.ctx.state_sharding
        self._put_sharding = sh if sh.is_fully_addressable else None

    # -- pool-state factory ------------------------------------------------

    def _mbit_layout(self, row_units: int, kind: str) -> bool:
        from redisson_tpu.tenancy import PoolKind

        return (
            kind == PoolKind.BITSET
            and row_units >= self._cfg.mbit_threshold_words
            and row_units % self.S == 0
        )

    def round_capacity(self, capacity: int, row_units: int = 0, kind: str = "") -> int:
        if self._mbit_layout(row_units, kind):
            # m-sharded rows span every shard; capacity needs no S-multiple.
            # Clamp the initial footprint like the base class (giant rows).
            if capacity * row_units > (1 << 27):
                return max(1, (1 << 27) // row_units)
            return capacity
        return -(-capacity // self.S) * self.S

    def make_pool_state(self, capacity: int, row_units: int, dtype, kind: str = ""):
        if self._mbit_layout(row_units, kind):
            # [S, T * W_local + 1]: each shard holds its word window of
            # EVERY row (plus its own scratch element).
            local_len = capacity * (row_units // self.S) + 1
        else:
            local_len = capacity // self.S * row_units + 1
        return self.ctx.make_state(local_len, dtype)

    def grow_pool_state(self, state, old_cap: int, new_cap: int, row_units: int, dtype, kind: str = ""):
        if self._mbit_layout(row_units, kind):
            extra_local = (new_cap - old_cap) * (row_units // self.S) + 1
        else:
            extra_local = (new_cap - old_cap) // self.S * row_units + 1
        new_state = jnp.concatenate(
            [state[:, :-1], jnp.zeros((self.S, extra_local), dtype)], axis=1
        )
        return jax.device_put(new_state, self.ctx.state_sharding)

    def state_from_host(self, pool, arr: np.ndarray) -> None:
        dev = jnp.asarray(arr)
        from redisson_tpu.executor.tpu_executor import _host_may_alias

        if _host_may_alias():
            # Same CPU zero-copy hazard as the base class: donated state
            # must never wrap host-owned memory (see the single-device
            # state_from_host).
            dev = jnp.copy(dev)
        pool.state = jax.device_put(dev, self.ctx.state_sharding)

    # -- builder cache (mesh.py builders are already jitted; jax handles
    # shape polymorphism internally, so keys don't need batch sizes) -------

    def _builder(self, key: tuple, make):
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    fn = make()
                    self._jit_cache[key] = fn
        return fn

    def _part(self, rows) -> _Partition:
        p = _Partition.from_rows(self.S, rows, self._bucket)
        if self.obs is not None:  # per-shard routing counts (obs registry)
            self.obs.record_shard_counts(p.counts)
        return p

    def _scatter_put(self, p: _Partition, col, fill=0):
        """``p.scatter`` into a reusable pinned staging buffer + one
        device_put — the sharded twin of the single-device fused staging
        path: per-dispatch [S, Bp] np.full allocations become buffer
        reuse, and the transfer's host block is pinned across flushes."""
        if _chaos.ENABLED:  # sharded scatter-staging fault point (ISSUE 3)
            _chaos.fire("h2d.scatter", data=col)
        col = np.asarray(col)
        shape = (p.S, p.Bp) + col.shape[1:]
        count = int(np.prod(shape))
        nwords = -(-count * col.dtype.itemsize // 4)
        # Depth 2 for lane blocks ([S, Bp, L] — tens of MB on big keyed
        # batches): a deep ring would pin 8x that in host RAM, same
        # reasoning as the single-device _staged_blocks.
        key = ("scatter", col.dtype.str, shape)
        if col.ndim > 1:
            slot = self._staging.acquire(key, nwords, depth=2)
        else:
            slot = self._staging.acquire(key, nwords)
        view = slot.buf[:nwords].view(col.dtype)[:count].reshape(shape)
        view[...] = fill
        view[p.sh_sorted, p.slot] = col[p.order]
        return _put_staged(slot, view, self._put_sharding)

    def _valid_put(self, p: _Partition):
        """[S, Bp] bool: True at the slots that hold an op."""
        return self._scatter_put(p, np.ones(p.B, bool))

    def _place(self, arr):
        """A small per-shard argument ([S, ...]) placed like the
        partitioned columns."""
        return jax.device_put(np.asarray(arr), self._put_sharding)

    def _bloom_put(self, p: _Partition, m_arr, is_add, *payload):
        """One partitioned Bloom launch as ONE [S, W, Bp] uint32 block
        (layout: parallel/mesh.py, BLOOM_HEAD), packed in a pinned
        staging buffer and shipped with one transfer.  ``payload``:
        per-op [B] or [B, L] uint32 columns after the head rows."""
        if _chaos.ENABLED:  # sharded scatter-staging fault point
            _chaos.fire("h2d.scatter", data=payload[0])
        W = pm.BLOOM_HEAD + sum(
            1 if c.ndim == 1 else c.shape[1] for c in payload)
        shape = (p.S, W, p.Bp)
        nwords = p.S * W * p.Bp
        # Depth 2, as for lane blocks: a keyed block can be tens of MB.
        slot = self._staging.acquire(("bloom_block",) + shape, nwords,
                                     depth=2)
        view = slot.buf[:nwords].reshape(shape)
        view[...] = 0
        view[:, 1] = 1  # padded m must be nonzero (mod arithmetic)
        s, j, o = p.sh_sorted, p.slot, p.order
        view[s, 0, j] = p.lrows[o]
        view[s, 1, j] = np.asarray(m_arr, np.uint32)[o]
        view[s, 2, j] = np.where(np.asarray(is_add, bool)[o],
                                 pm.BLOOM_ADD | pm.BLOOM_VALID, pm.BLOOM_VALID)
        r = pm.BLOOM_HEAD
        for c in payload:
            if c.ndim == 1:
                view[s, r, j] = c[o]
                r += 1
            else:
                view[s, r:r + c.shape[1], j] = c[o]
                r += c.shape[1]
        return _put_staged(slot, view, self._put_sharding)

    # -- m-sharded bitset pools (config 3): rows at/above the word
    # threshold split their words contiguously across shards ---------------

    def _is_mbit(self, pool) -> bool:
        from redisson_tpu.tenancy import PoolKind

        return (
            pool.spec.kind == PoolKind.BITSET
            and pool.row_units >= self._cfg.mbit_threshold_words
            and pool.row_units % self.S == 0
        )

    def _mbit_wl(self, pool) -> int:
        return pool.row_units // self.S

    def _mpart(self, pool, idx):
        """Partition single-bit ops by word-shard; returns (partition,
        local_idx) where local_idx is the bit index within the shard's
        word window of the row."""
        WL = self._mbit_wl(pool)
        idx = np.asarray(idx, np.int64)
        shard = (idx >> 5) // WL
        p = _Partition(self.S, shard, self._bucket)
        if self.obs is not None:
            self.obs.record_shard_counts(p.counts)
        lidx = (idx - shard * (WL * 32)).astype(np.uint32)
        return p, lidx

    # -- bloom (every Bloom op routes through the partitioned keyed
    # mixed kernel: adds are is_add=True ops, contains is_add=False) -------

    def bloom_mixed_keys(self, pool, rows, m_arr, k: int, blocks, lengths, is_add) -> LazyResult:
        """Partitioned device-hash path: key lanes ship to the owning shard
        only; murmur + exact 64-bit mod run in-kernel (ops/fastpath.py)."""
        wpr = pool.row_units
        blocks = np.asarray(blocks)
        blocks_t, L = self._trim_lanes(blocks)
        Lt = blocks_t.shape[1]
        fn = self._builder(
            ("psh_bloom_mixk", wpr, k, L, Lt),
            lambda: pm.psharded_bloom_mixed_keys(
                self.ctx, k=k, words_per_row=wpr, target_lanes=L
            ),
        )
        p = self._part(rows)
        lengths = np.asarray(lengths, np.uint32)
        if lengths.ndim == 0:
            lengths = np.full(len(rows), lengths, np.uint32)
        pool.state, packed = fn(pool.state, self._bloom_put(
            p, m_arr, is_add, lengths, blocks_t))
        # Each shard scans its own [Bp] slice; the count is one shard's.
        self._count_scan("bloom_mixed_keys", bloom_ops.scan_steps(p.Bp, k),
                         pool.state.shape[1])
        return LazyResult(packed, transform=p.unpack_bools)

    def bloom_contains_keys_st(self, pool, row: int, m: int, k: int, blocks, lengths) -> LazyResult:
        B = blocks.shape[0]
        return self.bloom_mixed_keys(
            pool,
            np.full(B, row, np.int32),
            np.full(B, m, np.uint32),
            k,
            blocks,
            lengths,
            np.zeros(B, bool),
        )

    def bloom_count(self, pool, row: int, m: int, k: int) -> LazyResult:
        wpr = pool.row_units
        fn = self._builder(
            ("sh_popcount", wpr),
            lambda: pm.sharded_row_reduce(
                self.ctx,
                lambda local, lrow: bitops.popcount_row(local, lrow, wpr),
            ),
        )
        x = fn(pool.state, row)
        return LazyResult(x, transform=lambda xv: bloom_count_from_bitcount(xv, m, k))

    # -- hll ---------------------------------------------------------------

    def _hll_changed_part(self, pool, rows, c0, c1, c2):
        fn = self._builder(
            ("psh_hll_add",), lambda: pm.psharded_hll_add_changed(self.ctx)
        )
        p = self._part(rows)
        pool.state, packed = fn(
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, np.asarray(c0, np.uint32)),
            self._scatter_put(p, np.asarray(c1, np.uint32)),
            self._scatter_put(p, np.asarray(c2, np.uint32)),
            self._valid_put(p),
        )
        return packed, p

    def hll_add(self, pool, rows, c0, c1, c2) -> LazyResult:
        self._hll_changed_part(pool, rows, c0, c1, c2)
        return LazyResult(True)

    def hll_add_changed(self, pool, rows, c0, c1, c2) -> LazyResult:
        packed, p = self._hll_changed_part(pool, rows, c0, c1, c2)
        return LazyResult(packed, transform=p.unpack_bools)

    def hll_add_single(self, pool, row: int, c0, c1, c2) -> LazyResult:
        rows = np.full(c0.shape[0], row, np.int32)
        packed, p = self._hll_changed_part(pool, rows, c0, c1, c2)
        return LazyResult(
            packed, transform=lambda v: bool(np.any(p.unpack_bools(v)))
        )

    def hll_add_keys_single(self, pool, row: int, blocks, lengths) -> LazyResult:
        blocks = np.asarray(blocks)
        B = blocks.shape[0]
        blocks_t, L = self._trim_lanes(blocks)
        Lt = blocks_t.shape[1]
        fn = self._builder(
            ("psh_hll_addk", L, Lt),
            lambda: pm.psharded_hll_add_keys(self.ctx, target_lanes=L),
        )
        p = self._part(np.full(B, row, np.int32))
        lengths = np.asarray(lengths, np.uint32)
        if lengths.ndim == 0:
            lengths = np.full(B, lengths, np.uint32)
        pool.state, packed = fn(
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, blocks_t),
            self._scatter_put(p, lengths),
            self._valid_put(p),
        )
        return LazyResult(
            packed, transform=lambda v: bool(np.any(p.unpack_bools(v)))
        )

    def hll_count(self, pool, row: int) -> LazyResult:
        from redisson_tpu.ops import hll as hll_ops

        fn = self._builder(
            ("sh_hll_hist",),
            lambda: pm.sharded_row_reduce(self.ctx, hll_ops.hll_histogram),
        )
        hist = fn(pool.state, row)
        return LazyResult(
            hist, transform=lambda h: int(round(golden.ertl_estimate(h)))
        )

    def hll_merge(self, pool, dst_row: int, src_rows) -> LazyResult:
        fn = self._builder(
            ("sh_hll_merge",), lambda: pm.sharded_hll_merge(self.ctx)
        )
        pool.state = fn(
            pool.state, dst_row, jnp.asarray(np.asarray(src_rows, np.int32))
        )
        return LazyResult(None)

    # -- bitset ------------------------------------------------------------

    def bitset_mixed(self, pool, rows, idx, opcodes) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            p, lidx = self._mpart(pool, idx)
            fn = self._builder(
                ("msh_bs_mixed", WL),
                lambda: pm.psharded_bitset_mixed(self.ctx, words_per_row=WL),
            )
            pool.state, packed = fn(
                pool.state,
                self._scatter_put(p, np.asarray(rows, np.int32)),
                self._scatter_put(p, lidx),
                self._scatter_put(
                    p, np.asarray(opcodes, np.uint32), fill=bitset_ops.OP_GET
                ),
                self._valid_put(p),
            )
            return LazyResult(packed, transform=p.unpack_bools)
        wpr = pool.row_units
        fn = self._builder(
            ("psh_bs_mixed", wpr),
            lambda: pm.psharded_bitset_mixed(self.ctx, words_per_row=wpr),
        )
        p = self._part(rows)
        pool.state, packed = fn(
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, np.asarray(idx, np.uint32)),
            self._scatter_put(
                p, np.asarray(opcodes, np.uint32), fill=bitset_ops.OP_GET
            ),
            self._valid_put(p),
        )
        return LazyResult(packed, transform=p.unpack_bools)

    def _bitset_rw(self, opname, kernel, pool, rows, idx):
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            p, lidx = self._mpart(pool, idx)
            fn = self._builder(
                ("msh_" + opname, WL),
                lambda: pm.psharded_bitset_rw(self.ctx, kernel, words_per_row=WL),
            )
            pool.state, packed = fn(
                pool.state,
                self._scatter_put(p, np.asarray(rows, np.int32)),
                self._scatter_put(p, lidx),
                self._valid_put(p),
            )
            return LazyResult(packed, transform=p.unpack_bools)
        wpr = pool.row_units
        fn = self._builder(
            ("psh_" + opname, wpr),
            lambda: pm.psharded_bitset_rw(self.ctx, kernel, words_per_row=wpr),
        )
        p = self._part(rows)
        pool.state, packed = fn(
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, np.asarray(idx, np.uint32)),
            self._valid_put(p),
        )
        return LazyResult(packed, transform=p.unpack_bools)

    def bitset_set(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_set", bitset_ops.bitset_set, pool, rows, idx)

    def bitset_clear_bits(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_clear", bitset_ops.bitset_clear, pool, rows, idx)

    def bitset_flip(self, pool, rows, idx) -> LazyResult:
        return self._bitset_rw("bs_flip", bitset_ops.bitset_flip, pool, rows, idx)

    def bitset_get(self, pool, rows, idx) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            p, lidx = self._mpart(pool, idx)
            fn = self._builder(
                ("msh_bs_get", WL),
                lambda: pm.psharded_bitset_get(self.ctx, words_per_row=WL),
            )
            packed = fn(
                pool.state,
                self._scatter_put(p, np.asarray(rows, np.int32)),
                self._scatter_put(p, lidx),
                self._valid_put(p),
            )
            return LazyResult(packed, transform=p.unpack_bools)
        wpr = pool.row_units
        fn = self._builder(
            ("psh_bs_get", wpr),
            lambda: pm.psharded_bitset_get(self.ctx, words_per_row=wpr),
        )
        p = self._part(rows)
        packed = fn(
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, np.asarray(idx, np.uint32)),
            self._valid_put(p),
        )
        return LazyResult(packed, transform=p.unpack_bools)

    def bitset_set_range(self, pool, row: int, from_bit: int, to_bit: int, value: bool) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            win = WL * 32
            offs = np.arange(self.S, dtype=np.int64) * win
            fb = np.clip(int(from_bit) - offs, 0, win).astype(np.int32)
            tb = np.clip(int(to_bit) - offs, 0, win).astype(np.int32)
            fn = self._builder(
                ("msh_bs_setrange", WL, bool(value)),
                lambda: pm.msharded_set_range(
                    self.ctx, words_local=WL, value=value
                ),
            )
            pool.state = fn(pool.state, row, self._place(fb), self._place(tb))
            return LazyResult(None)
        wpr = pool.row_units
        fn = self._builder(
            ("sh_bs_setrange", wpr, bool(value)),
            lambda: pm.sharded_bitset_set_range(
                self.ctx, words_per_row=wpr, value=value
            ),
        )
        pool.state = fn(pool.state, row, from_bit, to_bit)
        return LazyResult(None)

    def bitset_cardinality(self, pool, row) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            fn = self._builder(
                ("msh_bs_card", WL),
                lambda: pm.msharded_row_map(
                    self.ctx, lambda local, r: bitops.popcount_row(local, r, WL)
                ),
            )
            return LazyResult(
                fn(pool.state, row), transform=lambda v: int(np.sum(v))
            )
        wpr = pool.row_units
        fn = self._builder(
            ("sh_bs_card", wpr),
            lambda: pm.sharded_row_reduce(
                self.ctx, lambda local, lrow: bitops.popcount_row(local, lrow, wpr)
            ),
        )
        return LazyResult(fn(pool.state, row), transform=int)

    def bitset_length(self, pool, row) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            win = WL * 32

            def combine(parts):
                parts = np.asarray(parts)
                glob = [s * win + int(p) for s, p in enumerate(parts) if p > 0]
                return max(glob) if glob else 0

            fn = self._builder(
                ("msh_bs_len", WL),
                lambda: pm.msharded_row_map(
                    self.ctx, lambda local, r: bitops.bit_length_row(local, r, WL)
                ),
            )
            return LazyResult(fn(pool.state, row), transform=combine)
        wpr = pool.row_units
        fn = self._builder(
            ("sh_bs_len", wpr),
            lambda: pm.sharded_row_reduce(
                self.ctx, lambda local, lrow: bitops.bit_length_row(local, lrow, wpr)
            ),
        )
        return LazyResult(fn(pool.state, row), transform=int)

    def bitset_bitpos(self, pool, row, target_bit: int) -> LazyResult:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            win = WL * 32

            def combine(parts):
                parts = np.asarray(parts)
                if target_bit:
                    hits = [s * win + int(p) for s, p in enumerate(parts) if p >= 0]
                    return min(hits) if hits else -1
                # target 0: a shard reporting win means its window is full.
                for s, p in enumerate(parts):
                    if p < win:
                        return s * win + int(p)
                return self.S * win

            fn = self._builder(
                ("msh_bs_pos", WL, target_bit),
                lambda: pm.msharded_row_map(
                    self.ctx,
                    lambda local, r: bitops.bitpos_row(local, r, WL, target_bit),
                ),
            )
            return LazyResult(fn(pool.state, row), transform=combine)
        wpr = pool.row_units
        fn = self._builder(
            ("sh_bs_pos", wpr, target_bit),
            lambda: pm.sharded_row_reduce(
                self.ctx,
                lambda local, lrow: bitops.bitpos_row(
                    local, lrow, wpr, target_bit
                ),
            ),
        )
        return LazyResult(fn(pool.state, row), transform=int)

    def bitset_bitop(self, pool, dst_row: int, src_rows, op: str, limit_bits=None) -> LazyResult:
        S_src = len(src_rows)
        masked = limit_bits is not None
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            win = WL * 32
            offs = np.arange(self.S, dtype=np.int64) * win
            limit_local = np.clip(
                (int(limit_bits) if masked else 0) - offs, 0, win
            ).astype(np.int64)
            fn = self._builder(
                ("msh_bs_bitop", WL, S_src, op, masked),
                lambda: pm.msharded_bitop(
                    self.ctx, words_local=WL, op=op, n_src=S_src, masked=masked
                ),
            )
            pool.state = fn(
                pool.state,
                dst_row,
                jnp.asarray(np.asarray(src_rows, np.int32)),
                self._place(limit_local),
            )
            return LazyResult(None)
        wpr = pool.row_units
        fn = self._builder(
            ("sh_bs_bitop", wpr, S_src, op, masked),
            lambda: pm.sharded_bitop(
                self.ctx, words_per_row=wpr, op=op, n_src=S_src, masked=masked
            ),
        )
        pool.state = fn(
            pool.state,
            dst_row,
            jnp.asarray(np.asarray(src_rows, np.int32)),
            np.int64(limit_bits if masked else 0),
        )
        return LazyResult(None)

    def bitset_get_row(self, pool, row) -> LazyResult:
        return LazyResult(self._read_row_device(pool, row))

    # -- cms ---------------------------------------------------------------

    def _cms_part(self, pool, rows, h1w, h2w, weights, d, w, mode):
        u = pool.row_units
        fn = self._builder(
            ("psh_cms", u, d, w, mode),
            lambda: pm.psharded_cms_update_estimate(
                self.ctx,
                d=d,
                w=w,
                cells_per_row=u,
                estimate_only=(mode == "est"),
                update_only=(mode == "upd"),
            ),
        )
        p = self._part(rows)
        args = (
            pool.state,
            self._scatter_put(p, p.lrows),
            self._scatter_put(p, np.asarray(h1w, np.uint32)),
            self._scatter_put(p, np.asarray(h2w, np.uint32)),
            self._scatter_put(p, np.asarray(weights, np.uint32)),
            self._valid_put(p),
        )
        if mode == "est":
            est = fn(*args)
            return LazyResult(est, transform=p.gather_vals)
        if mode == "upd":
            pool.state = fn(*args)
            return LazyResult(None)
        pool.state, est = fn(*args)
        return LazyResult(est, transform=p.gather_vals)

    def cms_update(self, pool, rows, h1w, h2w, weights, d: int, w: int) -> LazyResult:
        return self._cms_part(pool, rows, h1w, h2w, weights, d, w, "upd")

    def cms_estimate(self, pool, rows, h1w, h2w, d: int, w: int) -> LazyResult:
        zeros = np.zeros(len(rows), np.uint32)
        return self._cms_part(pool, rows, h1w, h2w, zeros, d, w, "est")

    def cms_update_estimate(self, pool, rows, h1w, h2w, weights, d: int, w: int) -> LazyResult:
        return self._cms_part(pool, rows, h1w, h2w, weights, d, w, "updest")

    def cms_merge(self, pool, dst_row: int, src_rows) -> LazyResult:
        u = pool.row_units
        fn = self._builder(
            ("sh_cms_merge", u),
            lambda: pm.sharded_cms_merge(self.ctx, cells_per_row=u),
        )
        pool.state = fn(
            pool.state, dst_row, jnp.asarray(np.asarray(src_rows, np.int32))
        )
        return LazyResult(None)

    # -- generic -----------------------------------------------------------

    def _read_row_device(self, pool, row: int):
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            fn = self._builder(
                ("msh_read_row", WL),
                lambda: pm.msharded_row_map(
                    self.ctx, lambda local, r: bitops.row_slice(local, r, WL)
                ),
            )
            return fn(pool.state, row).reshape(-1)  # [S, WL] -> [U]
        u = pool.row_units
        dtype = str(pool.spec.dtype)
        fn = self._builder(
            ("sh_read_row", u, dtype),
            lambda: pm.sharded_row_read(self.ctx, row_units=u),
        )
        return fn(pool.state, row)

    def read_row(self, pool, row: int) -> np.ndarray:
        return np.asarray(ensure_addressable(self._read_row_device(pool, row)))

    def write_row(self, pool, row: int, data: np.ndarray) -> None:
        if self._is_mbit(pool):
            WL = self._mbit_wl(pool)
            fn = self._builder(
                ("msh_write_row", WL),
                lambda: pm.msharded_row_write(self.ctx, words_local=WL),
            )
            pool.state = fn(
                pool.state, row, self._place(np.asarray(data).reshape(self.S, WL))
            )
            return
        u = pool.row_units
        dtype = str(pool.spec.dtype)
        fn = self._builder(
            ("sh_write_row", u, dtype),
            lambda: pm.sharded_row_write(self.ctx, row_units=u),
        )
        pool.state = fn(pool.state, row, jnp.asarray(data))

    def zero_row(self, pool, row: int) -> None:
        self.write_row(
            pool, row, np.zeros(pool.row_units, dtype=pool.spec.dtype)
        )


# Same donated-buffer discipline as the base class, over the shared method
# list (the subclass defines fresh functions, so the base class's wrapping
# does not carry over; the shared tuple keeps the two executors in lockstep).
for _name in DISPATCH_METHODS:
    _impl = ShardedTpuCommandExecutor.__dict__.get(_name)
    if _impl is not None:  # methods not overridden inherit the wrapped base
        setattr(ShardedTpuCommandExecutor, _name, _locked(_impl))
