"""BloomFilter — parity with org/redisson/api/RBloomFilter.java /
org/redisson/RedissonBloomFilter.java (SURVEY.md §2.2).

Same public shape (tryInit/add/contains/count/getSize/...), same (m, k)
formulas, same Kirsch–Mitzenmacher index math — but add/contains ship one
vectorized device batch instead of k SETBIT/GETBIT commands per key.
camelCase aliases work via CamelCompatMixin (``bf.tryInit(...)``).
"""

from __future__ import annotations

import numpy as np

from redisson_tpu.objects.base import MappedFuture, RObject
from redisson_tpu.tenancy import PoolKind


class BloomFilter(RObject):
    KIND = PoolKind.BLOOM

    # Batch pipelining: sync-named calls ride these async forms inside
    # Batch.execute (resolved values match the sync contracts).
    _DEFERRED = {
        "add": "add_deferred",
        "add_all": "add_all_deferred",
        "contains": "contains_deferred",
        "contains_all": "contains_all_deferred",
        "contains_each": "contains_all_async",
    }

    def add_deferred(self, obj):
        return MappedFuture(self.add_all_async([obj]), lambda v: bool(v[0]))

    def add_all_deferred(self, objs):
        return MappedFuture(self.add_all_async(objs), lambda v: int(np.sum(v)))

    def contains_deferred(self, obj):
        return MappedFuture(self.contains_all_async([obj]), lambda v: bool(v[0]))

    def contains_all_deferred(self, objs):
        return MappedFuture(
            self.contains_all_async(objs), lambda v: int(np.sum(v))
        )

    # -- lifecycle ---------------------------------------------------------

    def try_init(self, expected_insertions: int, false_probability: float) -> bool:
        """→ RBloomFilter#tryInit: returns False if already initialized."""
        return self._engine.bloom_try_init(
            self._name, expected_insertions, false_probability
        )

    def _params(self) -> dict:
        p = self._engine.params(self._name)
        if p is None:
            raise RuntimeError(f"bloom filter {self._name!r} is not initialized")
        return p

    def get_size(self) -> int:
        """→ RBloomFilter#getSize (bit count m)."""
        return self._params()["size"]

    def get_hash_iterations(self) -> int:
        return self._params()["hash_iterations"]

    def get_expected_insertions(self) -> int:
        return self._params()["expected_insertions"]

    def get_false_probability(self) -> float:
        return self._params()["false_probability"]

    # -- data path ---------------------------------------------------------

    def add(self, obj) -> bool:
        """→ RBloomFilter#add(T): True iff at least one bit was newly set.
        ``obj`` is ONE key (wrapped explicitly — a tuple/list argument is
        a legal single key under pickle-style codecs; the batch forms
        would have hashed its ELEMENTS as separate keys)."""
        return bool(self.add_all_async([obj]).result()[0])

    def add_all(self, objs) -> int:
        """→ RBloomFilter#add(Collection): number of newly-added elements."""
        return int(np.sum(self.add_all_async(objs).result()))

    def add_all_async(self, objs):
        return self._engine.bloom_add_encoded(self._name, *self._encode(objs))

    add_async = add_all_async

    def contains(self, obj) -> bool:
        """One key, explicitly wrapped (see add)."""
        return bool(self.contains_all_async([obj]).result()[0])

    def contains_all(self, objs) -> int:
        """→ RBloomFilter#contains(Collection): how many are (probably)
        present."""
        return int(np.sum(self.contains_each(objs)))

    def contains_each(self, objs) -> np.ndarray:
        """Vectorized membership: bool per input (TPU-native extension used
        by the benchmark harness)."""
        return self.contains_all_async(objs).result()

    def contains_all_async(self, objs):
        return self._engine.bloom_contains_encoded(self._name, *self._encode(objs))

    contains_async = contains_all_async

    def mixed_async(self, objs, flags):
        """Ordered add/contains mix in ONE engine call (the front-door
        fused-run entry, ISSUE 6): ``flags[i]`` True adds ``objs[i]``
        (result: newly added), False tests membership.  Intra-batch
        sequencing matches issuing the ops one at a time."""
        return self._engine.bloom_mixed_encoded(
            self._name, *self._encode(objs), flags
        )

    def contains_many(self, batches) -> list:
        """Pipelined bulk membership: dispatch EVERY batch, then collect
        all results in one reply flush — the RBatch idiom (a Redisson
        batch of containsAsync calls executes as one pipeline with one
        reply read, → org/redisson/command/CommandBatchService.java,
        SURVEY.md §3.4).  On the TPU engine the flush is the device-side
        result mailbox: G packed result arrays concatenate on device and
        come home in ONE D2H (each host fetch costs a full link round
        trip).  Returns one bool array per input batch.

        Same-dtype integer ndarray batches additionally coalesce into a
        SINGLE launch (host concat → one H2D → one scan-chunked kernel →
        one fetch): membership is read-only, so splitting the result
        back per batch is exact, and the whole group costs three link
        transfers however many batches ride it.  The single-launch form
        needs the direct engine's scan-chunked ``bloom_contains_keys_st``
        (or the host engine); coalesced and replicated engines keep the
        per-batch pipelined form."""
        from redisson_tpu.executor.tpu_executor import defer_host_fetch

        batches = list(batches)
        single_launch_ok = (
            getattr(self._engine, "coalescer", None) is None
            and not self.is_replicated()
        )
        if (
            single_launch_ok
            and len(batches) > 1
            and all(
                isinstance(b, np.ndarray)
                and b.ndim == 1
                and b.dtype.kind in "iu"
                for b in batches
            )
            and len({b.dtype for b in batches}) == 1
        ):
            flat = self.contains_all_async(
                np.concatenate(batches)
            ).result()
            out = []
            off = 0
            for b in batches:
                # .copy(): a view would pin the whole flat result for as
                # long as any ONE batch's slice is retained.
                out.append(flat[off : off + len(b)].copy())
                off += len(b)
            return out
        with defer_host_fetch():  # no per-launch D2H: ONE grouped fetch
            futs = [self.contains_all_async(b) for b in batches]
        return self._client.collect(futs)

    # -- read replication (SURVEY §2.4 replication row) ---------------------

    def set_replicated(self) -> bool:
        """Copy this filter's row to EVERY mesh shard: reads spread
        round-robin across copies (contains() is read-heavy — the
        ReadMode.SLAVE analog), writes broadcast to all.  False on a
        single-device executor (nothing to spread across)."""
        return self._engine.bloom_replicate(self._name)

    def is_replicated(self) -> bool:
        return self._engine.bloom_is_replicated(self._name)

    def count(self) -> int:
        """→ RBloomFilter#count: estimated number of inserted elements."""
        return int(self._engine.bloom_count(self._name).result())
