"""redisson_tpu — a TPU-native framework with Redisson's capabilities.

Redisson (reference: ``hejy12/redisson``, a fork of ``redisson/redisson``) is a
Redis Java client / in-memory data grid.  This package re-designs its
capability surface TPU-first (see SURVEY.md):

- Probabilistic / bit-oriented objects (``RBloomFilter``, ``RHyperLogLog``,
  ``RBitSet``, plus the new ``RCountMinSketch``) execute on TPU: sketches live
  as stacked multi-tenant device arrays; per-call bit ops are coalesced into
  batches (the role of Redisson's ``CommandBatchService``,
  → org/redisson/command/CommandBatchService.java) and run as vectorized
  JAX/XLA/Pallas programs sharded over a ``jax.sharding.Mesh``.
- The broader RObject catalog (maps, sets, queues, locks, topics, …,
  → org/redisson/api/) is provided by an embedded host-side data grid so a
  Redisson user finds every object they expect.

Entry point mirrors ``Redisson.create(Config)``
(→ org/redisson/Redisson.java)::

    import redisson_tpu
    config = redisson_tpu.Config().use_tpu_sketch()
    client = redisson_tpu.create(config)
    bf = client.get_bloom_filter("bf")
    bf.try_init(1_000_000, 0.01)
    bf.add("hello")
    assert bf.contains("hello")
"""

from redisson_tpu.config import Config

__version__ = "0.1.0"

__all__ = ["Config", "connect_cluster", "create", "__version__"]


def connect_cluster(seeds, **kwargs):
    """Slot-aware cluster client (ISSUE 12): route commands across an
    N-node redisson_tpu cluster by CRC16 keyslot, with scatter/gather
    batching and MOVED/ASK redirect handling (docs/clustering.md).

    Imports only the wire-client tier — a pure routing process (bench
    client forks, sidecars) never pays for the grid/engine modules."""
    from redisson_tpu.cluster.client import ClusterClient

    return ClusterClient(seeds, **kwargs)


def create(config=None):
    """Create a client — the analog of ``Redisson.create(Config)``.

    → org/redisson/Redisson.java#create
    """
    from redisson_tpu.client import RedissonTpuClient

    return RedissonTpuClient(config or Config())
