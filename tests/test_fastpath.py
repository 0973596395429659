"""Device-hash kernels (ops/fastpath.py): bit-identical to the host hash
pipeline, with exact sequential add semantics on every engine path."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.codecs import LongCodec


def test_device_hash_path_matches_host_engine():
    """A direct engine's device-hash launches (murmur + 64-bit mod
    in-kernel) must be bit-identical to the host hash pipeline: same
    membership answers, same newly flags, same HLL changed booleans."""
    results = {}
    for mode, kwargs in (
        ("devhash", dict(coalesce=False)),
        ("host", None),
    ):
        cfg = Config()
        if kwargs is not None:
            cfg.use_tpu_sketch(min_bucket=64, **kwargs)
        cl = redisson_tpu.create(cfg)
        bf = cl.get_bloom_filter("dh-bf")
        bf.try_init(5000, 0.01)
        keys = [f"key-{i}" for i in range(300)]
        n_added = bf.add_all(keys)
        hits = bf.contains_each(keys + [f"miss-{i}" for i in range(300)])
        h = cl.get_hyper_log_log("dh-hll")
        first = h.add("x")
        second = h.add("x")
        h.add_all([f"v{i}" for i in range(2000)])
        results[mode] = (n_added, hits.tolist(), first, second, h.count())
        cl.shutdown()
    assert results["devhash"] == results["host"]


def test_mod64_bits_exact():
    """Device bit-Horner mod == host uint64 mod for random 64-bit values."""
    import jax
    import jax.numpy as jnp

    from redisson_tpu.ops import fastpath

    rng = np.random.default_rng(5)
    hi = rng.integers(0, 1 << 32, 512).astype(np.uint32)
    lo = rng.integers(0, 1 << 32, 512).astype(np.uint32)
    for m in (3, 17, 9585059, (1 << 31) - 1, 1 << 31):
        got = np.asarray(
            jax.jit(lambda h, l: fastpath.mod64_bits(h, l, np.uint32(m)))(
                jnp.asarray(hi), jnp.asarray(lo)
            )
        )
        h64 = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
        np.testing.assert_array_equal(got, (h64 % np.uint64(m)).astype(np.uint32))


def test_exact_add_semantics_key_is_unknown():
    """The inexact-add switch is gone: naming it fails like any unknown
    tpuSketch key."""
    with pytest.raises(ValueError, match="unknown tpuSketch config key"):
        Config().use_tpu_sketch(exact_add_semantics=False)


@pytest.mark.parametrize("num_shards", [1, 4])
def test_direct_add_duplicate_keys_are_exact(num_shards):
    """A direct engine (no coalescer) on one device or four answers a
    batch that holds the same key twice as the host engine does: True
    for the first add, False for the repeat."""
    keys = np.array([11, 12, 11, 13, 12, 11], np.uint64)
    results = {}
    for mode, kwargs in (
        ("direct", dict(min_bucket=64, coalesce=False,
                        num_shards=num_shards)),
        ("host", None),
    ):
        cfg = Config().set_codec(LongCodec())
        if kwargs is not None:
            cfg.use_tpu_sketch(**kwargs)
        cl = redisson_tpu.create(cfg)
        try:
            bf = cl.get_bloom_filter("dup")
            bf.try_init(1000, 0.01)
            newly = np.asarray(bf.add_all_async(keys).result())
            again = np.asarray(bf.add_all_async(keys[:2]).result())
            results[mode] = (newly.tolist(), again.tolist(),
                             bf.contains_each(keys).tolist())
        finally:
            cl.shutdown()
    assert results["direct"][0] == [True, True, False, True, False, False]
    assert results["direct"] == results["host"]
