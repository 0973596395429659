"""Device-side scan chunking (tpu_executor._SCAN_CHUNK): batches larger
than the chunk run as ONE launch whose kernel lax.scans fixed-size
chunks — results must be bit-identical to the unchunked path / golden
model.  The chunk size is monkeypatched small so the test exercises
multi-chunk scans at CPU-friendly sizes."""

import numpy as np
import pytest

import redisson_tpu
from redisson_tpu import Config
from redisson_tpu.executor import tpu_executor


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(tpu_executor, "_SCAN_CHUNK", 1 << 12)


@pytest.fixture
def client():
    c = redisson_tpu.create(
        Config().use_tpu_sketch(min_bucket=64, coalesce=False)
    )
    yield c
    c.shutdown()


class TestScanChunkedBloom:
    def test_contains_matches_host_engine_across_chunks(
        self, small_chunks, client
    ):
        """Oracle: the host golden engine through the same public API and
        codec — identical key bytes hash to identical bits."""
        bf = client.get_bloom_filter("scan-bf")
        bf.try_init(50_000, 0.01)
        loaded = np.arange(20_000, dtype=np.uint64)
        bf.add_all(loaded)

        host = redisson_tpu.create(Config())  # host engine, same codec
        try:
            hbf = host.get_bloom_filter("scan-bf")
            hbf.try_init(50_000, 0.01)
            hbf.add_all(loaded)

            # 16k probe keys -> 4 scan chunks of 4k at the patched size
            rng = np.random.default_rng(1)
            probe = rng.integers(0, 40_000, 1 << 14).astype(np.uint64)
            got = bf.contains_each(probe)
            want = hbf.contains_each(probe)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        finally:
            host.shutdown()

    def test_add_matches_golden_across_chunks(self, small_chunks, client):
        """A direct add over four chunks (bloom_mixed_keys' scan) gives
        the host engine's exact sequential flags: a key repeated in a
        later chunk reports False, as it would one op at a time."""
        bf = client.get_bloom_filter("scan-bf-add")
        bf.try_init(50_000, 0.01)
        keys = np.arange(1 << 14, dtype=np.uint64)  # 4 chunks
        keys[-100:] = keys[:100]  # repeats cross chunk boundaries
        newly = bf.add_all_async(keys).result()
        assert newly.shape == keys.shape
        assert not newly[-100:].any()

        host = redisson_tpu.create(Config())  # host engine, same codec
        try:
            hbf = host.get_bloom_filter("scan-bf-add")
            hbf.try_init(50_000, 0.01)
            want = hbf.add_all_async(keys).result()
            np.testing.assert_array_equal(np.asarray(newly), np.asarray(want))
        finally:
            host.shutdown()
        assert bool(np.all(bf.contains_each(keys)))

    def test_unaligned_batch_size(self, small_chunks, client):
        """A batch that is not a multiple of the chunk pads to the pow-2
        bucket; validity masking must keep results exact."""
        bf = client.get_bloom_filter("scan-bf-odd")
        bf.try_init(50_000, 0.01)
        keys = np.arange(777, 777 + (1 << 13) + 123, dtype=np.uint64)
        bf.add_all(keys)
        assert bool(np.all(bf.contains_each(keys)))
        misses = bf.contains_each(
            np.arange(500_000, 500_000 + 4096, dtype=np.uint64)
        )
        assert misses.mean() < 0.05

    def test_variable_length_keys_across_chunks(self, small_chunks):
        """Mixed-length (string) keys exercise the non-const-length scan
        branch."""
        c = redisson_tpu.create(Config().use_tpu_sketch(min_bucket=64))
        try:
            bf = c.get_bloom_filter("scan-bf-str")
            bf.try_init(50_000, 0.01)
            keys = [f"k{'x' * (i % 9)}{i}" for i in range(1 << 13)]
            bf.add_all(keys)
            assert all(bf.contains_each(keys))
            assert (
                np.mean(bf.contains_each([f"ghost{i}" for i in range(4096)]))
                < 0.05
            )
        finally:
            c.shutdown()


class TestScanChunkedHll:
    def test_hll_estimate_across_chunks(self, small_chunks, client):
        h = client.get_hyper_log_log("scan-hll")
        n = 1 << 14
        changed = h.add_all_async(np.arange(n, dtype=np.uint64)).result()
        assert changed is True or changed  # whole-batch changed flag
        est = h.count()
        assert abs(est - n) / n < 0.05

    def test_hll_matches_single_launch_path(self, small_chunks, client):
        """The scan-chunked registers must be IDENTICAL to the unchunked
        scatter-max (max-merge is order-independent)."""
        h1 = client.get_hyper_log_log("scan-hll-a")
        keys = np.random.default_rng(2).integers(
            0, 1 << 40, 1 << 14
        ).astype(np.uint64)
        h1.add_all_async(keys).result()
        est_chunked = h1.count()

        tpu_executor._SCAN_CHUNK = 1 << 20  # restore: single-launch path
        try:
            h2 = client.get_hyper_log_log("scan-hll-b")
            h2.add_all_async(keys).result()
            assert h2.count() == est_chunked
        finally:
            tpu_executor._SCAN_CHUNK = 1 << 12


class TestExecutorSweepFixes:
    """Regressions for the round-5 executor high-effort sweep."""

    def test_contains_many_on_coalescing_engine(self, small_chunks):
        """The host-concat single-launch path must NOT engage on a
        coalescing engine (its mixed-keys kernel has no scan chunking);
        the pipelined per-batch form must still produce exact results."""
        c = redisson_tpu.create(
            Config().use_tpu_sketch(min_bucket=64, coalesce=True,
                                    batch_window_us=200)
        )
        try:
            bf = c.get_bloom_filter("cm-coal")
            bf.try_init(50_000, 0.01)
            keys = np.arange(4096, dtype=np.uint64)
            bf.add_all(keys)
            batches = [keys[i : i + 512] for i in range(0, 4096, 512)]
            results = bf.contains_many(batches)
            assert all(bool(np.all(r)) for r in results)
        finally:
            c.shutdown()

    def test_non_multiple_min_bucket_rounds_to_chunk(self, small_chunks):
        """A custom min_bucket that is not a multiple of the scan chunk
        must still take the chunked path (rounded UP), never the giant
        single launch."""
        c = redisson_tpu.create(
            Config().use_tpu_sketch(min_bucket=(1 << 12) + 96,
                                    coalesce=False)
        )
        try:
            bf = c.get_bloom_filter("cm-odd")
            bf.try_init(50_000, 0.01)
            keys = np.arange(1 << 13, dtype=np.uint64)
            bf.add_all(keys)
            assert bool(np.all(bf.contains_each(keys)))
        finally:
            c.shutdown()

    def test_collect_group_odd_sizes_resolve_exact(self, client):
        """Groups whose size is not a power of 8 exercise the padded
        concat tree (duplicated pad results sliced off at resolution)."""
        bf = client.get_bloom_filter("cg-odd")
        bf.try_init(50_000, 0.01)
        loaded = np.arange(10_000, dtype=np.uint64)
        bf.add_all(loaded)
        from redisson_tpu.executor.tpu_executor import defer_host_fetch

        for g in (2, 3, 7, 9, 10, 17):
            batches = [
                np.arange(i * 256, (i + 1) * 256, dtype=np.uint64)
                for i in range(g)
            ]
            with defer_host_fetch():
                futs = [bf.contains_all_async(b) for b in batches]
            results = client.collect(futs)
            assert len(results) == g
            for b, r in zip(batches, results):
                want = b < 10_000
                np.testing.assert_array_equal(np.asarray(r), want)

    def test_collect_mixed_sizes_singleton_sigs(self, client):
        """Different batch sizes -> singleton signature groups: collect
        must still resolve every future correctly (async prefetch path)."""
        bf = client.get_bloom_filter("cg-mixed")
        bf.try_init(50_000, 0.01)
        bf.add_all(np.arange(5000, dtype=np.uint64))
        from redisson_tpu.executor.tpu_executor import defer_host_fetch

        sizes = [64, 200, 700, 1500]
        batches = [np.arange(s, dtype=np.uint64) for s in sizes]
        with defer_host_fetch():
            futs = [bf.contains_all_async(b) for b in batches]
        results = client.collect(futs)
        for b, r in zip(batches, results):
            np.testing.assert_array_equal(np.asarray(r), b < 5000)
