"""Kernels: share of the window's Bloom keyed ops that ran the one-pass
contains gather (``rtpu_dispatch_ops{method="bloom_contains_keys_runs"}``)
rather than the sort-chunked scan (``bloom_mixed_keys_runs``,
``bloom_mixed_keys``), %."""

from benchmark.metrics import _gather

snapshot = _gather.gather_ops


def read(before, after, run):
    return _gather.gather_share(before, after)
