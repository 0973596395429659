"""Byte counts of the roofline metrics, against hand-computed values for
both configurations, and the roofline share on one chip and on four."""

import importlib.util
import os

import pytest

from benchmark import reference, tracing
from benchmark.metrics import bloom_bytes

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_geometry_of_both_configurations():
    assert reference.geometry(10_000, 0.01) == (95_851, 7)
    assert reference.geometry(55_000_000, 0.03) == (401_414_247, 5)


def test_bytes_by_hand():
    # bloom-tenants-1k, k=7, 8-byte keys: 8 + 28 + 0.125 and + 28 written.
    assert bloom_bytes.contains_bytes(7, 8) == 36.125
    assert bloom_bytes.add_bytes(7, 8) == 64.125
    # bloom-55m, k=5: 8 + 20 + 0.125 and + 20 written.
    assert bloom_bytes.contains_bytes(5, 8) == 28.125
    assert bloom_bytes.add_bytes(5, 8) == 48.125


def _reader(name):
    s = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "metrics", name + ".py"))
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_roofline_share_by_hand():
    # 1e6 adds and 2e6 contains of which 5e5 hit the near cache, k=7:
    # 1e6 * 64.125 + 1.5e6 * 36.125 = 118,312,500 bytes over 819e9 B/s
    # x 0.01 s busy = 8.19e9 bytes -> 1.44459706959707 %.
    run = {"trace": {"busy_s": 0.01}, "peaks": {"hbm_bytes_per_s": 819e9},
           "chips": 1,
           "ops": {"add_keys": 1_000_000, "contains_keys": 2_000_000},
           "geometry": {"k": 7, "key_bytes": 8}}
    r = _reader("bloom_roofline.open")
    v = r.read({"hits": 0}, {"hits": 500_000}, run)
    assert abs(v - 100 * 118_312_500 / 8.19e9) < 1e-9


def test_roofline_reads_nothing_without_a_trace():
    r = _reader("bloom_roofline.bulk")
    assert r.read({"hits": 0}, {"hits": 0}, {"trace": None}) is None


MS = 1_000_000  # ns


def _traced(planes: int, busy_ms: float) -> dict:
    """A reduced trace of a 100 ms window whose ``planes`` device planes
    are each busy for ``busy_ms``."""
    ops = [(0, int(busy_ms * MS), "jit_bloom_mixk_runs/%fusion")]
    return tracing.reduce({
        "devices": {f"/device:TPU:{i}": ops for i in range(planes)},
        "host": [(0, 100 * MS, tracing.WINDOW)]})


@pytest.mark.parametrize("name", ["bloom_roofline.open",
                                  "bloom_roofline.bulk"])
def test_roofline_counts_every_chip(name):
    # The same bytes, and the same device seconds in all: one chip busy
    # 40 ms, or four chips busy 10 ms each, side by side.
    ops = {"add_keys": 1_000_000, "contains_keys": 2_000_000}
    base = {"peaks": {"hbm_bytes_per_s": 819e9}, "ops": ops,
            "geometry": {"k": 7, "key_bytes": 8}}
    one = {**base, "chips": 1, "trace": _traced(1, 40)}
    four = {**base, "chips": 4, "trace": _traced(4, 10)}
    r = _reader(name)
    v1 = r.read({"hits": 0}, {"hits": 500_000}, one)
    v4 = r.read({"hits": 0}, {"hits": 500_000}, four)
    assert abs(v1 - v4) < 1e-9 * v1
    # One chip reads what the one-chip formula always read:
    # bytes / (peak x busy), 118,312,500 / (819e9 x 0.04 s).
    assert abs(v1 - 100 * 118_312_500 / (819e9 * 0.04)) < 1e-9
