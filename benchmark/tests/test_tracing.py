"""Trace reduction on a synthetic trace whose numbers are worked out by
hand."""

from benchmark import tracing

MS = 1_000_000  # ns


def _trace():
    # Window 0..100 ms.  Device ops (ms): [10,20) [15,30) [50,60) [95,110):
    # union inside the window is [10,30) + [50,60) + [95,100) = 35 ms busy.
    ops = [(10 * MS, 20 * MS, "fusion.1"), (15 * MS, 30 * MS, "gather"),
           (50 * MS, 60 * MS, "gather"), (95 * MS, 110 * MS, "scatter")]
    host = [(0, 100 * MS, tracing.WINDOW),
            (0, 40 * MS, "bench:contains_many"),
            (32 * MS, 38 * MS, "rtpu:dispatch:bloom"),
            (60 * MS, 99 * MS, "bench:contains_many")]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_union_merges_and_clips():
    got = tracing.union([(10, 20), (15, 30), (50, 60), (95, 110)], 0, 100)
    assert got == [[10, 30], [50, 60], [95, 100]]


def test_busy_idle_and_gaps_by_hand():
    r = tracing.reduce(_trace())
    assert r["window_s"] == 0.1
    assert abs(r["busy_s"] - 0.035) < 1e-12
    assert abs(r["idle_share"] - 0.65) < 1e-12
    # Gaps: [0,10) 10 ms, [30,50) 20 ms, [60,95) 35 ms.  Midpoints 5, 40,
    # 77.5 ms: innermost open host spans are contains_many (0-40 and
    # 32-38 end before 40, so 40 falls in none), none, contains_many.
    assert [round(d, 9) for _, d in r["idle_gaps"]] == [0.035, 0.02, 0.01]
    assert [n for n, _ in r["idle_gaps"]] == [
        "bench:contains_many", "no host span", "bench:contains_many"]


def test_top_ops_sum_clipped_durations():
    r = dict((n, round(s, 9)) for n, s in tracing.reduce(_trace())
             ["device_ops"])
    # gather 15 + 10 = 25 ms, fusion.1 10 ms, scatter clipped to 5 ms.
    assert r == {"gather": 0.025, "fusion.1": 0.01, "scatter": 0.005}


def test_innermost_span_names_a_gap():
    t = _trace()
    t["devices"]["/device:TPU:0"] = [(0, 33 * MS, "x"), (37 * MS, 100 * MS,
                                                         "x")]
    assert tracing.reduce(t)["idle_gaps"][0][0] == "rtpu:dispatch:bloom"


def test_two_chips_average():
    t = _trace()
    t["devices"]["/device:TPU:1"] = [(0, 100 * MS, "x")]
    assert abs(tracing.reduce(t)["busy_s"] - (0.035 + 0.1) / 2) < 1e-12


def test_gaps_are_where_no_chip_is_busy():
    # A second chip, busy [25, 55) ms and in the same [95, 100) op as the
    # first: the union is [10, 60) + [95, 100), so one gap of 35 ms
    # (60-95) and one of 10 ms (0-10), each once, not once a chip.
    t = _trace()
    t["devices"]["/device:TPU:1"] = [(25 * MS, 55 * MS, "y"),
                                     (95 * MS, 110 * MS, "scatter")]
    r = tracing.reduce(t)
    assert [round(d, 9) for _, d in r["idle_gaps"]] == [0.035, 0.01]
    assert abs(r["busy_s"] - (0.035 + 0.035) / 2) < 1e-12


def test_no_window_or_no_device_reads_nothing():
    t = _trace()
    assert tracing.reduce({"devices": {}, "host": t["host"]}) == {}
    assert tracing.reduce({"devices": t["devices"], "host": []}) == {}


def test_ops_named_by_module_and_instruction():
    ops = [(5, 6, "%fusion.2 = u32[8] fusion(...)"), (20, 30, "%while.1 = x")]
    mods = [(0, 10, "jit_contains(1)"), (15, 40, "jit_add(2)")]
    assert [n for _, _, n in tracing.named(ops, mods)] == [
        "jit_contains(1)/%fusion.2", "jit_add(2)/%while.1"]
    assert tracing.named([(12, 13, "%x = y")], mods)[0][2] == "?/%x"
