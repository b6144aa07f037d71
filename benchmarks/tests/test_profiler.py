"""The reduction from a profiler trace to busy and idle time, on a trace
recorded on the chip and on one made by hand."""

import json
import os

import pytest

from benchmarks.harness import profiler as PR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_recorded_v5e_trace():
    """66 operations of six runs of a sort-and-matmul program on one TPU v5
    lite, two client threads (PR 24's trace probe). The operations do not
    overlap, so busy is the sum of their durations, taken here without the
    reduction's interval code: 45,505,830 ns of a span of 135,934,904 ns."""
    with open(os.path.join(DATA, "v5e_sort_trace.json")) as f:
        trace = json.load(f)
    ops = trace["devices"]["/device:TPU:0"]
    busy_ns = sum(d for _n, _s, d in ops)
    assert busy_ns == 45_505_830
    queries = [(s, s + d) for s, d in trace["annotations"]]
    out = PR.reduce_trace(trace, queries)
    assert out["chips"] == 1 and out["op_events"] == 66
    assert out["busy_s"] == pytest.approx(busy_ns / 1e9, abs=1e-12)
    assert out["window_s"] == pytest.approx(0.135934904, abs=1e-12)
    assert out["idle_share"] == pytest.approx(1 - 45_505_830 / 135_934_904)
    top = out["breakdown"]["device_ops"][0]
    assert top[0] == "op:?/sort.11"      # the cut holds no module events
    assert top[1] == pytest.approx(6 * 0.0075, rel=0.02)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["between_queries.total"] + gaps["in_query.total"] == \
        pytest.approx(out["window_s"] - out["busy_s"])
    assert gaps["between_queries.total"] > gaps["in_query.total"]


def test_hand_made_trace_two_chips():
    """Overlapping operations count once; chips are averaged; a gap is split
    at the query's edge."""
    trace = {
        "devices": {
            "/device:TPU:0": [["%a = f32[] add()", 100, 100],
                              ["%b = f32[] mul()", 150, 100],   # overlaps a
                              ["%a = f32[] add()", 400, 100]],
            "/device:TPU:1": [["%c = f32[] sort()", 0, 1000]],
        },
        "modules": {"/device:TPU:0": [["jit_fn(1)", 90, 200],
                                      ["jit_fn(2)", 390, 200]]},
        "annotations": [],
    }
    out = PR.reduce_trace(trace, [(0, 300)], span=(0, 1000))
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((250 + 1000) / 2 / 1e9)
    assert out["idle_share"] == pytest.approx(1 - 625 / 1000)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["op:?/c"] == pytest.approx(500e-9)
    assert ops["op:jit_fn(1)/a"] == pytest.approx(50e-9)
    assert ops["op:jit_fn(2)/a"] == pytest.approx(50e-9)
    assert ops["program:jit_fn(1)"] == pytest.approx(100e-9)
    assert list(ops)[0].startswith("program:")
    gaps = dict(out["breakdown"]["idle_gaps"])
    # chip 0 idles 0-100 and 250-300 inside the query, 300-400 and 500-1000 after
    assert gaps["in_query.total"] == pytest.approx(150 / 2 / 1e9)
    assert gaps["between_queries.total"] == pytest.approx(600 / 2 / 1e9)
    assert gaps["between_queries.gap1"] == pytest.approx(500e-9)


def test_span_clips_operations():
    trace = {"devices": {"/device:TPU:0": [["%a = x", 0, 100], ["%b = x", 900, 200]]},
             "annotations": []}
    out = PR.reduce_trace(trace, [], span=(50, 1000))
    assert out["busy_s"] == pytest.approx(150e-9)
    assert out["window_s"] == pytest.approx(950e-9)


def test_nothing_on_the_device_is_nothing_to_read():
    assert PR.reduce_trace({"devices": {}, "annotations": []}, []) is None
    assert PR.reduce_trace({"devices": {"/device:TPU:0": []},
                            "annotations": []}, [(0, 10)]) is None


def test_clock_offset_uses_the_earliest_of_both():
    assert PR.clock_offset([[500, 10], [300, 10]], [1200, 1000]) == -700
    assert PR.clock_offset([], [1000]) is None


def test_interval_helpers():
    assert PR.union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]
    assert PR.complement([(0, 3), (5, 7)], (0, 10)) == [(3, 5), (7, 10)]
    assert PR.clip([(0, 3), (5, 7)], (2, 6)) == [(2, 3), (5, 6)]
