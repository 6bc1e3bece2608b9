"""The trace reduction: on hand-made intervals, on the trace recorded from
``tpch_sf1.q1_s2`` on a TPU v5e (trimmed by tools/trace_dump.py), and the
loader on a trace made here."""

import json
import os

import pytest

from harness import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_q1_v5e.json")


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]]) == [
        [0, 3], [5, 8], [10, 11]]


def test_reduce_on_hand_made_planes():
    planes = {
        "device": {"/device:TPU:0": {
            trace.OPS_LINE: [["a", 0, 100], ["b", 50, 100], ["c", 400, 100]],
            trace.MODULES_LINE: [["jit_f(123)", 0, 150], ["jit_g(9)", 400, 100],
                                 ["jit_f(77)", 600, 0]],
        }},
        "host": [["bench.to_df", 100, 500], ["bench.to_df", 0, 1000],
                 ["bench.submit", 160, 10], ["bench.submit", 900, 10]],
    }
    got = trace.reduce(planes)
    assert got["busy_s"] == pytest.approx(250e-9)  # [0,150) and [400,500)
    assert got["device_ops"][:2] == [["jit_f", 150e-9], ["jit_g", 100e-9]]
    assert got["idle_gaps"] == [["submit*1+to_df*2", 250e-9]]


def test_reduce_counts_only_what_lies_inside_the_span():
    """Ops before the span, over each edge and after it: the parts outside
    count nowhere, and the edges bound the first and the last gap."""
    planes = {
        "device": {"/device:TPU:0": {
            trace.OPS_LINE: [["early", 0, 50], ["over_start", 80, 40],
                             ["inside", 200, 100], ["over_end", 380, 100],
                             ["late", 600, 50]],
            trace.MODULES_LINE: [["jit_e(1)", 0, 50], ["jit_s(2)", 80, 40],
                                 ["jit_i(3)", 200, 100],
                                 ["jit_x(4)", 380, 100], ["jit_l(5)", 600, 50]],
        }},
        "host": [[trace.ANCHOR, 100, 5]],
    }
    span = trace.span_ns(planes, 300e-9)
    assert span == (100, 400)
    got = trace.reduce(planes, span)
    # [100,120) + [200,300) + [380,400) of a 300 ns span
    assert got["busy_s"] == pytest.approx(140e-9)
    assert dict(map(tuple, got["device_ops"])) == pytest.approx(
        {"jit_i": 100e-9, "jit_s": 20e-9, "jit_x": 20e-9})
    assert [g for _, g in got["idle_gaps"]] == pytest.approx([80e-9, 80e-9])
    whole = trace.reduce(planes)
    assert whole["busy_s"] == pytest.approx(340e-9)
    # a span that holds no op says nothing, and no anchor means no span
    assert trace.reduce(planes, (125, 190)) is None
    assert trace.span_ns(dict(planes, host=[]), 1.0) is None


def test_reduce_says_nothing_without_device_ops():
    assert trace.reduce({"device": {}, "host": []}) is None
    assert trace.reduce({"device": {"/device:TPU:0": {trace.OPS_LINE: []}},
                         "host": []}) is None


def test_recorded_v5e_trace():
    with open(FIXTURE, encoding="utf-8") as f:
        planes = json.load(f)
    t0, t1 = planes["span_ns"]
    span_s = (t1 - t0) / 1e9
    got = trace.reduce(planes, (t0, t1))
    ops = [[n, max(s, t0), min(s + d, t1) - max(s, t0)] for n, s, d in
           planes["device"]["/device:TPU:0"][trace.OPS_LINE]]
    # the union lies between the longest op and the plain sum, and inside
    # the span; a sweep over a 1 us grid agrees with it
    assert max(d for _, _, d in ops) / 1e9 <= got["busy_s"]
    assert got["busy_s"] <= sum(d for _, _, d in ops) / 1e9
    lo = min(s for _, s, _ in ops)
    grid = bytearray((max(s + d for _, s, d in ops) - lo) // 1000 + 2)
    for _, s, d in ops:
        a, b = (s - lo) // 1000, (s + d - lo + 999) // 1000
        grid[a:b] = b"\x01" * (b - a)
    assert got["busy_s"] == pytest.approx(sum(grid) * 1e-6, rel=0.02)
    idle_pct = 100 * (1 - got["busy_s"] / span_s)
    assert 0 < idle_pct < 100
    # Q1 on the chip: one fused scan/filter/aggregate program is nearly all
    # of the device's time
    name, seconds = got["device_ops"][0]
    assert name == "jit_fused" and seconds > 0.9 * got["busy_s"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert all(label == "none" or "to_df" in label or "submit" in label
               for label, _ in got["idle_gaps"])


def test_load_finds_the_benchmarks_annotations(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.submit"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(trace.find_xplane(str(tmp_path)))
    assert [e[0] for e in planes["host"]] == ["bench.submit"]
    # no TPU plane in a CPU trace: nothing to reduce, and no zero reported
    assert planes["device"] == {} and trace.reduce(planes) is None


def test_requests_label_gaps_through_the_anchor():
    from harness.loadgen import Request

    planes = {"device": {"/device:TPU:0": {trace.OPS_LINE: [
        ["a", 1_000, 100], ["b", 3_000_000_000, 100]]}},
        "host": [[trace.ANCHOR, 500, 10]]}
    # a request that opened 2 s before the trace did and ends 4 s into it
    log = [Request(client=0, query="q", params={}, t_submit=98.0,
                   t_submitted=98.5, t_end=104.0)]
    got = trace.reduce(trace.with_requests(planes, log, anchor_s=100.0))
    assert got["idle_gaps"] == [["to_df*1", pytest.approx(3.0, rel=1e-3)]]
    # no anchor in the trace: the recorded annotations stay as they are
    bare = dict(planes, host=[])
    assert trace.with_requests(bare, log, 100.0) is bare
