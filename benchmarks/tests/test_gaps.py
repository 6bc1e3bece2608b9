"""tools/gaps.py: the innermost-annotation pieces of a thread, the idle
time by activity on hand-made planes, and a whole reduction of a trace made
here (the CPU has no TPU plane: threads only)."""

import os
import sys

import pytest

from conftest import BENCH_DIR

sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))

import gaps  # noqa: E402


def test_innermost_names_each_piece_by_the_deepest_open_annotation():
    thread = [["svc.quantum", 0, 500], ["svc.step", 10, 450],
              ["task:exec", 20, 440], ["exec.X", 100, 250],
              ["svc.quantum", 500, 1000], ["svc.park", 510, 990]]
    assert gaps.innermost(thread) == [
        [0, 10, "svc.loop"], [10, 20, "svc.step"], [20, 100, "task:exec"],
        [100, 250, "exec.X"], [250, 440, "task:exec"],
        [440, 450, "svc.step"], [450, 500, "svc.loop"],
        [500, 510, "svc.loop"], [510, 990, "svc.park"],
        [990, 1000, "svc.loop"]]
    # a child that outlives its parent (clock jitter) is cut to it
    assert gaps.innermost([["a", 0, 10], ["b", 5, 12]]) == [
        [0, 5, "a"], [5, 10, "b"]]


def test_idle_time_goes_to_the_thread_that_is_inside_something():
    planes = {
        "ops": [[0, 100], [300, 400], [900, 1000]],
        "modules": [["jit_f", 0, 100], ["jit_f", 300, 400],
                    ["jit_g", 900, 1000]],
        "threads": [
            [["svc.quantum", 0, 500], ["svc.step", 10, 450],
             ["task:exec", 20, 440], ["exec.X", 100, 250],
             ["svc.quantum", 500, 1000], ["svc.park", 510, 990]],
            [["svc.quantum", 0, 1000], ["svc.park", 5, 995],
             ["svc.finalize", 996, 999]]],
        "anchor": None}
    r = gaps.reduce(planes)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["idle_s"] == pytest.approx(700e-9) and r["gaps"] == 2
    by = {name: s for name, s, _share in r["idle_by_activity"]}
    # gap [100,300): exec.X 150, the dispatch's own 50; gap [400,900): the
    # dispatch's own 40, the step's 10, the loop's 60, then both parked
    assert by == pytest.approx({
        "exec.X": 150e-9, "task:exec": 90e-9, "svc.step": 10e-9,
        "svc.loop": 60e-9, "every worker parked": 390e-9})
    assert sum(share for _, _, share in r["idle_by_activity"]) == (
        pytest.approx(1.0))
    assert r["queries_finished"] == 1
    assert r["launches"] == [["jit_f", 2, 2.0], ["jit_g", 1, 1.0]]
    assert r["launches_per_query"] == 3.0
    assert "every worker parked" in gaps.render(r)


def test_no_annotation_anywhere_is_none_and_a_span_cuts():
    planes = {"ops": [[100, 200], [400, 500]], "modules": [],
              "threads": [[["svc.quantum", 150, 180]]], "anchor": 100}
    r = gaps.reduce(planes, seconds=350e-9)  # [100, 450)
    assert r["span_s"] == pytest.approx(350e-9)
    assert r["busy_s"] == pytest.approx(150e-9)
    assert r["idle_by_activity"] == [["none", pytest.approx(200e-9), 1.0]]
    assert gaps.reduce({"ops": [], "modules": [], "threads": [],
                        "anchor": None}) == {"span_s": 0.0, "threads": 0}


def test_a_trace_made_here_reduces_without_a_tpu_plane(tmp_path, capsys):
    """A traced rehearsal of the q1 cell keeps its trace; the tool reads the
    program's qk.* annotations from it."""
    import run

    kept = str(tmp_path / "kept")
    os.environ["BENCH_KEEP_TRACE"] = kept
    try:
        rc = run.main(["--workload", "tpch_sf1.q1_s2", "--seed",
                       str(2**31 + 78), "--seconds", "2", "--rehearse",
                       "--trace", "1"])
    finally:
        del os.environ["BENCH_KEEP_TRACE"]
    capsys.readouterr()
    assert rc == 0
    assert gaps.main([kept, "--json", str(tmp_path / "g.json")]) == 0
    out = capsys.readouterr().out
    assert "no TPU plane" in out and "task:" in out
    r = gaps.reduce(gaps.load(gaps.trace.find_xplane(kept)))
    assert r["threads"] >= 3 and r["queries_finished"] > 0
    assert "idle_s" not in r
