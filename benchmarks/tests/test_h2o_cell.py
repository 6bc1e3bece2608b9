"""The ``h2o_g1_1e7.q5_s2`` cell (ISSUE 32): it resolves from entries alone,
a rehearsal of it is ``correct`` (judged by requests answered, not by what a
window of seconds happens to hold), both bfloat16 controls fail its limits,
an altered sum and a dropped group each make ``correct`` false, its three
per-layer readers read what their files say and nothing (never an error)
from a run with nothing to read, and adding it edited no file the benchmark
already had."""

import json
import subprocess
import types

import pytest

from conftest import ROOT
from harness import check, loadgen, lowprec, spec, tables

CELL = "h2o_g1_1e7.q5_s2"
QUERY = "h2o_q5"
NEW_METRICS = ("groupby_roofline", "groupby_slots_per_row",
               "agg_merges_general_per_query")
PEAKS = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("metrics", name)


def run_cell(capsys, seed, *extra):
    import run

    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "4",
                   "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_cell_resolves_from_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "h2o_g1_1e7"
    conf = cell.config
    assert conf["datagen"] == {
        "module": "h2o", "args": {"n": 10000000, "k": 100},
        "rehearsal_args": {"n": 200000, "k": 2}}
    assert conf["reduced"] == [] and "1e8" in conf["reduced_why"]
    assert set(conf["shapes"]["columns"]) == {
        "id1", "id2", "id3", "id4", "id5", "id6", "v1", "v2", "v3"}
    for word in ("complete", "exact", "float64", "never", "non-durable"):
        assert word in conf["guarantees"]
    assert {"generator", "clients", "service"} <= set(conf["assumed"])
    assert cell.traffic["clients"] == 2 and cell.traffic["mix"] == {QUERY: 1}
    assert "params" not in cell.traffic  # the source's questions have none
    assert loadgen.plan(cell.traffic, 5) == {QUERY: [{}]}
    assert list(cell.queries) == [QUERY]
    entry = {c["name"]: c for c in cell.bench["configs"]}["h2o_g1_1e7"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert "h2oai/db-benchmark" in entry["source"]
    assert "G1_1e7_1e2_0_0" in entry["source"]
    # appended behind what was there (not "last": the next PR appends too)
    names = [c["name"] for c in cell.bench["configs"]]
    assert names.index("h2o_g1_1e7") > names.index("ticks_1d")
    cells = [w["name"] for w in cell.bench["workloads"]]
    assert cells.index(CELL) > cells.index("ticks_1d.asof_s2")
    assert "same query" in cell.entry["why"] and len(cell.entry["why"]) <= 200
    assert {e["name"] for e, _ in cell.metrics("end_to_end")} == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {e["name"]: e for e, _ in cell.metrics("per_layer")}
    order = [m["name"] for m in cell.bench["per_layer"]]
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    assert order.index(NEW_METRICS[0]) > order.index("join_direct_probe_pct")
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
    # the accepted per-layer metrics without a list are this cell's too
    assert {"scan_roofline", "device_idle_pct", "pad_waste_pct",
            "exec_host_ms", "compiles_in_window"} <= set(per_layer)
    for other in ("tpch_sf1.q1_s2", "tpch_sf1.q3_s2", "ticks_1d.asof_s2"):
        assert not set(NEW_METRICS) & {
            e["name"] for e, _ in spec.Cell(other).metrics("per_layer")}


def test_least_bytes_and_the_groups_from_the_files_statistics(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    q5 = spec.load_module("queries", QUERY)
    path = str(tmp_path / "g1.parquet")
    pq.write_table(pa.table({"id6": np.array([3, 1, 7, 7, 2, 5], np.int32),
                             "v1": np.arange(6, dtype=np.int32)}),
                   path, row_group_size=2)
    assert q5.groups({"g1": path}) == 7
    # 6 rows x 4 columns x 4 B + 7 groups x 4 columns x 4 B
    assert q5.least_bytes({"g1": path}) == 6 * 16 + 7 * 16
    m = reader("groupby_roofline")
    assert m.groupby_least_bytes(10_000_000, 100_000, 3) == 161_600_000


def test_a_traced_rehearsal_is_correct_by_requests_answered(capsys):
    rc, result = run_cell(capsys, 2**31 + 3201, "--trace", "1")
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0
    assert result["window"]["answered_right"] == result["attempted"] >= 4
    compared = result["compared"]
    assert compared[f"{QUERY}.wrong_cells"] == {"value": 0, "limit": 0}
    assert compared[f"{QUERY}.unanswered"] == {"value": 0, "limit": 0}
    assert 0 < compared[f"{QUERY}.sum_rel_err"]["value"] < (
        compared[f"{QUERY}.sum_rel_err"]["limit"])
    metrics = result["metrics"]
    # one 262,144-slot batch, one channel's merge and two finals' at 131,072
    # slots each over the rows every operator read
    assert 0.5 < metrics["groupby_slots_per_row"]["value"] < 2.0
    assert metrics["groupby_slots_per_row"]["unit"] == "slots/row"
    assert metrics["agg_merges_general_per_query"] == {
        "value": 3.0, "unit": "merges"}
    assert metrics["compiles_in_window"]["value"] == 0
    assert "groupby_roofline" not in metrics  # no chip, no peaks: no share


@pytest.mark.parametrize("precision", lowprec.CONTROLS,
                         ids=[p.name for p in lowprec.CONTROLS])
def test_both_controls_fail_the_limits_at_the_rehearsal_size(precision):
    cell = spec.Cell(CELL)
    q5 = cell.queries[QUERY]
    paths = tables.for_cell(cell, 2**31 + 3202, rehearse=True)
    reference = q5.reference(paths, {})
    assert len(reference) > 65_536  # the rehearsal keeps the merges large
    numbers, _ = check.compare([q5.control(paths, {}, precision)], reference,
                               q5.SORT_KEYS, q5.EXACT)
    assert numbers["wrong_cells"] == 0  # keys and integer sums stay exact
    assert numbers["sum_rel_err"] > 10 * q5.LIMITS["sum_rel_err"]
    assert not check.judge(numbers, q5.LIMITS)
    exact, _ = check.compare([reference], reference, q5.SORT_KEYS, q5.EXACT)
    assert check.judge(exact, q5.LIMITS)


@pytest.mark.parametrize("which", ["sum", "group"])
def test_one_altered_answer_is_not_correct(which, capsys, monkeypatch):
    """The second answer of the window altered where it is produced: one
    float sum off by a thousandth, or one group dropped; every other answer
    is right."""
    from quokka_tpu.service.session import QueryHandle

    to_df, run_closed, state = QueryHandle.to_df, loadgen.run_closed, {}

    def window_aware(*a, **kw):
        if kw.get("seconds") is not None:
            state["answers"] = 0
        return run_closed(*a, **kw)

    def altered(self, timeout=None):
        frame = to_df(self, timeout)
        if "answers" in state:
            state["answers"] += 1
            if state["answers"] == 2:
                frame = frame.copy()
                if which == "sum":
                    frame.loc[frame.index[0], "v3"] *= 1.001
                else:
                    frame = frame.drop(frame.index[0])
        return frame

    monkeypatch.setattr(loadgen, "run_closed", window_aware)
    monkeypatch.setattr(QueryHandle, "to_df", altered)
    rc, result = run_cell(capsys, 2**31 + 3203, "--trace", "0")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == 1 and result["attempted"] > 2
    key = f"{QUERY}.sum_rel_err" if which == "sum" else f"{QUERY}.wrong_cells"
    assert result["compared"][key]["value"] > result["compared"][key]["limit"]


# -- the three readers on hand-made runs -------------------------------------


def traced_run(device_ops, requests=((100.0, 104.0, 4.0, True),),
               span=(100.0, 105.0), peaks=PEAKS, query=QUERY):
    log = [loadgen.Request(client=0, query=query, params={}, t_submit=a,
                           t_done=b, t_end=b, run_s=c, ok=ok)
           for a, b, c, ok in requests]
    return types.SimpleNamespace(
        log=log, trace={"device_ops": device_ops, "busy_s": 4.9},
        trace_span=span, peaks=peaks)


def test_roofline_counts_the_groupby_modules_only_and_cannot_pass_100():
    m = reader("groupby_roofline")
    least = m.groupby_least_bytes(10_000_000, 100_000, 3)
    ops = [["jit_fused_groupby", 3.0], ["jit_sorted_groupby", 0.5],
           ["jit_agg_recombine", 0.2], ["jit_agg_final_tail", 0.1],
           ["jit__gather_all", 0.6], ["jit_fused", 0.4],
           ["jit__fused_concat_kernel", 0.2], ["jit__compact_idx", 0.1]]
    assert m.groupby_seconds(ops) == pytest.approx(3.8)
    got = m.read(traced_run(ops))
    assert got == pytest.approx(100 * least / 819e9 / 3.8)
    assert 0 < got < 100
    # half of a request inside the span counts half; a failed one nothing
    half = m.read(traced_run(ops, requests=((98.0, 102.0, 4.0, True),
                                            (100.0, 104.0, 4.0, False))))
    assert half == pytest.approx(got / 2)
    # the span's requests in the least time the peak allows: 100 exactly
    assert m.read(traced_run([["jit_fused_groupby", least / 819e9]])) == (
        pytest.approx(100.0))


def test_every_new_reader_returns_none_with_nothing_to_read(monkeypatch):
    from quokka_tpu.obs import querylog

    roofline = reader("groupby_roofline")
    empty = types.SimpleNamespace(log=[], trace=None, trace_span=None,
                                  peaks=PEAKS)
    for name in NEW_METRICS:
        assert reader(name).read(empty) is None
    # no peaks (a rehearsal), no group-by module among the span's (the
    # parent names the partial aggregate jit_fused), another query's
    # requests, no request at all
    assert roofline.read(traced_run([["jit_fused_groupby", 3.0]],
                                    peaks=None)) is None
    assert roofline.read(traced_run([["jit_fused", 3.0],
                                     ["jit__gather_all", 1.0]])) is None
    assert roofline.read(traced_run([["jit_fused_groupby", 3.0]],
                                    query="q1")) is None
    assert roofline.read(traced_run([["jit_fused_groupby", 3.0]],
                                    requests=())) is None
    # records without the counters (the parent's), and no record
    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    recs = [{"status": "done", "done": 103.0, "rows_in": 5}]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert reader("groupby_slots_per_row").read(run) is None
    assert reader("agg_merges_general_per_query").read(run) is None
    # the counter there but nothing sorted (every aggregate on the small
    # path): still nothing, never a 0
    recs[0]["groupby_sort_slots"] = 0
    assert reader("groupby_slots_per_row").read(run) is None
    monkeypatch.setattr(querylog, "records", lambda since=None: [])
    for name in NEW_METRICS[1:]:
        assert reader(name).read(run) is None


def test_the_counter_readers_read_the_window_records(monkeypatch):
    from quokka_tpu.obs import querylog

    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    recs = [
        {"status": "done", "done": 99.0, "rows_in": 1, "groupby_sort_slots":
         10**9, "agg_merges_general": 99},                       # before
        {"status": "done", "done": 103.0, "rows_in": 20_000_000,
         "groupby_sort_slots": 12_000_000, "agg_merges_general": 4},
        {"status": "done", "done": 108.0, "rows_in": 20_000_000,
         "groupby_sort_slots": 14_000_000, "agg_merges_general": 6},
        {"status": "failed", "done": 109.0, "rows_in": 1,
         "groupby_sort_slots": 10**9, "agg_merges_general": 99},  # no answer
    ]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert reader("groupby_slots_per_row").read(run) == pytest.approx(0.65)
    assert reader("agg_merges_general_per_query").read(run) == (
        pytest.approx(5.0))


def test_no_existing_benchmark_file_was_edited():
    """Against the checkout's HEAD: files under ``benchmarks/`` may be added
    (``??`` / ``A``), never changed, renamed or deleted."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain", "--", "benchmarks"], cwd=ROOT,
            capture_output=True, text=True, timeout=60, check=True).stdout
    except (OSError, subprocess.SubprocessError):
        pytest.skip("not a git checkout")
    edited = [line for line in out.splitlines()
              if line[:2].strip() not in ("??", "A")]
    assert not edited, edited
