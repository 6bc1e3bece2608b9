"""The ``tpch8_sf1.q9_s2`` cell (ISSUE 34): it resolves from entries alone, a
rehearsal of it is ``correct``, both bfloat16 controls fail its limit, an
altered sum, a dropped group and a ``partsupp`` with half its rows each make
``correct`` false, and its three per-layer readers read what their files say
and nothing (never an error, never a 0) from a run with nothing to read."""

import json
import types

import pytest

from harness import check, loadgen, lowprec, spec, tables

CELL = "tpch8_sf1.q9_s2"
QUERY = "q9"
NEW_METRICS = ("join_search_slots_per_row", "join_slots_per_row",
               "join_roofline")
PEAKS = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return spec.load_module("metrics", name)


def run_cell(capsys, seed, *extra):
    import run

    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                   "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_cell_resolves_from_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "tpch8_sf1"
    conf = cell.config
    assert conf["datagen"] == {"module": "tpch8", "args": {"sf": 1.0},
                               "rehearsal_args": {"sf": 0.01}}
    assert conf["reduced"] == ["scale_factor"]
    assert "30 GiB" in conf["reduced_why"]["scale_factor"]
    assert {"generator", "columns_left_out", "service", "precision"} <= set(
        conf["assumed"])
    for word in ("every", "exact", "float64", "never", "sampled",
                 "non-durable"):
        assert word in conf["guarantees"]
    traffic = cell.traffic
    assert traffic["clients"] == 2 and traffic["mix"] == {QUERY: 1}
    assert traffic["values_per_run"] == {QUERY: 8}
    assert traffic["request_timeout_s"] == 120
    words = traffic["params"][QUERY]["color"]["choice"]
    assert words == spec.load_module("datagen", "tpch8").P_NAME_WORDS
    sets = loadgen.plan(traffic, 2**31 + 3401)[QUERY]
    assert len(sets) == 8 and len({s["color"] for s in sets}) == 8
    assert list(cell.queries) == [QUERY]
    entry = {c["name"]: c for c in cell.bench["configs"]}["tpch8_sf1"]
    assert entry["reduced"] == ["scale_factor"]
    assert len(entry["source"]) <= 200 and "2.4.9" in entry["source"]
    assert len(cell.entry["why"]) <= 200
    # the entries by name, never by where they sit: later cells append
    assert entry["file"] == "benchmarks/configs/tpch8_sf1.json"
    assert {w["name"]: w for w in cell.bench["workloads"]}[CELL] == {
        "name": CELL, "config": "tpch8_sf1", "traffic": "q9_s2",
        "chips": 1, "why": cell.entry["why"]}
    assert {e["name"] for e, _ in cell.metrics("end_to_end")} == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {e["name"]: e for e, _ in cell.metrics("per_layer")}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
    units = [(per_layer[n]["unit"], per_layer[n]["layer"])
             for n in NEW_METRICS]
    assert units == [("slots/row", "executors"), ("slots/row", "executors"),
                     ("%", "kernels")]
    assert {"scan_roofline", "device_idle_pct", "pad_waste_pct",
            "exec_host_ms", "compiles_in_window"} <= set(per_layer)
    for other in ("tpch_sf1.q1_s2", "tpch_sf1.q3_s2", "ticks_1d.asof_s2",
                  "h2o_g1_1e7.q5_s2", "tpch_q18_sf1.q18_s2"):
        assert not set(NEW_METRICS) & {
            e["name"] for e, _ in spec.Cell(other).metrics("per_layer")}


def test_least_bytes_count_the_scanned_columns_and_the_joins_values():
    cell = spec.Cell(CELL)
    q9 = cell.queries[QUERY]
    paths = tables.for_cell(cell, 2**31 + 3402, rehearse=True)
    rows = {t: tables.row_count(paths, t) for t in q9.COLUMNS}
    assert q9.least_bytes(paths) == 4 * (
        6 * rows["lineitem"] + 2 * rows["part"] + 3 * rows["partsupp"]
        + 2 * rows["supplier"] + 2 * 25 + 2 * rows["orders"]) + 175 * 12
    m = reader("join_roofline")
    # SF 1, 326,000 matching line items: 6.0 M keys, 5 values a match, and
    # the builds' 0.4 + 2.4 + 0.02 + 3.0 M values and nation's 50
    assert m.join_least_bytes(6_000_000, 326_000, 1.0) == 4 * (
        6_000_000 + 5 * 326_000 + 400_000 + 2_400_000 + 20_000 + 3_000_000
        + 50)


def test_a_traced_rehearsal_is_correct(capsys):
    rc, result = run_cell(capsys, 2**31 + 3403, "--trace", "1")
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0
    assert result["window"]["answered_right"] == result["attempted"] >= 4
    assert result["window"]["parameter_sets"] == 8
    compared = result["compared"]
    assert compared[f"{QUERY}.wrong_cells"] == {"value": 0, "limit": 0}
    assert compared[f"{QUERY}.unanswered"] == {"value": 0, "limit": 0}
    assert 0 < compared[f"{QUERY}.sum_rel_err"]["value"] < (
        compared[f"{QUERY}.sum_rel_err"]["limit"])
    metrics = result["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # the readers divide by the configuration's 6.0 M rows, a hundred times
    # the rehearsal's: five probes over one 65,536-slot batch
    assert metrics["join_slots_per_row"]["unit"] == "slots/row"
    assert metrics["join_slots_per_row"]["value"] == pytest.approx(
        5 * 65_536 / 6e6)
    assert metrics["join_search_slots_per_row"]["value"] == pytest.approx(
        65_536 / 6e6)
    assert "join_roofline" not in metrics  # no chip, no peaks: no share


@pytest.mark.parametrize("precision", lowprec.CONTROLS,
                         ids=[p.name for p in lowprec.CONTROLS])
def test_both_controls_fail_the_limit_at_the_rehearsal_size(precision):
    cell = spec.Cell(CELL)
    q9 = cell.queries[QUERY]
    seed = 2**31 + 3404
    paths = tables.for_cell(cell, seed, rehearse=True)
    numbers = {}
    for params in loadgen.plan(cell.traffic, seed)[QUERY][:3]:
        reference = q9.reference(paths, params)
        assert len(reference) > 100  # the composite join keeps its rows
        found, _ = check.compare([q9.control(paths, params, precision)],
                                 reference, q9.SORT_KEYS, q9.EXACT)
        check.merge(numbers, found)
        exact, _ = check.compare([reference], reference, q9.SORT_KEYS,
                                 q9.EXACT)
        assert check.judge(exact, q9.LIMITS)
    assert numbers["wrong_cells"] == 0  # the keys stay exact
    assert numbers["sum_rel_err"] > 10 * q9.LIMITS["sum_rel_err"]
    assert not check.judge(numbers, q9.LIMITS)


@pytest.mark.parametrize("which", ["sum", "group"])
def test_one_altered_answer_is_not_correct(which, capsys, monkeypatch):
    """The second answer of the window altered where it is produced: one
    sum off by a thousandth, or one group dropped; every other answer is
    right."""
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
                    frame.loc[frame.index[0], "sum_profit"] *= 1.001
                else:
                    frame = frame.drop(frame.index[0])
        return frame

    monkeypatch.setattr(loadgen, "run_closed", window_aware)
    monkeypatch.setattr(QueryHandle, "to_df", altered)
    rc, result = run_cell(capsys, 2**31 + 3405, "--trace", "0")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == 1 and result["attempted"] > 2
    key = f"{QUERY}.sum_rel_err" if which == "sum" else f"{QUERY}.wrong_cells"
    assert result["compared"][key]["value"] > result["compared"][key]["limit"]


def test_half_of_partsupp_is_not_correct(capsys, monkeypatch):
    """The program reads a ``partsupp`` that lost every other row; the
    reference reads the whole one: every answer is wrong."""
    import pyarrow.parquet as pq

    q9 = spec.load_module("queries", QUERY)
    build = q9.build

    def thinned(ctx, paths, params):
        half = paths["partsupp"].replace(".parquet", ".half.parquet")
        pq.write_table(pq.read_table(paths["partsupp"])[::2], half)
        return build(ctx, dict(paths, partsupp=half), params)

    monkeypatch.setattr(q9, "build", thinned)
    rc, result = run_cell(capsys, 2**31 + 3406, "--trace", "0")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
    # a group that lost every row is a missing row; one that kept some is
    # a sum far off
    compared = result["compared"]
    assert (compared[f"{QUERY}.wrong_cells"]["value"] > 0
            or compared[f"{QUERY}.sum_rel_err"]["value"] > 0.1)


# -- the three readers on hand-made runs -------------------------------------


def traced_run(device_ops, requests=((100.0, 104.0, 4.0, True),),
               span=(100.0, 105.0), peaks=PEAKS, query=QUERY,
               colours=("green", "almond")):
    log = [loadgen.Request(client=0, query=query, params={}, t_submit=a,
                           t_done=b, t_end=b, run_s=c, ok=ok)
           for a, b, c, ok in requests]
    return types.SimpleNamespace(
        log=log, trace={"device_ops": device_ops, "busy_s": 4.9},
        trace_span=span, peaks=peaks,
        parameter_sets={query: [{"color": c} for c in colours]})


@pytest.fixture
def matched(monkeypatch):
    """The reference's counts for two of a run's colours."""
    q9 = spec.load_module("queries", QUERY)
    monkeypatch.setattr(q9, "MATCHED_ROWS", {"green": 330_000,
                                             "almond": 322_000})
    return 326_000


def test_roofline_counts_the_join_modules_only_and_cannot_pass_100(matched):
    m = reader("join_roofline")
    least = m.join_least_bytes(6_000_000, matched, 1.0)
    ops = [["jit__pk_probe_sorted", 1.2], ["jit__pk_probe_direct", 0.5],
           ["jit__pk_direct_build", 0.1], ["jit__sort_build_keys", 0.15],
           ["jit__mm_plan", 0.03], ["jit_hash_join_v2", 0.02],
           ["jit__gather_all", 0.9], ["jit__compact_idx", 0.3],
           ["jit_fused_groupby", 0.2], ["jit__fused_concat_kernel", 0.1]]
    assert m.join_seconds(ops) == pytest.approx(2.0)
    got = m.read(traced_run(ops))
    assert got == pytest.approx(100 * least / 819e9 / 2.0)
    assert 0 < got < 100
    # half of a request inside the span counts half; a failed one nothing
    half = m.read(traced_run(ops, requests=((98.0, 102.0, 4.0, True),
                                            (100.0, 104.0, 4.0, False))))
    assert half == pytest.approx(got / 2)
    # the span's request in the least time the peak allows: 100 exactly
    assert m.read(traced_run([["jit__pk_probe_direct", least / 819e9]])) == (
        pytest.approx(100.0))
    # a colour the reference never counted is left out of the mean
    assert m.matched_rows(traced_run(ops, colours=("green", "red"))) == (
        330_000)


def test_every_new_reader_returns_none_with_nothing_to_read(monkeypatch,
                                                            matched):
    from quokka_tpu.obs import querylog

    roofline = reader("join_roofline")
    empty = types.SimpleNamespace(log=[], trace=None, trace_span=None,
                                  peaks=PEAKS, parameter_sets={})
    for name in NEW_METRICS:
        assert reader(name).read(empty) is None
    probe = [["jit__pk_probe_direct", 3.0]]
    # no peaks (a rehearsal), no join module among the span's, another
    # query's requests, no request at all, no colour the reference counted
    assert roofline.read(traced_run(probe, peaks=None)) is None
    assert roofline.read(traced_run([["jit__gather_all", 1.0],
                                     ["jit_fused_groupby", 3.0]])) is None
    assert roofline.read(traced_run(probe, query="q3")) is None
    assert roofline.read(traced_run(probe, requests=())) is None
    assert roofline.read(traced_run(probe, colours=("red",))) is None
    # records without the counters (the parent has no join_probe_general),
    # and no record
    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    recs = [{"status": "done", "done": 103.0, "join_probe_direct": 5}]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert reader("join_slots_per_row").read(run) is None
    assert reader("join_search_slots_per_row").read(run) is None
    # the counters there but nothing probed that way: nothing, never a 0
    recs[0].update(join_probe_search=0, join_probe_general=0,
                   join_probe_direct=0)
    assert reader("join_slots_per_row").read(run) is None
    assert reader("join_search_slots_per_row").read(run) is None
    monkeypatch.setattr(querylog, "records", lambda since=None: [])
    for name in NEW_METRICS[:2]:
        assert reader(name).read(run) is None


def test_the_counter_readers_read_the_window_records(monkeypatch):
    from quokka_tpu.obs import querylog

    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    huge = {"join_probe_direct": 10**12, "join_probe_search": 10**12,
            "join_probe_general": 10**12}
    recs = [
        dict(huge, status="done", done=99.0),                    # before
        {"status": "done", "done": 103.0, "join_probe_direct": 7_000_000,
         "join_probe_search": 600_000, "join_probe_general": 0},
        {"status": "done", "done": 108.0, "join_probe_direct": 7_400_000,
         "join_probe_search": 1_200_000, "join_probe_general": 1_600_000},
        dict(huge, status="failed", done=109.0),                 # no answer
    ]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert reader("join_slots_per_row").read(run) == pytest.approx(
        17_800_000 / 12e6)
    assert reader("join_search_slots_per_row").read(run) == pytest.approx(
        0.15)
    # the search alone is read from a program that lacks the third counter
    for r in recs:
        r.pop("join_probe_general")
    assert reader("join_slots_per_row").read(run) is None
    assert reader("join_search_slots_per_row").read(run) == pytest.approx(
        0.15)
