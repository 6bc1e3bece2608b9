"""The ``tpch_q21_sf1.q21_s2`` cell: it resolves from entries alone, both
bfloat16 controls fail its limits at the rehearsal's size, ``least_bytes``
and its two per-layer readers compute what their files say (and nothing,
never an error or a 0, from a run with nothing to read), and a short traced
rehearsal is ``correct`` and reads both."""

import json
import types

import pytest

from harness import check, loadgen, lowprec, spec, tables

CELL = "tpch_q21_sf1.q21_s2"
QUERY = "q21"
NEW_METRICS = ("distinct_roofline", "distinct_sort_slots_per_row")
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 4201
OTHER_SEED = 2**31 + 4202


def reader(name):
    return spec.load_module("metrics", name)


def test_cell_resolves_from_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config_name == "tpch_q21_sf1"
    conf = cell.config
    assert conf["datagen"] == {"module": "tpch_q21", "args": {"sf": 1.0},
                               "rehearsal_args": {"sf": 0.02}}
    assert conf["reduced"] == ["scale_factor"]
    assert conf["source_scale_factor"] == "100 over 4 workers"
    assert {"generator", "dates", "service", "precision"} <= set(
        conf["assumed"])
    for word in ("complete", "exact", "never", "cached", "stale",
                 "non-durable"):
        assert word in conf["guarantees"]
    traffic = cell.traffic
    assert traffic["clients"] == 2 and traffic["mix"] == {QUERY: 1}
    nations = traffic["params"][QUERY]["nation"]["choice"]
    assert len(set(nations)) == 25 and "SAUDI ARABIA" in nations
    assert nations == [n for n, _ in spec.load_module(
        "datagen", "tpch8").NATIONS]
    assert traffic["values_per_run"] == {QUERY: 8}
    assert traffic["request_timeout_s"] == 120
    assert len(loadgen.plan(traffic, SEED)[QUERY]) == 8
    assert list(cell.queries) == [QUERY]
    entry = {c["name"]: c for c in cell.bench["configs"]}["tpch_q21_sf1"]
    assert entry["reduced"] == ["scale_factor"]
    assert entry["file"] == "benchmarks/configs/tpch_q21_sf1.json"
    assert len(entry["source"]) <= 200 and "2.4.21" in entry["source"]
    assert len(cell.entry["why"]) <= 200
    # the entries by name, never by where they sit: later cells append
    assert {w["name"]: w for w in cell.bench["workloads"]}[CELL] == {
        "name": CELL, "config": "tpch_q21_sf1", "traffic": "q21_s2",
        "chips": 1, "why": cell.entry["why"]}
    assert {e["name"] for e, _ in cell.metrics("end_to_end")} == {
        "queries_per_s", "latency_p50_ms", "setup_s"}
    per_layer = {e["name"]: e for e, _ in cell.metrics("per_layer")}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "queries_per_s"
    assert [(per_layer[n]["unit"], per_layer[n]["layer"],
             per_layer[n]["source"]) for n in NEW_METRICS] == [
        ("%", "kernels", "device_trace"),
        ("slots/row", "executors", "program_counter")]
    assert {"scan_roofline", "device_idle_pct", "host_syncs_per_query",
            "exec_host_ms", "compiles_in_window"} <= set(per_layer)
    for other in ("tpch_sf1.q1_s2", "tpch_sf1.q3_s2", "ticks_1d.asof_s2",
                  "h2o_g1_1e7.q5_s2", "tpch8_sf1.q9_s2",
                  "tpch_q18_sf1.q18_s2"):
        assert not set(NEW_METRICS) & {
            e["name"] for e, _ in spec.Cell(other).metrics("per_layer")}


@pytest.mark.parametrize("precision", lowprec.CONTROLS,
                         ids=[p.name for p in lowprec.CONTROLS])
def test_both_controls_fail_the_limits_at_the_rehearsal_size(precision):
    """bfloat16 spaces day numbers near 9,000 by 32 or 64 days, which
    flips the late test of most lines: the counts change."""
    cell = spec.Cell(CELL)
    q21 = cell.queries[QUERY]
    numbers = {}
    for seed in (SEED, OTHER_SEED):
        paths = tables.for_cell(cell, seed, rehearse=True)
        for params in loadgen.plan(cell.traffic, seed)[QUERY]:
            reference = q21.reference(paths, params)
            assert len(reference) >= 1
            exact, _ = check.compare([reference], reference, q21.SORT_KEYS,
                                     q21.EXACT)
            assert check.judge(exact, q21.LIMITS)
            found, _ = check.compare([q21.control(paths, params, precision)],
                                     reference, q21.SORT_KEYS, q21.EXACT)
            check.merge(numbers, found)
    assert numbers["wrong_cells"] > 0
    assert not check.judge(numbers, q21.LIMITS)


def test_a_program_without_the_distinct_counters_is_refused(monkeypatch):
    """An older program is refused at load, with a non-zero exit, even where
    ``ops/distinct.py`` lies beside it: the guard reads the query record's
    counters, which only the streaming distinct's program has."""
    import importlib.util

    from quokka_tpu.obs import querylog

    q21 = spec.load_module("queries", QUERY)
    assert not q21.streaming_distinct_missing()
    monkeypatch.setattr(querylog, "COUNTS", tuple(
        c for c in querylog.COUNTS if c != "distinct_sort_slots"))
    assert q21.streaming_distinct_missing()
    fresh = importlib.util.spec_from_file_location("q21_on_an_older_program",
                                                   q21.__file__)
    with pytest.raises(SystemExit) as refused:
        fresh.loader.exec_module(importlib.util.module_from_spec(fresh))
    assert "no streaming distinct" in str(refused.value.code)


def test_least_bytes_by_hand():
    cell = spec.Cell(CELL)
    paths = tables.for_cell(cell, SEED, rehearse=True)
    rows = {t: tables.row_count(paths, t) for t in paths}
    by_hand = (rows["lineitem"] * 16 + rows["orders"] * 8
               + rows["supplier"] * 12 + rows["nation"] * 8 + 100 * 2 * 4)
    assert cell.queries[QUERY].least_bytes(paths) == by_hand


# -- the two readers on hand-made runs ---------------------------------------


def traced_run(device_ops, requests=((100.0, 104.0, 4.0, True),),
               span=(100.0, 105.0), peaks=PEAKS, query=QUERY):
    log = [loadgen.Request(client=0, query=query, params={}, t_submit=a,
                           t_done=b, t_end=b, run_s=c, ok=ok)
           for a, b, c, ok in requests]
    return types.SimpleNamespace(
        log=log, trace={"device_ops": device_ops, "busy_s": 4.9},
        trace_span=span, peaks=peaks, parameter_sets={})


@pytest.fixture
def counted(monkeypatch):
    """What the reference counts of the tables at SF 1."""
    q21 = spec.load_module("queries", QUERY)
    monkeypatch.setattr(q21, "DISTINCT", {
        "all": {"rows": 6_001_215, "pairs": 5_941_000},
        "late": {"rows": 3_793_296, "pairs": 3_771_000}})
    return (6_001_215 + 5_941_000 + 3_793_296 + 3_771_000) * 8


def test_roofline_counts_the_distinct_modules_only_and_cannot_pass_100(
        counted):
    m = reader("distinct_roofline")
    assert counted == pytest.approx(156.0e6, rel=0.01)
    ops = [["jit_distinct_dedupe", 0.1], ["jit_distinct_probe", 0.2],
           ["jit_distinct_merge", 0.7], ["jit__gather_all", 0.9],
           ["jit_fused_groupby", 0.4], ["jit__pk_probe_direct", 0.3]]
    got = m.read(traced_run(ops))
    assert got == pytest.approx(100 * counted / 819e9 / 1.0)
    assert 0 < got < 100
    # half of a request inside the span counts half; a failed one nothing
    half = m.read(traced_run(ops, requests=((98.0, 102.0, 4.0, True),
                                            (100.0, 104.0, 4.0, False))))
    assert half == pytest.approx(got / 2)
    # the span's request in the least time the peak allows: 100 exactly
    assert m.read(traced_run([["jit_distinct_merge", counted / 819e9]])) == (
        pytest.approx(100.0))


def test_every_new_reader_returns_none_with_nothing_to_read(monkeypatch,
                                                            counted):
    from quokka_tpu.obs import querylog

    roofline = reader("distinct_roofline")
    empty = types.SimpleNamespace(log=[], trace=None, trace_span=None,
                                  peaks=PEAKS, parameter_sets={})
    for name in NEW_METRICS:
        assert reader(name).read(empty) is None
    ops = [["jit_distinct_merge", 3.0]]
    # no peaks (a rehearsal), no distinct module among the span's (the
    # parent's distinct runs group-by and join programs), another query's
    # requests, no request at all, nothing the reference counted
    assert roofline.read(traced_run(ops, peaks=None)) is None
    assert roofline.read(traced_run([["jit_sorted_groupby", 1.0],
                                     ["jit__mm_plan", 1.0]])) is None
    assert roofline.read(traced_run(ops, query="q18")) is None
    assert roofline.read(traced_run(ops, requests=())) is None
    monkeypatch.setattr(spec.load_module("queries", QUERY), "DISTINCT", {})
    assert roofline.read(traced_run(ops)) is None
    # records without the counters (the parent has none), no row into a
    # distinct, and no record
    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    recs = [{"status": "done", "done": 103.0, "groupby_sort_slots": 5}]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    slots = reader("distinct_sort_slots_per_row")
    assert slots.read(run) is None
    recs[0].update(distinct_sort_slots=0, distinct_rows_in=0)
    assert slots.read(run) is None
    monkeypatch.setattr(querylog, "records", lambda since=None: [])
    assert slots.read(run) is None


def test_the_slots_reader_reads_the_window_records(monkeypatch):
    from quokka_tpu.obs import querylog

    run = types.SimpleNamespace(log=[loadgen.Request(
        client=0, query=QUERY, params={}, t_submit=100.0, t_end=110.0)])
    recs = [
        {"status": "done", "done": 99.0, "distinct_sort_slots": 10**12,
         "distinct_rows_in": 1},  # before the window
        {"status": "done", "done": 103.0, "distinct_sort_slots": 20_971_520,
         "distinct_rows_in": 9_794_511},
        {"status": "done", "done": 108.0, "distinct_sort_slots": 20_971_520,
         "distinct_rows_in": 9_794_511},
        {"status": "failed", "done": 109.0, "distinct_sort_slots": 10**12,
         "distinct_rows_in": 1},
    ]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs if r["done"] > (since or 0)])
    assert reader("distinct_sort_slots_per_row").read(run) == pytest.approx(
        20_971_520 / 9_794_511)


# -- a traced rehearsal --------------------------------------------------------


def test_a_traced_rehearsal_is_correct_and_reads_both_metrics(
        capsys, monkeypatch):
    """The rehearsal has no chip, so no peaks and no device plane: the
    roofline's reader is given the run the rehearsal left, with the peaks
    and a device trace that holds the distinct's three programs."""
    import run

    kept = []

    class Keep(run.Run):
        def __init__(self):
            super().__init__()
            kept.append(self)

    monkeypatch.setattr(run, "Run", Keep)
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "6",
                   "--rehearse", "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0
    assert result["window"]["answered_right"] == result["attempted"] >= 4
    assert result["compared"][f"{QUERY}.wrong_cells"] == {"value": 0,
                                                         "limit": 0}
    metrics = result["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # a padded slot: two sorts, and the probe's window of two chunks of 128
    # (a fold against the empty state) or its halvings; every batch at least
    # as long as its rows and at most four times as long
    slots = metrics["distinct_sort_slots_per_row"]
    assert slots["unit"] == "slots/row" and 3.0 <= slots["value"] < 4 * 259
    assert "distinct_roofline" not in metrics  # no chip, no peaks
    done = kept[-1]
    assert sum(r.ok for r in done.log) == result["attempted"]
    first, last = done.log[0], done.log[-1]
    done.peaks = PEAKS
    done.trace = {"device_ops": [["jit_distinct_merge", 0.01]], "busy_s": 1}
    done.trace_span = (first.t_submit, last.t_done)
    share = reader("distinct_roofline").read(done)
    counted = spec.load_module("queries", QUERY).DISTINCT
    assert counted["all"]["rows"] > counted["late"]["rows"] > 0
    assert share is not None and share > 0
