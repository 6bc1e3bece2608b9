"""``compact_sort_pct``: its entry and the five cells that list it, its
arithmetic on hand-made records, nothing (never an error) against a program
whose records lack the counters or that compacted nothing, and in a traced
q3 rehearsal 100 under the TPU's compaction (``compact=sort``) and 0 under
the CPU's (``nonzero``)."""

import json
import sys
import types

import pytest

from harness import loadgen, spec

NAME = "compact_sort_pct"
CELLS = ["tpch_sf1.q3_s2", "ticks_1d.asof_s2", "h2o_g1_1e7.q5_s2",
         "tpch8_sf1.q9_s2", "tpch_q18_sf1.q18_s2"]


def reader():
    return spec.load_module("metrics", NAME)


def fake_run(t0=100.0, t1=110.0):
    log = [loadgen.Request(client=0, query="q3", params={}, t_submit=t0,
                           t_end=t1)]
    return types.SimpleNamespace(log=log)


def record(done, **kw):
    return dict({"status": "done", "done": done}, **kw)


def patch(monkeypatch, recs):
    from quokka_tpu.obs import querylog

    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs
                            if since is None or r["done"] > since])


def test_entry_names_the_counters_the_layer_and_the_cells():
    from quokka_tpu.obs import querylog

    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": CELLS}
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1
    assert {"compact_sort_slots", "compact_nonzero_slots"} <= set(
        querylog.COUNTS)
    for cell in CELLS:
        assert [e["name"] for e, _ in spec.Cell(cell).metrics(
            "per_layer")].count(NAME) == 1
    # q1 compacts nothing inside a dispatch: both counters read 0 there
    assert NAME not in [e["name"] for e, _ in spec.Cell(
        "tpch_sf1.q1_s2").metrics("per_layer")]


def test_share_of_the_windows_compaction_slots(monkeypatch):
    patch(monkeypatch, [
        record(99.0, compact_sort_slots=0,
               compact_nonzero_slots=1 << 30),  # before the window
        record(101.0, compact_sort_slots=6 << 20, compact_nonzero_slots=0),
        record(105.0, compact_sort_slots=1 << 20,
               compact_nonzero_slots=1 << 20),
        record(106.0, status="failed", compact_sort_slots=0,
               compact_nonzero_slots=1 << 30),  # not an answer
        record(111.0, compact_sort_slots=0,
               compact_nonzero_slots=1 << 30),  # after the window
    ])
    assert reader().read(fake_run()) == pytest.approx(87.5)
    patch(monkeypatch, [record(101.0, compact_sort_slots=0,
                               compact_nonzero_slots=1 << 20)])
    assert reader().read(fake_run()) == 0.0


def test_nothing_to_read_is_none_never_an_error(monkeypatch):
    run = fake_run()
    # the parent of the PR that added the counters: records without them
    patch(monkeypatch, [record(101.0, rows_in=5), record(105.0, rows_in=7)])
    assert reader().read(run) is None
    # nothing compacted inside a dispatch (q1)
    patch(monkeypatch, [record(101.0, compact_sort_slots=0,
                               compact_nonzero_slots=0)])
    assert reader().read(run) is None
    patch(monkeypatch, [])
    assert reader().read(run) is None
    assert reader().read(types.SimpleNamespace(log=[])) is None
    # a program from before the query log
    import quokka_tpu.obs

    monkeypatch.delattr(quokka_tpu.obs, "querylog")
    monkeypatch.setitem(sys.modules, "quokka_tpu.obs.querylog", None)
    assert reader().read(run) is None


@pytest.mark.parametrize("compact, share", [("sort", 100.0),
                                            ("nonzero", 0.0)])
def test_a_traced_q3_rehearsal_reads_the_compaction_that_ran(
        compact, share, capsys, monkeypatch):
    import run

    monkeypatch.setenv(
        "QK_KERNEL_STRATEGY",
        f"groupby=sort,join_build=sort,asof=sort,compact={compact}")
    rc = run.main(["--workload", "tpch_sf1.q3_s2", "--seed",
                   str(2**31 + 4101), "--seconds", "5", "--rehearse",
                   "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert result["window"]["answered_right"] == result["attempted"] >= 1
    assert result["metrics"][NAME] == {"value": share, "unit": "%"}
