"""``asof_sort_match_pct``: its entry, its arithmetic on hand-made records,
nothing (never an error) against a program whose records lack the counters,
and 100 in a traced rehearsal of the cell that lists it under the TPU's
strategies as they are since PR 35 (this directory's conftest still pins the
search for every other test)."""

import json
import sys
import types

import pytest

from harness import loadgen, spec

NAME = "asof_sort_match_pct"
CELL = "ticks_1d.asof_s2"


def reader():
    return spec.load_module("metrics", NAME)


def fake_run(t0=100.0, t1=110.0):
    log = [loadgen.Request(client=0, query="asof", params={}, t_submit=t0,
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


def test_entry_names_the_counter_the_layer_and_the_cell():
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": [CELL]}
    assert [e["name"] for e, _ in spec.Cell(CELL).metrics(
        "per_layer")].count(NAME) == 1
    for other in ("tpch_sf1.q1_s2", "tpch_sf1.q3_s2", "h2o_g1_1e7.q5_s2",
                  "tpch8_sf1.q9_s2"):
        assert NAME not in [e["name"] for e, _ in spec.Cell(other).metrics(
            "per_layer")]


def test_share_of_the_windows_flushes(monkeypatch):
    patch(monkeypatch, [
        record(99.0, asof_match_sort=0, asof_match_search=10**6),  # before
        record(101.0, asof_match_sort=5, asof_match_search=0),
        record(105.0, asof_match_sort=1, asof_match_search=2),
        record(106.0, status="failed", asof_match_sort=0,
               asof_match_search=10**6),  # not an answer
        record(111.0, asof_match_sort=0, asof_match_search=10**6),  # after
    ])
    assert reader().read(fake_run()) == pytest.approx(75.0)
    patch(monkeypatch, [record(101.0, asof_match_sort=0,
                               asof_match_search=5)])
    assert reader().read(fake_run()) == 0.0


def test_nothing_to_read_is_none_never_an_error(monkeypatch):
    run = fake_run()
    # the parent of the PR that added the counters: records without them
    patch(monkeypatch, [record(101.0, asof_flushes=5),
                        record(105.0, asof_flushes=5)])
    assert reader().read(run) is None
    # no flush took a device match (no asof join; the host merge)
    patch(monkeypatch, [record(101.0, asof_match_sort=0,
                               asof_match_search=0)])
    assert reader().read(run) is None
    patch(monkeypatch, [])
    assert reader().read(run) is None
    assert reader().read(types.SimpleNamespace(log=[])) is None
    # a program from before the query log
    import quokka_tpu.obs

    monkeypatch.delattr(quokka_tpu.obs, "querylog")
    monkeypatch.setitem(sys.modules, "quokka_tpu.obs.querylog", None)
    assert reader().read(run) is None


@pytest.mark.parametrize("asof, share", [("sort", 100.0),
                                         ("searchsorted", 0.0)])
def test_a_traced_asof_rehearsal_reads_the_strategy_that_ran(
        asof, share, capsys, monkeypatch):
    import run

    monkeypatch.setenv("QK_KERNEL_STRATEGY",
                       f"groupby=sort,join_build=sort,asof={asof}")
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 3501),
                   "--seconds", "2", "--rehearse", "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert result["metrics"][NAME] == {"value": share, "unit": "%"}
    assert result["metrics"]["asof_flushes_per_query"]["value"] > 0
