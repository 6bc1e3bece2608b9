"""``join_direct_probe_pct``: its entry, its arithmetic on hand-made records,
nothing (never an error) against a program whose records lack the counters,
and 100 in a traced rehearsal of the cell that lists it."""

import json
import sys
import types

import pytest

from harness import loadgen, spec

NAME = "join_direct_probe_pct"


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


def test_entry_names_the_counter_the_layer_and_the_cell():
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "queries_per_s", "workloads": ["tpch_sf1.q3_s2"]}
    assert bench["per_layer"][-1]["name"] == NAME  # appended, nothing moved
    assert [e["name"] for e, _ in spec.Cell("tpch_sf1.q3_s2").metrics(
        "per_layer")].count(NAME) == 1
    for other in ("tpch_sf1.q1_s2", "ticks_1d.asof_s2"):
        assert NAME not in [e["name"] for e, _ in spec.Cell(other).metrics(
            "per_layer")]


def test_share_of_the_windows_probe_slots(monkeypatch):
    patch(monkeypatch, [
        record(99.0, join_probe_direct=0, join_probe_search=10**9),  # before
        record(101.0, join_probe_direct=6_000_000, join_probe_search=0),
        record(105.0, join_probe_direct=3_000_000,
               join_probe_search=3_000_000),
        record(106.0, status="failed", join_probe_direct=0,
               join_probe_search=10**9),  # not an answer
        record(111.0, join_probe_direct=0, join_probe_search=10**9),  # after
    ])
    assert reader().read(fake_run()) == pytest.approx(75.0)


def test_nothing_to_read_is_none_never_an_error(monkeypatch):
    run = fake_run()
    # the parent of the PR that added the counters: records without them
    patch(monkeypatch, [record(101.0, rows_in=5), record(105.0, rows_in=7)])
    assert reader().read(run) is None
    # no join probed on the sort branch (q1; the hashtable strategy)
    patch(monkeypatch, [record(101.0, join_probe_direct=0,
                               join_probe_search=0)])
    assert reader().read(run) is None
    patch(monkeypatch, [])
    assert reader().read(run) is None
    assert reader().read(types.SimpleNamespace(log=[])) is None
    # a program from before the query log
    import quokka_tpu.obs

    monkeypatch.delattr(quokka_tpu.obs, "querylog")
    monkeypatch.setitem(sys.modules, "quokka_tpu.obs.querylog", None)
    assert reader().read(run) is None


def test_a_traced_q3_rehearsal_reads_100(capsys):
    import run

    rc = run.main(["--workload", "tpch_sf1.q3_s2", "--seed",
                   str(2**31 + 3101), "--seconds", "2", "--rehearse",
                   "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    assert result["metrics"][NAME] == {"value": 100.0, "unit": "%"}
