"""The six per-layer metrics that read the program's query records: numbers
in a traced rehearsal, the arithmetic on hand-made records, and nothing
(never an error) against a program that keeps no records."""

import json
import sys
import types

import pytest

from harness import loadgen, spec

NEW = ("worker_park_pct", "finalize_ms", "requeues_per_query",
       "dispatch_self_ms", "exec_host_ms", "pad_waste_pct")


def reader(name):
    return spec.load_module("metrics", name)


def fake_run(t0=100.0, t1=110.0):
    log = [loadgen.Request(client=0, query="q1", params={}, t_submit=t0,
                           t_end=t1)]
    return types.SimpleNamespace(log=log)


def record(done, **kw):
    base = {"status": "done", "done": done, "pool_size": 2,
            "park_s_total": 0.0, "service.finalize": 0.0, "requeues": 0,
            "runtime.pick": 0.0, "runtime.dispatch_self": 0.0,
            "runtime.push": 0.0, "executors.exec_self": 0.0,
            "rows_in": 0, "padded_in": 0}
    return dict(base, **kw)


def test_every_new_metric_has_its_entry_and_its_reader():
    entries = {m["name"]: m for m in spec.load_json(
        spec.ROOT + "/BENCHMARK.json")["per_layer"]}
    for name in NEW:
        assert entries[name]["source"] == "program_counter"
        assert "workloads" not in entries[name]
        assert callable(reader(name).read)
    assert [entries[n]["layer"] for n in NEW] == [
        "service", "service", "runtime", "runtime", "executors", "executors"]


def test_arithmetic_on_hand_made_records(monkeypatch):
    from quokka_tpu.obs import querylog

    recs = [
        record(99.0, requeues=1000),  # before the window: not read
        record(101.0, park_s_total=5.0, requeues=4, rows_in=30,
               padded_in=100, **{"service.finalize": 0.010,
                                 "runtime.pick": 0.001,
                                 "runtime.dispatch_self": 0.002,
                                 "runtime.push": 0.003,
                                 "executors.exec_self": 0.050}),
        record(105.0, park_s_total=7.0, requeues=6, rows_in=20,
               padded_in=100, **{"service.finalize": 0.030,
                                 "runtime.pick": 0.002,
                                 "runtime.dispatch_self": 0.002,
                                 "runtime.push": 0.002,
                                 "executors.exec_self": 0.150}),
        record(106.0, status="failed", requeues=1000),  # not an answer
        record(111.0, requeues=1000),  # after the window
    ]
    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs
                            if since is None or r["done"] > since])
    run = fake_run()
    # 2 s parked of 2 threads x 4 s between the first and last record
    assert reader("worker_park_pct").read(run) == pytest.approx(25.0)
    assert reader("finalize_ms").read(run) == pytest.approx(20.0)
    assert reader("requeues_per_query").read(run) == pytest.approx(5.0)
    assert reader("dispatch_self_ms").read(run) == pytest.approx(6.0)
    assert reader("exec_host_ms").read(run) == pytest.approx(100.0)
    assert reader("pad_waste_pct").read(run) == pytest.approx(75.0)


def test_nothing_to_read_is_none_never_an_error(monkeypatch):
    from quokka_tpu.obs import querylog

    run = fake_run()
    monkeypatch.setattr(querylog, "records", lambda since=None: [])
    assert [reader(n).read(run) for n in NEW] == [None] * 6
    # one record gives no interval for the parked share, and no padded slot
    # gives no waste
    monkeypatch.setattr(querylog, "records",
                        lambda since=None: [record(101.0)])
    assert reader("worker_park_pct").read(run) is None
    assert reader("pad_waste_pct").read(run) is None
    assert reader("finalize_ms").read(run) == 0.0
    # a program from before the query log (the parent of the PR that added
    # these readers): the import fails, the metric is left out
    import quokka_tpu.obs

    monkeypatch.delattr(quokka_tpu.obs, "querylog")
    monkeypatch.setitem(sys.modules, "quokka_tpu.obs.querylog", None)
    assert [reader(n).read(run) for n in NEW] == [None] * 6
    assert [reader(n).read(types.SimpleNamespace(log=[])) for n in NEW] == (
        [None] * 6)


def test_a_traced_rehearsal_reports_all_six(capsys):
    import run

    rc = run.main(["--workload", "tpch_sf1.q1_s2", "--seed",
                   str(2**31 + 79), "--seconds", "2", "--rehearse",
                   "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    for name in NEW:
        value = result["metrics"][name]["value"]
        assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert 0 <= result["metrics"]["worker_park_pct"]["value"] <= 100
    assert 0 <= result["metrics"]["pad_waste_pct"]["value"] < 100
