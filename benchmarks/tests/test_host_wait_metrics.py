"""The five per-layer metrics that read a request's wait for the device
(ISSUE 36): their entries, the arithmetic on hand-made records, nothing
(never an error) against records without the keys, and numbers in a traced
rehearsal."""

import json
import types

import pytest

from harness import loadgen, spec

NEW = ("host_syncs_per_query", "sync_wait_ms", "host_work_ms",
       "d2h_bytes_per_query", "h2d_bytes_per_query")
OUTSIDE = ("runtime.pick", "entry.submit", "service.finalize")


def reader(name):
    return spec.load_module("metrics", name)


def fake_run(t0=100.0, t1=110.0):
    log = [loadgen.Request(client=0, query="q1", params={}, t_submit=t0,
                           t_end=t1)]
    return types.SimpleNamespace(log=log)


def record(done, **kw):
    base = {"status": "done", "done": done, "task_s": 0.0,
            "runtime.pick": 0.0, "entry.submit": 0.0,
            "service.finalize": 0.0, "syncs": 0, "sync.wait": 0.0,
            "sync.in_dispatch": 0.0, "sync.offthread": 0.0, "d2h_bytes": 0,
            "h2d_bytes": 0, "sync_sites": []}
    return dict(base, **kw)


def served(monkeypatch, recs):
    from quokka_tpu.obs import querylog

    monkeypatch.setattr(
        querylog, "records",
        lambda since=None: [r for r in recs
                            if since is None or r["done"] > since])


def test_the_five_entries_were_appended_and_name_layer_and_moves():
    per_layer = spec.load_json(spec.ROOT + "/BENCHMARK.json")["per_layer"]
    # appended behind the 21 entries the benchmark had, together and in this
    # order (the last five at this PR; a later PR appends behind them)
    first = [m["name"] for m in per_layer].index(NEW[0])
    last = per_layer[first:first + 5]
    assert first == 21 and tuple(m["name"] for m in last) == NEW
    assert [(m["unit"], m["better"], m["layer"], m["moves"]) for m in last] \
        == [("reads", "lower", "executors", "latency_p50_ms"),
            ("ms", "lower", "executors", "latency_p50_ms"),
            ("ms", "lower", "runtime", "queries_per_s"),
            ("bytes", "lower", "device", "latency_p50_ms"),
            ("bytes", "lower", "device", "queries_per_s")]
    for m in last:
        assert m["source"] == "program_counter" and "workloads" not in m
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert callable(reader(m["name"]).read)
    # every cell reports the end-to-end metric each of them moves
    bench = spec.load_json(spec.ROOT + "/BENCHMARK.json")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert all("workloads" not in e2e[m["moves"]] for m in last)


def test_arithmetic_on_hand_made_records(monkeypatch):
    recs = [
        record(99.0, syncs=1000),  # before the window: not read
        record(101.0, syncs=30, d2h_bytes=4000, h2d_bytes=0, task_s=0.400,
               **{"sync.wait": 0.300, "sync.in_dispatch": 0.250,
                  "sync.offthread": 0.040, "runtime.pick": 0.010,
                  "entry.submit": 0.020, "service.finalize": 0.030}),
        record(105.0, syncs=50, d2h_bytes=6000, h2d_bytes=1 << 20,
               task_s=0.600,
               **{"sync.wait": 0.500, "sync.in_dispatch": 0.450,
                  "sync.offthread": 0.020, "runtime.pick": 0.030,
                  "entry.submit": 0.020, "service.finalize": 0.050}),
        record(106.0, status="failed", syncs=1000),  # not an answer
        record(111.0, syncs=1000),  # after the window
    ]
    served(monkeypatch, recs)
    run = fake_run()
    assert reader("host_syncs_per_query").read(run) == pytest.approx(40.0)
    assert reader("sync_wait_ms").read(run) == pytest.approx(400.0)
    # (0.460 - 0.260) and (0.700 - 0.480): the helper threads' 0.040 and
    # 0.020 s of reads were never in the four sums
    assert reader("host_work_ms").read(run) == pytest.approx(210.0)
    assert reader("d2h_bytes_per_query").read(run) == pytest.approx(5000.0)
    assert reader("h2d_bytes_per_query").read(run) == pytest.approx(1 << 19)


def test_host_work_never_exceeds_the_threads_seconds(monkeypatch):
    recs = [record(101.0 + i, task_s=0.1 * i, syncs=i,
                   **{"sync.wait": 0.07 * i, "sync.in_dispatch": 0.05 * i,
                      "sync.offthread": 0.02 * i, "runtime.pick": 0.01,
                      "entry.submit": 0.02, "service.finalize": 0.005 * i})
            for i in range(1, 6)]
    served(monkeypatch, recs)
    run = fake_run()
    whole = 1e3 * sum(r["task_s"] + sum(r[k] for k in OUTSIDE)
                      for r in recs) / len(recs)
    work = reader("host_work_ms").read(run)
    assert 0.0 < work <= whole
    assert work == pytest.approx(whole - 1e3 * sum(
        r["sync.wait"] - r["sync.offthread"] for r in recs) / len(recs))
    # no read at all: the host's work is all of the threads' seconds
    served(monkeypatch, [record(101.0, task_s=0.2, **{"entry.submit": 0.1})])
    assert reader("host_work_ms").read(run) == pytest.approx(300.0)


def test_records_without_the_keys_read_as_nothing(monkeypatch):
    """The parent of the PR that added the keys: its records hold the
    partition and none of the five's keys."""
    old = {"status": "done", "done": 101.0, "task_s": 0.4,
           "runtime.pick": 0.01, "entry.submit": 0.02,
           "service.finalize": 0.03, "other": 0.01}
    served(monkeypatch, [old])
    run = fake_run()
    assert [reader(n).read(run) for n in NEW] == [None] * 5
    # records of both kinds (a log that outlived a reload): the new ones count
    served(monkeypatch, [old, record(102.0, syncs=7, d2h_bytes=28)])
    assert reader("host_syncs_per_query").read(run) == 7
    assert reader("d2h_bytes_per_query").read(run) == 28
    # no record, no window
    served(monkeypatch, [])
    assert [reader(n).read(run) for n in NEW] == [None] * 5
    assert [reader(n).read(types.SimpleNamespace(log=[])) for n in NEW] == (
        [None] * 5)


def test_a_traced_rehearsal_reports_all_five(capsys):
    import run

    rc = run.main(["--workload", "tpch_sf1.q1_s2", "--seed",
                   str(2**31 + 36), "--seconds", "2", "--rehearse",
                   "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    got = {n: result["metrics"][n]["value"] for n in NEW}
    assert all(isinstance(v, (int, float)) and v >= 0 for v in got.values())
    assert got["host_syncs_per_query"] >= 3  # count_valid, result, snapshot
    assert 0 < got["sync_wait_ms"]
    assert got["host_work_ms"] > 0
    # the answer's padded columns and mask, and a few bytes a read
    assert got["d2h_bytes_per_query"] > 4 * got["host_syncs_per_query"] * 0.5
    # the warm-up read the table: the window finds it in the scan cache
    assert got["h2d_bytes_per_query"] == 0
