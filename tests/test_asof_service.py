"""The tick asof join through the served path (ISSUE 28): the cell's plan
(benchmarks/queries/asof.py ``build``) over the configuration's rehearsal
size, submitted to a ``QueryService`` as the benchmark submits it.  A second
request of one process asks the compile plane for no program of the join's,
its record carries the executor's four counters and the match's two, and a
killed exec channel replays to the same answer from checkpoints that do not
keep the buffers' padding."""

import importlib.util
import json
import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.executors.ts_execs import SortedAsofExecutor
from quokka_tpu.obs import querylog
from quokka_tpu.service import QueryService

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
AGGREGATORS = ("fused_concat", "partial_agg_small")
COUNTERS = ("asof_flushes", "asof_probe_rows", "asof_probe_padded",
            "asof_quote_padded")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ticks(tmp_path_factory):
    """The configuration's tables at its rehearsal size, as Parquet with
    several row groups a table (a cell's table has one per 1 << 20 rows)."""
    with open(os.path.join(BENCH, "configs", "ticks_1d.json")) as f:
        conf = json.load(f)
    tables = _load(os.path.join(BENCH, "datagen", "ticks.py"),
                   "bench_datagen_ticks").generate(
        7, **conf["datagen"]["rehearsal_args"])
    d = tmp_path_factory.mktemp("ticks")
    paths = {}
    for name, table in tables.items():
        paths[name] = str(d / f"{name}.parquet")
        pq.write_table(table, paths[name], row_group_size=8192)
    t, q = (tables[n].to_pandas() for n in ("trades", "quotes"))
    j = pd.merge_asof(t, q, on="time", by="symbol").dropna(subset=["bid"])
    j["notional"] = j.bid * j["size"]
    exp = j.groupby("symbol").agg(total=("notional", "sum"),
                                  n=("notional", "size")).reset_index()
    return {"paths": paths, "expected": exp, "service": conf["service"],
            "trades": len(t)}


def _stream(ticks, **exec_config):
    svc = ticks["service"]
    ctx = QuokkaContext(io_channels=svc["io_channels"],
                        exec_channels=svc["exec_channels"])
    for key, value in exec_config.items():
        ctx.set_config(key, value)
    t = ctx.read_sorted_parquet(ticks["paths"]["trades"], sorted_by="time")
    q = ctx.read_sorted_parquet(ticks["paths"]["quotes"], sorted_by="time")
    return (t.join_asof(q, on="time", by="symbol")
            .with_columns_sql("bid * size as notional")
            .groupby("symbol")
            .agg_sql("sum(notional) as total, count(*) as n"))


def _check(got, ticks):
    got = got.sort_values("symbol").reset_index(drop=True)
    exp = ticks["expected"]
    assert list(got.symbol) == list(exp.symbol)
    assert (got.n.to_numpy() == exp.n.to_numpy()).all()
    assert float(np.max(np.abs(got.total - exp.total) / exp.total)) < 4e-5


@pytest.fixture
def small_flushes(monkeypatch):
    """TPU kernel strategies, and thresholds at which the rehearsal size
    flushes several times a channel."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY",
                       "asof=sort,groupby=sort,join_build=sort")
    monkeypatch.setattr(SortedAsofExecutor, "MIN_FLUSH_ROWS", 1024)
    monkeypatch.setattr(SortedAsofExecutor, "COALESCE_ROWS", 256)


def test_second_request_asks_for_no_program_of_the_join(ticks, small_flushes):
    svc = QueryService(pool_size=ticks["service"]["pool_size"])
    try:
        t0 = querylog.records()[-1]["done"] if querylog.size() else 0.0
        for _ in range(2):
            _check(svc.submit(_stream(ticks)).to_df(timeout=300), ticks)
        first, second = querylog.records(since=t0)[-2:]
    finally:
        svc.shutdown()
    # at this size the join's chunks are small enough (at most 65,536 padded
    # rows, ops/aggtail.py SMALL_ROWS) for PartialAggExecutor to concatenate
    # the ones a dispatch found ready: those two kinds follow arrival here,
    # and not at the cell's size, whose chunks of 524,288 slots stay apart
    late = [c for c in second["compiled"] if c[0] not in AGGREGATORS]
    assert not late, late
    for rec in (first, second):
        assert all(rec[k] > 0 for k in COUNTERS), {k: rec[k] for k in COUNTERS}
        # every flush was the merge's (the TPU's pick), none the search's
        assert rec["asof_match_sort"] == rec["asof_flushes"]
        assert rec["asof_match_search"] == 0
        # every trade went through one chunk probe, matched or dropped
        assert rec["asof_probe_rows"] == ticks["trades"]
        # chunks of one size, each against the whole quote buffer
        slots, per_flush = (rec["asof_probe_padded"] // rec["asof_flushes"],
                            rec["asof_quote_padded"] // rec["asof_flushes"])
        assert rec["asof_probe_padded"] == slots * rec["asof_flushes"]
        assert rec["asof_quote_padded"] == per_flush * rec["asof_flushes"]
        assert slots == 1024 and per_flush == 65536
        assert rec["asof_flushes"] >= -(-ticks["trades"] // slots)


def test_killed_channel_replays_to_the_same_answer(ticks, small_flushes,
                                                   tmp_path, monkeypatch):
    # actors: 0 trades, 1 quotes, 2 the asof join.  The engine asserts
    # re_emitted == emitted while it replays the killed channel's tape from
    # a checkpoint whose buffers come back compacted
    restored = []
    restore = SortedAsofExecutor.restore
    monkeypatch.setattr(
        SortedAsofExecutor, "restore",
        lambda self, state: (restored.append(state is not None),
                             restore(self, state))[1])
    ft = {"fault_tolerance": True, "checkpoint_interval": 2}
    svc = QueryService(pool_size=ticks["service"]["pool_size"],
                       spill_dir=str(tmp_path), exec_config=dict(ft))
    try:
        stream = _stream(ticks, inject_failure={"after_tasks": 14,
                                                "channels": [(2, 0)]}, **ft)
        _check(svc.submit(stream).to_df(timeout=300), ticks)
    finally:
        svc.shutdown()
    assert any(restored), "the kill did not restore a checkpointed state"
