"""The h2oai db-benchmark's group-by questions (ISSUE 32) through the served
path against a pandas float64 reference, and property P of the general
group-by: for fixed data, the set of compile-plane keys the two aggregate
executors ask for in q5 does not depend on the order or the grouping per
``execute`` in which the scan batches arrive.

The table is the benchmark's own generator's (``benchmarks/datagen/h2o.py``)
at a test size.  At n = 1e5, k = 100 a query is four batches of 32,768 rows;
at n = 2e5, k = 2 (the benchmark's rehearsal size) it is one batch whose
small-group keys keep 1e5 distinct values, so the partials' merges sum over
65,536 padded rows and take ``ops/aggtail.py``'s general path."""

import functools
import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext, config, sqlparse
from quokka_tpu.executors.sql_execs import FinalAggExecutor, PartialAggExecutor
from quokka_tpu.expression import Alias
from quokka_tpu.obs import querylog
from quokka_tpu.ops import bridge, kernels, sigkey
from quokka_tpu.ops.batch import DeviceBatch
from quokka_tpu.ops.expr_compile import plan_aggregation
from quokka_tpu.service import QueryService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROW_GROUP = 1 << 15
CHANNELS = 2

# question -> (keys, the program's aggregates, pandas' named aggregations)
QUESTIONS = {
    "q1": (["id1"], "sum(v1) as v1", {"v1": ("v1", "sum")}),
    "q2": (["id1", "id2"], "sum(v1) as v1", {"v1": ("v1", "sum")}),
    "q3": (["id3"], "sum(v1) as v1, avg(v3) as v3",
           {"v1": ("v1", "sum"), "v3": ("v3", "mean")}),
    "q4": (["id4"], "avg(v1) as v1, avg(v2) as v2, avg(v3) as v3",
           {"v1": ("v1", "mean"), "v2": ("v2", "mean"), "v3": ("v3", "mean")}),
    "q5": (["id6"], "sum(v1) as v1, sum(v2) as v2, sum(v3) as v3",
           {"v1": ("v1", "sum"), "v2": ("v2", "sum"), "v3": ("v3", "sum")}),
}
CASES = [(q, 100_000, 100, ROW_GROUP) for q in QUESTIONS] + [
    ("q3", 200_000, 2, 1 << 20), ("q5", 200_000, 2, 1 << 20)]


@functools.lru_cache(maxsize=1)
def _generator():
    path = os.path.join(ROOT, "benchmarks", "datagen", "h2o.py")
    spec = importlib.util.spec_from_file_location("bench_datagen_h2o", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def g1(tmp_path_factory):
    """(n, k, rows a row group) -> (Parquet path, the table as a frame),
    made once each."""
    made = {}

    def get(n, k, row_group=ROW_GROUP):
        if (n, k, row_group) not in made:
            table = _generator().generate(32, n, k)["g1"]
            path = str(tmp_path_factory.mktemp("h2o") / f"g1_{n}_{k}.parquet")
            pq.write_table(table, path, row_group_size=row_group)
            made[n, k, row_group] = (path, table.to_pandas())
        return made[n, k, row_group]

    return get


@pytest.fixture(scope="module")
def service():
    svc = QueryService(pool_size=2)
    yield svc
    svc.shutdown()


def test_generator_has_the_sources_shapes():
    t = _generator().generate(1, 20_000, 100)["g1"]
    assert t.column_names == ["id1", "id2", "id3", "id4", "id5", "id6",
                              "v1", "v2", "v3"]
    df = t.to_pandas()
    assert df.id1.str.fullmatch(r"id\d{3}").all()
    assert df.id3.str.fullmatch(r"id\d{10}").all()
    assert df.id1.nunique() == 100 and df.id3.nunique() <= 200
    for col, low, high in (("id4", 1, 100), ("id5", 1, 100), ("id6", 1, 200),
                           ("v1", 1, 5), ("v2", 1, 15)):
        assert str(df[col].dtype) == "int32"
        assert df[col].min() == low and df[col].max() == high
    assert df.v3.between(0, 100).all()
    assert (df.v3 == df.v3.round(6)).all() and not df.isna().any().any()
    again = _generator().generate(1, 20_000, 100)["g1"]
    assert again.equals(t), "the same seed gives the same rows"


@pytest.mark.parametrize("question,n,k,row_group", CASES,
                         ids=[f"{q}-n{n}-k{k}" for q, n, k, _ in CASES])
def test_question_matches_pandas_through_the_service(question, n, k,
                                                     row_group, g1, service):
    path, frame = g1(n, k, row_group)
    keys, aggs, named = QUESTIONS[question]
    ctx = QuokkaContext(io_channels=3, exec_channels=CHANNELS)
    stream = ctx.read_parquet(path).groupby(keys).agg_sql(aggs)
    got = service.submit(stream).to_df(timeout=600)
    exp = frame.astype({"v1": np.int64, "v2": np.int64}).groupby(
        keys).agg(**named).reset_index()
    got = got.sort_values(keys).reset_index(drop=True)
    exp = exp.sort_values(keys).reset_index(drop=True)
    assert list(got.columns) == list(exp.columns)
    assert len(got) == len(exp), "every group present, none twice"
    for key in keys:
        assert (got[key].to_numpy() == exp[key].to_numpy()).all()
    for name, (_, how) in named.items():
        if how == "sum" and name != "v3":
            np.testing.assert_array_equal(got[name].to_numpy(),
                                          exp[name].to_numpy())
        else:
            np.testing.assert_allclose(got[name].to_numpy(dtype=np.float64),
                                       exp[name].to_numpy(), rtol=1e-9)
    rec = querylog.records()[-1]
    assert rec["status"] == "done"
    if n // k > 65_536:
        # 86 K groups in the one batch's partial: its channel's merge and
        # both final channels' lie beyond the compiled tail's bound
        assert rec["agg_merges_general"] >= 1 + CHANNELS
        assert rec["groupby_groups_out"] >= got.shape[0]
        assert rec["groupby_sort_slots"] >= config.bucket_size(n)


def test_q5_spans_and_counters_in_the_ring_and_the_record(g1, service,
                                                          monkeypatch):
    """The rehearsal's shape under the TPU's strategy: one 262,144-slot
    batch through ``fused_groupby``, its channel's merge over the partial's
    131,072 compacted slots, and the two final channels' over their half
    of the groups, 65,536 slots each."""
    from quokka_tpu import obs

    monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=sort")
    path, _ = g1(200_000, 2, 1 << 20)
    events = obs.RECORDER.snapshot()
    seq = events[-1][0] if events else -1
    ctx = QuokkaContext(io_channels=3, exec_channels=CHANNELS)
    keys, aggs, _ = Q5
    handle = service.submit(ctx.read_parquet(path).groupby(keys).agg_sql(aggs))
    groups = len(handle.to_df(timeout=600))
    rec = querylog.records()[-1]
    assert rec["q"] == handle.query_id and rec["status"] == "done"
    assert rec["groupby_sort_slots"] == 262_144 + 131_072 + 2 * 65_536
    assert rec["agg_merges_general"] == 3 and rec["agg_merges_compiled"] == 2
    # every emission counted once, from device counts read at the snapshot:
    # the partial's and its merge's groups, and the two finals' halves
    assert rec["groupby_groups_out"] == 3 * groups
    assert sum(rec[k] for k in (
        "runtime.dispatch_self", "executors.exec_self", "runtime.push",
        "io.read", "emit.d2h", "compile.acquire", "other")) == pytest.approx(
            rec["task_s"])
    spans = [ev for ev in obs.RECORDER.snapshot(since=seq)
             if ev[2] == "span" and ev[3].startswith("groupby.")]
    assert all(ev[6]["q"] == handle.query_id for ev in spans)
    parents = {}
    for ev in spans:
        parents.setdefault(ev[3], set()).add(ev[6]["p"])
    assert parents == {
        "groupby.partial": {"exec.PartialAggExecutor"},
        "groupby.merge": {"done.PartialAggExecutor", "done.FinalAggExecutor"},
        "groupby.final": {"done.FinalAggExecutor"}}


# -- property P: the program set does not follow arrival ---------------------

KINDS = ("partial_agg", "partial_agg_small", "agg_recombine",
         "agg_final_tail", "fused_concat", "compact_idx", "gather",
         "partition_ids", "split_masks")
Q5 = QUESTIONS["q5"]


def _scan_batches(path):
    """The table's row groups as device batches, dealt to the partial
    aggregate's channels as the engine deals them (row group -> io channel
    of 3 -> exec channel of 2 by the passthrough edge)."""
    pf = pq.ParquetFile(path)
    out = [[] for _ in range(CHANNELS)]
    for i in range(pf.num_row_groups):
        table = pf.read_row_group(i, columns=["id6", "v1", "v2", "v3"])
        out[(i % 3) % CHANNELS].append(bridge.arrow_to_device(table))
    return out


def _fresh(b: DeviceBatch) -> DeviceBatch:
    """A batch as the engine hands it over: its own object, count unread."""
    return DeviceBatch(dict(b.columns), b.valid, None, b.sorted_by,
                       b.nrows_dev)


def _run_q5(batches, order, group):
    """q5's two aggregate executors per channel under one arrival schedule:
    ``order(parts)`` reorders a channel's scan batches, ``group`` is how many
    an ``execute`` holds.  Returns the answer as a frame."""
    keys, aggs, _ = Q5
    exprs = [e if isinstance(e, Alias) else Alias(e, f"col{i}")
             for i, e in enumerate(sqlparse.parse_select_list(aggs))]
    plan = plan_aggregation(exprs)
    partials = []
    for ch in range(CHANNELS):
        ex = PartialAggExecutor(keys, plan)
        parts = order(list(batches[ch]))
        for i in range(0, len(parts), group):
            out = ex.execute([_fresh(b) for b in parts[i:i + group]], 0, ch)
            assert out is None  # 0.57 groups a row: it aggregates
        partials.append(ex.done(ch))
    finals = [FinalAggExecutor(keys, plan) for _ in range(CHANNELS)]
    for part in partials:
        pids = kernels.partition_ids(part, keys, CHANNELS)
        for ch, piece in enumerate(
                kernels.split_by_partition(part, pids, CHANNELS)):
            finals[ch].execute([piece], 0, ch)
    frames = [bridge.device_to_arrow(kernels.compact(f.done(ch))).to_pandas()
              for ch, f in enumerate(finals)]
    return pd.concat(frames).sort_values(keys).reset_index(drop=True)


def _keys():
    return {kind: set(sigkey.ledger_keys(kind)) for kind in KINDS}


SCHEDULES = {
    "in_order_one_a_dispatch": (lambda parts: parts, 1),
    "reversed_three_a_dispatch": (lambda parts: parts[::-1], 3),
    "in_order_all_at_once": (lambda parts: parts, 8),
    "interleaved_two_a_dispatch": (lambda parts: parts[1::2] + parts[::2], 2),
}


@pytest.mark.parametrize("name", list(SCHEDULES)[1:])
def test_q5_asks_for_the_same_programs_under_every_arrival(name, g1,
                                                           monkeypatch):
    """13 batches of 32,768 rows into 26,562 groups, 9 on one channel and 4
    on the other, a merge every 4 partials (131,072 padded rows: the general
    path): under the first schedule and under ``name`` the executors answer
    alike and ask for the same keys."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=sort")
    monkeypatch.setattr(PartialAggExecutor, "MERGE_EVERY", 4)
    path, frame = g1(425_000, 16)
    batches = _scan_batches(path)
    assert sorted(len(b) for b in batches) == [4, 9]
    sigkey.reset_ledger()
    first_answer = _run_q5(batches, *SCHEDULES["in_order_one_a_dispatch"])
    first = _keys()
    got = _run_q5(batches, *SCHEDULES[name])
    later = _keys()
    new = {k: sorted(later[k] - first[k], key=repr) for k in KINDS
           if later[k] - first[k]}
    assert not new, f"{name} asked for programs outside the first set: {new}"
    assert first["partial_agg"] and first["fused_concat"]
    exp = frame.astype({"v1": np.int64, "v2": np.int64}).groupby(
        "id6")[["v1", "v2", "v3"]].sum().reset_index()
    for answer in (first_answer, got):
        assert len(answer) == len(exp)
        for c in ("id6", "v1", "v2"):
            np.testing.assert_array_equal(answer[c].to_numpy(),
                                          exp[c].to_numpy())
        np.testing.assert_allclose(answer.v3.to_numpy(), exp.v3.to_numpy(),
                                   rtol=1e-9)
