"""TPC-H Q18 over the deployment ``tpch_q18_sf1`` at a small size.

(a) the generator's contract (``benchmarks/datagen/tpch_q18.py``): the
tables of ``datagen/tpch.py`` and clause 4.2.3's ``c_name``; (b) the
one-shot and the served answer equal the benchmark's plain reference, for a
QUANTITY that keeps orders and for one that keeps none (an empty frame with
the answer's columns); (c) a group-by of about four rows a group whose
partial and final aggregates both merge, with the merge thresholds lowered,
against pandas, and the ``agg_merge_slots`` counter against the merges'
shapes."""

import os
import sys
import types

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.executors.sql_execs import FinalAggExecutor, PartialAggExecutor
from quokka_tpu.obs import querylog
from quokka_tpu.service import QueryService
from quokka_tpu import config

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SEED, SF = 38, 0.02
KEEPS = 280   # keeps 14 orders of this seed's 3,000 at SF 0.02
NONE = 350    # 7 lines of 50 at most: no order passes
ANSWER = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice",
          "sum_qty"]
SORT = "groupby=sort,join_build=sort"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark's own files for this deployment, read and not touched:
    its generator, its query and the comparison that decides ``correct``;
    the tables as Parquet and as frames."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, tables

        conf = spec.load_json(os.path.join(BENCH, "configs",
                                           "tpch_q18_sf1.json"))
        gen = spec.load_module("datagen", conf["datagen"]["module"])
        base = spec.load_module("datagen", "tpch")
        q18 = spec.load_module("queries", "q18")
    finally:
        sys.path.remove(BENCH)
    made = gen.generate(SEED, SF)
    paths = tables.ensure(str(tmp_path_factory.mktemp("q18") / "t"),
                          conf["datagen"]["module"], {"sf": SF}, SEED)
    return types.SimpleNamespace(
        conf=conf, gen=gen, base=base, q18=q18, check=check, tables=made,
        paths=paths, frames={t: made[t].to_pandas() for t in made})


@pytest.fixture(scope="module")
def service():
    svc = QueryService(pool_size=2)
    yield svc
    svc.shutdown()


def _context(conf):
    svc = conf["service"]
    return QuokkaContext(io_channels=svc["io_channels"],
                         exec_channels=svc["exec_channels"])


# -- (a) the generator's contract --------------------------------------------


def test_tables_are_tpch_py_s_with_clause_4_2_3_names(bench):
    base = bench.base.generate(SEED, SF)
    assert bench.tables["lineitem"].equals(base["lineitem"])
    assert bench.tables["orders"].equals(base["orders"])
    customer = bench.tables["customer"]
    assert customer.drop_columns(["c_name"]).equals(base["customer"])
    names = bench.frames["customer"].c_name
    keys = bench.frames["customer"].c_custkey
    assert names.iloc[0] == "Customer#000000001"
    assert (names == "Customer#" + keys.astype(str).str.zfill(9)).all()
    assert bench.conf["datagen"]["rehearsal_args"] == {"sf": 0.1}


# -- (b) the answer against the plain reference ------------------------------


def _answer(bench, service, quantity, served):
    stream = bench.q18.build(_context(bench.conf), bench.paths,
                             {"quantity": quantity})
    if served:
        return service.submit(stream).to_df(timeout=600)
    return stream.collect()


def _judge(bench, got, ref):
    q18 = bench.q18
    numbers, _ = bench.check.compare([got], ref, q18.SORT_KEYS, q18.EXACT)
    assert bench.check.judge(numbers, q18.LIMITS), numbers


@pytest.mark.parametrize("strategy", ["default", SORT])
@pytest.mark.parametrize("served", [True, False], ids=["served", "one_shot"])
def test_q18_equals_the_reference(served, strategy, bench, service,
                                  monkeypatch):
    if strategy != "default":
        monkeypatch.setenv("QK_KERNEL_STRATEGY", strategy)
    got = _answer(bench, service, KEEPS, served)
    ref = bench.q18.reference(bench.paths, {"quantity": KEEPS})
    assert list(got.columns) == ANSWER
    assert len(ref) >= 5, "a QUANTITY that keeps a handful of orders"
    _judge(bench, got, ref)
    # the query's own order: o_totalprice descending
    assert list(got.o_orderkey) == list(ref.o_orderkey)
    assert got.o_totalprice.is_monotonic_decreasing


@pytest.mark.parametrize("served", [True, False], ids=["served", "one_shot"])
def test_a_quantity_that_keeps_no_order_gives_an_empty_answer(
        served, bench, service):
    got = _answer(bench, service, NONE, served)
    assert len(bench.q18.reference(bench.paths, {"quantity": NONE})) == 0
    assert got is not None and len(got) == 0
    assert list(got.columns) == ANSWER


def test_least_bytes_count_lineitem_twice(bench):
    rows = {t: len(bench.frames[t]) for t in ("lineitem", "orders",
                                              "customer")}
    assert bench.q18.least_bytes(bench.paths) == 4 * (
        2 * 2 * rows["lineitem"] + 4 * rows["orders"]
        + 2 * rows["customer"]) + 100 * 6 * 4


# -- (c) a many-groups aggregate whose partials and finals merge -------------


@pytest.fixture(scope="module")
def many_groups(tmp_path_factory):
    """300,000 rows of about 4 a group, in row groups of 65,536 rows: a
    scan batch is too large to be coalesced with another, so each partial
    aggregate sorts one batch's padded slots."""
    r = np.random.default_rng(38)
    n = 300_000
    frame = pd.DataFrame({"k": r.integers(0, n // 4, n).astype(np.int64),
                          "v": r.integers(1, 51, n).astype(np.float64)})
    path = str(tmp_path_factory.mktemp("groups") / "t.parquet")
    pq.write_table(pa.Table.from_pandas(frame, preserve_index=False), path,
                   row_group_size=1 << 16)
    batches = [min(1 << 16, n - i) for i in range(0, n, 1 << 16)]
    return path, frame, batches


def _grouped(path, lowered, monkeypatch):
    if lowered:
        monkeypatch.setattr(PartialAggExecutor, "MERGE_EVERY", 2)
        monkeypatch.setattr(FinalAggExecutor, "MERGE_EVERY", 2)
    ctx = QuokkaContext(io_channels=3, exec_channels=2)
    svc = QueryService(pool_size=2)
    try:
        handle = svc.submit(ctx.read_parquet(path).groupby("k")
                            .agg_sql("sum(v) as s"))
        got = handle.to_df(timeout=600)
    finally:
        svc.shutdown()
    rec = querylog.records()[-1]
    assert rec["q"] == handle.query_id
    return got, rec


@pytest.mark.parametrize("strategy", ["default", SORT])
def test_lowered_thresholds_merge_in_both_aggregators(strategy, many_groups,
                                                      monkeypatch):
    if strategy != "default":
        monkeypatch.setenv("QK_KERNEL_STRATEGY", strategy)
    path, frame, batches = many_groups
    exp = frame.groupby("k").v.sum()
    merges = {}
    for lowered in (False, True):
        got, rec = _grouped(path, lowered, monkeypatch)
        got = got.set_index("k").s.sort_index()
        assert len(got) == len(exp) > 70_000
        np.testing.assert_array_equal(got.index, exp.index)
        np.testing.assert_allclose(got.to_numpy(), exp.to_numpy())
        merges[lowered] = rec["agg_merges_compiled"] + rec["agg_merges_general"]
        # every slot a merge sorted is a group-by slot; the partials' are
        # the scan batches' padded lengths
        partials = sum(config.bucket_size(b) for b in batches)
        assert rec["agg_merge_slots"] == rec["groupby_sort_slots"] - partials
        assert rec["agg_merge_slots"] > 0
    # two partial channels and two final channels each fold once at the end;
    # the lowered thresholds fold before it too
    assert merges[True] > merges[False] >= 4


# -- (d) the program set does not follow arrival -----------------------------


def test_later_requests_ask_the_compile_plane_for_nothing(bench, monkeypatch,
                                                          tmp_path):
    """Lineitem in row groups of 65,536 rows (each scan batch alone at a
    partial aggregate and at a probe), two requests to warm up, then two
    clients at once: every later request finds every program it asks for,
    whatever order its batches, partials and builds arrive in."""
    import threading

    monkeypatch.setenv("QK_KERNEL_STRATEGY", SORT)
    paths = dict(bench.paths, lineitem=str(tmp_path / "lineitem.parquet"))
    pq.write_table(bench.tables["lineitem"], paths["lineitem"],
                   row_group_size=1 << 16)
    svc = QueryService(pool_size=2)
    answers, t0 = [], (querylog.records()[-1]["done"] if querylog.size()
                       else 0.0)

    def client(n):
        for _ in range(n):
            answers.append(svc.submit(bench.q18.build(
                _context(bench.conf), paths, {"quantity": KEEPS})).to_df(
                    timeout=600))

    try:
        client(2)  # the warm-up: every program of the plan
        threads = [threading.Thread(target=client, args=(3,))
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = querylog.records(since=t0)
    finally:
        svc.shutdown()
    assert len(recs) == 8 and all(r["status"] == "done" for r in recs)
    late = {r["q"]: r["compiled"] for r in recs[2:] if r["compiled"]}
    assert not late, late
    # the HAVING's few orders build both joins: one gather a probe row
    assert all(r["join_probe_search"] == 0 and r["join_probe_direct"] > 0
               for r in recs)
    ref = bench.q18.reference(bench.paths, {"quantity": KEEPS})
    for got in answers:
        _judge(bench, got, ref)
