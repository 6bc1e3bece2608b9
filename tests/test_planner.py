"""Cost-based planner tests (planner/cost.py, planner/decide.py).

Three layers:
- cost-model precedence: measured cardprofile figures beat catalog samples
  beat size_hint() guesses, and derived estimates carry the weakest input
  basis so decisions stay auditable;
- the plan flip: the same query plans broadcast on a cold profile and
  partition once the (injected) cardprofile says the build side is big —
  recorded in the decision log with the measured figures and rendered by
  explain's planner-decision section;
- QK026 known-answer fixtures: adapt_salt on anything but an inner,
  non-broadcast, unordered hash join is flagged, as is a user column
  colliding with the reserved salt name.
"""

import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from quokka_tpu import logical, optimizer
from quokka_tpu.analysis import planck
from quokka_tpu.context import QuokkaContext
from quokka_tpu.expression import col, date
from quokka_tpu.obs import explain
from quokka_tpu.planner import cost, decide

import tpch_data


@pytest.fixture(scope="module")
def pq_env(tmp_path_factory):
    root = tmp_path_factory.mktemp("planner")
    r = np.random.default_rng(7)
    n = 20_000
    fact = pa.table({
        "fk": r.integers(0, 100, n).astype(np.int64),
        "x": r.integers(0, 1000, n).astype(np.int64),
    })
    dim = pa.table({
        "pk": np.arange(100, dtype=np.int64),
        "w": np.arange(100, dtype=np.int64) * 10,
    })
    fp, dp = str(root / "fact.parquet"), str(root / "dim.parquet")
    pq.write_table(fact, fp, row_group_size=2048)
    pq.write_table(dim, dp)
    return fp, dp


def _subplan(stream):
    sub, _ = stream.ctx._copy_subgraph(stream.node_id)
    sink = logical.SinkNode([stream.node_id], sub[stream.node_id].schema)
    sid = max(sub) + 1
    sub[sid] = sink
    return sub, sid


def _source_ids(sub):
    return [nid for nid, n in sub.items()
            if isinstance(n, logical.SourceNode)]


def _joins(sub, sid):
    return [sub[n] for n in optimizer._reachable(sub, sid)
            if isinstance(sub[n], logical.JoinNode)]


class _AnySig:
    """Profile stub answering every source signature with one record —
    sidesteps recomputing post-pushdown signatures in tests."""

    def __init__(self, rec):
        self.rec = rec

    def get(self, _sig, default=None):
        return dict(self.rec)


# -- cost-model precedence ----------------------------------------------------


class TestPrecedence:
    def test_measured_beats_everything(self, pq_env):
        fp, _ = pq_env
        ctx = QuokkaContext()
        sub, sid = _subplan(ctx.read_parquet(fp))
        (src,) = _source_ids(sub)
        model = cost.CostModel(
            sub, catalog=optimizer._get_catalog(),
            profile=_AnySig({"rows": 777, "bytes": 6216}))
        est = model.estimate(src)
        assert est.basis == cost.BASIS_MEASURED
        assert est.rows == 777 and est.bytes == 6216

    def test_sampled_beats_hint(self, pq_env):
        fp, _ = pq_env
        ctx = QuokkaContext()
        sub, sid = _subplan(ctx.read_parquet(fp))
        (src,) = _source_ids(sub)
        est = cost.CostModel(sub, catalog=optimizer._get_catalog(),
                             profile={}).estimate(src)
        assert est.basis == cost.BASIS_SAMPLED
        assert est.rows == pytest.approx(20_000, rel=0.05)

    def test_hint_is_the_floor(self, pq_env):
        fp, _ = pq_env
        ctx = QuokkaContext()
        sub, sid = _subplan(ctx.read_parquet(fp))
        (src,) = _source_ids(sub)
        est = cost.CostModel(sub, catalog=None, profile={}).estimate(src)
        assert est.basis == cost.BASIS_HINT
        assert est.rows > 0  # synthesized from size_hint() bytes

    def test_filter_keeps_basis_and_reduces(self, pq_env):
        fp, _ = pq_env
        ctx = QuokkaContext(optimize=False)
        q = ctx.read_parquet(fp).filter(col("x") > 10)
        sub, sid = _subplan(q)
        (src,) = _source_ids(sub)
        model = cost.CostModel(sub, catalog=None,
                               profile=_AnySig({"rows": 1000, "bytes": 8000}))
        (flt,) = [nid for nid, n in sub.items()
                  if isinstance(n, logical.FilterNode)]
        est = model.estimate(flt)
        assert est.basis == cost.BASIS_MEASURED
        assert est.rows == pytest.approx(1000 * cost.FILTER_SELECTIVITY)

    def test_join_carries_weakest_input_basis(self, pq_env):
        fp, dp = pq_env
        ctx = QuokkaContext(optimize=False)
        q = ctx.read_parquet(fp).join(ctx.read_parquet(dp),
                                      left_on="fk", right_on="pk")
        sub, sid = _subplan(q)
        (join,) = [nid for nid, n in sub.items()
                   if isinstance(n, logical.JoinNode)]
        # no catalog, no profile: both inputs are hint-basis guesses
        est = cost.CostModel(sub, catalog=None, profile={}).estimate(join)
        assert est.basis == cost.BASIS_HINT
        assert cost._weaker(cost.BASIS_MEASURED, cost.BASIS_HINT) \
            == cost.BASIS_HINT
        assert cost._weaker(cost.BASIS_MEASURED, cost.BASIS_SAMPLED) \
            == cost.BASIS_SAMPLED

    def test_source_signature_is_plan_independent(self, pq_env):
        fp, _ = pq_env
        ctx = QuokkaContext()
        sub, _ = _subplan(ctx.read_parquet(fp))
        (src,) = _source_ids(sub)
        node = sub[src]
        a = cost.source_signature(node.reader, node.predicate,
                                  node.projection)
        b = cost.source_signature(node.reader, node.predicate,
                                  node.projection)
        assert a == b
        assert cost.source_signature(node.reader, col("x") > 5,
                                     node.projection) != a


# -- the plan flip ------------------------------------------------------------


class TestPlanFlip:
    def _optimize(self, pq_env, monkeypatch, profile, how="inner"):
        from quokka_tpu.obs import opstats

        monkeypatch.setattr(opstats, "measured_sources", lambda: profile)
        fp, dp = pq_env
        ctx = QuokkaContext()
        q = ctx.read_parquet(fp).join(ctx.read_parquet(dp),
                                      left_on="fk", right_on="pk", how=how)
        sub, sid = _subplan(q)
        decide.begin_decisions()
        optimizer.optimize(sub, sid)
        return _joins(sub, sid), decide.take_decisions()

    def test_cold_broadcasts_warm_partitions(self, pq_env, monkeypatch):
        monkeypatch.setenv("QK_BROADCAST_BYTES", str(1 << 20))
        # cold: the 100-row dim samples far under the legacy row threshold
        joins, cold_log = self._optimize(pq_env, monkeypatch, {})
        assert joins and joins[0].broadcast
        cold = [d for d in cold_log if d["kind"] == "broadcast"]
        assert cold and cold[0]["choice"] == "broadcast"
        assert cold[0]["basis"] != cost.BASIS_MEASURED
        # warm: a measured profile says the build side is 4 MiB — over the
        # byte threshold, the SAME query must flip to partition
        joins, warm_log = self._optimize(
            pq_env, monkeypatch,
            _AnySig({"rows": 500_000, "bytes": 4 << 20}))
        assert joins and not joins[0].broadcast
        warm = [d for d in warm_log if d["kind"] == "broadcast"]
        assert warm and warm[0]["choice"] == "partition"
        assert warm[0]["basis"] == cost.BASIS_MEASURED
        assert warm[0]["build_bytes"] > warm[0]["threshold_bytes"]
        # the flip is render-able: explain's decision line carries the
        # measured figures that drove it
        line = explain._decision_line(warm[0])
        assert "partition" in line and "basis=measured" in line

    def test_measured_under_threshold_stays_broadcast(self, pq_env,
                                                      monkeypatch):
        monkeypatch.setenv("QK_BROADCAST_BYTES", str(1 << 20))
        joins, log = self._optimize(
            pq_env, monkeypatch, _AnySig({"rows": 100, "bytes": 800}))
        assert joins and joins[0].broadcast
        rec = [d for d in log if d["kind"] == "broadcast"][0]
        assert rec["basis"] == cost.BASIS_MEASURED
        assert rec["choice"] == "broadcast"


    @pytest.mark.parametrize("how", ["inner", "semi", "anti", "left"])
    @pytest.mark.parametrize("side", ["under", "over"])
    def test_measured_build_follows_the_byte_rule(self, pq_env, monkeypatch,
                                                  how, side):
        """The choice is the measured build's bytes against
        QK_BROADCAST_BYTES and nothing else: no estimate of seconds enters
        it, and the record says so."""
        limit = 1 << 20
        monkeypatch.setenv("QK_BROADCAST_BYTES", str(limit))
        nbytes = limit if side == "under" else limit + 1
        joins, log = self._optimize(
            pq_env, monkeypatch, _AnySig({"rows": 1000, "bytes": nbytes}),
            how=how)
        assert joins and joins[0].broadcast is (side == "under")
        rec = [d for d in log if d["kind"] == "broadcast"]
        assert rec == [{
            "kind": "broadcast", "node": joins[0].describe(),
            "choice": "broadcast" if side == "under" else "partition",
            "basis": cost.BASIS_MEASURED, "build_rows": 1000,
            "build_bytes": nbytes, "threshold_bytes": limit}]


# -- the TPC-H flip: a recorded cardprofile flips Q3's orders build -----------


@pytest.fixture(scope="module")
def q3_paths(tmp_path_factory):
    root = tmp_path_factory.mktemp("planner_q3")
    tables = tpch_data.generate(sf=0.01, seed=7)
    # cluster orders by o_orderdate: the catalog's head-rows sample then
    # only ever sees the earliest dates, so a late-date predicate samples
    # near zero rows while actually keeping a large slice of the table —
    # the classic misestimate only a measured profile corrects
    orders = tables["orders"].sort_by([("o_orderdate", "ascending")])
    paths = {}
    for name, table in (("lineitem", tables["lineitem"]),
                        ("orders", orders),
                        ("customer", tables["customer"])):
        p = str(root / f"{name}.parquet")
        pq.write_table(table, p, row_group_size=4096)
        paths[name] = p
    return paths


def _q3(ctx, paths):
    lineitem = ctx.read_parquet(
        paths["lineitem"],
        columns=["l_orderkey", "l_extendedprice", "l_discount"])
    orders = ctx.read_parquet(
        paths["orders"],
        columns=["o_orderkey", "o_custkey", "o_orderdate"],
    ).filter(col("o_orderdate") >= date("1996-01-01"))
    customer = ctx.read_parquet(
        paths["customer"], columns=["c_custkey", "c_mktsegment"],
    ).filter(col("c_mktsegment") == "BUILDING")
    return (
        lineitem.join(orders, left_on="l_orderkey", right_on="o_orderkey")
        .join(customer, left_on="o_custkey", right_on="c_custkey")
        .groupby("l_orderkey")
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue, "
                 "count(*) as n")
    )


def _orders_broadcast_decision(snap):
    return [d for d in (snap or {}).get("planner") or []
            if d.get("kind") == "broadcast" and "o_orderkey" in d["node"]]


class TestTPCHQ3Flip:
    def test_recorded_profile_flips_orders_build(self, q3_paths, tmp_path,
                                                 monkeypatch):
        from quokka_tpu.service import QueryService

        monkeypatch.setenv("QK_CARDPROFILE_DIR", str(tmp_path))
        monkeypatch.setenv("QK_MEMPROFILE_DIR", "")
        monkeypatch.setenv("QK_BROADCAST_BYTES", str(1 << 16))
        with QueryService(pool_size=2) as svc:
            h = svc.submit(_q3(QuokkaContext(), q3_paths))
            cold_t = h.to_arrow(timeout=300)
            cold_snap = h.explain(as_dict=True)
            h = svc.submit(_q3(QuokkaContext(), q3_paths))
            warm_t = h.to_arrow(timeout=300)
            warm_snap = h.explain(as_dict=True)
            warm_text = h.explain()
        cold = _orders_broadcast_decision(cold_snap)
        assert cold, cold_snap.get("planner")
        assert cold[0]["choice"] == "broadcast"
        assert cold[0]["basis"] != cost.BASIS_MEASURED
        warm = _orders_broadcast_decision(warm_snap)
        assert warm, warm_snap.get("planner")
        assert warm[0]["basis"] == cost.BASIS_MEASURED
        assert warm[0]["choice"] == "partition"
        assert warm[0]["build_bytes"] > warm[0]["threshold_bytes"]
        assert "planner decisions:" in warm_text
        assert "basis=measured" in warm_text
        # the flip trades shuffle topology, never the answer
        cs = cold_t.sort_by("l_orderkey")
        ws = warm_t.sort_by("l_orderkey")
        assert cs["l_orderkey"].equals(ws["l_orderkey"])
        assert cs["n"].equals(ws["n"])
        assert np.allclose(cs["revenue"].to_numpy(),
                           ws["revenue"].to_numpy(), rtol=1e-9)


# -- the benchmark's Q3 at its rehearsal size: the plan PR 29 recorded -------

# benchmarks/queries/q3.py over benchmarks/datagen/tpch.py at sf 0.01, seed
# 7, the first parameter set of the seed: the optimized plan's nodes in
# lowering order and the choices behind them, as commit 30d78c1 planned it
Q3_PLAN = [
    "Source(InputParquetDataset, filter=(l_shipdate > date '1995-03-26'))",
    "Source(InputParquetDataset, filter=(o_orderdate < date '1995-03-26'))",
    "Source(InputParquetDataset, filter=(c_mktsegment = 'BUILDING'))",
    "FusedStage(\n"
    "  BroadcastJoin(inner, ['l_orderkey']=['o_orderkey'])\n"
    "  BroadcastJoin(inner, ['o_custkey']=['c_custkey'])\n"
    "  Agg(keys=['l_orderkey', 'o_orderdate', 'o_shippriority'],"
    " out=['revenue'])\n)",
    "TopK(['revenue'], k=10)",
    "Collect",
]
Q3_JOINS = ["BroadcastJoin(inner, ['l_orderkey']=['o_orderkey'])",
            "BroadcastJoin(inner, ['o_custkey']=['c_custkey'])"]
# rows and bytes that run measured for the three scans (its cardprofile)
Q3_MEASURED = {"l_shipdate": (31657, 1114112), "o_orderdate": (7378, 278528),
               "c_mktsegment": (302, 36864)}


@pytest.fixture(scope="module")
def bench_q3(tmp_path_factory):
    """plan() -> (the optimized plan's nodes in lowering order, the decision
    records, the plan), over the benchmark's own files, read and not
    touched."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import loadgen, spec, tables

        config = spec.load_json(
            os.path.join(bench, "configs", "tpch_sf1.json"))
        traffic = spec.load_json(os.path.join(bench, "traffic", "q3_s2.json"))
        gen = config["datagen"]
        paths = tables.ensure(str(tmp_path_factory.mktemp("bench_q3") / "t"),
                              gen["module"], gen["rehearsal_args"], 7)
        q3 = spec.load_module("queries", "q3")
        params = loadgen.plan(traffic, 7)["q3"][0]
    finally:
        sys.path.remove(bench)
    svc = config["service"]

    def plan():
        ctx = QuokkaContext(io_channels=svc["io_channels"],
                            exec_channels=svc["exec_channels"])
        sub, sink = ctx._prepare_plan(q3.build(ctx, paths, params).node_id)
        return ([sub[n].describe() for n in ctx._toposort(sub, sink)],
                decide.take_decisions(), sub)

    return plan


def test_q3_rehearsal_plan_is_the_recorded_one(bench_q3, monkeypatch):
    from quokka_tpu.obs import opstats

    nodes, cold, sub = bench_q3()
    assert nodes == Q3_PLAN
    assert [(d["node"], d["choice"], d["basis"]) for d in cold] == [
        (j, "broadcast", cost.BASIS_SAMPLED) for j in Q3_JOINS]
    # the same plan once the scans have been measured: ordered and chosen
    # by measured rows and bytes, with no key of seconds in any record
    profile = {}
    for n in sub.values():
        if isinstance(n, logical.SourceNode):
            rows, nbytes = next(v for k, v in Q3_MEASURED.items()
                                if k in n.predicate.sql())
            profile[cost.source_signature(
                n.reader, n.predicate, n.projection)] = {
                    "rows": rows, "bytes": nbytes}
    assert len(profile) == 3
    monkeypatch.setattr(opstats, "measured_sources", lambda: profile)
    nodes, warm, _ = bench_q3()
    assert nodes == Q3_PLAN
    assert [d for d in warm if d["kind"] == "broadcast"] == [
        {"kind": "broadcast", "node": j, "choice": "broadcast",
         "basis": cost.BASIS_MEASURED, "build_rows": rows,
         "build_bytes": nbytes, "threshold_bytes": 8 << 20}
        for j, (rows, nbytes) in zip(
            Q3_JOINS, (Q3_MEASURED["o_orderdate"],
                       Q3_MEASURED["c_mktsegment"]))]
    assert not [k for d in warm for k in d
                if k.endswith("_s") or k.endswith("_s_basis")]


# -- QK026: adaptive-exchange legality ----------------------------------------


def _armed_plan(pq_env, monkeypatch):
    monkeypatch.setenv("QK_BROADCAST_BYTES", "1")
    monkeypatch.setattr(optimizer, "BROADCAST_THRESHOLD", 0)
    fp, dp = pq_env
    ctx = QuokkaContext()
    q = ctx.read_parquet(fp).join(ctx.read_parquet(dp),
                                  left_on="fk", right_on="pk")
    sub, sid = _subplan(q)
    optimizer.optimize(sub, sid)
    joins = _joins(sub, sid)
    assert joins and getattr(joins[0], "adapt_salt", False), \
        "eligibility pass should arm the inner exchange join"
    return sub, sid, joins[0]


def _qk026_rules(sub, sid):
    return {v.rule for v in planck.collect(sub, sid)
            if v.rule == "QK026"}


class TestQK026:
    def test_armed_inner_join_is_clean(self, pq_env, monkeypatch):
        sub, sid, _ = _armed_plan(pq_env, monkeypatch)
        assert not _qk026_rules(sub, sid)

    def test_left_join_flagged(self, pq_env, monkeypatch):
        sub, sid, join = _armed_plan(pq_env, monkeypatch)
        join.how = "left"
        assert _qk026_rules(sub, sid)

    def test_broadcast_join_flagged(self, pq_env, monkeypatch):
        sub, sid, join = _armed_plan(pq_env, monkeypatch)
        join.broadcast = True
        assert _qk026_rules(sub, sid)

    def test_ordered_join_flagged(self, pq_env, monkeypatch):
        sub, sid, join = _armed_plan(pq_env, monkeypatch)
        join.sorted_by = ["fk"]
        assert _qk026_rules(sub, sid)

    def test_salt_column_reserved(self, pq_env, monkeypatch):
        sub, sid, join = _armed_plan(pq_env, monkeypatch)
        join.schema = list(join.schema) + [decide.SALT_COLUMN]
        assert _qk026_rules(sub, sid)

    def test_adapt_off_never_arms(self, pq_env, monkeypatch):
        monkeypatch.setenv("QK_ADAPT", "0")
        monkeypatch.setenv("QK_BROADCAST_BYTES", "1")
        monkeypatch.setattr(optimizer, "BROADCAST_THRESHOLD", 0)
        fp, dp = pq_env
        ctx = QuokkaContext()
        q = ctx.read_parquet(fp).join(ctx.read_parquet(dp),
                                      left_on="fk", right_on="pk")
        sub, sid = _subplan(q)
        optimizer.optimize(sub, sid)
        assert not any(getattr(j, "adapt_salt", False)
                       for j in _joins(sub, sid))
