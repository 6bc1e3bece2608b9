"""Optimizer pass tests: pushdown reaches readers, projection prunes columns,
broadcast selection fires, and optimized plans stay correct vs unoptimized."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext, col, date, logical
from quokka_tpu.optimizer import optimize


@pytest.fixture(scope="module")
def pq_env(tmp_path_factory):
    root = tmp_path_factory.mktemp("opt")
    r = np.random.default_rng(11)
    n = 20_000
    fact = pa.table(
        {
            "k": r.integers(0, 100, n).astype(np.int64),
            "x": r.normal(size=n),
            "big": [f"payload-{i}" for i in range(n)],  # should get pruned
            "d": pa.array(r.integers(8000, 12000, n).astype(np.int32), type=pa.int32()).cast(
                pa.date32()
            ),
        }
    )
    dim = pa.table(
        {
            "k": np.arange(100, dtype=np.int64),
            "name": [f"n{i}" for i in range(100)],
        }
    )
    fp, dp = str(root / "fact.parquet"), str(root / "dim.parquet")
    pq.write_table(fact, fp, row_group_size=2048)
    pq.write_table(dim, dp)
    return fp, dp, fact.to_pandas(), dim.to_pandas()


def optimized_plan(stream):
    ctx = stream.ctx
    sub, _ = ctx._copy_subgraph(stream.node_id)
    sink = logical.SinkNode([stream.node_id], sub[stream.node_id].schema)
    sid = max(sub) + 1
    sub[sid] = sink
    optimize(sub, sid)
    return sub, sid


def find_nodes(sub, sid, cls):
    from quokka_tpu.optimizer import _reachable

    return [sub[n] for n in _reachable(sub, sid) if isinstance(sub[n], cls)]


class TestPushdown:
    def test_filter_reaches_source(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        q = ctx.read_parquet(fp).filter(col("k") > 50).filter(col("x") > 0)
        sub, sid = optimized_plan(q)
        srcs = find_nodes(sub, sid, logical.SourceNode)
        assert len(srcs) == 1
        assert srcs[0].predicate is not None
        assert not find_nodes(sub, sid, logical.FilterNode)

    def test_filter_pushes_through_join(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        f = ctx.read_parquet(fp)
        d = ctx.read_parquet(dp)
        q = f.join(d, on="k", suffix="_r").filter(col("x") > 1.0)
        sub, sid = optimized_plan(q)
        srcs = find_nodes(sub, sid, logical.SourceNode)
        fact_src = [s for s in srcs if "x" in s.schema][0]
        assert fact_src.predicate is not None and "x" in fact_src.predicate.sql()

    @pytest.mark.parametrize("limit", [None, 5])
    def test_filter_over_a_groupby_is_its_having(self, pq_env, limit):
        """A filter over a group-by's output folds into the group-by as its
        HAVING, where the final aggregate compacts what it keeps; over a
        LIMIT it must stay after it."""
        fp, dp, fdf, ddf = pq_env
        exp = fdf.groupby("k").agg(n=("x", "size"), sx=("x", "sum"))
        exp = exp[(exp.n > 200) & (exp.sx > 0)].reset_index()
        for opt in (True, False):
            ctx = QuokkaContext(optimize=opt)
            q = (ctx.read_parquet(fp).groupby("k", orderby=["k"])
                 .agg_sql("count(*) as n, sum(x) as sx"))
            if limit is not None:
                q = q._child(logical.AggNode(
                    list(q._node.parents), q.schema, q._node.keys,
                    q._node.plan, order_by=[("k", False)], limit=limit))
            q = q.filter(col("n") > 200).filter(col("sx") > 0)
            if opt:
                sub, sid = optimized_plan(q)
                aggs = find_nodes(sub, sid, logical.AggNode)
                filters = find_nodes(sub, sid, logical.FilterNode)
                if limit is None:
                    assert not filters and aggs[-1].having is not None
                    assert aggs[-1].having.required_columns() == {"n", "sx"}
                else:
                    assert filters and aggs[-1].having is None
            got = q.collect().sort_values("k").reset_index(drop=True)
            want = exp if limit is None else (
                exp[exp.k.isin(range(limit))].reset_index(drop=True))
            pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                          rtol=1e-9)

    def test_pushdown_correctness(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        for opt in (True, False):
            ctx = QuokkaContext(optimize=opt)
            got = (
                ctx.read_parquet(fp)
                .join(ctx.read_parquet(dp), on="k")
                .filter(col("x") > 1.0)
                .groupby("name")
                .agg_sql("count(*) as n, sum(x) as sx")
                .collect()
            )
            m = fdf[fdf.x > 1.0].merge(ddf, on="k")
            exp = m.groupby("name").agg(n=("x", "size"), sx=("x", "sum")).reset_index()
            got = got.sort_values("name").reset_index(drop=True)
            exp = exp.sort_values("name").reset_index(drop=True)
            pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)

    def test_rowgroup_pruning_happens(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        # d > all data -> every row group pruned -> zero rows, fast
        got = ctx.read_parquet(fp).filter(col("d") > date("2200-01-01")).count()
        assert got == 0
        g = ctx.latest_graph
        src = [a for a in g.actors.values() if a.kind == "input"][0]
        n_pieces = sum(
            len(v) for v in src.reader.get_own_state(1).values()
        )
        assert n_pieces == 0  # all row groups excluded by min/max stats


class TestProjection:
    def test_source_prunes_columns(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        q = (
            ctx.read_parquet(fp)
            .filter(col("k") > 10)
            .groupby("k")
            .agg_sql("sum(x) as sx")
        )
        sub, sid = optimized_plan(q)
        src = find_nodes(sub, sid, logical.SourceNode)[0]
        assert src.projection is not None
        assert "big" not in src.projection
        assert "x" in src.projection and "k" in src.projection

    def test_projection_correctness(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        got = (
            ctx.read_parquet(fp)
            .filter(col("k") > 10)
            .groupby("k")
            .agg_sql("sum(x) as sx")
            .collect()
        )
        exp = fdf[fdf.k > 10].groupby("k").x.sum().reset_index(name="sx")
        got = got.sort_values("k").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)


class TestJoinSuffixUnderProjection:
    def test_pruned_clash_column_keeps_planned_suffix(self):
        # left(a, c) join right(k, c): selecting only the RIGHT c (c_2) prunes
        # the left c; the planned rename must still apply (regression: the
        # runtime collision detection used to emit 'c' and crash the select)
        ctx = QuokkaContext()
        left = pa.table({"a": np.arange(10, dtype=np.int64),
                         "c": np.arange(10, dtype=np.float64)})
        right = pa.table({"k": np.arange(10, dtype=np.int64),
                          "c": np.arange(10, dtype=np.float64) * 10})
        got = (
            ctx.from_arrow(left)
            .join(ctx.from_arrow(right), left_on="a", right_on="k")
            .select(["a", "c_2"])
            .collect()
        )
        got = got.sort_values("a").reset_index(drop=True)
        np.testing.assert_allclose(got.c_2.to_numpy(), np.arange(10) * 10.0)


class TestBroadcast:
    def test_small_build_becomes_broadcast(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        q = ctx.read_parquet(fp).join(ctx.read_parquet(dp), on="k")
        sub, sid = optimized_plan(q)
        joins = find_nodes(sub, sid, logical.JoinNode)
        assert len(joins) == 1
        assert joins[0].broadcast  # dim is 100 rows < threshold

    def test_broadcast_correctness(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext()
        got = ctx.read_parquet(fp).join(ctx.read_parquet(dp), on="k").count()
        exp = len(fdf.merge(ddf, on="k"))
        assert got == exp


class TestParallelSort:
    def test_sort_becomes_range_partitioned(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext(exec_channels=2)
        q = ctx.read_parquet(fp).sort(["x"])
        sub, sid = optimized_plan(q)
        sorts = find_nodes(sub, sid, logical.SortNode)
        assert len(sorts) == 1 and sorts[0].boundaries is not None
        assert len(sorts[0].boundaries) == 1  # n_channels - 1

    def test_parallel_sort_correct(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        for desc in (False, True):
            ctx = QuokkaContext(exec_channels=2)
            got = ctx.read_parquet(fp).sort(["x"], [desc]).collect()
            exp = fdf.sort_values("x", ascending=not desc).reset_index(drop=True)
            np.testing.assert_allclose(got.x.to_numpy(), exp.x.to_numpy())

    def test_parallel_sort_with_filter(self, pq_env):
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext(exec_channels=2)
        got = ctx.read_parquet(fp).filter(col("k") > 50).sort(["x"]).collect()
        exp = fdf[fdf.k > 50].sort_values("x").reset_index(drop=True)
        np.testing.assert_allclose(got.x.to_numpy(), exp.x.to_numpy())

    def test_sort_then_chain_stays_ordered(self, pq_env):
        # regression: ops chained after a parallel sort must preserve order
        fp, dp, fdf, ddf = pq_env
        ctx = QuokkaContext(exec_channels=2)
        got = ctx.read_parquet(fp).sort(["x"]).select(["x"]).collect()
        np.testing.assert_allclose(got.x.to_numpy(), np.sort(fdf.x.to_numpy()))

    def test_unsampleable_schema_does_not_break_planning(self, tmp_path):
        # a list column the query never touches must not crash the sampler
        t = pa.table(
            {
                "x": np.random.default_rng(0).normal(size=1000),
                "weird": pa.array([[1, 2]] * 1000, type=pa.list_(pa.int64())),
            }
        )
        p = str(tmp_path / "weird.parquet")
        pq.write_table(t, p)
        ctx = QuokkaContext(exec_channels=2)
        got = ctx.read_parquet(p, columns=["x"]).filter(col("x") > 0).sort(["x"]).collect()
        exp = np.sort(t.column("x").to_numpy()[t.column("x").to_numpy() > 0])
        np.testing.assert_allclose(got.x.to_numpy(), exp)


class TestJoinReorderAndFoldMap:
    """VERDICT r1 item 6: cardinality-greedy join reordering + map folding."""

    def _tables(self):
        r = np.random.default_rng(0)
        fact = pa.table({"k1": r.integers(0, 1000, 20000).astype(np.int64),
                         "k2": r.integers(0, 50, 20000).astype(np.int64),
                         "v": r.uniform(0, 1, 20000)})
        big = pa.table({"k1": np.arange(1000, dtype=np.int64),
                        "b1": r.uniform(0, 1, 1000)})
        small = pa.table({"k2": np.arange(50, dtype=np.int64),
                          "s1": r.uniform(0, 1, 50)})
        return fact, big, small

    def test_chain_reordered_smallest_first(self):
        fact, big, small = self._tables()
        ctx = QuokkaContext()
        q = (ctx.from_arrow(fact)
             .join(ctx.from_arrow(big), on="k1")
             .join(ctx.from_arrow(small), on="k2")
             .groupby("k2").agg_sql("sum(v) as s"))
        plan = q.explain()
        # the small (k2) join must appear BELOW the big (k1) join post-reorder
        k2_line = next(i for i, l in enumerate(plan.splitlines()) if "['k2']=['k2']" in l)
        k1_line = next(i for i, l in enumerate(plan.splitlines()) if "['k1']=['k1']" in l)
        assert k2_line < k1_line, plan
        got = q.collect().sort_values("k2").reset_index(drop=True)
        df = fact.to_pandas().merge(big.to_pandas(), on="k1").merge(
            small.to_pandas(), on="k2")
        exp = df.groupby("k2").v.sum().reset_index(name="s")
        np.testing.assert_allclose(got.s.to_numpy(), exp.s.to_numpy(), rtol=1e-9)

    def test_snowflake_dependency_respected(self):
        # customer key comes from the orders dim: customer join CANNOT move
        # below the orders join no matter how small customer is
        r = np.random.default_rng(1)
        li = pa.table({"ok": r.integers(0, 500, 10000).astype(np.int64),
                       "v": r.uniform(0, 1, 10000)})
        orders = pa.table({"ok": np.arange(500, dtype=np.int64),
                           "ck": r.integers(0, 20, 500).astype(np.int64)})
        cust = pa.table({"ck": np.arange(20, dtype=np.int64),
                         "seg": np.array(["A", "B"])[np.arange(20) % 2]})
        ctx = QuokkaContext()
        q = (ctx.from_arrow(li)
             .join(ctx.from_arrow(orders), on="ok")
             .join(ctx.from_arrow(cust), on="ck")
             .groupby("seg").agg_sql("sum(v) as s"))
        got = q.collect().sort_values("seg").reset_index(drop=True)
        df = li.to_pandas().merge(orders.to_pandas(), on="ok").merge(
            cust.to_pandas(), on="ck")
        exp = df.groupby("seg").v.sum().reset_index(name="s")
        np.testing.assert_allclose(got.s.to_numpy(), exp.s.to_numpy(), rtol=1e-9)

    def test_fold_map_no_actor_hop(self):
        fact, big, _ = self._tables()
        ctx = QuokkaContext()
        q = (ctx.from_arrow(fact)
             .join(ctx.from_arrow(big), on="k1")
             .with_columns_sql("v * b1 as vb")
             .groupby("k2").agg_sql("sum(vb) as s"))
        plan = q.explain()
        assert "FoldedMap" in plan, plan
        got = q.collect().sort_values("k2").reset_index(drop=True)
        df = fact.to_pandas().merge(big.to_pandas(), on="k1")
        df["vb"] = df.v * df.b1
        exp = df.groupby("k2").vb.sum().reset_index(name="s")
        np.testing.assert_allclose(got.s.to_numpy(), exp.s.to_numpy(), rtol=1e-9)
