"""Small-key group-by fast path (ops/fuse.py FusedPartialAgg) vs the general
sort+segment path: identical results on nulls-in-keys, empty batches, single
groups, and high-cardinality fallback.  Every case of TestSmallGroupby runs
under both of the path's builders: the scatter one (the CPU's default
group-by strategy) and the one-hot one the chip runs (strategy ``sort``:
float sums and count(*) through one matmul, integer sums through a masked
reduction over the same one-hot)."""

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.ops import bridge, fuse, kernels
from quokka_tpu.ops.expr_compile import evaluate_predicate, plan_aggregation
from quokka_tpu.sqlparse import parse_expression, parse_select_list


def run_agg(t, keys, aggs="sum(v) as sv, count(*) as n, count(v) as nv, avg(v) as av"):
    ctx = QuokkaContext()
    got = (
        ctx.from_arrow(t)
        .groupby(keys)
        .agg_sql(aggs)
        .collect()
        .sort_values(keys)
        .reset_index(drop=True)
    )
    return got


def oracle(t, keys):
    pdf = t.to_pandas()
    g = pdf.groupby(keys, dropna=False)
    out = g.agg(
        sv=("v", "sum"), n=("v", "size"), nv=("v", "count"), av=("v", "mean")
    ).reset_index()
    return out.sort_values(keys).reset_index(drop=True)


class TestSmallGroupby:
    def _table(self, n=20000, seed=0, null_keys=False, null_vals=True):
        r = np.random.default_rng(seed)
        flag = np.array(["A", "B", "C"], dtype=object)[r.integers(0, 3, n)]
        if null_keys:
            flag[r.random(n) < 0.05] = None
        v = r.uniform(0, 10, n).round(3)
        if null_vals:
            v[r.random(n) < 0.1] = np.nan
        return pa.table(
            {
                "flag": pa.array(flag, type=pa.string()),
                "status": np.array(["X", "Y"])[r.integers(0, 2, n)],
                "v": v,
            }
        )

    @pytest.fixture(autouse=True, params=["default", "sort"])
    def strategy(self, request, monkeypatch):
        """The group-by strategy of the case, and a record of every dispatch
        that took the small-key path (its ``use_tables``: True = the scatter
        builder, False = the one-hot builder, the only one ``sort`` runs)."""
        if request.param == "sort":
            monkeypatch.setenv("QK_KERNEL_STRATEGY", "groupby=sort")
        else:
            monkeypatch.delenv("QK_KERNEL_STRATEGY", raising=False)
        self.small_calls = []
        call_small = fuse.FusedPartialAgg._call_small

        def recording(agg, batch, pre, pre_exprs, num_inputs, dims, use_tables):
            self.small_calls.append(use_tables)
            return call_small(agg, batch, pre, pre_exprs, num_inputs, dims,
                              use_tables)

        monkeypatch.setattr(fuse.FusedPartialAgg, "_call_small", recording)
        yield
        if request.param == "sort":
            assert not any(self.small_calls)

    def _small_used(self):
        return bool(self.small_calls)

    def _partial(self, t, keys, aggs, where=None, x64=True):
        """One batch straight through FusedPartialAgg in this thread (so that
        ``x64=False`` runs it in the chip's dtypes): the partial columns
        ``__agg_<i>`` by key, sorted."""
        with jax.enable_x64(x64):
            plan = plan_aggregation(parse_select_list(aggs))
            batch = bridge.arrow_to_device(t)
            if where is not None:
                batch = kernels.apply_mask(
                    batch, evaluate_predicate(parse_expression(where), batch))
            out = fuse.FusedPartialAgg(keys, plan)(batch)
            got = bridge.device_to_arrow(out).to_pandas()
        return got.sort_values(keys).reset_index(drop=True)

    def test_matches_oracle_with_null_values(self):
        t = self._table()
        got = run_agg(t, ["flag", "status"])
        exp = oracle(t, ["flag", "status"])
        assert self._small_used()
        np.testing.assert_allclose(got.sv.to_numpy(), exp.sv.to_numpy(), rtol=1e-9)
        assert got.n.tolist() == exp.n.tolist()
        assert got.nv.tolist() == exp.nv.tolist()
        np.testing.assert_allclose(got.av.to_numpy(), exp.av.to_numpy(), rtol=1e-9)

    def test_null_keys_form_one_group(self):
        t = self._table(null_keys=True)
        got = run_agg(t, ["flag"])
        exp = oracle(t, ["flag"])
        # pandas sorts NaN-keyed group last; ours yields None -> compare on
        # the non-null groups plus the null group's aggregate values
        got_nn = got[got.flag.notna()].reset_index(drop=True)
        exp_nn = exp[exp.flag.notna()].reset_index(drop=True)
        np.testing.assert_allclose(
            got_nn.sv.to_numpy(), exp_nn.sv.to_numpy(), rtol=1e-9
        )
        assert got_nn.n.tolist() == exp_nn.n.tolist()
        g_null = got[got.flag.isna()]
        e_null = exp[exp.flag.isna()]
        assert len(g_null) == len(e_null) == 1
        assert g_null.n.iloc[0] == e_null.n.iloc[0]
        np.testing.assert_allclose(
            g_null.sv.iloc[0], e_null.sv.iloc[0], rtol=1e-9
        )

    def test_single_group(self):
        t = pa.table({"flag": ["A"] * 1000, "status": ["X"] * 1000,
                      "v": np.arange(1000, dtype=np.float64)})
        got = run_agg(t, ["flag"])
        assert len(got) == 1
        assert got.sv.iloc[0] == float(np.arange(1000).sum())
        assert got.n.iloc[0] == 1000

    def test_integer_sum_stays_exact(self):
        r = np.random.default_rng(1)
        n = 30000
        t = pa.table(
            {
                "flag": np.array(["A", "B"])[r.integers(0, 2, n)],
                "q": r.integers(0, 1000, n),
                "v": r.uniform(0, 1, n),
            }
        )
        ctx = QuokkaContext()
        got = (
            ctx.from_arrow(t)
            .groupby("flag")
            .agg_sql("sum(q) as sq, count(*) as n")
            .collect()
            .sort_values("flag")
            .reset_index(drop=True)
        )
        exp = (
            t.to_pandas().groupby("flag").agg(sq=("q", "sum"), n=("q", "size"))
            .reset_index()
        )
        assert got.sq.tolist() == exp.sq.tolist()
        assert got.n.tolist() == exp.n.tolist()

    def test_high_cardinality_falls_back(self):
        r = np.random.default_rng(2)
        n = 5000
        # 500 distinct keys -> beyond _SMALL_GROUPBY_MAX_BUCKETS with the
        # second key, must fall back to the sort path and still be right
        k1 = np.array([f"k{i:04d}" for i in r.integers(0, 500, n)])
        t = pa.table({"flag": k1, "v": r.uniform(0, 10, n).round(3)})
        got = run_agg(t, ["flag"])
        exp = oracle(t, ["flag"])
        assert not self.small_calls
        np.testing.assert_allclose(got.sv.to_numpy(), exp.sv.to_numpy(), rtol=1e-9)
        assert got.n.tolist() == exp.n.tolist()

    def test_count_and_avg_skip_null_integers(self):
        """count(v), avg(v) and sum(v) of a nullable integer column: three
        integer partials (sum(__nn0(v)), sum(__nncount(v)) twice over)."""
        r = np.random.default_rng(3)
        n = 20000
        v = pa.array(r.integers(-50, 1000, n), mask=r.random(n) < 0.2)
        t = pa.table({
            "flag": np.array(["A", "B", "C"])[r.integers(0, 3, n)],
            "status": np.array(["X", "Y"])[r.integers(0, 2, n)],
            "v": v,
        })
        got = run_agg(t, ["flag", "status"])
        exp = oracle(t, ["flag", "status"])
        assert self._small_used()
        assert got.sv.astype("int64").tolist() == exp.sv.astype("int64").tolist()
        assert got.n.tolist() == exp.n.tolist()
        assert got.nv.tolist() == exp.nv.tolist()
        assert (exp.nv < exp.n).all()
        np.testing.assert_allclose(got.av.to_numpy(), exp.av.to_numpy(), rtol=1e-12)

    @pytest.mark.parametrize("lo,hi", [(0, 5000), (-5000, 1000)],
                             ids=["past_2_24", "negative"])
    def test_integer_sum_exact_in_the_chips_dtypes(self, lo, hi):
        """x64 off, as on the chip: an int32 sum whose group totals pass 2^24
        (a float32 detour would lose the low bits) and one below zero."""
        r = np.random.default_rng(4)
        n = 30000
        t = pa.table({
            "flag": np.array(["A", "B"])[r.integers(0, 2, n)],
            "q": r.integers(lo, hi, n) | 1,  # odd totals need every bit
        })
        got = self._partial(t, ["flag"], "sum(q) as sq, count(*) as n",
                            x64=False)
        exp = t.to_pandas().groupby("flag").agg(
            sq=("q", "sum"), n=("q", "size")).reset_index()
        assert self._small_used()
        assert str(got["__agg_0"].dtype) == "int32"
        assert (exp.sq.abs() > 1 << 24).all()
        assert got["__agg_0"].tolist() == exp.sq.tolist()
        assert got["__agg_1"].tolist() == exp.n.tolist()

    def test_masked_and_padded_rows_fall_in_the_dump_bucket(self):
        """Rows a filter masked out and the bucket's padding contribute to
        no group, whatever their values; null keys are a group of their own."""
        r = np.random.default_rng(5)
        n = 5000  # padded to 8192: 3192 padding rows
        flag = np.array(["A", "B", "C"], dtype=object)[r.integers(0, 3, n)]
        flag[r.random(n) < 0.1] = None
        t = pa.table({
            "flag": pa.array(flag, type=pa.string()),
            "q": r.integers(1, 1 << 20, n),
        })
        got = self._partial(t, ["flag"], "sum(q) as sq, count(q) as nq, "
                            "count(*) as n", where="q > 500000")
        d = t.to_pandas()
        exp = (d[d.q > 500000].groupby("flag", dropna=False)
               .agg(sq=("q", "sum"), n=("q", "size")).reset_index()
               .sort_values("flag").reset_index(drop=True))
        assert self._small_used()
        assert len(got) == len(exp) == 4 and got.flag.isna().sum() == 1
        # the nn0 sum, the nncount sum and count(*); both sorts put the
        # null group last
        assert got["__agg_0"].tolist() == exp.sq.tolist()
        assert got["__agg_1"].tolist() == exp.n.tolist()
        assert got["__agg_2"].tolist() == exp.n.tolist()

    @pytest.mark.parametrize("distinct,small", [(127, True), (128, False)])
    def test_dictionary_at_the_bucket_gate(self, distinct, small):
        """127 values + the null slot = 128 buckets + the dump bucket: the
        widest one-hot the path admits; one value more doubles the canonical
        dim and the general path takes the batch."""
        assert fuse._SMALL_GROUPBY_MAX_BUCKETS == 256
        r = np.random.default_rng(6)
        n = 20000
        t = pa.table({
            "flag": np.array([f"k{i:03d}" for i in range(distinct)])[
                r.permutation(np.arange(n) % distinct)],
            "q": r.integers(-1000, 1000, n),
        })
        got = self._partial(t, ["flag"], "sum(q) as sq, count(*) as n")
        exp = (t.to_pandas().groupby("flag")
               .agg(sq=("q", "sum"), n=("q", "size")).reset_index())
        assert self._small_used() == small
        assert len(got) == distinct
        assert got["__agg_0"].tolist() == exp.sq.tolist()
        assert got["__agg_1"].tolist() == exp.n.tolist()


class TestAdaptivePartialAgg:
    """Near-unique group keys flip PartialAggExecutor into passthrough
    (partial-FORM rows, no per-batch sort); results must be identical."""

    def _data(self, n=40_000, uniq=True, seed=5):
        import numpy as np
        import pyarrow as pa

        r = np.random.default_rng(seed)
        keys = (
            np.arange(n, dtype=np.int64) if uniq
            else r.integers(0, 50, n).astype(np.int64)
        )
        return pa.table({
            "k": r.permutation(keys),
            "v": r.uniform(0, 10, n).round(4),
            "w": r.integers(1, 9, n).astype(np.int64),
        })

    def _q(self, ctx, t, batch_rows):
        from quokka_tpu import logical
        from quokka_tpu.dataset.readers import InputArrowDataset

        src = ctx.new_stream(logical.SourceNode(
            InputArrowDataset(t, batch_rows=batch_rows), list(t.column_names)
        ))
        return (
            src.groupby("k")
            .agg_sql("sum(v) as sv, count(*) as n, avg(w) as aw, max(v) as mv")
            .collect()
            .sort_values("k")
            .reset_index(drop=True)
        )

    def test_unique_keys_match_pandas(self):
        import numpy as np

        from quokka_tpu import QuokkaContext

        t = self._data(uniq=True)
        d = t.to_pandas()
        ctx = QuokkaContext(io_channels=2, exec_channels=2)
        got = self._q(ctx, t, batch_rows=8192)
        exp = (
            d.groupby("k")
            .agg(sv=("v", "sum"), n=("v", "size"), aw=("w", "mean"),
                 mv=("v", "max"))
            .reset_index()
            .sort_values("k")
            .reset_index(drop=True)
        )
        assert len(got) == len(exp)
        np.testing.assert_array_equal(got.k.to_numpy(), exp.k.to_numpy())
        np.testing.assert_allclose(got.sv.to_numpy(), exp.sv.to_numpy(), rtol=1e-9)
        np.testing.assert_array_equal(got.n.to_numpy(), exp.n.to_numpy())
        np.testing.assert_allclose(got.aw.to_numpy(), exp.aw.to_numpy(), rtol=1e-9)
        np.testing.assert_allclose(got.mv.to_numpy(), exp.mv.to_numpy(), rtol=1e-9)

    def test_passthrough_decision(self):
        import pyarrow as pa

        from quokka_tpu.ops import bridge
        from quokka_tpu.ops.expr_compile import plan_aggregation
        from quokka_tpu.executors.sql_execs import PartialAggExecutor
        from quokka_tpu.sqlparse import parse_select_list

        plan = plan_aggregation(parse_select_list(
            "sum(v) as sv, count(*) as n"))
        # near-unique keys -> passthrough after batch 1
        t = self._data(n=10_000, uniq=True)
        ex = PartialAggExecutor(["k"], plan)
        b = bridge.arrow_to_device(t)
        assert ex.execute([b], 0, 0) is None  # batch 1 always aggregates
        assert ex._passthrough is True
        out = ex.execute([b], 0, 0)  # batch 2 passes through immediately
        assert out is not None and out.count_valid() == 10_000
        # low-cardinality keys -> stays aggregating
        t2 = self._data(n=10_000, uniq=False)
        ex2 = PartialAggExecutor(["k"], plan)
        b2 = bridge.arrow_to_device(t2)
        ex2.execute([b2], 0, 0)
        assert ex2._passthrough is False
        assert ex2.execute([b2], 0, 0) is None

    def test_checkpoint_carries_decision(self):
        from quokka_tpu.ops import bridge
        from quokka_tpu.ops.expr_compile import plan_aggregation
        from quokka_tpu.executors.sql_execs import PartialAggExecutor
        from quokka_tpu.sqlparse import parse_select_list

        plan = plan_aggregation(parse_select_list("count(*) as n"))
        t = self._data(n=10_000, uniq=True)
        ex = PartialAggExecutor(["k"], plan)
        ex.execute([bridge.arrow_to_device(t)], 0, 0)
        snap = ex.checkpoint()
        ex2 = PartialAggExecutor(["k"], plan)
        ex2.restore(snap)
        assert ex2._passthrough is True
