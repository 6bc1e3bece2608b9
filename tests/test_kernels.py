"""Unit tests for the device columnar layer (bridge + kernels + joins),
with pandas as the correctness oracle (replacing the reference's
eyeball-vs-DuckDB strategy, SURVEY.md section 4)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu.ops import bridge, kernels
from quokka_tpu.ops import distinct as distinct_ops
from quokka_tpu.ops import join as join_ops
from quokka_tpu.ops.batch import DeviceBatch, NumCol, StrCol, key_limbs

from conftest import make_table


def roundtrip(table):
    return bridge.device_to_arrow(bridge.arrow_to_device(table))


class TestBridge:
    def test_roundtrip_mixed(self, table):
        out = roundtrip(table)
        assert out.num_rows == table.num_rows
        pd.testing.assert_frame_equal(
            out.to_pandas(), table.to_pandas(), check_dtype=False
        )

    def test_roundtrip_empty(self):
        t = pa.table({"a": pa.array([], type=pa.int64())})
        out = roundtrip(t)
        assert out.num_rows == 0

    def test_strings_dictionary(self, table):
        b = bridge.arrow_to_device(table)
        s = b.columns["s"]
        assert isinstance(s, StrCol)
        assert len(s.dictionary) <= 4

    def test_wide_int_limbs(self):
        vals = np.array([2**40, -(2**40), 5, -5, 0, 2**62], dtype=np.int64)
        import jax

        was = jax.config.read("jax_enable_x64")
        jax.config.update("jax_enable_x64", False)
        try:
            t = pa.table({"a": vals})
            b = bridge.arrow_to_device(t)
            assert b.columns["a"].hi is not None
            out = bridge.device_to_arrow(b)
            np.testing.assert_array_equal(out.column("a").to_numpy(), vals)
            # limb sort order == numeric order
            s = kernels.sort_batch(b, ["a"])
            out2 = bridge.device_to_arrow(s)
            np.testing.assert_array_equal(out2.column("a").to_numpy(), np.sort(vals))
        finally:
            jax.config.update("jax_enable_x64", was)

    def test_concat_batches_merges_dicts(self):
        t1 = pa.table({"s": ["a", "b"], "x": [1.0, 2.0]})
        t2 = pa.table({"s": ["b", "c"], "x": [3.0, 4.0]})
        b = bridge.concat_batches([bridge.arrow_to_device(t1), bridge.arrow_to_device(t2)])
        out = bridge.device_to_arrow(b).to_pandas().sort_values("x").reset_index(drop=True)
        assert list(out["s"]) == ["a", "b", "b", "c"]


class TestKernels:
    def test_filter_compact(self, table, pdf):
        b = bridge.arrow_to_device(table)
        mask = b.columns["q"].data > 25
        f = kernels.compact(kernels.apply_mask(b, mask))
        expect = pdf[pdf.q > 25]
        assert f.count_valid() == len(expect)
        got = bridge.device_to_arrow(f).to_pandas()
        pd.testing.assert_frame_equal(
            got.reset_index(drop=True), expect.reset_index(drop=True), check_dtype=False
        )

    def test_groupby_sum_count(self, table, pdf):
        b = bridge.arrow_to_device(table)
        g = kernels.groupby_aggregate(
            b,
            ["k"],
            [
                ("v_sum", "sum", b.columns["v"].data),
                ("n", "count", None),
                ("q_max", "max", b.columns["q"].data),
            ],
        )
        got = (
            bridge.device_to_arrow(kernels.compact(g))
            .to_pandas()
            .sort_values("k")
            .reset_index(drop=True)
        )
        exp = (
            pdf.groupby("k")
            .agg(v_sum=("v", "sum"), n=("v", "size"), q_max=("q", "max"))
            .reset_index()
        )
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)

    def test_groupby_string_key(self, table, pdf):
        b = bridge.arrow_to_device(table)
        g = kernels.groupby_aggregate(b, ["s"], [("n", "count", None)])
        got = (
            bridge.device_to_arrow(kernels.compact(g))
            .to_pandas()
            .sort_values("s")
            .reset_index(drop=True)
        )
        exp = pdf.groupby("s").size().reset_index(name="n")
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_groupby_multi_key(self, table, pdf):
        b = bridge.arrow_to_device(table)
        g = kernels.groupby_aggregate(b, ["k", "s"], [("v_min", "min", b.columns["v"].data)])
        got = (
            bridge.device_to_arrow(kernels.compact(g))
            .to_pandas()
            .sort_values(["k", "s"])
            .reset_index(drop=True)
        )
        exp = pdf.groupby(["k", "s"]).agg(v_min=("v", "min")).reset_index()
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)

    def test_groupby_no_keys(self, table, pdf):
        b = bridge.arrow_to_device(table)
        g = kernels.groupby_aggregate(b, [], [("t", "sum", b.columns["v"].data)])
        got = bridge.device_to_arrow(kernels.compact(g)).to_pandas()
        assert len(got) == 1
        np.testing.assert_allclose(got["t"][0], pdf.v.sum(), rtol=1e-9)

    def test_sort_multi(self, table, pdf):
        b = bridge.arrow_to_device(table)
        s = kernels.sort_batch(b, ["k", "v"], [False, True])
        got = bridge.device_to_arrow(s).to_pandas().reset_index(drop=True)
        exp = pdf.sort_values(["k", "v"], ascending=[True, False]).reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_sort_string_lexicographic(self, table, pdf):
        b = bridge.arrow_to_device(table)
        s = kernels.sort_batch(b, ["s"])
        got = bridge.device_to_arrow(s).to_pandas()["s"].tolist()
        assert got == sorted(pdf.s.tolist())

    def test_top_k(self, table, pdf):
        b = bridge.arrow_to_device(table)
        t = kernels.top_k(b, ["v"], 7, [True])
        got = bridge.device_to_arrow(t).to_pandas()["v"].to_numpy()
        exp = pdf.v.nlargest(7).to_numpy()
        np.testing.assert_allclose(got, exp)

    def test_distinct(self, table, pdf):
        b = bridge.arrow_to_device(table)
        seen = distinct_ops.SortedKeys()
        emit, added = seen.fold(key_limbs(b, ["k", "s"]), b.valid)
        got = bridge.device_to_arrow(kernels.compact(DeviceBatch(
            {k: b.columns[k] for k in ("k", "s")}, emit, added))).to_pandas()
        exp = pdf[["k", "s"]].drop_duplicates()
        assert added == seen.count == len(exp)
        assert got.astype(str).values.tolist() == (
            exp.astype(str).values.tolist())
        # the same rows again: every key seen, nothing new
        assert seen.fold(key_limbs(b, ["k", "s"]), b.valid)[1] == 0

    def test_partition_deterministic_and_complete(self, table):
        b = bridge.arrow_to_device(table)
        pids = kernels.partition_ids(b, ["k"], 4)
        parts = kernels.split_by_partition(b, pids, 4)
        assert sum(p.count_valid() for p in parts) == b.count_valid()
        # same key always lands in the same partition
        k = np.asarray(b.columns["k"].data)[np.asarray(b.valid)]
        pid = np.asarray(pids)[np.asarray(b.valid)]
        df = pd.DataFrame({"k": k, "p": pid})
        assert (df.groupby("k").p.nunique() == 1).all()

    def test_head(self, table):
        b = bridge.arrow_to_device(table)
        h = kernels.head(b, 10)
        assert h.count_valid() == 10


def _join_oracle(ldf, rdf, on, how):
    return ldf.merge(rdf, on=on, how=how)


class TestJoins:
    def setup_method(self):
        r = np.random.default_rng(7)
        self.left = pa.table(
            {
                "key": r.integers(0, 50, 300).astype(np.int64),
                "lv": r.normal(size=300),
            }
        )
        # unique build side (PK)
        self.right_pk = pa.table(
            {
                "key": np.arange(0, 40, dtype=np.int64),
                "rv": r.normal(size=40),
            }
        )
        # duplicated build side
        self.right_mm = pa.table(
            {
                "key": r.integers(0, 30, 80).astype(np.int64),
                "rv": r.normal(size=80),
            }
        )

    def test_pk_inner(self):
        lb = bridge.arrow_to_device(self.left)
        rb = bridge.arrow_to_device(self.right_pk)
        out = join_ops.hash_join_pk(lb, rb, ["key"], ["key"], "inner", ["rv"])
        got = (
            bridge.device_to_arrow(kernels.compact(out))
            .to_pandas()
            .sort_values(["key", "lv"])
            .reset_index(drop=True)
        )
        exp = (
            _join_oracle(self.left.to_pandas(), self.right_pk.to_pandas(), "key", "inner")
            .sort_values(["key", "lv"])
            .reset_index(drop=True)
        )
        pd.testing.assert_frame_equal(got[exp.columns.tolist()], exp, check_dtype=False)

    def test_pk_semi_anti(self):
        lb = bridge.arrow_to_device(self.left)
        rb = bridge.arrow_to_device(self.right_pk)
        semi = join_ops.hash_join_pk(lb, rb, ["key"], ["key"], "semi")
        anti = join_ops.hash_join_pk(lb, rb, ["key"], ["key"], "anti")
        ldf = self.left.to_pandas()
        keys = set(self.right_pk.to_pandas().key)
        assert kernels.compact(semi).count_valid() == int(ldf.key.isin(keys).sum())
        assert kernels.compact(anti).count_valid() == int((~ldf.key.isin(keys)).sum())

    def test_mm_inner(self):
        lb = bridge.arrow_to_device(self.left)
        rb = bridge.arrow_to_device(self.right_mm)
        out = join_ops.hash_join_general(lb, rb, ["key"], ["key"], "inner", ["rv"])
        got = (
            bridge.device_to_arrow(kernels.compact(out))
            .to_pandas()
            .sort_values(["key", "lv", "rv"])
            .reset_index(drop=True)
        )
        exp = (
            _join_oracle(self.left.to_pandas(), self.right_mm.to_pandas(), "key", "inner")
            .sort_values(["key", "lv", "rv"])
            .reset_index(drop=True)
        )
        pd.testing.assert_frame_equal(got[exp.columns.tolist()], exp, check_dtype=False)

    def test_mm_left_count(self):
        lb = bridge.arrow_to_device(self.left)
        rb = bridge.arrow_to_device(self.right_mm)
        out = join_ops.hash_join_general(lb, rb, ["key"], ["key"], "left", ["rv"])
        exp = _join_oracle(self.left.to_pandas(), self.right_mm.to_pandas(), "key", "left")
        assert kernels.compact(out).count_valid() == len(exp)

    def test_string_key_join(self):
        l = pa.table({"s": ["a", "b", "c", "a"], "x": [1.0, 2.0, 3.0, 4.0]})
        r_ = pa.table({"s": ["a", "c"], "y": [10.0, 30.0]})
        out = join_ops.hash_join_pk(
            bridge.arrow_to_device(l), bridge.arrow_to_device(r_), ["s"], ["s"], "inner", ["y"]
        )
        got = (
            bridge.device_to_arrow(kernels.compact(out))
            .to_pandas()
            .sort_values("x")
            .reset_index(drop=True)
        )
        assert got.y.tolist() == [10.0, 30.0, 10.0]

    def test_build_unique_check(self):
        rb = bridge.arrow_to_device(self.right_pk)
        mb = bridge.arrow_to_device(self.right_mm)
        assert join_ops.build_keys_unique(rb, ["key"])
        assert not join_ops.build_keys_unique(mb, ["key"])
