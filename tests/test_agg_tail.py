"""The aggregation tail as compiled programs (ops/aggtail.py) against the
general op-by-op path on the same parts: bit-equal keys, counts and sums;
the path follows the parts' shapes alone; the program set follows the plan
and not how many partials happened to be buffered; nothing compiles outside
the compile plane between the first done() and the result."""

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu import obs, sqlparse
from quokka_tpu.executors.sql_execs import FinalAggExecutor, PartialAggExecutor
from quokka_tpu.expression import Alias
from quokka_tpu.ops import aggtail, bridge, fuse
from quokka_tpu.ops.batch import DeviceBatch, NumCol
from quokka_tpu.ops.expr_compile import plan_aggregation
from quokka_tpu.runtime import compileplane
from quokka_tpu.utils import compilestats

import tpch_data


def counters():
    return (int(obs.REGISTRY.counter("agg.merges_compiled").value),
            int(obs.REGISTRY.counter("agg.merges_general").value))


def masked(table: pa.Table, keep=None) -> DeviceBatch:
    """A part as the exchange hands it over: padded, with a mask that is
    not a prefix and a live count nobody has read."""
    b = bridge.arrow_to_device(table)
    valid = np.asarray(b.valid)
    if keep is not None:
        valid = valid & np.resize(np.asarray(keep, dtype=bool), len(valid))
    return DeviceBatch(b.columns, jax.device_put(valid), None, None)


def frame(batch: DeviceBatch, by) -> pd.DataFrame:
    df = bridge.device_to_arrow(batch).to_pandas()
    return df.sort_values(list(by)).reset_index(drop=True) if by else df


def strings(r, n, values, nulls=0.0):
    out = np.array(values, dtype=object)[r.integers(0, len(values), n)]
    if nulls:
        out[r.random(n) < nulls] = None
    return pa.array(out, type=pa.string())


def value_columns(r, n):
    return {"a": r.normal(size=n), "b": r.normal(size=n) * 1e6,
            "n": r.integers(1, 9, n).astype(np.int32)}


def widen(table: pa.Table, name: str) -> DeviceBatch:
    """The part with column ``name`` as a two-limb wide integer (the layout
    of an int64 key on the chip, where x64 is off)."""
    b = bridge.arrow_to_device(table)
    v = np.zeros(b.padded_len, dtype=np.int64)
    v[: table.num_rows] = table[name].to_numpy()
    hi = (v >> 32).astype(np.int32)
    lo = ((v & 0xFFFFFFFF) - 2**31).astype(np.int32)
    cols = dict(b.columns)
    cols[name] = NumCol(jax.device_put(lo), "i", hi=jax.device_put(hi))
    return DeviceBatch(cols, b.valid, None, None)


SUMS = [("a", "sum"), ("b", "sum"), ("n", "sum")]


def case_parts(case):
    """-> (keys, recombine ops, buffered parts, state or None)."""
    r = np.random.default_rng(27)
    odd = [True, False, True, True, False]
    if case == "string_keys_with_different_dictionaries":
        t1 = pa.table({"s": strings(r, 300, ["A", "B", "C"]),
                       "t": strings(r, 300, ["X", "Y"]), **value_columns(r, 300)})
        t2 = pa.table({"s": strings(r, 200, ["D", "B"]),
                       "t": strings(r, 200, ["Z", "Y", "W"]), **value_columns(r, 200)})
        t3 = pa.table({"s": strings(r, 900, ["C", "E", "A", "B"]),
                       "t": strings(r, 900, ["Y"]), **value_columns(r, 900)})
        return ["s", "t"], SUMS, [masked(t1, odd), masked(t2)], masked(t3, odd)
    if case == "null_key":
        ts = [pa.table({"s": strings(r, n, vals, nulls=0.2), **value_columns(r, n)})
              for n, vals in ((250, ["A", "B"]), (250, ["B", "C"]), (100, ["A"]))]
        return ["s"], SUMS, [masked(t, odd) for t in ts], None
    if case == "wide_int_key":
        ts = [pa.table({"k": r.integers(0, 6, n) * (1 << 33) - (1 << 34),
                        **value_columns(r, n)}) for n in (256, 200, 700)]
        return ["k"], SUMS, [widen(t, "k") for t in ts[:2]], widen(ts[2], "k")
    if case == "date_key":
        ts = [pa.table({"d": pa.array(r.integers(9000, 9005, n).astype(np.int32),
                                      type=pa.int32()).cast(pa.date32()),
                        **value_columns(r, n)}) for n in (256, 100, 256)]
        return ["d"], SUMS, [masked(t, odd) for t in ts], None
    if case == "no_keys":
        ts = [pa.table(value_columns(r, n)) for n in (256, 10, 1)]
        return [], SUMS, [masked(t, odd) for t in ts], None
    if case == "all_invalid_part":
        ts = [pa.table({"s": strings(r, n, ["A", "B", "C"]), **value_columns(r, n)})
              for n in (256, 256, 256)]
        return ["s"], SUMS, [masked(ts[0], odd), masked(ts[1], [False]),
                             masked(ts[2])], None
    if case == "min_max_mean_first":
        ts = [pa.table({"s": strings(r, n, ["A", "B", "C"]),
                        "k": r.integers(0, 3, n).astype(np.int32),
                        **value_columns(r, n)}) for n in (256, 256, 1000)]
        ops = [("a", "min"), ("b", "max"), ("n", "mean"), ("a", "first")]
        return ["s", "k"], ops, [masked(t, odd) for t in ts[:2]], masked(ts[2])
    raise AssertionError(case)


def final_parts(rows=300):
    """Partial-form parts of ``sum(v) as sv, avg(v) as av, count(*) as c``
    with a string key whose parts carry different dictionaries."""
    r = np.random.default_rng(3)
    plan = plan_aggregation([
        e if isinstance(e, Alias) else Alias(e, f"col{i}") for i, e in
        enumerate(sqlparse.parse_select_list(
            "sum(v) as sv, avg(v) as av, count(*) as c"))])
    parts = []
    for vals in (list("ABCDEFG"), list("EFGHIJ"), list("KA")):
        cols = {"s": strings(r, rows, vals)}
        for pname, op, _tmp in plan.partials:
            cols[pname] = (r.integers(1, 5, rows).astype(np.int32)
                           if op == "count" else r.normal(size=rows) + 1.0)
        parts.append(masked(pa.table(cols), [True, True, False]))
    return plan, parts


def run_both(monkeypatch, fn):
    """fn() on the compiled path, then with the threshold at zero, which
    sends the same parts down the general path; -> both results and the
    paths each took."""
    c0 = counters()
    compiled = fn()
    c1 = counters()
    monkeypatch.setattr(aggtail, "SMALL_ROWS", 0)
    general = fn()
    c2 = counters()
    monkeypatch.undo()
    took = ((c1[0] - c0[0], c1[1] - c0[1]), (c2[0] - c1[0], c2[1] - c1[1]))
    return compiled, general, took


RECOMBINE_CASES = [
    "string_keys_with_different_dictionaries", "null_key", "wide_int_key",
    "date_key", "no_keys", "all_invalid_part", "min_max_mean_first"]


@pytest.mark.parametrize("case", RECOMBINE_CASES + [
    "having_order_by_desc_string_limit", "limit_alone", "under_the_threshold",
    "over_the_threshold", "checkpoint_and_restore"])
def test_compiled_tail_equals_the_general_path(case, monkeypatch):
    if case in RECOMBINE_CASES:
        keys, ops, buffer, state = case_parts(case)
        # duplicate output names ("a" twice) are two aggregates of one input
        named = [(f"o{i}", op) for i, (_, op) in enumerate(ops)]

        def fold():
            def rename(p):
                cols = {k: p.columns[k] for k in keys}
                for (out, _), (src, _) in zip(named, ops):
                    cols[out] = p.columns[src]
                return DeviceBatch(cols, p.valid, None, None)

            return aggtail.recombine(
                keys, named, [rename(p) for p in buffer],
                None if state is None else rename(state))

        compiled, general, took = run_both(monkeypatch, fold)
        assert took == ((1, 0), (0, 1))
        assert compiled.nrows is None  # nothing was read back
        got, want = frame(compiled, keys), frame(general, keys)
        assert len(want) > 0
        pd.testing.assert_frame_equal(got, want, check_exact=True)
        return
    if case in ("having_order_by_desc_string_limit", "limit_alone"):
        plan, parts = final_parts()
        having = order = None
        if case.startswith("having"):
            having = plan.rewrite(sqlparse.parse_expression("sum(v) > 10"))
            order = [("s", True)]

        def final():
            ex = FinalAggExecutor(["s"], plan, having, order, 4)
            ex.execute(list(parts), 0, 0)
            return ex.done(0)

        compiled, general, took = run_both(monkeypatch, final)
        assert took == ((2, 0), (0, 2))  # one merge, one tail
        got, want = frame(compiled, None), frame(general, None)
        assert list(got.columns) == ["s", "sv", "av", "c"] and len(want) == 4
        assert compiled.padded_len == 256
        if not order:
            # LIMIT alone keeps whichever groups come first, and the order
            # of groups follows the table's size: any 4 rows of the answer
            monkeypatch.setattr(aggtail, "SMALL_ROWS", 0)
            ex = FinalAggExecutor(["s"], plan)
            ex.execute(list(parts), 0, 0)
            whole = frame(ex.done(0), ["s"])
            assert got["s"].is_unique and len(got) == 4
            want = whole[whole["s"].isin(got["s"])].reset_index(drop=True)
            got = got.sort_values("s").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, want, check_exact=True)
        if order:
            assert list(got["s"]) == sorted(got["s"], reverse=True)
            assert compiled.sorted_by == general.sorted_by == ["s"]
        return
    if case in ("under_the_threshold", "over_the_threshold"):
        r = np.random.default_rng(5)
        n = 1 << 14  # a rung of the ladder: four parts are 1 << 16 rows
        ts = [pa.table({"k": r.integers(0, 50, n).astype(np.int32),
                        **value_columns(r, n)}) for _ in range(4)]
        if case == "over_the_threshold":
            ts.append(pa.table(
                {"k": np.arange(3, dtype=np.int32), **value_columns(r, 3)}))
        parts = [masked(t) for t in ts]
        assert (sum(p.padded_len for p in parts) > 1 << 16) == (
            case == "over_the_threshold")
        c0 = counters()
        out = aggtail.recombine(["k"], SUMS, parts, None)
        c1 = counters()
        assert (c1[0] - c0[0], c1[1] - c0[1]) == (
            (0, 1) if case == "over_the_threshold" else (1, 0))
        want = pd.concat([t.to_pandas() for t in ts]).groupby("k")["n"].sum()
        assert list(frame(out, ["k"])["n"]) == list(want.sort_index())
        return
    assert case == "checkpoint_and_restore"
    r = np.random.default_rng(9)
    plan = plan_aggregation([
        e for e in sqlparse.parse_select_list(
            "sum(v) as sv, count(*) as c, min(v) as lo")])
    # integer-valued floats: a checkpoint folds early, which regroups the
    # additions, and these sums are exact under any grouping
    tables = [pa.table({"s": strings(r, 500, ["A", "B", "C", "D"][: 2 + i % 3]),
                        "v": r.integers(-50, 50, 500).astype(np.float64)})
              for i in range(11)]

    def run(checkpoint_after=None):
        ex = PartialAggExecutor(["s"], plan)
        for i, t in enumerate(tables):
            if i == checkpoint_after:
                blob = ex.checkpoint()
                ex = PartialAggExecutor(["s"], plan)
                ex.restore(blob)
            assert ex.execute([bridge.arrow_to_device(t)], 0, 0) is None
        fin = FinalAggExecutor(["s"], plan)
        fin.execute([ex.done(0)], 0, 0)
        return frame(fin.done(0), ["s"])

    c0 = counters()
    want = run()
    c1 = counters()
    assert c1[1] == c0[1] and c1[0] > c0[0]  # every fold compiled
    assert list(want["c"]) == [
        sum((np.asarray(t["s"].to_pylist()) == k).sum() for t in tables)
        for k in want["s"]]
    for k in (3, 8):
        # (under x64 a restored state's int32 counts come back as int64)
        pd.testing.assert_frame_equal(run(checkpoint_after=k), want,
                                      check_exact=True, check_dtype=False)


def test_a_dictionarys_tables_are_copied_to_the_device_once():
    """hash_limbs and the string sort read a dictionary's hash and rank
    tables from one cached device copy per dictionary, not one per call, and
    give what the host tables give (nulls: hash (0, 0), rank -1)."""
    from quokka_tpu.ops import batch as qbatch, kernels

    b = bridge.arrow_to_device(pa.table(
        {"s": pa.array(["pear", "fig", None, "pear", "apple"], pa.string())}))
    col = b.columns["s"]
    d, codes = col.dictionary, np.asarray(col.codes)[:5]
    assert qbatch.hash_tables(d)[0] is qbatch.hash_tables(d)[0]
    assert qbatch.rank_table(d) is qbatch.rank_table(d)
    null = (codes < 0) | np.array([d.values[c] is None for c in codes])
    hi, lo = col.hash_limbs()
    for got, table in ((hi, d.hash_hi), (lo, d.hash_lo)):
        np.testing.assert_array_equal(
            np.asarray(got)[:5], np.where(null, 0, table[np.maximum(codes, 0)]))
    (limb,) = kernels.sort_limbs(b, ["s"])
    live = np.asarray(limb)[:5]
    words = [None if n else d.values[c] for c, n in zip(codes, null)]
    order = sorted(range(5), key=lambda i: (words[i] is not None, words[i] or ""))
    assert list(np.argsort(live, kind="stable")) == order


def test_the_program_set_follows_the_plan_not_the_arrival(monkeypatch):
    """A Q1-shaped plan with 1, 2, 3, 5 and 8 partials buffered asks the
    compile plane for one agg_recombine key throughout, and from the first
    done() to the result every compile is a compile-plane miss: with the jit
    caches cold, each eager jnp call would compile a jit_<op> program of its
    own that no compile.acquire event names."""
    lineitem = tpch_data.generate(sf=0.003, seed=11)["lineitem"]
    outputs = sqlparse.parse_select_list(
        "sum(l_quantity) as sum_qty, sum(l_extendedprice) as sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
        "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
        "avg(l_discount) as avg_disc, count(*) as count_order")
    plan = plan_aggregation(outputs)
    keys = ["l_returnflag", "l_linestatus"]
    order = [("l_returnflag", False), ("l_linestatus", False)]
    want = (lineitem.to_pandas().groupby(keys)
            .agg(sum_qty=("l_quantity", "sum"), count_order=("l_quantity", "size"))
            .reset_index())
    asked = []
    real_dispatch = aggtail._dispatch_program
    monkeypatch.setattr(
        aggtail, "_dispatch_program",
        lambda sig, builder, args: (asked.append(sig),
                                    real_dispatch(sig, builder, args))[1])
    # no persisted executable to load: a program not yet in memory is a miss
    monkeypatch.setattr(compileplane, "_entry_path",
                        lambda key, create=False: None)
    for key in [k for k in fuse._FUSED_PROGRAMS if k[0].startswith("agg_")]:
        del fuse._FUSED_PROGRAMS[key]
    recombine_keys, missed = [], []
    for n_partials in (1, 2, 3, 5, 8):
        step = -(-lineitem.num_rows // n_partials)
        partial = PartialAggExecutor(keys, plan)
        for i in range(n_partials):
            batch = bridge.arrow_to_device(lineitem.slice(i * step, step))
            assert partial.execute([batch], 0, 0) is None
        del asked[:]
        jax.clear_caches()
        seq = (obs.RECORDER.snapshot() or [[-1]])[-1][0]
        before = compilestats.snapshot()["backend_compiles"]
        final = FinalAggExecutor(keys, plan, None, order, None)
        final.execute([partial.done(0)], 0, 0)
        out = final.done(0)
        compiles = compilestats.snapshot()["backend_compiles"] - before
        misses = [ev for ev in obs.RECORDER.snapshot(since=seq)
                  if ev[2] == "span" and ev[3] == "compile.acquire"
                  and ev[6]["hit"] == "miss"]
        assert compiles == len(misses), (n_partials, compiles, misses)
        missed.extend(ev[6]["kind"] for ev in misses)
        recombine_keys.append({s for s in asked if s[0] == "agg_recombine"})
        assert [s[0] for s in asked].count("agg_final_tail") == 1
        got = frame(out, None)
        assert list(got["l_returnflag"] + got["l_linestatus"]) == sorted(
            want["l_returnflag"] + want["l_linestatus"])  # ORDER BY held
        assert list(got["count_order"]) == list(want["count_order"])
        np.testing.assert_allclose(got["sum_qty"], want["sum_qty"], rtol=1e-12)
    # the first round compiled the two programs (both executors' merges are
    # one agg_recombine); all five rounds asked for the same
    assert sorted(missed) == ["agg_final_tail", "agg_recombine"]
    assert len(recombine_keys[0]) == 1
    assert all(keys_ == recombine_keys[0] for keys_ in recombine_keys)
