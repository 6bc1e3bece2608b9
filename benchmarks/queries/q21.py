"""TPC-H Q21 (suppliers who kept orders waiting, clause 2.4.21).  One
parameter drawn per request (2.4.21.3): ``nation``, one of the 25 N_NAME
values; the validation value is SAUDI ARABIA.

    select s_name, count(*) as numwait
    from supplier, lineitem l1, orders, nation
    where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey
      and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate
      and exists (select * from lineitem l2
                  where l2.l_orderkey = l1.l_orderkey
                    and l2.l_suppkey <> l1.l_suppkey)
      and not exists (select * from lineitem l3
                      where l3.l_orderkey = l1.l_orderkey
                        and l3.l_suppkey <> l1.l_suppkey
                        and l3.l_receiptdate > l3.l_commitdate)
      and s_nationkey = n_nationkey and n_name = '[NATION]'
    group by s_name order by numwait desc, s_name
    -- the first 100 rows

The engine has no join with a residual inequality, so ``build``
decorrelates: given that l1 is late, EXISTS holds where the order's lines
have more than one distinct supplier, and NOT EXISTS where its late lines
have exactly one.  Both counts are ``count(distinct l_suppkey)`` by order,
each a distinct on (l_orderkey, l_suppkey) then a count, and they build the
joins that the late line items (l1, every one of them) probe.  ``reference``
is the SQL in pandas over the same Parquet files, each order's suppliers and
late suppliers as sets; ``control`` is the same reference with the two dates
as day numbers in one of ``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools
import importlib.util

import numpy as np
import pandas as pd

from harness import lowprec
from harness.tables import read_columns, row_count

# This cell measures the streaming distinct that folds each batch into
# sorted state (quokka_tpu/ops/distinct.py) and counts it on each query's
# record (obs/querylog.py ``distinct_sort_slots``).  A program without it
# de-duplicates by an anti join of every batch against all the keys seen
# before: its state's every size is a new program, and a run on a cold
# compile cache does not reach its window inside a run's time limit.  Such a
# program is refused here, before a table is made, with a non-zero exit.  The
# test reads the record's counters and not a file's presence, so a tree
# holding the new module beside an older program is refused as well.
DISTINCT_COUNTERS = ("distinct_rows_in", "distinct_rows_out",
                     "distinct_sort_slots")


def streaming_distinct_missing() -> bool:
    """True where the program lacks the streaming distinct or its counters."""
    if importlib.util.find_spec("quokka_tpu.ops.distinct") is None:
        return True
    from quokka_tpu.obs import querylog

    return not set(DISTINCT_COUNTERS) <= set(querylog.COUNTS)


if streaming_distinct_missing():
    raise SystemExit(
        "q21: this program has no streaming distinct folded into sorted "
        "state and counted on its query records (quokka_tpu/ops/distinct.py, "
        "obs/querylog.py); its per-batch anti join cannot run TPC-H Q21 at "
        "SF 1 inside a run's time limit")

COLUMNS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"],
    "orders": ["o_orderkey", "o_orderstatus"],
    "supplier": ["s_suppkey", "s_name", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}
# the query's own order is part of the answer
SORT_KEYS = None
EXACT = ["s_name", "numwait"]
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-5}
ROWS = 100
# the rows into each of the plan's two distincts and the (l_orderkey,
# l_suppkey) pairs out of it, as the reference counted them
# (metrics/distinct_roofline.py reads them after the answers were judged)
DISTINCT = {}


def _nation(params):
    return params.get("nation", "SAUDI ARABIA")


def build(ctx, paths, params):
    def read(table):
        return ctx.read_parquet(paths[table], columns=COLUMNS[table])

    lineitem = read("lineitem")
    late = lineitem.filter_sql("l_receiptdate > l_commitdate")
    n_supp = (lineitem.groupby("l_orderkey")
              .agg_sql("count(distinct l_suppkey) as n_supp")
              .rename({"l_orderkey": "ns_orderkey"}))
    n_late = (late.groupby("l_orderkey")
              .agg_sql("count(distinct l_suppkey) as n_late")
              .rename({"l_orderkey": "nl_orderkey"}))
    nation = read("nation").filter_sql(f"n_name = '{_nation(params)}'")
    return (
        late.select(["l_orderkey", "l_suppkey"])
        .join(read("orders").filter_sql("o_orderstatus = 'F'"),
              left_on="l_orderkey", right_on="o_orderkey", how="semi")
        .join(n_supp, left_on="l_orderkey", right_on="ns_orderkey")
        .join(n_late, left_on="l_orderkey", right_on="nl_orderkey")
        .filter_sql("n_supp > 1 and n_late = 1")
        .join(read("supplier"), left_on="l_suppkey", right_on="s_suppkey")
        .join(nation, left_on="s_nationkey", right_on="n_nationkey",
              how="semi")
        .groupby("s_name")
        .agg_sql("count(*) as numwait")
        .top_k(["numwait", "s_name"], ROWS, [True, False])
    )


def _days(dates: pd.Series) -> np.ndarray:
    return dates.to_numpy().astype("datetime64[D]").astype(np.int64)


def _suppliers(lines: pd.DataFrame) -> pd.Series:
    """Each order's set of suppliers, by its size."""
    pairs = lines[["l_orderkey", "l_suppkey"]].drop_duplicates()
    return pairs.groupby("l_orderkey").size()


@functools.lru_cache(maxsize=1)
def _tables(lineitem, orders, supplier, nation):
    paths = {"lineitem": lineitem, "orders": orders, "supplier": supplier,
             "nation": nation}
    li, o, s, n = (read_columns(paths, t, COLUMNS[t]) for t in COLUMNS)
    commit, receipt = _days(li.l_commitdate), _days(li.l_receiptdate)
    li = li[["l_orderkey", "l_suppkey"]]
    late = receipt > commit
    DISTINCT.update(
        all={"rows": len(li), "pairs": int(_suppliers(li).sum())},
        late={"rows": int(late.sum()),
              "pairs": int(_suppliers(li[late]).sum())})
    return li, commit, receipt, o, s, n


@functools.lru_cache(maxsize=4)
def _waiting(lineitem, orders, supplier, nation, precision):
    """The late line items (l1) of 'F' orders for which EXISTS and NOT
    EXISTS hold, with the dates in ``precision``: every nation's."""
    li, commit, receipt, o, _, _ = _tables(lineitem, orders, supplier,
                                           nation)
    late = precision.column(receipt) > precision.column(commit)
    l1 = li[late]
    return l1[l1.l_orderkey.isin(o.o_orderkey[o.o_orderstatus == "F"])
              & (l1.l_orderkey.map(_suppliers(li)) > 1)
              & (l1.l_orderkey.map(_suppliers(l1)) == 1)]


def _answer(paths, params, precision):
    tables = tuple(paths[t] for t in COLUMNS)
    _, _, _, _, s, n = _tables(*tables)
    keys = n.n_nationkey[n.n_name == _nation(params)]
    named = s[s.s_nationkey.isin(keys)]
    g = (_waiting(*tables, precision)
         .merge(named, left_on="l_suppkey", right_on="s_suppkey")
         .groupby("s_name").size().rename("numwait").reset_index()
         .sort_values(["numwait", "s_name"], ascending=[False, True],
                      kind="stable").head(ROWS))
    return g.reset_index(drop=True)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Every row of the eleven scanned columns at 4 bytes each, and the
    100 x 2 result."""
    return (sum(row_count(paths, t) * 4 * len(cols)
                for t, cols in COLUMNS.items())
            + ROWS * 2 * 4)
