"""TPC-H Q9 (product type profit measure, clause 2.4.9).  One parameter drawn
per request (2.4.9.3): ``color``, one of the 92 words of P_NAME's list; the
validation value is ``green``.

    select nation, o_year, sum(amount) as sum_profit
    from (select n_name as nation, extract(year from o_orderdate) as o_year,
                 l_extendedprice * (1 - l_discount)
                 - ps_supplycost * l_quantity as amount
          from part, supplier, lineitem, partsupp, orders, nation
          where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
            and ps_partkey = l_partkey and p_partkey = l_partkey
            and o_orderkey = l_orderkey and s_nationkey = n_nationkey
            and p_name like '%[COLOR]%') as profit
    group by nation, o_year
    order by nation, o_year desc

``build`` is the plan of tests/test_tpch2.py::test_q9 with the ORDER BY and
the parameter; ``reference`` is written from the SQL above in pandas and
numpy float64 over the same Parquet files (the join of the five tables that
no parameter touches is made once a run, the part filter once per colour);
``control`` is the same reference in one of ``harness/lowprec.py``'s bfloat16
precisions.
"""

import functools

import numpy as np
import pandas as pd

from harness import lowprec
from harness.tables import read_columns, row_count

COLUMNS = {
    "lineitem": ["l_partkey", "l_suppkey", "l_orderkey", "l_quantity",
                 "l_extendedprice", "l_discount"],
    "part": ["p_partkey", "p_name"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
    "orders": ["o_orderkey", "o_orderdate"],
}
SORT_KEYS = EXACT = ["nation", "o_year"]
MEASURES = ["l_extendedprice", "l_discount", "ps_supplycost", "l_quantity"]
GROUPS = 25 * 7  # nations x order years 1992..1998
# sums of about 1,900 profits a group at SF 1: see PERF.md section 2 for the
# readings the limit stands between
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-5}
# colour -> line items the part filter keeps, as the reference counted them
# (metrics/join_roofline.py reads it after the answers were judged)
MATCHED_ROWS = {}


def _color(params):
    return str(params.get("color", "green"))


def build(ctx, paths, params):
    t = {name: ctx.read_parquet(paths[name], columns=cols)
         for name, cols in COLUMNS.items()}
    return (
        t["lineitem"]
        .join(t["part"].filter_sql(f"p_name like '%{_color(params)}%'"),
              left_on="l_partkey", right_on="p_partkey", how="semi")
        .join(t["partsupp"], left_on=["l_partkey", "l_suppkey"],
              right_on=["ps_partkey", "ps_suppkey"])
        .join(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .join(t["nation"], left_on="s_nationkey", right_on="n_nationkey")
        .join(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .with_columns_sql(
            "n_name as nation, extract(year from o_orderdate) as o_year, "
            "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity "
            "as amount")
        .groupby(["nation", "o_year"],
                 orderby=["nation", ("o_year", "desc")])
        .agg_sql("sum(amount) as sum_profit")
    )


@functools.lru_cache(maxsize=1)
def _joined(*files):
    """lineitem x partsupp x supplier x nation x orders: every predicate of
    the WHERE clause but the two on part, as numpy arrays; and part."""
    paths = dict(zip(COLUMNS, files))
    li, pt, ps, su, na, o = (read_columns(paths, t, COLUMNS[t])
                             for t in COLUMNS)
    j = (li.merge(ps, left_on=["l_partkey", "l_suppkey"],
                  right_on=["ps_partkey", "ps_suppkey"])
         .merge(su, left_on="l_suppkey", right_on="s_suppkey")
         .merge(na, left_on="s_nationkey", right_on="n_nationkey")
         .merge(o, left_on="l_orderkey", right_on="o_orderkey"))
    cols = {c: j[c].to_numpy() for c in ["l_partkey", "n_name"] + MEASURES}
    cols["o_year"] = j.o_orderdate.dt.year.to_numpy()
    return cols, pt


def _matching(paths, params):
    """(the joined columns, the mask of the rows whose part's name holds the
    colour)."""
    cols, part = _joined(*(paths[t] for t in COLUMNS))
    color = _color(params)
    keys = part.p_partkey[part.p_name.str.contains(color, regex=False)]
    keep = np.isin(cols["l_partkey"], keys.to_numpy())
    MATCHED_ROWS[color] = int(keep.sum())
    return cols, keep


def _answer(paths, params, precision):
    cols, keep = _matching(paths, params)
    price, disc, cost, qty = (precision.column(cols[c][keep])
                              for c in MEASURES)
    j = pd.DataFrame({"nation": cols["n_name"][keep],
                      "o_year": cols["o_year"][keep]})
    j["sum_profit"] = precision.accumulator(price * (1 - disc) - cost * qty)
    g = j.groupby(EXACT).sum_profit.sum().reset_index()
    return precision.results(
        g.sort_values(EXACT, ascending=[True, False]), EXACT)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Every row of the seventeen scanned columns at 4 bytes each, and the
    175 x 3 result."""
    return (sum(row_count(paths, t) * 4 * len(cols)
                for t, cols in COLUMNS.items()) + GROUPS * 3 * 4)
