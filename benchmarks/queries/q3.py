"""TPC-H Q3 (shipping priority).  Two parameters drawn per request (clause
2.4.3.3): ``segment`` from the five market segments and ``date`` from
[1995-03-01, 1995-03-31]; the validation values are BUILDING, 1995-03-15.

``build`` and ``reference`` are copied from chip_smoke.py (build_q3, ref_q3)
at commit 949ddc4 and given the parameters (the reference joins first and
filters after, once per request's values: the same rows); ``control`` is the
same reference in one of ``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools

import numpy as np
import pandas as pd

from harness import lowprec
from harness.tables import read_columns, row_count

COLUMNS = {
    "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "customer": ["c_custkey", "c_mktsegment"],
}
# the query's own order (top 10 by revenue) is part of the answer
SORT_KEYS = None
EXACT = ["l_orderkey", "o_orderdate", "o_shippriority"]
# sums of at most 7 rows: see PERF.md section 2 for the readings
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-5}


def _values(params):
    return (params.get("segment", "BUILDING"),
            str(params.get("date", "1995-03-15")))


def build(ctx, paths, params):
    from quokka_tpu.expression import col

    segment, date = _values(params)
    lineitem = ctx.read_parquet(paths["lineitem"], columns=COLUMNS["lineitem"])
    orders = ctx.read_parquet(paths["orders"], columns=COLUMNS["orders"])
    customer = ctx.read_parquet(paths["customer"], columns=COLUMNS["customer"])
    return (
        lineitem.filter_sql(f"l_shipdate > date '{date}'")
        .join(orders.filter_sql(f"o_orderdate < date '{date}'"),
              left_on="l_orderkey", right_on="o_orderkey")
        .join(customer.filter(col("c_mktsegment") == segment),
              left_on="o_custkey", right_on="c_custkey")
        .groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue")
        .top_k(["revenue"], 10, [True])
    )


@functools.lru_cache(maxsize=1)
def _joined(lineitem, orders, customer):
    """lineitem x orders x customer, unfiltered, as numpy arrays."""
    paths = {"lineitem": lineitem, "orders": orders, "customer": customer}
    li, o, c = (read_columns(paths, t, COLUMNS[t]) for t in COLUMNS)
    c["c_mktsegment"] = c.c_mktsegment.astype("category")
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey"))
    keep = EXACT + ["l_shipdate", "l_extendedprice", "l_discount"]
    cols = {name: j[name].to_numpy() for name in keep}
    cols["segment"] = j.c_mktsegment.cat.codes.to_numpy()
    return cols, list(j.c_mktsegment.cat.categories)


def _answer(paths, params, precision):
    segment, date = _values(params)
    cut = np.datetime64(date)
    cols, segments = _joined(*(paths[t] for t in COLUMNS))
    keep = ((cols["l_shipdate"] > cut) & (cols["o_orderdate"] < cut)
            & (cols["segment"] == segments.index(segment)))
    price, disc = (precision.column(cols[c][keep])
                   for c in ("l_extendedprice", "l_discount"))
    j = pd.DataFrame({c: cols[c][keep] for c in EXACT})
    j["revenue"] = precision.accumulator(price * (1 - disc))
    j["o_orderdate"] = j.o_orderdate.dt.date
    g = j.groupby(EXACT).revenue.sum().reset_index()
    return precision.results(
        g.sort_values("revenue", ascending=False).head(10), EXACT)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Every row of the ten scanned columns at 4 bytes each, and the
    10 x 4 result."""
    return (sum(row_count(paths, t) * 4 * len(cols)
                for t, cols in COLUMNS.items()) + 10 * 4 * 8)
