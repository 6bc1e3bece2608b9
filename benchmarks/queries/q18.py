"""TPC-H Q18 (large volume customer, clause 2.4.18).  One parameter drawn per
request (2.4.18.3): ``quantity``, an integer in [312, 315]; the validation
value is 300.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey
                         having sum(l_quantity) > [QUANTITY])
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate
    -- the first 100 rows

``build`` is that SQL as a plan: lineitem is read twice, once into the
subquery's group-by, whose HAVING output builds the semi join that orders
probe, and once as the probe side of the join against those orders (with
their customer).  ``reference`` is the SQL in pandas and numpy float64 over
the same Parquet files; ``control`` is the same reference in one of
``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools

import numpy as np
import pandas as pd

from harness import lowprec
from harness.tables import read_columns, row_count

COLUMNS = {
    "lineitem": ["l_orderkey", "l_quantity"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "customer": ["c_custkey", "c_name"],
}
KEYS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"]
# the query's own order is part of the answer
SORT_KEYS = None
EXACT = ["c_name", "c_custkey", "o_orderkey", "o_orderdate"]
# sum_qty sums at most 7 integers of 1..50; o_totalprice is a float32 group
# key on the device: see PERF.md section 2 for the readings the limits stand
# between
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-5, "rel_err.o_totalprice": 1e-6}
ROWS = 100
# lineitem's rows and its distinct l_orderkey, as the reference counted them
# (metrics/highcard_groupby_roofline.py reads them after the answers were
# judged)
SUBQUERY = {}


def _quantity(params):
    return int(params.get("quantity", 300))


def build(ctx, paths, params):
    def read(table):
        return ctx.read_parquet(paths[table], columns=COLUMNS[table])

    large = (read("lineitem").groupby("l_orderkey")
             .agg_sql("sum(l_quantity) as order_qty")
             .filter_sql(f"order_qty > {_quantity(params)}"))
    orders = (read("orders")
              .join(large, left_on="o_orderkey", right_on="l_orderkey",
                    how="semi")
              .join(read("customer"), left_on="o_custkey",
                    right_on="c_custkey"))
    # each join keeps its probe's key: o_custkey carries c_custkey, and
    # l_orderkey carries o_orderkey
    return (
        read("lineitem")
        .join(orders, left_on="l_orderkey", right_on="o_orderkey")
        .groupby(["c_name", "o_custkey", "l_orderkey", "o_orderdate",
                  "o_totalprice"])
        .agg_sql("sum(l_quantity) as sum_qty")
        .top_k(["o_totalprice", "o_orderdate"], ROWS, [True, False])
        .rename({"o_custkey": "c_custkey", "l_orderkey": "o_orderkey"})
    )


@functools.lru_cache(maxsize=1)
def _tables(lineitem, orders, customer):
    paths = {"lineitem": lineitem, "orders": orders, "customer": customer}
    li, o, c = (read_columns(paths, t, COLUMNS[t]) for t in COLUMNS)
    SUBQUERY.update(rows=len(li), groups=int(li.l_orderkey.nunique()))
    return li, o, c


def _answer(paths, params, precision):
    li, o, c = _tables(*(paths[t] for t in COLUMNS))
    li = pd.DataFrame({"l_orderkey": li.l_orderkey.to_numpy(),
                       "l_quantity": precision.accumulator(
                           precision.column(li.l_quantity.to_numpy()))})
    per_order = precision.results(
        li.groupby("l_orderkey").l_quantity.sum().reset_index(),
        ["l_orderkey"])
    large = per_order.l_orderkey[per_order.l_quantity > _quantity(params)]
    j = (li.merge(o[o.o_orderkey.isin(large)], left_on="l_orderkey",
                  right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey"))
    j["o_totalprice"] = precision.column(
        j.o_totalprice.to_numpy()).astype(np.float64)
    g = (j.groupby(KEYS).l_quantity.sum().rename("sum_qty").reset_index()
         .sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(ROWS))
    g["o_orderdate"] = g.o_orderdate.dt.date
    return precision.results(g.reset_index(drop=True), EXACT)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Every row of the eight scanned columns at 4 bytes each, lineitem's
    two columns twice (it is read twice), and the 100 x 6 result."""
    return (sum(row_count(paths, t) * 4 * len(cols)
                for t, cols in COLUMNS.items())
            + row_count(paths, "lineitem") * 4 * len(COLUMNS["lineitem"])
            + ROWS * 6 * 4)
