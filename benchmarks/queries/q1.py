"""TPC-H Q1 (pricing summary report).  One parameter, ``delta_days``: the
specification's DELTA, drawn per request from [60, 120] (clause 2.4.1.3; the
validation value is 90).

``build`` and ``reference`` are copied from chip_smoke.py (build_q1, ref_q1)
at commit 949ddc4 and given the parameter; ``control`` is the same reference
in one of ``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools

import numpy as np
import pandas as pd
import pyarrow.parquet as pq

from harness import lowprec
from harness.tables import row_count

COLUMNS = {"lineitem": [
    "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
    "l_discount", "l_tax", "l_shipdate"]}
# rows sorted by these before comparing; these columns compared exactly
SORT_KEYS = ["l_returnflag", "l_linestatus"]
EXACT = ["l_returnflag", "l_linestatus", "count_order"]
# largest relative error of a sum or mean: see PERF.md section 2 for the
# readings each limit stands between
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-4}

AGGS = (
    "sum(l_quantity) as sum_qty, "
    "sum(l_extendedprice) as sum_base_price, "
    "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
    "avg(l_quantity) as avg_qty, "
    "avg(l_extendedprice) as avg_price, "
    "avg(l_discount) as avg_disc, "
    "count(*) as count_order"
)


def build(ctx, paths, params):
    delta = int(params.get("delta_days", 90))
    return (
        ctx.read_parquet(paths["lineitem"], columns=COLUMNS["lineitem"])
        .filter_sql(f"l_shipdate <= date '1998-12-01' - interval '{delta}' day")
        .groupby(["l_returnflag", "l_linestatus"])
        .agg_sql(AGGS)
    )


@functools.lru_cache(maxsize=1)
def _lineitem(path):
    """The scanned columns as numpy arrays, each flag as codes and names."""
    table = pq.read_table(path, columns=COLUMNS["lineitem"])
    cols = {c: table.column(c).to_numpy() for c in COLUMNS["lineitem"][2:]}
    cols["l_shipdate"] = cols["l_shipdate"].astype("datetime64[D]")
    names = {}
    for c, short in zip(SORT_KEYS, ("flag", "status")):
        coded = table.column(c).combine_chunks().dictionary_encode()
        cols[short] = coded.indices.to_numpy()
        names[c] = coded.dictionary.to_pylist()
    return cols, names


def _answer(paths, params, precision):
    cols, names = _lineitem(paths["lineitem"])
    cut = np.datetime64("1998-12-01") - np.timedelta64(
        int(params.get("delta_days", 90)), "D")
    keep = cols["l_shipdate"] <= cut
    qty, price, disc, tax = (precision.column(cols[c][keep]) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (1 - disc)
    n_status = len(names["l_linestatus"])
    group = cols["flag"][keep].astype(np.int64) * n_status + cols["status"][keep]

    def total(values):  # per group, in float64 (float32 and above: exact)
        return np.bincount(group, precision.accumulator(values))

    count = np.bincount(group)
    seen = np.flatnonzero(count)
    count = count[seen]
    sum_qty, sum_price, sum_disc = (total(v)[seen] for v in (qty, price, disc))
    out = pd.DataFrame({
        "l_returnflag": [names["l_returnflag"][g // n_status] for g in seen],
        "l_linestatus": [names["l_linestatus"][g % n_status] for g in seen],
        "sum_qty": sum_qty,
        "sum_base_price": sum_price,
        "sum_disc_price": total(disc_price)[seen],
        "sum_charge": total(disc_price * (1 + tax))[seen],
        "avg_qty": sum_qty / count,
        "avg_price": sum_price / count,
        "avg_disc": sum_disc / count,
        "count_order": count,
    })
    return precision.results(out, EXACT)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Bytes no correct Q1 can skip: every row of the seven scanned columns
    at the 4 bytes the device holds each in, and the 4 x 10 result."""
    return (row_count(paths, "lineitem") * 4 * len(COLUMNS["lineitem"])
            + 4 * 10 * 8)
