"""Tick backtest: each trade joined asof (backward) to its symbol's last
quote, notional = bid * size, summed and counted per symbol.  No parameter:
upstream's apps/time-series query has none.

``build`` and ``reference`` are copied from chip_smoke.py (build_asof,
ref_asof) at commit 949ddc4; ``control`` is the same reference in one of
``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools

import pandas as pd

from harness import lowprec
from harness.tables import read_columns, row_count

COLUMNS = {"trades": ["time", "symbol", "size"],
           "quotes": ["time", "symbol", "bid"]}
SORT_KEYS = ["symbol"]
EXACT = ["symbol", "n"]
# see PERF.md section 2 for the readings each limit stands between
LIMITS = {"wrong_cells": 0, "sum_rel_err": 4e-5}


def build(ctx, paths, params):
    t = ctx.read_sorted_parquet(paths["trades"], sorted_by="time")
    q = ctx.read_sorted_parquet(paths["quotes"], sorted_by="time")
    return (
        t.join_asof(q, on="time", by="symbol")
        .with_columns_sql("bid * size as notional")
        .groupby("symbol")
        .agg_sql("sum(notional) as total, count(*) as n")
    )


@functools.lru_cache(maxsize=1)
def _joined(trades, quotes):
    paths = {"trades": trades, "quotes": quotes}
    t, q = (read_columns(paths, name, COLUMNS[name]) for name in COLUMNS)
    return pd.merge_asof(t, q, on="time", by="symbol",
                         direction="backward").dropna(subset=["bid"])


def _answer(paths, params, precision):
    j = _joined(paths["trades"], paths["quotes"])
    bid, size = (precision.column(j[c].to_numpy()) for c in ("bid", "size"))
    j = j.assign(notional=precision.accumulator(bid * size))
    out = j.groupby("symbol").agg(
        total=("notional", "sum"), n=("notional", "size")).reset_index()
    return precision.results(out, EXACT)


def reference(paths, params):
    return _answer(paths, params, lowprec.FLOAT64)


def control(paths, params, precision):
    return _answer(paths, params, precision)


def least_bytes(paths):
    """Every row of both tables' three columns at 4 bytes each (a day in ms
    fits 32 bits), and the symbols x 3 result."""
    symbols = 100
    return (sum(row_count(paths, t) * 4 * len(cols)
                for t, cols in COLUMNS.items()) + symbols * 3 * 8)
