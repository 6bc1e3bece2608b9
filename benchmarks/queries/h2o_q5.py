"""h2oai db-benchmark, group-by task, question 5 ("basic questions"):
``sum(v1), sum(v2), sum(v3) by id6`` over the G1 table: every row into one of
N/K groups of about K rows on a one-limb integer key.  No parameter: the
source's questions have none, so every request of a run is the same query.

``reference`` is pandas over the same Parquet file in int64 / float64,
independent of the program; ``control`` is the same sums with ``v3`` in one
of ``harness/lowprec.py``'s bfloat16 precisions.
"""

import functools

import numpy as np
import pandas as pd
import pyarrow.parquet as pq

from harness import lowprec
from harness.tables import read_columns, row_count

COLUMNS = {"g1": ["id6", "v1", "v2", "v3"]}
SORT_KEYS = ["id6"]
# integer sums are exact in int32: at most 15 x the few hundred rows a group has
EXACT = ["id6", "v1", "v2"]
# see PERF.md section 2 for the readings the limit stands between
LIMITS = {"wrong_cells": 0, "sum_rel_err": 1e-5}


def build(ctx, paths, params):
    return (
        ctx.read_parquet(paths["g1"])
        .groupby("id6")
        .agg_sql("sum(v1) as v1, sum(v2) as v2, sum(v3) as v3")
    )


@functools.lru_cache(maxsize=1)
def _g1(path):
    return read_columns({"g1": path}, "g1", COLUMNS["g1"])


def reference(paths, params):
    g1 = _g1(paths["g1"])
    return g1.astype({"v1": np.int64, "v2": np.int64, "v3": np.float64}).groupby(
        "id6")[["v1", "v2", "v3"]].sum().reset_index()


def control(paths, params, precision):
    """The integer sums as they are (exact in any precision that holds the
    integers to 15); ``v3`` stored, summed and left as ``precision`` says."""
    g1 = _g1(paths["g1"])
    ids, group = np.unique(g1["id6"].to_numpy(), return_inverse=True)
    v3 = precision.accumulator(precision.column(g1["v3"].to_numpy()))
    out = pd.DataFrame({
        "id6": ids,
        "v1": np.bincount(group, g1["v1"].to_numpy()).astype(np.int64),
        "v2": np.bincount(group, g1["v2"].to_numpy()).astype(np.int64),
        "v3": np.bincount(group, v3),  # float64 bins: exact for float32 terms
    })
    return precision.results(out, EXACT)


def groups(paths) -> int:
    """The largest ``id6`` any row group's statistics hold: ids run from 1,
    drawn with replacement, so this bounds the groups from above."""
    meta = pq.read_metadata(paths["g1"])
    at = meta.schema.names.index("id6")
    return max(meta.row_group(i).column(at).statistics.max
               for i in range(meta.num_row_groups))


def least_bytes(paths):
    """Every row's key and three values at 4 bytes each, read once, and the
    answer's four columns a group written once (160 MB a query at 1e7 rows
    and 1e5 groups)."""
    return row_count(paths, "g1") * 4 * 4 + groups(paths) * 4 * 4
