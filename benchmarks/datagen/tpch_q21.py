"""TPC-H's eight tables from a seed for Q21: ``datagen/tpch8.py``'s tables
for the same seed and scale (loaded, not edited), with one column
**replaced**: ``o_orderstatus``, by clause 4.2.3, from the order's lines
rather than drawn: 'F' where every line's ``l_linestatus`` is 'F', 'O' where
every one is 'O', 'P' otherwise.  Nothing else is drawn, so every other
column equals ``tpch8.py``'s for the seed.  The status is computed over the
lines' runs (lineitem holds each order's lines together, in the order of
``orders``) by numpy reductions; no row is touched by a Python loop.
"""

import numpy as np
import pyarrow as pa

from harness import spec

STATUSES = ["F", "O", "P"]


def order_status(l_orderkey: np.ndarray, line_open: np.ndarray,
                 o_orderkey: np.ndarray) -> np.ndarray:
    """Clause 4.2.3's O_ORDERSTATUS code (an index into ``STATUSES``) of
    each order of ``o_orderkey``, from its lines' keys and whether each line
    is open ('O'); every order needs at least one line."""
    starts = np.flatnonzero(np.r_[True, l_orderkey[1:] != l_orderkey[:-1]])
    keys = l_orderkey[starts]
    lines = np.diff(np.r_[starts, len(l_orderkey)])
    open_lines = np.add.reduceat(line_open.astype(np.int64), starts)
    code = np.where(open_lines == 0, 0, np.where(open_lines == lines, 1, 2))
    at = np.searchsorted(keys, o_orderkey)
    if not np.array_equal(keys[np.minimum(at, len(keys) - 1)], o_orderkey):
        raise ValueError("an order without lines, or lines out of order")
    return code[at].astype(np.int8)


def generate(seed: int, sf: float = 1.0) -> dict:
    """Return {table: pyarrow.Table} of all eight tables."""
    tables = spec.load_module("datagen", "tpch8").generate(seed, sf)
    lineitem, orders = tables["lineitem"], tables["orders"]
    code = order_status(
        lineitem.column("l_orderkey").to_numpy(),
        lineitem.column("l_linestatus").to_numpy(zero_copy_only=False) == "O",
        orders.column("o_orderkey").to_numpy())
    status = pa.DictionaryArray.from_arrays(
        pa.array(code), pa.array(STATUSES)).cast(pa.string())
    orders = orders.set_column(
        orders.schema.get_field_index("o_orderstatus"), "o_orderstatus",
        status)
    return dict(tables, orders=orders)
