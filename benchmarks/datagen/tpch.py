"""TPC-H lineitem/orders/customer from a seed (numpy, not dbgen).

Copied from tests/tpch_data.py at commit 949ddc4 and trimmed: the same value
domains and the same order of draws for the columns kept, but only the three
tables the benchmark's queries read, without the free-text columns the
original fills in Python loops (names, addresses, phones, clerks, comments:
24 s per unit of SF there, 4 s here).  Dropped: the tables part, supplier,
partsupp, nation and region, and the columns c_name, c_address, c_phone,
c_comment, o_clerk, o_comment, l_shipinstruct and l_comment.  No query of the
benchmark reads one of them.  l_returnflag and l_linestatus follow dbgen's
rule (TPC-H 4.2.3: status O where the line shipped after 1995-06-17, else F;
flag R or A at random where it was received by then, else N), which gives
Q1 its four groups, one of them (N, F) small; the original draws six uniform
groups.  Because draws are skipped a seed gives other rows here than in
tests/tpch_data.py; the benchmark compares only with its own reference over
these same files.
"""

import datetime

import numpy as np
import pyarrow as pa

EPOCH = datetime.date(1970, 1, 1)
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
CURRENT_DATE = (datetime.date(1995, 6, 17) - EPOCH).days  # dbgen's CURRENTDATE


def _dates(r, n, lo, hi):
    lo_d = (datetime.date.fromisoformat(lo) - EPOCH).days
    hi_d = (datetime.date.fromisoformat(hi) - EPOCH).days
    return r.integers(lo_d, hi_d, n).astype(np.int32)


def _take(r, values, n):
    codes = pa.array(r.integers(0, len(values), n).astype(np.int8))
    return pa.DictionaryArray.from_arrays(
        codes, pa.array(values)).cast(pa.string())


def _date32(days):
    return pa.array(days.astype(np.int32), type=pa.int32()).cast(pa.date32())


def generate(seed: int, sf: float = 1.0) -> dict:
    """Return {table: pyarrow.Table}; sf=1 is the TPC-H SF1 row counts."""
    r = np.random.default_rng(seed)
    n_orders = max(int(1_500_000 * sf), 50)
    n_cust = max(int(150_000 * sf), 20)
    n_part = max(int(200_000 * sf), 25)
    n_supp = max(int(10_000 * sf), 10)

    customer = pa.table({
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_nationkey": r.integers(0, 25, n_cust).astype(np.int64),
        "c_acctbal": np.round(r.uniform(-999, 9999, n_cust), 2),
        "c_mktsegment": _take(r, SEGMENTS, n_cust),
    })
    o_orderdate = _dates(r, n_orders, "1992-01-01", "1998-08-02")
    # dbgen-alike: customers with custkey % 3 == 0 place no orders
    with_orders = np.arange(1, n_cust + 1, dtype=np.int64)
    with_orders = with_orders[with_orders % 3 != 0]
    orders = pa.table({
        "o_orderkey": np.arange(1, n_orders + 1, dtype=np.int64) * 4,
        "o_custkey": with_orders[r.integers(0, len(with_orders), n_orders)],
        "o_orderstatus": _take(r, ["F", "O", "P"], n_orders),
        "o_totalprice": np.round(r.uniform(1000, 400000, n_orders), 2),
        "o_orderdate": _date32(o_orderdate),
        "o_orderpriority": _take(r, PRIORITIES, n_orders),
        "o_shippriority": np.zeros(n_orders, dtype=np.int64),
    })
    # lineitem: 1-7 lines per order
    lines_per = r.integers(1, 8, n_orders)
    n_li = int(lines_per.sum())
    starts = np.cumsum(lines_per) - lines_per
    odate = np.repeat(o_orderdate, lines_per)
    l_shipdate = odate + r.integers(1, 122, n_li)
    l_commitdate = odate + r.integers(30, 91, n_li)
    l_receiptdate = l_shipdate + r.integers(1, 31, n_li)
    qty = r.integers(1, 51, n_li).astype(np.float64)
    price = np.round(qty * (900 + r.uniform(0, 1200, n_li)) / 10, 2)
    flags = pa.array(["R", "A", "N"])
    returnflag = np.where(l_receiptdate <= CURRENT_DATE,
                          r.integers(0, 2, n_li), 2).astype(np.int8)
    status = pa.array(["F", "O"])
    linestatus = (l_shipdate > CURRENT_DATE).astype(np.int8)
    lineitem = pa.table({
        "l_orderkey": np.repeat(orders.column("o_orderkey").to_numpy(),
                                lines_per),
        "l_partkey": r.integers(1, n_part + 1, n_li).astype(np.int64),
        "l_suppkey": r.integers(1, n_supp + 1, n_li).astype(np.int64),
        "l_linenumber": (np.arange(n_li, dtype=np.int64)
                         - np.repeat(starts, lines_per) + 1),
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": np.round(r.uniform(0, 0.1, n_li), 2),
        "l_tax": np.round(r.uniform(0, 0.08, n_li), 2),
        "l_returnflag": pa.DictionaryArray.from_arrays(
            pa.array(returnflag), flags).cast(pa.string()),
        "l_linestatus": pa.DictionaryArray.from_arrays(
            pa.array(linestatus), status).cast(pa.string()),
        "l_shipdate": _date32(l_shipdate),
        "l_commitdate": _date32(l_commitdate),
        "l_receiptdate": _date32(l_receiptdate),
        "l_shipmode": _take(r, SHIPMODES, n_li),
    })
    return {"lineitem": lineitem, "orders": orders, "customer": customer}
