"""TPC-H lineitem, orders and customer from a seed, with ``c_name``: the
tables of ``datagen/tpch.py`` for the same seed and scale (loaded, not
edited), and customer's ``c_name`` by clause 4.2.3: "Customer#" followed by
``c_custkey`` in nine digits, zero-padded.  The string is built by arrow's
string kernels; no row is touched by a Python loop.
"""

import pyarrow as pa
import pyarrow.compute as pc

from harness import spec


def customer_names(custkey: pa.Array) -> pa.Array:
    """C_NAME of clause 4.2.3 for each key."""
    return pc.binary_join_element_wise(
        pa.scalar("Customer#"),
        pc.utf8_lpad(custkey.cast(pa.string()), 9, "0"), "")


def generate(seed: int, sf: float = 1.0) -> dict:
    """Return {table: pyarrow.Table}: lineitem, orders and customer."""
    tables = spec.load_module("datagen", "tpch").generate(seed, sf)
    customer = tables["customer"]
    customer = customer.add_column(
        1, "c_name", customer_names(customer.column("c_custkey").combine_chunks()))
    return dict(tables, customer=customer)
