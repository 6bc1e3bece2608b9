"""TPC-H's eight tables from a seed (numpy and arrow, not dbgen), with the
data rules of clause 4.2.3 that tie a line item to its part and supplier.

``lineitem``, ``orders`` and ``customer`` are ``datagen/tpch.py``'s for the
same seed and scale (loaded, not edited), with two lineitem columns
**replaced**:

- ``l_suppkey``: the i-th (i drawn from 0..3) of the part's four suppliers,
  by the formula that fills ``ps_suppkey``; ``tpch.py`` draws it uniformly,
  independent of ``l_partkey``, which leaves the (l_partkey, l_suppkey) =
  (ps_partkey, ps_suppkey) join of Q9 and Q20 with 4 / S of its rows;
- ``l_extendedprice`` = ``l_quantity`` x the part's ``p_retailprice``, so that
  Q9's ``amount`` is a profit and not a difference of unrelated draws.

Added: ``part`` (200,000 x sf), ``partsupp`` (four rows a part), ``supplier``
(10,000 x sf), ``nation`` (25) and ``region`` (5), with the spec's formulas:

- ``p_name``: five distinct words of the 92-word list, joined by spaces;
- ``p_retailprice`` = (90000 + ((p_partkey / 10) mod 20001)
  + 100 x (p_partkey mod 1000)) / 100;
- ``ps_suppkey`` = (ps_partkey + i x (S / 4 + (ps_partkey - 1) / S)) mod S + 1
  for i in 0..3, S the number of suppliers (four distinct suppliers a part
  from S = 100, sf 0.01, up);
- ``p_mfgr`` ``Manufacturer#M``, ``p_brand`` ``Brand#MN`` (M, N in 1..5),
  ``p_type`` of the 150 three-syllable types, ``p_size`` 1..50,
  ``p_container`` of the 40 two-syllable containers, ``ps_availqty`` 1..9999,
  ``ps_supplycost`` in [1, 1000], ``s_name`` ``Supplier#<9 digits>``,
  ``s_acctbal`` in [-999.99, 9999.99], the spec's nation names and region
  keys.

Left out, as in ``tpch.py``: the ``*_comment``, ``*_address`` and ``*_phone``
columns, ``c_name``, ``o_clerk`` and ``l_shipinstruct``.  Strings are spread
from dictionary codes by arrow casts; no row is touched by a Python loop.
"""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from harness import spec

# clause 4.2.3: the words of P_NAME, as the specification prints them
P_NAME_WORDS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1)]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
SUPPLIERS_A_PART = 4


def _spread(codes: np.ndarray, values) -> pa.Array:
    """``values[codes]`` as a string column, by arrow's dictionary cast."""
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)), pa.array(values)).cast(pa.string())


def part_suppkey(partkey: np.ndarray, i: np.ndarray, n_supp: int):
    """The i-th supplier of each part (clause 4.2.3, PS_SUPPKEY)."""
    return (partkey + i * (n_supp // SUPPLIERS_A_PART
                           + (partkey - 1) // n_supp)) % n_supp + 1


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of clause 4.2.3, a function of the key alone."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def _part_names(r, n: int) -> pa.Array:
    """Five distinct list words a part: the five smallest of 92 uniform
    draws name them, smallest first."""
    draws = r.random((n, len(P_NAME_WORDS)), dtype=np.float32)
    five = np.argsort(draws, axis=1)[:, :5]
    return pc.binary_join_element_wise(
        *(_spread(five[:, k], P_NAME_WORDS) for k in range(5)), " ")


def generate(seed: int, sf: float = 1.0) -> dict:
    """Return {table: pyarrow.Table} of all eight tables."""
    tables = spec.load_module("datagen", "tpch").generate(seed, sf)
    r = np.random.default_rng([seed, 8])
    # the sizes datagen/tpch.py drew l_partkey and l_suppkey from
    n_part = max(int(200_000 * sf), 25)
    n_supp = max(int(10_000 * sf), 10)

    partkey = np.arange(1, n_part + 1, dtype=np.int64)
    mfgr = r.integers(0, 5, n_part)
    part = pa.table({
        "p_partkey": partkey,
        "p_name": _part_names(r, n_part),
        "p_mfgr": _spread(mfgr, [f"Manufacturer#{m}" for m in range(1, 6)]),
        "p_brand": _spread(mfgr * 5 + r.integers(0, 5, n_part),
                           [f"Brand#{m}{n}" for m in range(1, 6)
                            for n in range(1, 6)]),
        "p_type": _spread(r.integers(0, len(TYPES), n_part), TYPES),
        "p_size": r.integers(1, 51, n_part).astype(np.int64),
        "p_container": _spread(r.integers(0, len(CONTAINERS), n_part),
                               CONTAINERS),
        "p_retailprice": retail_price(partkey),
    })
    ps_partkey = np.repeat(partkey, SUPPLIERS_A_PART)
    n_ps = len(ps_partkey)
    partsupp = pa.table({
        "ps_partkey": ps_partkey,
        "ps_suppkey": part_suppkey(
            ps_partkey, np.tile(np.arange(SUPPLIERS_A_PART), n_part), n_supp),
        "ps_availqty": r.integers(1, 10_000, n_ps).astype(np.int64),
        "ps_supplycost": np.round(r.uniform(1, 1000, n_ps), 2),
    })
    suppkey = np.arange(1, n_supp + 1, dtype=np.int64)
    supplier = pa.table({
        "s_suppkey": suppkey,
        "s_name": pc.binary_join_element_wise(
            pa.scalar("Supplier#"),
            pc.utf8_lpad(pa.array(suppkey).cast(pa.string()), 9, "0"), ""),
        "s_nationkey": r.integers(0, len(NATIONS), n_supp).astype(np.int64),
        "s_acctbal": np.round(r.uniform(-999.99, 9999.99, n_supp), 2),
    })
    nation = pa.table({
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": [name for name, _ in NATIONS],
        "n_regionkey": np.array([rk for _, rk in NATIONS], dtype=np.int64),
    })
    region = pa.table({
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
        "r_name": REGIONS,
    })

    lineitem = tables["lineitem"]
    l_partkey = lineitem.column("l_partkey").to_numpy()
    qty = lineitem.column("l_quantity").to_numpy()
    replaced = {
        "l_suppkey": part_suppkey(
            l_partkey, r.integers(0, SUPPLIERS_A_PART, len(l_partkey)),
            n_supp),
        "l_extendedprice": np.round(qty * retail_price(l_partkey), 2),
    }
    for name, values in replaced.items():
        lineitem = lineitem.set_column(
            lineitem.schema.get_field_index(name), name, pa.array(values))
    return dict(tables, lineitem=lineitem, part=part, partsupp=partsupp,
                supplier=supplier, nation=nation, region=region)
