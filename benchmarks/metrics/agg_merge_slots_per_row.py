"""Executors: padded slots the aggregates' merges sorted for each lineitem
row a query scanned: sum ``agg_merge_slots`` over the window's query records
/ (the configuration's lineitem rows x those records).  Every fold of
buffered partials into an aggregate's state, in ``PartialAggExecutor`` and in
``FinalAggExecutor``, and the final's tail where it groups, counts the padded
length it sorted (``opstats.note`` in quokka_tpu/ops/aggtail.py), compiled
or op by op; the per-batch partials do not count here (they are in
``groupby_sort_slots``).  The rows are the configuration's
(``configs/tpch_q18_sf1.json``: 6,000,000 x its scale factor), so a
rehearsal at another size reads another scale.  Nothing where the records
lack the counter (a program from before it) or no slot was merged."""

import os

from harness import spec

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "tpch_q18_sf1.json")
LINEITEM_ROWS_AT_SF1 = 6_000_000
COUNTER = "agg_merge_slots"


def lineitem_rows() -> float:
    return LINEITEM_ROWS_AT_SF1 * spec.load_json(CONFIG)["datagen"]["args"]["sf"]


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if COUNTER in r]
    slots = sum(r[COUNTER] for r in recs)
    if not slots:
        return None
    return slots / (lineitem_rows() * len(recs))
