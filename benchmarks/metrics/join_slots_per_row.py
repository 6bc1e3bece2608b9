"""Executors: padded slots the joins of a query probed for each lineitem row
it scanned: sum of ``join_probe_direct``, ``join_probe_search`` and
``join_probe_general`` over the window's query records / (the
configuration's lineitem rows x those records).  Every probe call counts the
padded length of the batch it was given (``opstats.note`` in
quokka_tpu/ops/join.py), so Q9's five joins over batches padded to their
bucket read 5 or more where nothing is compacted between them, and little
over 1 where the chain shrinks to the rows the part filter keeps before the
second probe.  The rows are the configuration's (``configs/tpch8_sf1.json``:
6,000,000 x its scale factor; the generator's lineitem has 5,995,918 to
6,004,281 by seed), so a rehearsal at another size reads another scale.
Nothing where the records lack the counters (a program from before them) or
no slot was probed."""

import os

from harness import spec

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "tpch8_sf1.json")
LINEITEM_ROWS_AT_SF1 = 6_000_000
COUNTERS = ("join_probe_direct", "join_probe_search", "join_probe_general")


def lineitem_rows() -> float:
    return LINEITEM_ROWS_AT_SF1 * spec.load_json(CONFIG)["datagen"]["args"]["sf"]


def slots_per_row(run, counters):
    """Sum of ``counters`` over the window's records that carry every one of
    them, a lineitem row of those records; None with nothing to read."""
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if all(c in r for c in counters)]
    slots = sum(r[c] for r in recs for c in counters)
    if not slots:
        return None
    return slots / (lineitem_rows() * len(recs))


def read(run):
    return slots_per_row(run, COUNTERS)
