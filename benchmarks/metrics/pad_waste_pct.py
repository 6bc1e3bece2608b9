"""Executors: padded slots carried that held no live row, over the
window's query records: 100 x (1 - sum rows_in / sum padded_in), from the
counts ``opstats`` resolves when a query finishes."""

from harness import spec


def read(run):
    recs = spec.load_module("metrics", "_window").records(run)
    padded = sum(r["padded_in"] for r in recs)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(r["rows_in"] for r in recs) / padded)
