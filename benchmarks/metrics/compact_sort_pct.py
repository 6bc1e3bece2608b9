"""Kernels: the share of a window's compaction slots whose live indices one
sort of a packed key found (``kernels.live_indices`` with ``method="sort"``:
each slot's index, plus the mask's length where the slot is dead, sorted
once; nothing indexed) and not ``jnp.nonzero`` (a ``bincount``: one
scatter-add update a mask slot): 100 x sum ``compact_sort_slots`` / sum of
that and ``compact_nonzero_slots`` over the window's query records.  Every
compaction, fused concat and asof take counts its padded mask slots.
Nothing where the records lack the counters (a program from before them)
or nothing was compacted inside a dispatch."""

from harness import spec


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if "compact_sort_slots" in r and "compact_nonzero_slots" in r]
    sorted_slots = sum(r["compact_sort_slots"] for r in recs)
    slots = sorted_slots + sum(r["compact_nonzero_slots"] for r in recs)
    if not slots:
        return None
    return 100.0 * sorted_slots / slots
