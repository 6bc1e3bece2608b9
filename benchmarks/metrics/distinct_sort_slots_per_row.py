"""Executors: padded slots the streaming distinct sorted or compared for
each row into it: sum ``distinct_sort_slots`` / sum ``distinct_rows_in`` over
the window's query records.  The probe counts, on the device, what it ran for
each batch a distinct folds in (quokka_tpu/ops/distinct.py): two sorts a
padded slot (the dedupe's and the flags'), the halvings of the chunks'
bounds, and then either the state slots of its window a padded slot (a
batch whose keys fall between the state's: 2 + 256 a slot and a little) or
the binary search's halvings a slot (a batch spread over the state: the log
of its size).  The merge's linear pass is not counted.  A slot compared in
the window costs less than one the search gathers: this counts work, not
time.  A distinct that re-sorted its whole state with every batch would read
more as the state grows; this one does not.  Nothing where the records lack
the counters (a program from before them) or no row went into a
distinct."""

from harness import spec

SLOTS, ROWS = "distinct_sort_slots", "distinct_rows_in"


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if SLOTS in r and ROWS in r]
    rows = sum(r[ROWS] for r in recs)
    if not rows:
        return None
    return sum(r[SLOTS] for r in recs) / rows
