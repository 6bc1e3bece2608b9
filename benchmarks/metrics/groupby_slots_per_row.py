"""Executors: padded slots the sort group-by took for each row read: sum
``groupby_sort_slots`` / sum ``rows_in`` over the window's query records.
Every partial aggregate, merge and final that runs the sort group-by counts
the padded length it sorted (``opstats.note(groupby_sort_slots=...)``), so one
pass over scan batches padded to their bucket reads about 1.05 and every
merge adds its share.  ``rows_in`` counts the rows every operator of the
query read, so the reading compares runs of one plan, not plans.  Nothing
where the records lack the counter (a program from before it) or no slot was
sorted."""

from harness import spec


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if "groupby_sort_slots" in r]
    slots = sum(r["groupby_sort_slots"] for r in recs)
    rows = sum(r.get("rows_in", 0) for r in recs)
    if not slots or not rows:
        return None
    return slots / rows
