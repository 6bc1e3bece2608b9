"""Kernels: the share of a window's asof flushes that the device merge
answered (``_asof_match``: one sort of both sides together, two running maxima,
a compacting sort; nothing indexed over the quote slots) and not the device
binary search (a quote sort, then 24 halvings of three gathers a trade):
100 x sum ``asof_match_sort`` / sum of that and ``asof_match_search`` over
the window's query records.  Nothing where the records lack the counters (a
program from before them) or no flush took either device match."""

from harness import spec


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if "asof_match_sort" in r and "asof_match_search" in r]
    merged = sum(r["asof_match_sort"] for r in recs)
    flushes = merged + sum(r["asof_match_search"] for r in recs)
    if not flushes:
        return None
    return 100.0 * merged / flushes
