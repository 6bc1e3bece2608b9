"""Kernels: the share of a window's primary-key probe slots that the sort
branch of ``hash_join_pk`` answered from a direct-address table (one gather
a probe row) and not by binary search (about twenty): 100 x sum
``join_probe_direct`` / sum of that and ``join_probe_search`` over the
window's query records.  Nothing where the records lack the counters (a
program from before them) or no slot was probed either way."""

from harness import spec


def read(run):
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if "join_probe_direct" in r and "join_probe_search" in r]
    direct = sum(r["join_probe_direct"] for r in recs)
    slots = direct + sum(r["join_probe_search"] for r in recs)
    if not slots:
        return None
    return 100.0 * direct / slots
