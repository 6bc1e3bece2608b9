"""Executors: mean ``agg_merges_general`` of the window's query records: the
merges and final tails of a query's aggregators that ``ops/aggtail.py``'s two
compiled programs did not take (parts over 65,536 summed padded rows, or of
mixed layouts), and so ran compaction, concat and group-by one after the
other.  Nothing where the records lack the counter."""

from harness import spec


def read(run):
    counts = [r["agg_merges_general"]
              for r in spec.load_module("metrics", "_window").records(run)
              if "agg_merges_general" in r]
    if not counts:
        return None
    return sum(counts) / len(counts)
