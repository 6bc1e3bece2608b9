"""Executors: mean ``asof_flushes`` of the window's query records: the
chunks of ready trades ``SortedAsofExecutor`` matched in a query, over its
channels.  Each flush searches the whole quote buffer, so this times the
record's ``asof_quote_padded`` per flush is the device's work.  A program
whose records lack the counter reports nothing."""

from harness import spec


def read(run):
    counts = [r["asof_flushes"]
              for r in spec.load_module("metrics", "_window").records(run)
              if "asof_flushes" in r]
    if not counts:
        return None
    return sum(counts) / len(counts)
