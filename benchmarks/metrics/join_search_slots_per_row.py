"""Executors: padded slots the binary-search probe (``_pk_probe_sorted``,
the probe of every build with several key columns or limbs, strings or
duplicates) took for each lineitem row a query scanned: sum
``join_probe_search`` over the window's query records / (the configuration's
lineitem rows x those records; ``join_slots_per_row`` says which rows).  In
Q9 the search serves the (l_partkey, l_suppkey) join with partsupp: 1.0 means
it ran over every padded lineitem slot, about 0.06 to 0.1 that the chain was
compacted to the rows the part filter keeps before it.  Nothing where the
records lack the counter or the search never ran."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "join_slots_per_row").slots_per_row(
        run, ("join_probe_search",))
