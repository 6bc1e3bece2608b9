"""Kernels: the share of the HBM roofline at which the device did the joins'
least work, over the time it spent in the joins' own programs.

100 x (the joins' least bytes x queries done in the traced span, counted as
``scan_roofline`` counts them) / peak HBM bytes/s / the device seconds of the
modules that implement the joins.  Those are the XLA modules of the traced
span's ``device_ops`` whose names hold ``_pk_``, ``join``, ``_mm_`` or
``sort_build_keys`` (today ``jit__pk_probe_direct``, ``jit__pk_probe_sorted``,
``jit__pk_direct_build``, ``jit__sort_build_keys``, ``jit__mm_plan``,
``jit__mm_expand``; quokka_tpu/ops/join.py).  **A kernel that replaces them
keeps ``join`` in its function's name.**  ``device_ops`` holds the span's ten
largest modules: a join module too small to be among them is not in the time,
and the gathers of the payload (``jit__gather_all``, shared with every
compaction) are not the joins' alone and are left out.

The joins' least bytes are what no implementation of Q9 can skip, at 4 bytes
a value: every lineitem row's ``l_partkey`` once (the semi join reads it);
``l_suppkey``, ``l_orderkey`` and the three measures of the rows whose part
matches (the reference's count, ``queries/q9.py`` ``MATCHED_ROWS``, the mean
over the run's parameter sets); every build row's key and payload columns
once (part 2, partsupp 3, supplier 2, nation 2, orders 2).  Rows are the
configuration's (``configs/tpch8_sf1.json``: the specification's counts x its
scale factor).  The programs cannot move those bytes faster than the peak, so
the share cannot pass 100.
"""

from harness import spec

QUERY = "q9"
# rows at SF 1 and the columns a join reads of each build row
BUILDS = {"part": (200_000, 2), "partsupp": (800_000, 3),
          "supplier": (10_000, 2), "orders": (1_500_000, 2)}
NATION_VALUES = 25 * 2
PROBE_COLUMNS_OF_A_MATCH = 5  # l_suppkey, l_orderkey, three measures


def join_least_bytes(lineitem_rows: float, matched_rows: float,
                     sf: float) -> float:
    builds = sum(rows * sf * cols for rows, cols in BUILDS.values())
    return 4.0 * (lineitem_rows + matched_rows * PROBE_COLUMNS_OF_A_MATCH
                  + builds + NATION_VALUES)


def is_join_module(module: str) -> bool:
    return any(part in module
               for part in ("_pk_", "join", "_mm_", "sort_build_keys"))


def join_seconds(device_ops) -> float:
    return sum(seconds for module, seconds in device_ops
               if is_join_module(module))


def matched_rows(run):
    """Mean over the run's parameter sets of the line items the part filter
    keeps, as the reference counted them; None before any reference ran."""
    counted = spec.load_module("queries", QUERY).MATCHED_ROWS
    counts = [counted[p.get("color")]
              for p in run.parameter_sets.get(QUERY, [])
              if p.get("color") in counted]
    return sum(counts) / len(counts) if counts else None


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    seconds = join_seconds(run.trace["device_ops"])
    matched = matched_rows(run)
    if seconds <= 0 or matched is None:
        return None
    slots = spec.load_module("metrics", "join_slots_per_row")
    sf = spec.load_json(slots.CONFIG)["datagen"]["args"]["sf"]
    least = join_least_bytes(slots.lineitem_rows(), matched, sf)
    t0, t1 = run.trace_span
    done_bytes = 0.0
    for r in run.log:
        if (r.query != QUERY or r.t_done is None or not r.run_s
                or not r.ok):
            continue
        start = r.t_done - r.run_s
        inside = max(0.0, min(r.t_done, t1) - max(start, t0))
        done_bytes += least * inside / r.run_s
    if done_bytes == 0:
        return None
    return 100.0 * done_bytes / run.peaks["hbm_bytes_per_s"] / seconds
