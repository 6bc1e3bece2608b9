"""Kernels: the share of the HBM roofline at which the device did the
group-by's least work, over the time it spent in the group-by's own programs.

100 x (the group-by's least bytes x queries done in the traced span, counted
as ``scan_roofline`` counts them) / peak HBM bytes/s / the device seconds of
the modules that implement the group-by.  Those are the XLA modules of the
traced span's ``device_ops`` whose names hold ``groupby`` or start
``jit_agg_`` (today ``jit_fused_groupby``, the partial aggregate of one scan
batch through the sort path, quokka_tpu/ops/fuse.py; ``jit_sorted_groupby``,
the eager merges' and the final's, ops/kernels.py; ``jit_agg_recombine`` and
``jit_agg_final_tail``, ops/aggtail.py).  **A kernel that replaces them keeps
``groupby`` in its function's name.**  ``device_ops`` holds the span's ten
largest modules: a group-by module too small to be among them is not in the
time.

The group-by's least bytes are what no implementation can skip: every row's
key and each aggregated value read once (4 bytes each), and every group's key
and aggregates written once.  The rows and groups are the configuration's
(``configs/h2o_g1_1e7.json``: 1e7 rows into 1e5 groups, three sums: 161.6 MB
a query, 197 us at the peak).  The programs cannot move those bytes faster
than the peak, so the share cannot pass 100.
"""

import os

from harness import spec

CONFIG = os.path.join(spec.BENCH_DIR, "configs", "h2o_g1_1e7.json")
QUERY = "h2o_q5"
VALUES = 3  # q5 sums v1, v2 and v3


def groupby_least_bytes(rows: int, groups: int, values: int) -> int:
    return rows * 4 * (1 + values) + groups * 4 * (1 + values)


def is_groupby_module(module: str) -> bool:
    return "groupby" in module or module.startswith("jit_agg_")


def groupby_seconds(device_ops) -> float:
    return sum(seconds for module, seconds in device_ops
               if is_groupby_module(module))


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    seconds = groupby_seconds(run.trace["device_ops"])
    if seconds <= 0:
        return None
    args = spec.load_json(CONFIG)["datagen"]["args"]
    least = groupby_least_bytes(args["n"], args["n"] // args["k"], VALUES)
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
