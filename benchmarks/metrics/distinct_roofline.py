"""Kernels: the share of the HBM roofline at which the device did Q21's two
distincts' least work, over the time it spent in the distinct's programs.

100 x (the two distincts' least bytes x queries done in the traced span,
counted as ``scan_roofline`` counts them) / peak HBM bytes/s / the device
seconds of the modules whose names hold ``distinct`` (the streaming
distinct's programs, quokka_tpu/ops/distinct.py: ``jit_distinct_dedupe``,
``jit_distinct_probe``, ``jit_distinct_merge``; **a kernel that replaces
them keeps ``distinct`` in its function's name**).  ``device_ops`` holds the
span's ten largest modules: a distinct module too small to be among them is
not in the time.

A distinct's least bytes are what no implementation can skip: every row's
two 4-byte keys read once, and every distinct (l_orderkey, l_suppkey) pair's
written once: rows x 8 + pairs x 8, summed over the plan's two distincts
(all line items, and the late ones: about 157 MB a query at SF 1).  The rows
and pairs are the tables', as the reference counted them (``queries/q21.py``
``DISTINCT``).  The programs cannot move those bytes faster than the peak,
so the share cannot pass 100.  A program without such modules (one from
before the streaming distinct had programs of its own) reads nothing.
"""

from harness import spec

QUERY = "q21"
KEY_BYTES = 8  # l_orderkey and l_suppkey, 4 bytes each


def is_distinct_module(module: str) -> bool:
    return "distinct" in module


def distinct_seconds(device_ops) -> float:
    return sum(seconds for module, seconds in device_ops
               if is_distinct_module(module))


def distinct_least_bytes(counted: dict) -> int:
    """Rows into and pairs out of every distinct of ``counted`` (name ->
    {"rows", "pairs"}), each at ``KEY_BYTES``."""
    return sum((d["rows"] + d["pairs"]) * KEY_BYTES for d in counted.values())


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    seconds = distinct_seconds(run.trace["device_ops"])
    counted = spec.load_module("queries", QUERY).DISTINCT
    if seconds <= 0 or not counted:
        return None
    least = distinct_least_bytes(counted)
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
