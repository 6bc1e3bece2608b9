"""Kernels: the share of the HBM roofline at which the device did the asof
match's least work, over the time it spent in the match's own programs.

100 x (the match's least bytes x queries done in the traced span, counted
as ``scan_roofline`` counts them) / peak HBM bytes/s / the device seconds of
the modules that implement the match.  Those are the XLA modules of the
traced span's ``device_ops`` whose names start ``jit_asof``, ``jit__asof`` or
``jit__ss_`` (today ``jit__ss_sort_quotes`` and ``jit__ss_probe``,
quokka_tpu/ops/asof.py; a kernel that replaces them keeps ``asof`` in its
function's name).  ``device_ops`` holds the span's ten largest modules: a
match module too small to be among them is not in the time.

The match's least bytes are what no implementation can skip: every trade's
time and symbol code and every quote's (4 bytes each, read once), and a
matched quote index per trade (4 bytes written).  The rows are the
configuration's (``configs/ticks_1d.json``; 61.8 MB a query, 75 us at the
peak).  The programs cannot move those bytes faster than the peak, so the
share cannot pass 100.
"""

import os

from harness import spec

MATCH_MODULES = ("jit_asof", "jit__asof", "jit__ss_")
CONFIG = os.path.join(spec.BENCH_DIR, "configs", "ticks_1d.json")
QUERY = "asof"


def match_least_bytes(trades: int, quotes: int) -> int:
    return trades * (4 + 4) + quotes * (4 + 4) + trades * 4


def match_seconds(device_ops) -> float:
    return sum(seconds for module, seconds in device_ops
               if module.startswith(MATCH_MODULES))


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    seconds = match_seconds(run.trace["device_ops"])
    if seconds <= 0:
        return None
    rows = spec.load_json(CONFIG)["datagen"]["args"]
    least = match_least_bytes(rows["trades"], rows["quotes"])
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
