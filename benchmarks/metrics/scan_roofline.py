"""Kernels: the share of the HBM roofline at which the device did the
queries' least work while it was busy.

100 x (least bytes of each query x queries done in the traced span) / peak
HBM bytes/s / device busy seconds of that span.  A query counts by the
share of its run (started -> finished, the service's own timestamps) that
lies inside the span, so a span's edges cut queries and not the metric.
Least bytes are what no correct implementation can skip (queries/<name>.py
``least_bytes``), so the share is a lower bound on the kernels' own and
cannot pass 100.
"""


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    t0, t1 = run.trace_span
    done_bytes = 0.0
    for r in run.log:
        if r.t_done is None or r.run_s is None or not r.ok:
            continue
        start = r.t_done - r.run_s
        inside = max(0.0, min(r.t_done, t1) - max(start, t0))
        if r.run_s > 0:
            done_bytes += run.least_bytes[r.query] * inside / r.run_s
    if done_bytes == 0 or run.trace["busy_s"] <= 0:
        return None
    return (100.0 * done_bytes / run.peaks["hbm_bytes_per_s"]
            / run.trace["busy_s"])
