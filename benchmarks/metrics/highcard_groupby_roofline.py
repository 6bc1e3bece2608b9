"""Kernels: the share of the HBM roofline at which the device did Q18's
subquery aggregate's least work, over the time it spent in the group-by's
programs.

100 x (the subquery's least bytes x queries done in the traced span, counted
as ``scan_roofline`` counts them) / peak HBM bytes/s / the device seconds of
the group-by modules.  Which modules those are, and the least bytes of a
group-by, are ``metrics/groupby_roofline.py``'s (``is_groupby_module``,
``groupby_seconds``, ``groupby_least_bytes``): every lineitem row's
``l_orderkey`` and ``l_quantity`` read once and every order's key and sum
written once, 4 bytes each (6.0 M rows into 1.5 M groups at SF 1: about 60
MB a query).  The rows and groups are the tables', as the reference counted
them (``queries/q18.py`` ``SUBQUERY``).  The same modules also run the final
5-key group-by over the few hundred line items of the orders that pass the
HAVING; its bytes are not counted, so the share is a lower bound and cannot
pass 100.
"""

from harness import spec

QUERY = "q18"


def read(run):
    if not run.trace or not run.trace_span or not run.peaks:
        return None
    groupby = spec.load_module("metrics", "groupby_roofline")
    seconds = groupby.groupby_seconds(run.trace["device_ops"])
    counted = spec.load_module("queries", QUERY).SUBQUERY
    if seconds <= 0 or not counted:
        return None
    least = groupby.groupby_least_bytes(counted["rows"], counted["groups"], 1)
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
