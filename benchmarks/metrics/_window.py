"""The query records of a run's window, for the per-layer metrics that read
them: ``quokka_tpu.obs.querylog`` keeps one flat record per finished query,
stamped on ``time.perf_counter()``, the clock of the completion log.  A
program without that module (a checkout from before it) has no records."""


def records(run):
    """Records of the queries that finished (``done``) between the window's
    first ``t_submit`` and its last ``t_end``, oldest first."""
    try:
        from quokka_tpu.obs import querylog
    except ImportError:
        return []
    if not run.log:
        return []
    first = min(r.t_submit for r in run.log)
    last = max(r.t_end for r in run.log)
    return [rec for rec in querylog.records(since=first)
            if rec["done"] <= last and rec["status"] == "done"]


def mean(run, *keys, scale=1.0):
    """Mean over the window's records of the sum of ``keys``; None where
    there is no record."""
    recs = records(run)
    if not recs:
        return None
    return scale * sum(sum(r[k] for k in keys) for r in recs) / len(recs)
