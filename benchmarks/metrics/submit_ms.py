"""Entry and planning: median of the benchmark's own clock around
``svc.submit(stream)`` (plan, optimize, lower, enqueue)."""

from harness import loadgen


def read(run):
    return loadgen.percentile(
        [1e3 * (r.t_submitted - r.t_submit) for r in run.log
         if r.t_submitted], 50)
