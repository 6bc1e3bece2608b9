"""Requests answered correctly per second of the window: first submit to
the end of the last request submitted before ``--seconds`` ran out."""

from harness import loadgen


def read(run):
    return loadgen.queries_per_s(run.log)
