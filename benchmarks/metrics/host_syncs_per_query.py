"""Executors: mean ``syncs`` of the window's query records: the blocking
device reads a request's threads made (dispatches, ``svc.finalize``,
``submit``, the helper threads), each a ``sync.<site>`` span.  A function of
the plan and the tables: the same from seed to seed."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_host_wait").mean(
        run, lambda r: r["syncs"], "syncs")
