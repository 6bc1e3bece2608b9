"""Executors: mean ``executors.exec_self`` of the window's query records:
the self time of the ``exec.*`` and ``done.*`` spans (the executors' host
code and what it blocks on, less the compile plane and the push)."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_window").mean(
        run, "executors.exec_self", scale=1e3)
