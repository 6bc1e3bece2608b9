"""Service: mean ``service.finalize`` of the window's query records:
``QuerySession.finish`` from entry to the record's ``done``, just before the
client's ``to_df`` is released (flush, snapshots, ``graph.cleanup()``)."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_window").mean(
        run, "service.finalize", scale=1e3)
