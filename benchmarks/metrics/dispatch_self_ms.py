"""Runtime: mean per query of the runtime's own host time around the
executors: ``runtime.pick`` (the walk to the next task) +
``runtime.dispatch_self`` (the dispatch outside every span) +
``runtime.push``, self times summed over the query's dispatches."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_window").mean(
        run, "runtime.pick", "runtime.dispatch_self", "runtime.push",
        scale=1e3)
