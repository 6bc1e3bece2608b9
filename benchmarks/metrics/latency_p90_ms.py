"""90th percentile of submit() -> to_df() returned, every request of the
window."""

from harness import loadgen


def read(run):
    return loadgen.percentile(loadgen.latencies_ms(run.log), 90)
