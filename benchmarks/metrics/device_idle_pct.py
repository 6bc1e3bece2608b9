"""Device: 100 x (1 - union of device-op intervals / traced span), from the
profiler's trace."""


def read(run):
    if not run.trace or not run.trace_span:
        return None
    span = run.trace_span[1] - run.trace_span[0]
    return 100.0 * (1.0 - run.trace["busy_s"] / span)
