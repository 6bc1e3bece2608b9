"""Runtime: mean ``requeues`` of the window's query records: quanta a pool
thread spent on the query that found nothing to run (``service_step``
returned ``wait`` or ``idle``)."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_window").mean(run, "requeues")
