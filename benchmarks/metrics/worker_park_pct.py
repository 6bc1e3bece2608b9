"""Service: share of the pool's thread-time spent parked with no runnable
query (``svc.park``: ``_wake.wait(0.005)``), between the first and the last
query record of the window: 100 x (park_s_total of the last - of the first)
/ (pool_size x the time between their ``done``)."""

from harness import spec


def read(run):
    recs = spec.load_module("metrics", "_window").records(run)
    if len(recs) < 2:
        return None
    first, last = recs[0], recs[-1]
    thread_s = last["pool_size"] * (last["done"] - first["done"])
    if thread_s <= 0:
        return None
    return 100.0 * (last["park_s_total"] - first["park_s_total"]) / thread_s
