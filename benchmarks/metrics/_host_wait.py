"""The window's query records for the five metrics that read a request's wait
for the device (``quokka_tpu.obs.spans.device_read``: every blocking read is
a ``sync.<site>`` span, summed into the record's ``syncs``, ``sync.wait``,
``sync.in_dispatch``, ``sync.offthread``, ``d2h_bytes``, ``h2d_bytes``).  A
program from before those keys has records without them: nothing to read."""

from harness import spec


def mean(run, value, *needs, scale=1.0):
    """Mean of ``value(record)`` over the window's records that hold every
    key of ``needs``; None where none does."""
    recs = [r for r in spec.load_module("metrics", "_window").records(run)
            if all(k in r for k in needs)]
    if not recs:
        return None
    return scale * sum(value(r) for r in recs) / len(recs)
