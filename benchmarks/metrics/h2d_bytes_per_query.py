"""Device: mean ``h2d_bytes`` of the window's query records: the wire bytes
``bridge.arrow_to_device`` put on the device for a request (the
``bridge.to_device`` span, on whichever thread).  0 in a warm window while
the scan cache holds the tables: anything else is a table read again."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_host_wait").mean(
        run, lambda r: r["h2d_bytes"], "h2d_bytes")
