"""Device: mean ``d2h_bytes`` of the window's query records: the host bytes
a request's device reads returned, the result's padded columns and mask
included; beyond the answer, 1 to 8 bytes a read."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_host_wait").mean(
        run, lambda r: r["d2h_bytes"], "d2h_bytes")
