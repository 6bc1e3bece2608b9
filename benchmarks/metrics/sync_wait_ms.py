"""Executors: mean ``sync.wait`` of the window's query records: the seconds
a request's threads spent blocked in device reads (``sync.<site>`` spans),
wherever they ran; the part of ``exec_host_ms`` + ``other`` that is a wait
behind the device's queue and not host work."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "_host_wait").mean(
        run, lambda r: r["sync.wait"], "sync.wait", scale=1e3)
