"""Runtime: the thread-milliseconds a request costs the host that are not
waits for the device: mean of ``task_s`` + ``runtime.pick`` +
``entry.submit`` + ``service.finalize`` less the device reads on those
threads (``sync.wait`` - ``sync.offthread``: the helper threads' reads lie
outside the four sums).  With ``pool_size`` workers the rate cannot pass
``pool_size`` / this: the floor under a device-bound cell."""

from harness import spec

SUMS = ("task_s", "runtime.pick", "entry.submit", "service.finalize")


def read(run):
    return spec.load_module("metrics", "_host_wait").mean(
        run,
        lambda r: (sum(r[k] for k in SUMS)
                   - (r["sync.wait"] - r["sync.offthread"])),
        "sync.wait", "sync.offthread", *SUMS, scale=1e3)
