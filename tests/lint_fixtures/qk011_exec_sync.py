"""QK011 fixture: blocking device reads in an executor, beside the funnel.

Three findings, all in ``FakeJoinExecutor.execute``: device_get of the build
statistics, .item() of a count, np.asarray of a column.  ``done`` makes the
same three reads through ``spans.device_read`` and is clean.
"""

import jax
import jax.numpy as jnp
import numpy as np

from quokka_tpu.obs import spans as tracing


class FakeJoinExecutor:
    def execute(self, batches, stream_id, channel):
        build = batches[0]
        dup, n_ok = jax.device_get(build.stats)  # finding 1
        nready = jnp.sum(build.valid).item()  # finding 2
        keys = np.asarray(build.columns["k"].data)  # finding 3
        return dup, n_ok, nready, keys

    def done(self, channel):
        build = self.build
        dup, n_ok = tracing.device_read("join.build_stats", build.stats)
        nready = int(tracing.device_read("join.ready",
                                         jnp.sum(build.valid)))
        keys = tracing.device_read("join.keys", build.columns["k"].data)
        return dup, n_ok, nready, keys
