"""Numeric/linear-algebra executors: gramian, covariance, approximate
quantiles.

Reference parity: DataStream.gramian/covariance/approximate_quantile
(pyquokka/datastream.py:1033/1100/921).  Gramian partials are X^T X matmuls —
pure MXU work — summed across batches and channels; approximate quantiles use
per-channel uniform reservoir sampling (the reference's t-digest dependency is
optional there too)."""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from quokka_tpu.executors.base import Executor
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import bridge
from quokka_tpu.ops.batch import DeviceBatch


class GramianExecutor(Executor):
    """Running X^T X (and column sums + count for covariance) over the given
    float columns."""

    def __init__(self, columns: Sequence[str], covariance: bool = False):
        self.columns = list(columns)
        self.covariance = covariance
        self.gram: Optional[jnp.ndarray] = None
        self.sums: Optional[jnp.ndarray] = None
        self.count = 0

    @staticmethod
    @jax.jit
    def _accumulate(mat, valid):
        m = jnp.where(valid[:, None], mat, 0.0)
        return m.T @ m, jnp.sum(m, axis=0)

    def execute(self, batches, stream_id, channel):
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            mat = jnp.stack([b.columns[c].data for c in self.columns], axis=1)
            g, s = self._accumulate(mat.astype(jnp.float32), b.valid)
            self.gram = g if self.gram is None else self.gram + g
            self.sums = s if self.sums is None else self.sums + s
            self.count += b.count_valid()

    def done(self, channel):
        if self.gram is None:
            return None
        # emit RAW partials (gram rows + a sums row + a count row): channels
        # must combine raw moments before any normalization, otherwise
        # per-channel covariances sum to N-channels times the true value
        g, sums = tracing.device_read("linalg.gram", (self.gram, self.sums))
        g, sums = g.astype(np.float64), sums.astype(np.float64)
        labels = list(self.columns) + ["__sums__", "__count__"]
        count_row = np.zeros(len(self.columns))
        count_row[0] = self.count
        mat = np.vstack([g, sums[None, :], count_row[None, :]])
        cols = {"__row": np.array(labels, dtype=object)}
        for j, c in enumerate(self.columns):
            cols[c] = mat[:, j]
        self.gram = None
        self.sums = None
        return bridge.arrow_to_device(pa.table(cols))


class CombineGramianExecutor(Executor):
    """Sum per-channel RAW gramian partials, then normalize once."""

    def __init__(self, columns: Sequence[str], covariance: bool = False):
        self.columns = list(columns)
        self.covariance = covariance
        self.parts: List[DeviceBatch] = []

    def execute(self, batches, stream_id, channel):
        self.parts.extend(b for b in batches if b is not None)

    def done(self, channel):
        if not self.parts:
            return None
        import pandas as pd

        dfs = [bridge.to_pandas(b) for b in self.parts]
        self.parts = []
        acc = dfs[0].set_index("__row")[self.columns]
        for d in dfs[1:]:
            acc = acc + d.set_index("__row")[self.columns]
        g = acc.loc[self.columns].to_numpy()
        if self.covariance:
            count = float(acc.loc["__count__"].to_numpy()[0])
            sums = acc.loc["__sums__"].to_numpy()
            if count > 1:
                mu = sums / count
                g = g / count - np.outer(mu, mu)
        out = pd.DataFrame({"column": self.columns})
        for j, c in enumerate(self.columns):
            out[c] = g[:, j]
        return bridge.arrow_to_device(pa.Table.from_pandas(out, preserve_index=False))


class ReservoirQuantileExecutor(Executor):
    """Per-channel MERGEABLE quantile sketch (merging t-digest,
    ops/tdigest.py — the ldbpy t-digest role in the reference).  Emits the
    serialized digest; the combine stage merges digests exactly, so results
    are partitioning-independent (the round-1 reservoir version averaged
    per-channel quantiles).  Name kept for API stability."""

    def __init__(self, column: str, quantiles: Sequence[float],
                 compression: float = 200.0, **_legacy):
        from quokka_tpu.ops.tdigest import TDigest

        self.column = column
        self.quantiles = list(quantiles)
        self.digest = TDigest(compression)

    def execute(self, batches, stream_id, channel):
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            x, live = tracing.device_read(
                "linalg.quantile_col", (b.columns[self.column].data, b.valid))
            x = x[live]
            self.digest.add(x.astype(np.float64))

    def done(self, channel):
        means, weights = self.digest.to_arrays()
        if len(means) == 0:
            return None
        return bridge.arrow_to_device(
            pa.table({"__td_mean": means, "__td_weight": weights})
        )


class CombineQuantileExecutor(Executor):
    """Merge the per-channel t-digests EXACTLY, then evaluate the quantiles
    on the combined sketch — no partitioning dependence."""

    def __init__(self, column: str, quantiles: Sequence[float],
                 compression: float = 200.0):
        from quokka_tpu.ops.tdigest import TDigest

        self.column = column
        self.quantiles = list(quantiles)
        self.digest = TDigest(compression)
        self.any = False

    def execute(self, batches, stream_id, channel):
        from quokka_tpu.ops.tdigest import TDigest

        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            t = bridge.device_to_arrow(b)
            self.digest.merge(TDigest.from_arrays(
                t.column("__td_mean").to_numpy(zero_copy_only=False),
                t.column("__td_weight").to_numpy(zero_copy_only=False),
            ))
            self.any = True

    def done(self, channel):
        if not self.any:
            return None
        qs = [self.digest.quantile(q) for q in self.quantiles]
        return bridge.arrow_to_device(
            pa.table({"quantile": np.array(self.quantiles), self.column: np.array(qs)})
        )
