"""Vector search executors: streaming brute-force top-k cosine similarity.

Reference parity: DFProbeDataStreamNNExecutor1/2 (pyquokka/executors/
vector_executors.py:3-114): per-partition brute-force top-k via BLAS matmul,
then a global reduce of the per-partition top-ks.  On TPU the Q x D @ D x N
similarity matrix is exactly what the MXU is for; the running per-query top-k
merges with jax.lax.top_k each batch, so state stays at [Q, k]."""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from quokka_tpu.executors.base import Executor
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import bridge
from quokka_tpu.ops.batch import DeviceBatch, NumCol, VecCol


class NearestNeighborExecutor(Executor):
    """Probe every batch's vectors against a fixed query matrix; keep the
    running top-k (by cosine similarity) per query.  Emits at done:
    (query_idx, score, <payload columns of the matched rows>)."""

    def __init__(self, queries: np.ndarray, vec_col: str, k: int,
                 payload: Optional[List[str]] = None):
        q = np.asarray(queries, dtype=np.float32)
        q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        self.queries = jnp.asarray(q)  # [Q, D] normalized
        self.vec_col = vec_col
        self.k = k
        self.payload = payload
        # running state: scores [Q, k] and matched host rows per (query, slot)
        self.scores: Optional[jnp.ndarray] = None
        self.rows: Optional[list] = None  # parallel [Q][k] arrow row indices
        self.row_tables: List[pa.Table] = []

    def execute(self, batches, stream_id, channel):
        for b in batches:
            if b is None or b.count_valid() == 0:
                continue
            self._probe(b)

    @staticmethod
    @jax.jit
    def _sims(queries, vecs, valid):
        v = vecs / jnp.maximum(
            jnp.linalg.norm(vecs, axis=1, keepdims=True), 1e-12
        )
        sims = queries @ v.T  # [Q, N] on the MXU
        return jnp.where(valid[None, :], sims, -jnp.inf)

    def _probe(self, b: DeviceBatch):
        vec = b.columns[self.vec_col]
        assert isinstance(vec, VecCol), f"{self.vec_col} is not a vector column"
        sims = self._sims(self.queries, vec.data.astype(jnp.float32), b.valid)
        k = min(self.k, sims.shape[1])
        top_s, top_i = jax.lax.top_k(sims, k)  # [Q, k] per batch
        # stash matched rows host-side, merge scores with running state
        table_idx = len(self.row_tables)
        payload_cols = self.payload or [c for c in b.names if c != self.vec_col]
        self.row_tables.append(
            bridge.device_to_arrow(b.select(payload_cols))
        )
        # map padded row index -> compacted arrow row index
        valid_np, top_i_np, top_s_np = tracing.device_read(
            "vector.topk", (b.valid, top_i, top_s))
        remap = np.cumsum(valid_np) - 1
        top_i_np = remap[top_i_np]
        handles = np.stack(
            [np.full_like(top_i_np, table_idx), top_i_np], axis=-1
        )  # [Q, k, 2]
        if self.scores is None:
            self.scores = top_s_np
            self.rows = handles
        else:
            merged_s = np.concatenate([self.scores, top_s_np], axis=1)
            merged_r = np.concatenate([self.rows, handles], axis=1)
            order = np.argsort(-merged_s, axis=1)[:, : self.k]
            self.scores = np.take_along_axis(merged_s, order, axis=1)
            self.rows = np.take_along_axis(
                merged_r, order[..., None], axis=1
            )

    def done(self, channel):
        if self.scores is None:
            return None
        qn, kn = self.scores.shape
        qi_g, sl_g = np.meshgrid(np.arange(qn), np.arange(kn), indexing="ij")
        alive = self.scores != -np.inf
        qi_f = qi_g[alive]
        scores_f = self.scores[alive]
        ti_f = self.rows[..., 0][alive]
        ri_f = self.rows[..., 1][alive]
        if len(qi_f) == 0:
            return None
        # gather payload rows with ONE take per source table, then one
        # permutation take to restore (query, slot) order
        order = np.argsort(ti_f, kind="stable")
        parts = []
        for ti in np.unique(ti_f):
            sel = order[ti_f[order] == ti]
            parts.append(self.row_tables[int(ti)].take(pa.array(ri_f[sel])))
        payload_sorted = pa.concat_tables(parts, promote_options="permissive")
        inverse = np.empty(len(order), dtype=np.int64)
        inverse[order] = np.arange(len(order))
        payload = payload_sorted.take(pa.array(inverse))
        out = pa.table(
            {
                "query_idx": pa.array(qi_f.astype(np.int64)),
                "score": pa.array(scores_f.astype(np.float64)),
                **{c: payload.column(c) for c in payload.column_names},
            }
        )
        self.scores = None
        self.rows = None
        self.row_tables = []
        return bridge.arrow_to_device(out)


class GlobalTopKReduceExecutor(Executor):
    """Second stage: merge per-partition (query_idx, score, payload) top-ks
    into the global top-k per query (vector_executors.py:53)."""

    def __init__(self, k: int):
        self.k = k
        self.parts: List[DeviceBatch] = []

    def execute(self, batches, stream_id, channel):
        self.parts.extend(b for b in batches if b is not None)

    def done(self, channel):
        if not self.parts:
            return None
        import pandas as pd

        df = pd.concat([bridge.to_pandas(b) for b in self.parts], ignore_index=True)
        self.parts = []
        out = (
            df.sort_values(["query_idx", "score"], ascending=[True, False])
            .groupby("query_idx")
            .head(self.k)
            .reset_index(drop=True)
        )
        return bridge.arrow_to_device(pa.Table.from_pandas(out, preserve_index=False))
