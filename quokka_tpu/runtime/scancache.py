"""Device-resident scan (buffer-pool) cache.

The role a buffer pool / page cache plays in a CPU database: hot table
segments stay resident so repeated scans skip IO.  Here the cached unit is
the POST-BRIDGE DeviceBatch — decoded, dictionary-encoded, packed and already
living in device HBM — so a warm re-scan skips parquet decode, host encode
AND the host->device transfer (the two dominant costs of a scan on a
single-core ingest host behind a thin accelerator link).

Correctness: entries are keyed by the reader-provided identity of the
underlying bytes (path, mtime_ns, size, row-group, projection), so a
rewritten file never serves stale data.  DeviceBatch columns are immutable
jax arrays; the cache hands out a shallow copy so callers can attach their
own nrows/sorted_by metadata.

Scope: readers opt in by exposing ``cache_key(channel, lineage)``; lineages
whose bytes are not reproducible (REST pages, ray objects) return None and
bypass the cache.  Capped by bytes with LRU eviction
(QUOKKA_SCAN_CACHE_BYTES, 0 disables).

Sharing: ``GLOBAL`` is PROCESS-global and thread-safe — one LRU serves every
concurrent query in the query service, so a second query scanning the same
parquet is a warm hit even while the first is still running.  Keys carry the
file's byte identity, never a query id; accounting is per-query
(``get(..., query=...)`` feeds ``stats()["by_query"]``) so the service can
attribute warmth without fragmenting the cache.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional, Tuple

from quokka_tpu.ops.batch import DeviceBatch

def _default_bytes() -> int:
    env = os.environ.get("QUOKKA_SCAN_CACHE_BYTES")
    if env is not None:
        return int(env)
    import jax

    # TPU HBM is >= 16 GB; host-memory (CPU) runs get a modest default so
    # tests and small boxes are not pinned by cached scans
    return (2 << 30) if jax.default_backend() != "cpu" else (256 << 20)


def _batch_nbytes(batch: DeviceBatch) -> int:
    from quokka_tpu.runtime.cache import _batch_nbytes as nb

    return nb(batch)


class ScanCache:
    def __init__(self, cap_bytes: Optional[int] = None):
        self.cap = _default_bytes() if cap_bytes is None else cap_bytes
        self._lock = threading.Lock()
        self._data: "OrderedDict[Tuple, Tuple[DeviceBatch, int]]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        # query_id -> {"hits": n, "misses": n}: per-query attribution for
        # the service's shared cache (concurrent queries, one LRU)
        self._by_query: dict = {}

    @property
    def enabled(self) -> bool:
        return self.cap > 0

    def _account(self, query: Optional[str], field: str) -> None:
        if query is None:
            return
        q = self._by_query.get(query)
        if q is None:
            q = self._by_query[query] = {"hits": 0, "misses": 0}
        q[field] += 1

    def get(self, key: Tuple,
            query: Optional[str] = None) -> Optional[DeviceBatch]:
        with self._lock:
            ent = self._data.get(key)
            if ent is None:
                self.misses += 1
                self._account(query, "misses")
                return None
            self._data.move_to_end(key)
            self.hits += 1
            self._account(query, "hits")
            b, _ = ent
        return DeviceBatch(dict(b.columns), b.valid, b.nrows, b.sorted_by, b.nrows_dev)

    def put(self, key: Tuple, batch: DeviceBatch) -> None:
        if not self.enabled:
            return
        nb = _batch_nbytes(batch)
        if nb > self.cap:
            return
        snap = DeviceBatch(
            dict(batch.columns), batch.valid, batch.nrows, batch.sorted_by, batch.nrows_dev
        )
        evicted = []
        with self._lock:
            old = self._data.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            self._data[key] = (snap, nb)
            self._bytes += nb
            while self._bytes > self.cap and self._data:
                k, (_, oldnb) = self._data.popitem(last=False)
                self._bytes -= oldnb
                evicted.append(k)
        # memory ledger outside the LRU lock.  query=None: entries are
        # keyed by FILE identity and deliberately outlive the query that
        # warmed them — process-global residency, never a per-query leak
        from quokka_tpu.obs import memplane

        memplane.LEDGER.track(("scan", id(self), key),
                              memplane.SITE_READER, nb)
        for k in evicted:
            if k != key:
                memplane.LEDGER.retire(("scan", id(self), k))

    def clear(self) -> None:
        with self._lock:
            keys = list(self._data.keys())
            self._data.clear()
            self._bytes = 0
        from quokka_tpu.obs import memplane

        for k in keys:
            memplane.LEDGER.retire(("scan", id(self), k))

    def drop_query(self, query: str) -> None:
        """Forget a finished query's ACCOUNTING.  Cached batches stay — they
        are keyed by file identity and are exactly the warmth the next query
        over the same files wants."""
        with self._lock:
            self._by_query.pop(query, None)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._data),
                "bytes": self._bytes,
                "hits": self.hits,
                "misses": self.misses,
                "by_query": {q: dict(c) for q, c in self._by_query.items()},
            }


GLOBAL = ScanCache()


def clear() -> None:
    GLOBAL.clear()
