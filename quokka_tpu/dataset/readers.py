"""Input readers.

Reader protocol (same as the reference, pyquokka/dataset/unordered_readers.py:30-42):
  get_own_state(num_channels) -> {channel: [lineage, ...]}
  execute(channel, lineage) -> pyarrow.Table
Lineage entries are small, picklable descriptions of an input slice — the unit
of deterministic re-execution for fault tolerance.

Implemented here: Parquet (per-row-group partitioning with column pushdown +
row-group min/max skipping), CSV (byte-range partitioning with newline-boundary
refinement, the technique of InputDiskCSVDataset, unordered_readers.py:273-442),
JSON-lines, and in-memory Arrow tables.
"""

from __future__ import annotations

import collections
import functools
import glob as globmod
import io
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.json as pajson
import pyarrow.parquet as pq

from quokka_tpu.expression import (
    BinOp,
    ColRef,
    DateLit,
    Expr,
    InList,
    Literal,
    split_conjuncts,
)


class InputArrowDataset:
    """In-memory table split into row slices (from_arrow / from_pandas)."""

    def __init__(self, table: pa.Table, batch_rows: int = 1 << 20):
        self.table = table
        self.batch_rows = batch_rows

    @property
    def schema(self) -> pa.Schema:
        return self.table.schema

    def get_own_state(self, num_channels: int) -> Dict[int, List]:
        n = self.table.num_rows
        slices = []
        start = 0
        while start < n:
            end = min(start + self.batch_rows, n)
            slices.append((start, end - start))
            start = end
        if not slices:
            slices = [(0, 0)]
        return {ch: slices[ch::num_channels] for ch in range(num_channels)}

    def execute(self, channel: int, lineage) -> pa.Table:
        start, length = lineage
        return self.table.slice(start, length)

    def size_hint(self) -> int:
        """Estimated source bytes (query-service admission control)."""
        return self.table.nbytes

    def num_rows(self) -> int:
        """Rows this scan can deliver at the most (a plan-time bound)."""
        return self.table.num_rows


class _Readahead:
    """One-segment scan readahead: while a channel's current batch executes,
    the NEXT lineage in that channel's schedule is read on a small IO pool,
    so a cold scan overlaps disk latency with device work instead of
    alternating read-then-compute (Q1 cold scan sat at 0.13 GB/s without it).

    Reads are pure (lineage -> same bytes every time), so serving a prefetch
    changes nothing the lineage/replay machinery can observe — a mismatched
    or failed prefetch silently falls back to the synchronous read.  One slot
    per (dataset, channel); the slot table is FIFO-bounded so dead datasets
    can't pin prefetched tables forever."""

    _MAX_SLOTS = 64

    def __init__(self):
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._slots: "collections.OrderedDict" = collections.OrderedDict()

    def _ensure_pool(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="quokka-readahead"
                )
            return self._pool

    def take(self, ds, channel: int, lineage):
        """The prefetched table for this exact lineage, or None."""
        key = (id(ds), channel)
        with self._lock:
            ent = self._slots.pop(key, None)
        if ent is None or ent[0] != lineage:
            return None
        try:
            table = ent[1].result()
        except Exception:
            return None
        from quokka_tpu.obs.metrics import REGISTRY

        REGISTRY.counter("scan.readahead_hit").inc()
        return table

    def arm(self, ds, channel: int, lineage, read_fn) -> None:
        key = (id(ds), channel)
        fut = self._ensure_pool().submit(read_fn)
        with self._lock:
            self._slots[key] = (lineage, fut)
            while len(self._slots) > self._MAX_SLOTS:
                self._slots.popitem(last=False)


_READAHEAD = _Readahead()


def _successor_map(state: Dict[int, List]) -> Dict:
    """(channel, lineage) -> the channel's next lineage."""
    succ = {}
    for ch, pieces in state.items():
        for cur, nxt in zip(pieces, pieces[1:]):
            succ[(ch, cur)] = nxt
    return succ


def _expand_paths(path) -> List[str]:
    if isinstance(path, (list, tuple)):
        out = []
        for p in path:
            out.extend(_expand_paths(p))
        return out
    if os.path.isdir(path):
        return sorted(
            p for p in globmod.glob(os.path.join(path, "**", "*"), recursive=True)
            if os.path.isfile(p)
        )
    matches = sorted(globmod.glob(path))
    return matches if matches else [path]


class InputParquetDataset:
    """Local/posix Parquet reader: channels own (file, row_group) pairs;
    supports projection pushdown and row-group skipping from min/max stats
    (the pushdown surface of InputEC2ParquetDataset, unordered_readers.py:3-72)."""

    def __init__(self, path, columns: Optional[Sequence[str]] = None, predicate: Optional[Expr] = None):
        self.path = path
        self.columns = list(columns) if columns else None
        self.predicate = predicate  # conjunction usable for row-group skipping
        # ANN pushdown (optimizer.push_ann): (queries, nprobe) restricts the
        # scan to row groups owning the queries' closest IVF cells when an
        # .ivf.npz sidecar exists (dataset/vector.py — the Lance-index role)
        self.ann_prune = None

    @property
    def schema(self) -> pa.Schema:
        f = pq.ParquetFile(_expand_paths(self.path)[0])
        return f.schema_arrow

    def get_own_state(self, num_channels: int) -> Dict[int, List]:
        pieces = []
        for f in _expand_paths(self.path):
            keep_rgs = None
            if self.ann_prune is not None:
                from quokka_tpu.dataset.vector import prune_row_groups

                queries, nprobe = self.ann_prune
                keep = prune_row_groups(f, queries, nprobe)
                if keep is not None:
                    keep_rgs = set(int(i) for i in keep)
            pf = pq.ParquetFile(f)
            meta = pf.metadata
            schema = pf.schema_arrow
            for rg in range(meta.num_row_groups):
                if keep_rgs is not None and rg not in keep_rgs:
                    continue
                if self.predicate is not None and _rowgroup_prunable(
                    meta.row_group(rg), self.predicate, schema
                ):
                    continue
                pieces.append((f, rg))
        state = {ch: pieces[ch::num_channels] for ch in range(num_channels)}
        self._succ = _successor_map(state)
        return state

    def execute(self, channel: int, lineage) -> pa.Table:
        table = _READAHEAD.take(self, channel, lineage)
        if table is None:
            table = self._read(lineage)
        nxt = getattr(self, "_succ", {}).get((channel, lineage))
        if nxt is not None:
            _READAHEAD.arm(self, channel, nxt,
                           functools.partial(self._read, nxt))
        return table

    def _read(self, lineage) -> pa.Table:
        f, rg = lineage
        # read_dictionary: string columns whose parquet pages are already
        # dictionary-encoded come back as DictionaryArray — the bridge then
        # skips a full host-side re-encode (single-core ingest hosts care)
        pf = pq.ParquetFile(f, read_dictionary=self._dict_columns(f))
        return pf.read_row_group(rg, columns=self.columns)

    def cache_key(self, channel: int, lineage):
        """Scan-cache identity of this lineage's bytes (engine buffer pool).
        mtime_ns + size guard against serving a rewritten file."""
        f, rg = lineage
        try:
            st = os.stat(f)
        except OSError:
            return None
        return ("parquet", f, rg, st.st_mtime_ns, st.st_size,
                tuple(self.columns) if self.columns else None)

    def size_hint(self) -> int:
        """Estimated source bytes (query-service admission control): the
        on-disk footprint of every file this scan touches."""
        total = 0
        for f in _expand_paths(self.path):
            try:
                total += os.path.getsize(f)
            except OSError:
                continue
        return total

    def num_rows(self) -> Optional[int]:
        """Rows this scan can deliver at the most, from the files' footers
        (row groups a predicate prunes still count: a plan-time bound)."""
        try:
            return sum(pq.ParquetFile(f).metadata.num_rows
                       for f in _expand_paths(self.path))
        except OSError:
            return None

    def _dict_columns(self, f) -> List[str]:
        cached = getattr(self, "_dict_cols_cache", None)
        if cached is not None:
            return cached
        schema = pq.read_schema(f)  # footer-only read, once per dataset
        cols = [
            fld.name
            for fld in schema
            if pa.types.is_string(fld.type) or pa.types.is_large_string(fld.type)
        ]
        self._dict_cols_cache = cols
        return cols


def _rowgroup_prunable(rg_meta, predicate: Expr, schema: pa.Schema) -> bool:
    """True if row-group min/max stats prove no row satisfies the predicate."""
    stats = {}
    for i in range(rg_meta.num_columns):
        col = rg_meta.column(i)
        name = col.path_in_schema
        if col.statistics is not None and col.statistics.has_min_max:
            stats[name] = (col.statistics.min, col.statistics.max)
    for conj in split_conjuncts(predicate):
        if _conjunct_excludes(conj, stats):
            return True
    return False


def _conjunct_excludes(conj: Expr, stats) -> bool:
    if not isinstance(conj, BinOp) or conj.op not in ("<", "<=", ">", ">=", "="):
        return False
    left, right, op = conj.left, conj.right, conj.op
    if not isinstance(left, ColRef):
        left, right = right, left
        op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}[op]
    if not isinstance(left, ColRef) or left.name not in stats:
        return False
    if isinstance(right, DateLit):
        val = right.days
        mn, mx = stats[left.name]
        import datetime

        if isinstance(mn, datetime.date):
            mn = (mn - datetime.date(1970, 1, 1)).days
            mx = (mx - datetime.date(1970, 1, 1)).days
    elif isinstance(right, Literal) and isinstance(right.value, (int, float)):
        val = right.value
        mn, mx = stats[left.name]
        if not isinstance(mn, (int, float)):
            return False
    else:
        return False
    if op == "<":
        return mn >= val
    if op == "<=":
        return mn > val
    if op == ">":
        return mx <= val
    if op == ">=":
        return mx < val
    if op == "=":
        return val < mn or val > mx
    return False


class InputCSVDataset:
    """CSV reader with byte-range channel partitioning.  Each lineage is
    (file, start, end); ranges are refined to newline boundaries at read time:
    a non-zero start skips the (partial) first line, and the read extends past
    `end` to the next newline — so every row is read exactly once
    (technique of unordered_readers.py:273-442)."""

    def __init__(
        self,
        path,
        schema: Optional[List[str]] = None,
        has_header: bool = True,
        sep: str = ",",
        stride: int = 16 << 20,
    ):
        self.path = path
        self.names = schema
        self.has_header = has_header
        self.sep = sep
        self.stride = stride
        self._schema_cache: Optional[pa.Schema] = None

    @property
    def schema(self) -> pa.Schema:
        if self._schema_cache is None:
            f = _expand_paths(self.path)[0]
            ropts = pacsv.ReadOptions(
                column_names=None if self.has_header else self.names
            )
            head = pacsv.read_csv(
                io.BytesIO(_head_bytes(f, 1 << 20)),
                read_options=ropts,
                parse_options=pacsv.ParseOptions(delimiter=self.sep),
            )
            self._schema_cache = head.schema
        return self._schema_cache

    def get_own_state(self, num_channels: int) -> Dict[int, List]:
        pieces = []
        for f in _expand_paths(self.path):
            size = os.path.getsize(f)
            start = 0
            while start < size:
                end = min(start + self.stride, size)
                pieces.append((f, start, end))
                start = end
        state = {ch: pieces[ch::num_channels] for ch in range(num_channels)}
        self._succ = _successor_map(state)
        return state

    def size_hint(self) -> int:
        """Estimated source bytes (query-service admission control)."""
        total = 0
        for f in _expand_paths(self.path):
            try:
                total += os.path.getsize(f)
            except OSError:
                continue
        return total

    def execute(self, channel: int, lineage) -> pa.Table:
        table = _READAHEAD.take(self, channel, lineage)
        if table is None:
            table = self._read(lineage)
        nxt = getattr(self, "_succ", {}).get((channel, lineage))
        if nxt is not None:
            _READAHEAD.arm(self, channel, nxt,
                           functools.partial(self._read, nxt))
        return table

    def _read(self, lineage) -> pa.Table:
        f, start, end = lineage
        data = _read_line_range(f, start, end)
        if not data:
            return self.schema.empty_table()
        if not self.has_header and self.names is None:
            raise ValueError("headerless CSV requires an explicit schema")
        if self.has_header and start == 0:
            names = None  # the first range carries the header row itself
        else:
            names = self.names if not self.has_header else list(self.schema.names)
        table = pacsv.read_csv(
            io.BytesIO(data),
            read_options=pacsv.ReadOptions(column_names=names),
            parse_options=pacsv.ParseOptions(delimiter=self.sep),
            convert_options=pacsv.ConvertOptions(
                column_types={f.name: f.type for f in self.schema}
            ),
        )
        return table


def _read_line_range(path: str, start: int, end: int) -> bytes:
    """Read the newline-delimited rows OWNED by byte range [start, end).

    Ownership rule (each row read by exactly one range): a range owns every row
    whose first byte lies in [start, end).  A row starts at offset 0 or right
    after a newline — so the range peeks at byte start-1: if it is a newline,
    the row beginning at `start` is owned here; otherwise the torn first line
    belongs to the previous range and is skipped.  Reads extend past `end`
    only while the last owned row is incomplete."""
    size = os.path.getsize(path)
    from quokka_tpu.utils import native

    with open(path, "rb") as fh:
        if start > 0:
            fh.seek(start - 1)
            prev = fh.read(1)
            own_first = prev == b"\n"
        else:
            own_first = True
        data = fh.read(end - start)
        pos = end
        while pos < size and (not data or data[-1:] != b"\n"):
            chunk = fh.read(1 << 16)
            if not chunk:
                break
            nl = native.find_newline(chunk)
            if nl >= 0:
                data += chunk[: nl + 1]
                break
            data += chunk
            pos += len(chunk)
    if not own_first:
        nl = native.find_newline(data)
        data = data[nl + 1 :] if nl >= 0 else b""
    return data


def _head_bytes(path: str, n: int) -> bytes:
    with open(path, "rb") as fh:
        data = fh.read(n)
    # trim to last complete line so schema inference never sees a torn row
    nl = data.rfind(b"\n")
    return data[: nl + 1] if nl >= 0 else data


class InputJSONDataset:
    """JSON-lines reader (InputDiskJSONDataset equivalent,
    unordered_readers.py:445)."""

    def __init__(self, path, stride: int = 16 << 20):
        self.path = path
        self.stride = stride

    @property
    def schema(self) -> pa.Schema:
        f = _expand_paths(self.path)[0]
        return pajson.read_json(io.BytesIO(_head_bytes(f, 1 << 20))).schema

    def get_own_state(self, num_channels: int) -> Dict[int, List]:
        pieces = []
        for f in _expand_paths(self.path):
            size = os.path.getsize(f)
            start = 0
            while start < size:
                end = min(start + self.stride, size)
                pieces.append((f, start, end))
                start = end
        return {ch: pieces[ch::num_channels] for ch in range(num_channels)}

    def execute(self, channel: int, lineage) -> pa.Table:
        f, start, end = lineage
        data = _read_line_range(f, start, end)
        if not data.strip():
            return self.schema.empty_table()
        return pajson.read_json(io.BytesIO(data))
