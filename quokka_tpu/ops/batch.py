"""Device-resident columnar batch.

The unit of data flowing through the engine.  Where the reference keeps Polars
DataFrames on the host (pyquokka/core.py push/execute paths), quokka-tpu keeps
batches as dicts of padded ``jax.Array`` columns plus a validity mask, so every
relational kernel (filter/project/hash/agg/join) is a jitted XLA program with
static shapes.

Strings are dictionary-encoded at ingest: the device sees only int32 codes; the
dictionary (small: unique values) stays on the host together with 64-bit FNV
hashes split into two uint32 limbs (TPU-native — no 64-bit ints needed on
device).  Predicates on strings are evaluated once on the dictionary host-side
and gathered by code on device; joins/groupbys on strings use the hash limbs.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from quokka_tpu import config
from quokka_tpu.ops import sigkey

# ---------------------------------------------------------------------------
# String dictionaries
# ---------------------------------------------------------------------------

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = 0xFFFFFFFFFFFFFFFF


def fnv1a64(s) -> int:
    """Stable 64-bit FNV-1a hash (process-independent, unlike Python hash()).
    Accepts str or bytes (binary dictionary values hash their raw bytes)."""
    h = _FNV_OFFSET
    data = (
        s if isinstance(s, (bytes, bytearray))
        else s.encode("utf-8", errors="surrogatepass")
    )
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & _MASK64
    return h


def _hash_strings(values: Sequence) -> np.ndarray:
    try:
        from quokka_tpu.utils import native  # C++ fast path if built

        out = native.fnv1a64_many(values)
        if out is not None:
            return out
    except Exception:
        pass
    return np.array([fnv1a64(v) if v is not None else 0 for v in values], dtype=np.uint64)


class StringDict:
    """Host-side dictionary for a string (or binary) column: values + 64-bit
    hashes as two uint32 limb arrays (device-friendly).  `binary` marks a
    bytes-valued dictionary (whole-file blob columns) so device_to_arrow
    round-trips to pa.binary instead of pa.string."""

    def __init__(self, values: np.ndarray, binary: Optional[bool] = None):
        # values: np object array of unique strings/bytes (may contain None)
        vals = np.asarray(values, dtype=object)
        if len(vals) == 0:
            # invariant: a dictionary is never empty.  All-invalid batches
            # get one null slot so every consumer can gather by clamped code
            # without special-casing zero-length host arrays.
            vals = np.array([None], dtype=object)
        self.values = vals
        if binary is None:
            # value sniff is a fallback only: an ALL-NULL dictionary can't be
            # sniffed, so producers that know the arrow type (bridge) pass
            # the flag explicitly to keep binary columns binary across
            # all-null batches
            binary = next(
                (isinstance(v, (bytes, bytearray)) for v in vals if v is not None),
                False,
            )
        self.binary = bool(binary)
        self._h64: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.values)

    @property
    def h64(self) -> np.ndarray:
        if self._h64 is None:
            self._h64 = _hash_strings(self.values)
        return self._h64

    @property
    def hash_hi(self) -> np.ndarray:
        return (self.h64 >> np.uint64(32)).astype(np.uint32).astype(np.int32)

    @property
    def hash_lo(self) -> np.ndarray:
        return (self.h64 & np.uint64(0xFFFFFFFF)).astype(np.uint32).astype(np.int32)

    def code_of(self, literal: str) -> int:
        """Code of a literal in this dictionary, or -1 if absent."""
        hits = np.nonzero(self.values == literal)[0]
        return int(hits[0]) if len(hits) else -1

    @property
    def rank(self) -> np.ndarray:
        """int32 rank of every entry in the values' lexicographic order: an
        ascending sort of ranks is a true string sort, not hash order."""
        if getattr(self, "_rank", None) is None:
            order = np.argsort(self.values.astype(str), kind="stable")
            rank = np.empty(len(order), dtype=np.int32)
            rank[order] = np.arange(len(order), dtype=np.int32)
            self._rank = rank
        return self._rank

    @property
    def none_entries(self) -> Optional[np.ndarray]:
        """Bool mask of None (null) entries, or None when there are none."""
        if not hasattr(self, "_none_entries"):
            m = np.array([x is None for x in self.values], dtype=bool)
            self._none_entries = m if m.any() else None
        return self._none_entries


# ---------------------------------------------------------------------------
# Columns
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class NumCol:
    """Numeric / boolean / date / timestamp column on device.

    kind: 'f' float, 'i' int, 'b' bool, 'd' date32 (days), 't' timestamp.
    ``hi`` is the optional high 32-bit limb for wide integers/timestamps when
    running without x64 (TPU): value = hi * 2^32 + uint32(data).
    """

    data: jax.Array
    kind: str = "f"
    hi: Optional[jax.Array] = None
    unit: Optional[str] = None  # timestamp unit ('s','ms','us','ns')

    @property
    def padded_len(self) -> int:
        return self.data.shape[0]

    def take(self, idx: jax.Array) -> "NumCol":
        return NumCol(
            self.data[idx], self.kind, None if self.hi is None else self.hi[idx], self.unit
        )


class _IdCache:
    """LRU of device tables derived from host objects, keyed by the objects'
    IDENTITY (an entry keeps its objects alive, so an id cannot be reused
    while it is cached) and bounded by the elements held on the device."""

    def __init__(self, budget: int):
        self._budget = budget
        self._held = 0
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()

    def get(self, tag: str, objs: Sequence, build):
        """``build() -> (value, elements)`` on a miss (outside the lock: two
        threads may both build, one copy stays)."""
        key = (tag,) + tuple(id(o) for o in objs)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                return hit[1]
        value, cost = build()
        with self._lock:
            if key not in self._entries:
                self._entries[key] = (tuple(objs), value, cost)
                self._held += cost
                while self._held > self._budget and len(self._entries) > 1:
                    _, (_objs, _v, c) = self._entries.popitem(last=False)
                    self._held -= c
            return self._entries[key][1]


# device copies of dictionaries' host tables, made once per dictionary and
# not per call: 32 MB of int32 at the most
DEVICE_TABLES = _IdCache(budget=1 << 23)


def pad_table(values: np.ndarray, size: int) -> np.ndarray:
    out = np.zeros(size, dtype=np.int32)
    out[: len(values)] = values
    return out


def _device_tables(tag: str, dictionary: StringDict, host_tables):
    """Host int32 tables of a dictionary as device arrays, padded to a power
    of two (a table's length is part of a compiled program's signature)."""
    def build():
        size = sigkey.pow2_dim(len(dictionary))
        tables = tuple(jax.device_put(pad_table(t, size))
                       for t in host_tables())
        return tables, len(tables) * size

    return DEVICE_TABLES.get(tag, (dictionary,), build)


def hash_tables(dictionary: StringDict):
    """(hash_hi, hash_lo) of a dictionary on the device."""
    return _device_tables(
        "hash", dictionary, lambda: (dictionary.hash_hi, dictionary.hash_lo))


def rank_table(dictionary: StringDict) -> jax.Array:
    return _device_tables("rank", dictionary, lambda: (dictionary.rank,))[0]


def code_hash_limbs(codes, hash_hi, hash_lo):
    """(hi, lo) hash limbs of dictionary codes, given the dictionary's two
    hash tables as device arrays.  Null rows (code < 0) get the hash of
    null, (0, 0) — the pair _hash_strings assigns to None dictionary entries
    — so all nulls land in one group for groupby/sort instead of aliasing
    the last entry."""
    c = jnp.maximum(codes, 0)
    isnull = codes < 0
    return jnp.where(isnull, 0, hash_hi[c]), jnp.where(isnull, 0, hash_lo[c])


@functools.lru_cache(maxsize=None)
def _hash_limbs_kernel():
    return jax.jit(code_hash_limbs)  # built at first use, not at import


def map_codes(codes, table):
    """Dictionary codes through an int32 table on the device, null rows
    (code -1) staying -1 (a bare gather would clamp -1 onto entry 0): codes
    of one dictionary as codes of a merged one (bridge.merge_dicts' remap),
    or as sort limbs (StringDict.rank: nulls sort first ascending)."""
    return jnp.where(codes < 0, -1, table[jnp.maximum(codes, 0)])


@dataclasses.dataclass
class StrCol:
    """Dictionary-encoded string column: int32 codes on device, dict on host."""

    codes: jax.Array
    dictionary: StringDict

    @property
    def padded_len(self) -> int:
        return self.codes.shape[0]

    def hash_limbs(self):
        """Two int32 device arrays (hi, lo) of the 64-bit value hash per row
        (nulls: the hash of null, see code_hash_limbs): one program, over
        the dictionary's cached device tables."""
        from quokka_tpu.runtime import compileplane

        return compileplane.aot_kernel_call(
            "hash_limbs", _hash_limbs_kernel(),
            (self.codes, *hash_tables(self.dictionary)))

    def take(self, idx: jax.Array) -> "StrCol":
        return StrCol(self.codes[idx], self.dictionary)


@dataclasses.dataclass
class VecCol:
    """Fixed-width vector (embedding) column: [rows, dim] device array.
    Bridge target for arrow fixed_size_list<float> columns; the payload of
    vector search (top-k cosine runs as a matmul on the MXU)."""

    data: jax.Array  # [padded_rows, dim]

    @property
    def padded_len(self) -> int:
        return self.data.shape[0]

    @property
    def dim(self) -> int:
        return self.data.shape[1]

    def take(self, idx: jax.Array) -> "VecCol":
        return VecCol(self.data[idx])


Column = object  # NumCol | StrCol | VecCol


# ---------------------------------------------------------------------------
# Null representation (sentinel encoding)
#
# The reference carries Polars/Arrow validity bitmaps; device batches instead
# reserve one value per kind as NULL and map it back to a real Arrow null at
# the device->host boundary (bridge.device_to_arrow):
#   floats            NaN
#   narrow int/date   INT32_MIN (INT64_MIN under x64)
#   wide int/ts       INT64_MIN (both limbs == INT32_MIN under the lo-2^31
#                     encoding)
#   strings           dictionary code -1
#   bools             no null (ingest fills False); nulled bools upcast to 'i'
# Consequences (documented divergence): INT_MIN as real data reads as null,
# and nulls sort first (smallest) rather than Polars' nulls-last.
# ---------------------------------------------------------------------------

NULL_I32 = -(2**31)
NULL_I64 = -(2**63)


def _int_sentinel(dtype):
    return NULL_I64 if dtype == jnp.int64 else NULL_I32


def null_mask(col) -> jax.Array:
    """Per-row null mask for any column kind."""
    if isinstance(col, StrCol):
        isnull = col.codes < 0
        none = col.dictionary.none_entries
        if none is not None:
            isnull = isnull | jnp.asarray(none)[jnp.maximum(col.codes, 0)]
        return isnull
    if isinstance(col, VecCol):
        return jnp.zeros(col.padded_len, dtype=bool)
    if col.kind == "f":
        return jnp.isnan(col.data)
    if col.kind == "b":
        return jnp.zeros(col.padded_len, dtype=bool)
    if col.hi is not None:
        return (col.hi == NULL_I32) & (col.data == NULL_I32)
    return col.data == _int_sentinel(col.data.dtype)


def with_nulls(col, null_where: jax.Array):
    """Return `col` with rows where `null_where` marked null (sentinel)."""
    if isinstance(col, StrCol):
        return StrCol(jnp.where(null_where, -1, col.codes), col.dictionary)
    if isinstance(col, VecCol):
        return VecCol(jnp.where(null_where[:, None], 0.0, col.data))
    if col.kind == "f":
        return NumCol(jnp.where(null_where, jnp.nan, col.data), "f", unit=col.unit)
    if col.kind == "b":
        # bools have no spare value: upcast to int (0/1/NULL)
        data = jnp.where(null_where, NULL_I32, col.data.astype(jnp.int32))
        return NumCol(data, "i")
    if col.hi is not None:
        return NumCol(
            jnp.where(null_where, jnp.int32(NULL_I32), col.data),
            col.kind,
            hi=jnp.where(null_where, jnp.int32(NULL_I32), col.hi),
            unit=col.unit,
        )
    sent = _int_sentinel(col.data.dtype)
    return NumCol(jnp.where(null_where, sent, col.data), col.kind, unit=col.unit)


# ---------------------------------------------------------------------------
# Batch
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceBatch:
    """A padded columnar batch.  ``valid`` marks live rows; all kernels must
    respect it.  ``nrows`` is the host-known live count when available (None
    after device-side filtering until a sync).  ``nrows_dev`` is an optional
    device scalar of the live count whose host copy was started asynchronously
    at creation — ``count_valid()`` then blocks on an (almost always already
    finished) transfer instead of paying a full device round trip."""

    columns: Dict[str, Column]
    valid: jax.Array  # bool[padded]
    nrows: Optional[int] = None
    sorted_by: Optional[List[str]] = None  # ordered-stream metadata
    nrows_dev: Optional[jax.Array] = None

    @property
    def padded_len(self) -> int:
        return self.valid.shape[0]

    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    def count_valid(self) -> int:
        if self.nrows is None:
            from quokka_tpu.obs import spans as tracing

            src = self.nrows_dev if self.nrows_dev is not None else jnp.sum(self.valid)
            # the one read with a layer of its own: ``other``, where its
            # span has always been
            self.nrows = int(tracing.device_read("count_valid", src,
                                                 own_layer=True))
        return self.nrows

    def note_count(self, num: jax.Array) -> "DeviceBatch":
        """Record a device scalar as this batch's live count and start its
        async device->host copy (free to read later)."""
        try:
            num.copy_to_host_async()
        except Exception:
            pass  # tracers / numpy scalars: count stays device-lazy
        self.nrows_dev = num
        return self

    def select(self, names: Sequence[str]) -> "DeviceBatch":
        return DeviceBatch(
            {n: self.columns[n] for n in names}, self.valid, self.nrows,
            self.sorted_by, self.nrows_dev,
        )

    def drop(self, names: Sequence[str]) -> "DeviceBatch":
        keep = [n for n in self.columns if n not in set(names)]
        return self.select(keep)

    def rename(self, mapping: Dict[str, str]) -> "DeviceBatch":
        return DeviceBatch(
            {mapping.get(n, n): c for n, c in self.columns.items()},
            self.valid,
            self.nrows,
            self.sorted_by,
            self.nrows_dev,
        )

    def with_column(self, name: str, col: Column) -> "DeviceBatch":
        cols = dict(self.columns)
        cols[name] = col
        return DeviceBatch(cols, self.valid, self.nrows, self.sorted_by, self.nrows_dev)

    def take(self, idx: jax.Array, valid: jax.Array, nrows: Optional[int]) -> "DeviceBatch":
        cols = gather_columns(self.columns, idx)
        return DeviceBatch(cols, valid, nrows, self.sorted_by)


@jax.jit
def _gather_all(arrays, idx):
    """One compiled program gathering EVERY column at once: eager per-column
    `a[idx]` costs a separate dispatch (and bounds-check chain) per array —
    the dominant cost of wide-row takes in the engine's join path."""
    return tuple(a[idx] for a in arrays)


def column_arrays(col: "Column") -> List[jax.Array]:
    """A column's device arrays: string codes, vector data, a wide
    column's hi limb before its lo."""
    if isinstance(col, StrCol):
        return [col.codes]
    if isinstance(col, VecCol) or col.hi is None:
        return [col.data]
    return [col.hi, col.data]


def flat_arrays(columns: Dict[str, "Column"]) -> List[jax.Array]:
    return [a for c in columns.values() for a in column_arrays(c)]


def rebuild_columns(columns: Dict[str, "Column"], arrays) -> Dict[str, "Column"]:
    """Inverse of ``flat_arrays``: the same columns over new arrays."""
    it = iter(arrays)
    out: Dict[str, Column] = {}
    for n, c in columns.items():
        if isinstance(c, StrCol):
            out[n] = StrCol(next(it), c.dictionary)
        elif isinstance(c, VecCol):
            out[n] = VecCol(next(it))
        else:
            hi = next(it) if c.hi is not None else None
            out[n] = NumCol(next(it), c.kind, hi=hi, unit=c.unit)
    return out


def gather_columns(columns: Dict[str, "Column"], idx: jax.Array) -> Dict[str, "Column"]:
    """Row-gather a whole column dict through a single fused XLA program."""
    from quokka_tpu.runtime import compileplane

    return rebuild_columns(columns, compileplane.aot_kernel_call(
        "gather", _gather_all, (tuple(flat_arrays(columns)), idx)))


def key_limbs(batch: DeviceBatch, cols: Sequence[str]) -> List[jax.Array]:
    """Flatten key columns into a list of 32-bit (or native-width) integer/float
    arrays usable as lexicographic sort keys and equality keys.  Strings become
    their two hash limbs; wide ints contribute (hi, lo)."""
    limbs: List[jax.Array] = []
    for name in cols:
        c = batch.columns[name]
        if isinstance(c, StrCol):
            hi, lo = c.hash_limbs()
            limbs.append(hi)
            limbs.append(lo)
        else:
            if c.hi is not None:
                limbs.append(c.hi)
            limbs.append(c.data)
    return limbs
