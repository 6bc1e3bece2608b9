"""Arrow <-> device bridge (+ device batch concat).

Converts pyarrow Tables (what readers produce and writers consume) into
DeviceBatch (what kernels consume).  Mirrors the role Polars conversion plays
at pyquokka/core.py:287-299 (batch arrives -> to polars -> executor), but the
target is padded jax Arrays with dictionary-encoded strings.

Wide integers (int64 / timestamps) without x64: stored as two int32 limbs
(hi = arithmetic >> 32, lo = low 32 bits with the sign bit flipped so that
signed-int32 lexicographic (hi, lo) order equals numeric order).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from quokka_tpu import config
from quokka_tpu.ops import pack
from quokka_tpu.ops.batch import (
    DeviceBatch,
    NumCol,
    StrCol,
    StringDict,
    VecCol,
    map_codes,
)

_I32_MIN = -(2**31)
_I32_MAX = 2**31 - 1


def _pad(arr: np.ndarray, padded: int, fill=0) -> np.ndarray:
    n = len(arr)
    if n == padded:
        return arr
    out = np.full(padded, fill, dtype=arr.dtype)
    out[:n] = arr
    return out


def _wide_int_limbs(vals: np.ndarray, padded: int):
    """Split int64 numpy values into (hi, lo_sortable) int32 limbs.

    lo_sortable = lo - 2**31 (sign-bit flip), so signed (hi, lo_sortable)
    lexicographic order equals numeric int64 order for every value —
    including when the low 32 bits straddle 2**31.
    """
    hi = (vals >> np.int64(32)).astype(np.int32)
    lo = (vals & np.int64(0xFFFFFFFF)).astype(np.int64)
    lo_sortable = (lo - 2**31).astype(np.int32)
    return _pad(hi, padded), _pad(lo_sortable, padded)


def _limbs_to_int64(hi: np.ndarray, lo_sortable: np.ndarray) -> np.ndarray:
    lo = lo_sortable.astype(np.int64) + 2**31
    return (hi.astype(np.int64) << np.int64(32)) | lo


def _ints_to_col(vals: np.ndarray, padded: int, kind: str, unit=None, nullm=None) -> NumCol:
    """nullm: optional bool mask of null rows (vals are 0-filled there); nulls
    become the kind's sentinel (batch.NULL_I32 / NULL_I64)."""
    from quokka_tpu.ops.batch import NULL_I32, NULL_I64

    vals = np.ascontiguousarray(vals)
    if config.x64_enabled():
        v = vals.astype(np.int64)
        if nullm is not None:
            v = np.where(nullm, np.int64(NULL_I64), v)
        return NumCol(_pad(v, padded), kind, unit=unit)
    if vals.size == 0 or (vals.min() >= _I32_MIN and vals.max() <= _I32_MAX):
        v = vals.astype(np.int32)
        if nullm is not None:
            v = np.where(nullm, np.int32(NULL_I32), v)
        return NumCol(_pad(v, padded), kind, unit=unit)
    v = vals.astype(np.int64)
    if nullm is not None:
        v = np.where(nullm, np.int64(NULL_I64), v)  # limbs: (NULL_I32, NULL_I32)
    hi, lo = _wide_int_limbs(v, padded)
    return NumCol(lo, kind, hi=hi, unit=unit)


def arrow_column_to_device(arr: pa.ChunkedArray, padded: int):
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    t = arr.type
    if pa.types.is_dictionary(t):
        idx = arr.indices
        if idx.null_count:
            idx = pc.fill_null(idx, -1)  # null rows -> code -1
        codes = idx.to_numpy(zero_copy_only=False).astype(np.int32)
        values = arr.dictionary.to_pylist()
        # the arrow value type decides binary-ness — a value sniff would
        # misclassify an all-null batch of a binary column as string
        is_bin = pa.types.is_binary(t.value_type) or pa.types.is_large_binary(
            t.value_type
        )
        return StrCol(
            _pad(codes, padded),
            StringDict(np.array(values, dtype=object), binary=is_bin),
        )
    if (
        pa.types.is_string(t) or pa.types.is_large_string(t)
        or pa.types.is_binary(t) or pa.types.is_large_binary(t)
    ):
        # binary columns (whole-file blobs) dictionary-encode like strings:
        # bytes stay on the host dictionary, int32 codes go on device
        enc = pc.dictionary_encode(arr)
        if isinstance(enc, pa.ChunkedArray):
            enc = enc.combine_chunks()
        return arrow_column_to_device(enc, padded)
    if pa.types.is_fixed_size_list(t):
        # must run before fill_null (lists can't fill with a scalar) and must
        # not rely on flatten() alone — it drops null slots, misaligning rows;
        # null rows become zero vectors explicitly
        dim = t.list_size
        valid_np = arr.is_valid().to_numpy(zero_copy_only=False)
        flat = arr.flatten().to_numpy(zero_copy_only=False).astype(config.float_dtype())
        out = np.zeros((padded, dim), dtype=flat.dtype)
        out[np.nonzero(valid_np)[0]] = flat.reshape(-1, dim)
        return VecCol(out)
    from quokka_tpu.ops.batch import NULL_I32

    nullm = None
    if arr.null_count:
        # nulls become kind sentinels (NaN / INT_MIN / code -1) — real Arrow
        # nulls again at device_to_arrow.  Bools have no spare value: False.
        nullm = np.logical_not(arr.is_valid().to_numpy(zero_copy_only=False))
        arr = pc.fill_null(arr, float("nan") if pa.types.is_floating(t) else 0)
    if pa.types.is_boolean(t):
        vals = arr.to_numpy(zero_copy_only=False).astype(np.bool_)
        return NumCol(_pad(vals, padded, fill=False), "b")
    if pa.types.is_date32(t):
        vals = arr.cast(pa.int32()).to_numpy(zero_copy_only=False).astype(np.int32)
        if nullm is not None:
            vals = np.where(nullm, np.int32(NULL_I32), vals)
        return NumCol(_pad(vals, padded), "d")
    if pa.types.is_date64(t):
        vals = arr.cast(pa.timestamp("ms")).cast(pa.int64()).to_numpy(zero_copy_only=False)
        vals = (vals // 86400000).astype(np.int32)
        if nullm is not None:
            vals = np.where(nullm, np.int32(NULL_I32), vals)
        return NumCol(_pad(vals, padded), "d")
    if pa.types.is_timestamp(t):
        vals = arr.cast(pa.int64()).to_numpy(zero_copy_only=False)
        return _ints_to_col(vals, padded, "t", unit=t.unit, nullm=nullm)
    if pa.types.is_decimal(t):
        vals = arr.cast(pa.float64()).to_numpy(zero_copy_only=False)
        vals = vals.astype(config.float_dtype())
        if nullm is not None:
            vals = np.where(nullm, np.nan, vals)
        return NumCol(_pad(vals, padded), "f")
    if pa.types.is_integer(t):
        vals = arr.to_numpy(zero_copy_only=False)
        return _ints_to_col(vals, padded, "i", nullm=nullm)
    if pa.types.is_floating(t):
        vals = arr.to_numpy(zero_copy_only=False).astype(config.float_dtype())
        return NumCol(_pad(vals, padded), "f")
    raise NotImplementedError(f"arrow type {t} not supported on device yet")


def arrow_to_device(table: pa.Table, sorted_by: Optional[List[str]] = None) -> DeviceBatch:
    n = table.num_rows
    padded = config.bucket_size(n)
    cols = {name: arrow_column_to_device(table.column(name), padded) for name in table.column_names}
    return host_cols_to_device(cols, n, padded, sorted_by)


def host_cols_to_device(
    cols, n: int, padded: int, sorted_by: Optional[List[str]] = None
) -> DeviceBatch:
    """Move numpy-backed columns to device as ONE packed transfer."""
    leaves: List[np.ndarray] = [pack.ValidCount(padded, n)]
    slots = []  # (col, attr)
    for col in cols.values():
        if isinstance(col, StrCol):
            leaves.append(col.codes)
            slots.append((col, "codes"))
        elif isinstance(col, VecCol):
            leaves.append(col.data)
            slots.append((col, "data"))
        else:
            leaves.append(col.data)
            slots.append((col, "data"))
            if col.hi is not None:
                leaves.append(col.hi)
                slots.append((col, "hi"))
    device = pack.pack_put(leaves)
    valid = device[0]
    for (col, attr), arr in zip(slots, device[1:]):
        setattr(col, attr, arr)
    return DeviceBatch(cols, valid, nrows=n, sorted_by=sorted_by)


def device_to_arrow(batch: DeviceBatch, site: str = "to_arrow") -> pa.Table:
    """Sync a batch to the host as a compacted Arrow table (valid rows only).
    All columns + the validity mask come back in ONE device->host transfer,
    the blocking read ``sync.<site>`` (``spans.device_read``)."""
    leaves = [batch.valid]
    slots = []
    for col in batch.columns.values():
        if isinstance(col, StrCol):
            leaves.append(col.codes)
            slots.append(1)
        elif isinstance(col, VecCol):
            leaves.append(col.data)
            slots.append(1)
        else:
            leaves.append(col.data)
            if col.hi is not None:
                leaves.append(col.hi)
                slots.append(2)
            else:
                slots.append(1)
    host = pack.get_packed(leaves, site)
    mask = host[0]
    host_cols = {}
    i = 1
    for (name, col), width in zip(batch.columns.items(), slots):
        if width == 2:
            host_cols[name] = (host[i], host[i + 1])
        else:
            host_cols[name] = (host[i], None)
        i += width
    arrays = []
    names = []
    for name, col in batch.columns.items():
        h_data, h_hi = host_cols[name]
        names.append(name)
        if isinstance(col, VecCol):
            mat = h_data[mask]
            flat = pa.array(mat.reshape(-1))
            arrays.append(
                pa.FixedSizeListArray.from_arrays(flat, col.dim)
            )
        elif isinstance(col, StrCol):
            codes = h_data[mask]
            vals = col.dictionary.values
            out = np.empty(len(codes), dtype=object)
            for i, c in enumerate(codes):
                out[i] = vals[c] if 0 <= c < len(vals) else None
            typ = pa.binary() if col.dictionary.binary else pa.string()
            arrays.append(pa.array(out, type=typ))
        else:
            from quokka_tpu.ops.batch import NULL_I32, NULL_I64

            data = h_data[mask]
            if col.hi is not None:
                hi = h_hi[mask]
                v64 = _limbs_to_int64(hi, data)
                nullm = v64 == NULL_I64
                nullm = nullm if nullm.any() else None
                if col.kind == "t":
                    arrays.append(
                        pa.array(v64, mask=nullm).cast(pa.timestamp(col.unit or "us"))
                    )
                else:
                    arrays.append(pa.array(v64, type=pa.int64(), mask=nullm))
            elif col.kind == "d":
                d32 = data.astype(np.int32)
                nullm = d32 == np.int32(NULL_I32)
                nullm = nullm if nullm.any() else None
                arrays.append(pa.array(d32, mask=nullm).cast(pa.date32()))
            elif col.kind in ("i", "t"):
                sent = NULL_I64 if data.dtype == np.int64 else NULL_I32
                nullm = data == sent
                nullm = nullm if nullm.any() else None
                if col.kind == "t":
                    arrays.append(
                        pa.array(data.astype(np.int64), mask=nullm).cast(
                            pa.timestamp(col.unit or "us")
                        )
                    )
                else:
                    arrays.append(pa.array(data, mask=nullm))
            elif col.kind == "b":
                arrays.append(pa.array(data.astype(np.bool_)))
            else:
                arrays.append(pa.array(data))
    return pa.table(arrays, names=names)


def merge_dicts(dicts: Sequence[StringDict]):
    """Merge string dictionaries; returns (merged StringDict, [remap arrays])."""
    if all(d is dicts[0] for d in dicts):
        # one dictionary object throughout (parts cut from one batch, or one
        # cached scan): it is its own merge, and keeps its identity
        return dicts[0], [None] * len(dicts)
    all_vals = np.concatenate([d.values for d in dicts])
    # np.unique on object arrays with None fails; substitute sentinel.
    # Uniqueness keys are str() reprs (injective per column type); merged
    # values are the ORIGINAL objects so bytes dictionaries survive intact.
    sent = "\x00__null__"
    flat = np.array([sent if v is None else v for v in all_vals], dtype=object)
    uniq, first_idx, inverse = np.unique(
        flat.astype(str), return_index=True, return_inverse=True
    )
    merged_vals = np.array(
        [None if flat[i] == sent else all_vals[i] for i in first_idx],
        dtype=object,
    )
    merged = StringDict(merged_vals, binary=any(d.binary for d in dicts))
    remaps = []
    off = 0
    for d in dicts:
        remaps.append(inverse[off : off + len(d)].astype(np.int32))
        off += len(d)
    return merged, remaps


def concat_batches(batches: Sequence[DeviceBatch]) -> DeviceBatch:
    """Concatenate same-schema batches into one padded batch (host-coordinated:
    dictionaries merge on host, data stays on device).

    When any batch's live count is unknown host-side, the concat runs fully
    on device with NO sync: padded regions are concatenated as-is (validity
    masks included) instead of compacting first.  The result is looser-packed
    but avoids a blocking device round trip per input batch."""
    if len(batches) == 1:
        return batches[0]
    # resolve counts that are nearly free first: host-known nrows, or an
    # async-copied device count that has normally landed by concat time
    unresolved = 0
    for b in batches:
        if b.nrows is None:
            if b.nrows_dev is not None:
                b.count_valid()
            else:
                unresolved += 1
    if unresolved:
        if sum(b.padded_len for b in batches) > config.MAX_BUCKET:
            # sparse concat would blow past the bucket cap on padded length
            # alone; pay the blocking counts and compact instead
            for b in batches:
                b.count_valid()
        else:
            return _concat_batches_device(batches)
    names = batches[0].names
    total = sum(b.count_valid() for b in batches)
    padded = config.bucket_size(total)
    fused = _try_fused_concat(batches, total, padded)
    if fused is not None:
        return fused
    # compact each batch first (gather valid rows), then concat + pad
    from quokka_tpu.ops import kernels

    compacted = [kernels.compact(b) for b in batches]
    counts = [b.count_valid() for b in compacted]
    out_cols = {}
    for name in names:
        cols = [b.columns[name] for b in compacted]
        if isinstance(cols[0], StrCol):
            merged, remaps = merge_dicts([c.dictionary for c in cols])
            code_parts = []
            for c, remap, cnt in zip(cols, remaps, counts):
                codes = c.codes[:cnt]
                if remap is not None:
                    codes = map_codes(codes, jnp.asarray(remap))
                code_parts.append(codes)
            codes = _pad_device(jnp.concatenate(code_parts), padded)
            out_cols[name] = StrCol(codes, merged)
        elif isinstance(cols[0], VecCol):
            data = jnp.concatenate([c.data[:cnt] for c, cnt in zip(cols, counts)])
            if data.shape[0] < padded:
                data = jnp.pad(data, ((0, padded - data.shape[0]), (0, 0)))
            out_cols[name] = VecCol(data[:padded])
        else:
            cols = _align_limbs(cols)
            data = jnp.concatenate([c.data[:cnt] for c, cnt in zip(cols, counts)])
            data = _pad_device(data, padded)
            hi = None
            if cols[0].hi is not None:
                hi = _pad_device(
                    jnp.concatenate([c.hi[:cnt] for c, cnt in zip(cols, counts)]), padded
                )
            out_cols[name] = NumCol(data, cols[0].kind, hi=hi, unit=cols[0].unit)
    valid = jnp.arange(padded) < total
    sorted_by = batches[0].sorted_by
    return DeviceBatch(out_cols, valid, nrows=total, sorted_by=sorted_by)


@functools.partial(jax.jit, static_argnames=("out_padded", "method"))
def _fused_concat_kernel(part_arrays, valids, out_padded: int, method: str):
    """One XLA program for the whole compact-concat: stack validity, gather
    the live rows of every column to the front of one bucketed output.
    ``part_arrays``: per column, the tuple of per-part arrays.  Replaces the
    eager per-part compact + per-column concat chain (dozens of dispatches
    and intermediate buffers per call) that dominated the vectorized
    probe/aggregate pipelines' host overhead.  ``method``: how the live
    rows' indices are found (``kernels.live_indices``)."""
    from quokka_tpu.ops import kernels

    vcat = jnp.concatenate(valids)
    idx = kernels.live_indices(vcat, out_padded, method)
    live = jnp.arange(out_padded) < jnp.sum(vcat.astype(jnp.int32))
    outs = []
    for arrays in part_arrays:
        g = jnp.concatenate(arrays)[idx]
        # zero the invalid tail (the index's fill duplicates row 0 there):
        # downstream sort-segmented kernels key off raw limb values and a
        # duplicated real key could extend a segment into the padding
        m = live if g.ndim == 1 else live[:, None]
        outs.append(jnp.where(m, g, jnp.zeros((), g.dtype)))
    return tuple(outs), live


def _try_fused_concat(batches, total: int, padded: int):
    """Fused compact-concat when every column concatenates as plain device
    arrays: NumCol limbs align, StrCol codes remap on host first (dict
    merge), VecCol joins the fast path via its 2D data.  Returns None when
    a column mix needs the general path."""
    names = batches[0].names
    per_col = []  # (name, kind-tuple) with per-part arrays
    str_meta = {}
    for name in names:
        cols = [b.columns[name] for b in batches]
        if isinstance(cols[0], StrCol):
            merged, remaps = merge_dicts([c.dictionary for c in cols])
            parts = []
            for c, remap in zip(cols, remaps):
                codes = c.codes
                if remap is not None:
                    codes = map_codes(codes, jnp.asarray(remap))
                parts.append(codes)
            per_col.append((name, "str", tuple(parts)))
            str_meta[name] = merged
        elif isinstance(cols[0], VecCol):
            if len({c.dim for c in cols}) != 1:
                return None
            per_col.append((name, "vec", tuple(c.data for c in cols)))
        else:
            cols = _align_limbs(cols)
            if len({c.data.dtype for c in cols}) != 1:
                return None  # mixed narrow dtypes: general path promotes
            per_col.append((name, "num", tuple(c.data for c in cols)))
            if cols[0].hi is not None:
                per_col.append((name + "\0hi", "hi",
                                tuple(c.hi for c in cols)))
            str_meta[name] = cols[0]  # aligned kind/unit source
    valids = tuple(jnp.asarray(b.valid) for b in batches)
    from quokka_tpu.ops import kernels
    from quokka_tpu.runtime import compileplane

    method = kernels.compact_method(sum(v.shape[0] for v in valids))
    outs, valid = compileplane.aot_kernel_call(
        "fused_concat", _fused_concat_kernel,
        (tuple(arrs for (_n, _k, arrs) in per_col), valids),
        (padded, method), form=(kernels.COMPACT_FORM,))
    out_cols = {}
    it = iter(zip(per_col, outs))
    pending_hi = {}
    for (name, kind, _arrs), arr in it:
        if kind == "str":
            out_cols[name] = StrCol(arr, str_meta[name])
        elif kind == "vec":
            out_cols[name] = VecCol(arr)
        elif kind == "hi":
            pending_hi[name[:-3]] = arr
        else:
            src = str_meta[name]
            out_cols[name] = NumCol(arr, src.kind, unit=src.unit)
    for name, hi in pending_hi.items():
        c = out_cols[name]
        out_cols[name] = NumCol(c.data, c.kind, hi=hi, unit=c.unit)
    return DeviceBatch(out_cols, valid, nrows=total,
                       sorted_by=batches[0].sorted_by)


def _concat_batches_device(batches: Sequence[DeviceBatch]) -> DeviceBatch:
    """Sync-free concat: stack full padded regions + validity masks."""
    names = batches[0].names
    total_padded = config.bucket_size(sum(b.padded_len for b in batches))
    out_cols = {}
    for name in names:
        cols = [b.columns[name] for b in batches]
        if isinstance(cols[0], StrCol):
            merged, remaps = merge_dicts([c.dictionary for c in cols])
            code_parts = []
            for c, remap in zip(cols, remaps):
                codes = c.codes
                if remap is not None:
                    codes = map_codes(codes, jnp.asarray(remap))
                code_parts.append(codes)
            out_cols[name] = StrCol(
                _pad_device(jnp.concatenate(code_parts), total_padded), merged
            )
        elif isinstance(cols[0], VecCol):
            data = jnp.concatenate([c.data for c in cols])
            if data.shape[0] < total_padded:
                data = jnp.pad(data, ((0, total_padded - data.shape[0]), (0, 0)))
            out_cols[name] = VecCol(data[:total_padded])
        else:
            cols = _align_limbs(cols)
            data = _pad_device(jnp.concatenate([c.data for c in cols]), total_padded)
            hi = None
            if cols[0].hi is not None:
                hi = _pad_device(jnp.concatenate([c.hi for c in cols]), total_padded)
            out_cols[name] = NumCol(data, cols[0].kind, hi=hi, unit=cols[0].unit)
    valid = _pad_device(
        jnp.concatenate([jnp.asarray(b.valid) for b in batches]), total_padded
    )  # zero-fill: padded tail rows are invalid
    sorted_by = batches[0].sorted_by
    return DeviceBatch(out_cols, valid, nrows=None, sorted_by=sorted_by)


def _align_limbs(cols: Sequence[NumCol]) -> Sequence[NumCol]:
    """Promote plain-int32 columns to the two-limb representation when ANY
    sibling batch carries limbs.  _ints_to_col picks int32 vs limbs per batch
    from that batch's value range, so a stream can legitimately mix the two —
    concatenating a biased lo_sortable limb with plain values (and dropping
    hi) would silently corrupt every wide row."""
    if all(c.hi is None for c in cols) or all(c.hi is not None for c in cols):
        return cols
    from quokka_tpu.ops.batch import NULL_I32
    from quokka_tpu.ops.timewide import widen_limbs

    out = []
    for c in cols:
        if c.hi is not None:
            out.append(c)
            continue
        hi, lo = widen_limbs(c)
        # the plain-int32 null sentinel must become the wide null sentinel
        # (hi, lo) == (NULL_I32, NULL_I32), not the numeric value -2**31
        isnull = c.data == NULL_I32
        hi = jnp.where(isnull, jnp.int32(NULL_I32), hi)
        lo = jnp.where(isnull, jnp.int32(NULL_I32), lo)
        out.append(NumCol(lo, c.kind, hi=hi, unit=c.unit))
    return out


def _pad_device(arr, padded):
    n = arr.shape[0]
    if n == padded:
        return arr
    if n > padded:
        return arr[:padded]
    return jnp.pad(arr, (0, padded - n))


def to_pandas(batch_or_table):
    t = batch_or_table
    if isinstance(t, DeviceBatch):
        t = device_to_arrow(t)
    return t.to_pandas()
