"""Two-limb (wide int64) ordering/arithmetic helpers.

Without x64, int64/ns-timestamp columns live on device as two int32 limbs
(hi = value >> 32, lo_sortable = (value & 0xFFFFFFFF) - 2**31) so that signed
lexicographic (hi, lo_sortable) order equals numeric order (ops/bridge.py).
This module centralises every operation that must respect both limbs:

- widen_limbs / scalar_limbs: uniform limb views of narrow cols & host ints
- not_limbs: exact order-reversal (int64 bitwise NOT == per-limb NOT)
- limb comparisons for range partitioning
- host_i64: exact host int64 view of a column
- rebase_narrow / add_base: exact rebase of a wide time column onto an int32
  window relative to a host base (the "rescaled epoch" strategy for the
  streaming time-series tier; raises when the stream span overflows int32)

Reference counterpart: pyquokka's executors operate on host Polars int64
columns directly (ts_executors.py); here the 64-bit arithmetic must be
explicit because the device path is 32-bit.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops.batch import DeviceBatch, NumCol

_SIGN = np.uint32(0x80000000)


def _bitcast(x, dt):
    return jax.lax.bitcast_convert_type(x, dt)


def widen_limbs(col: NumCol) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo_sortable) int32 limb view of any integer-kind NumCol."""
    if col.hi is not None:
        return col.hi, col.data
    d = col.data
    if jnp.issubdtype(d.dtype, jnp.floating):
        raise TypeError("widen_limbs on float column")
    if d.dtype == jnp.int64:
        # narrow int64 storage (x64 mode): split exactly — the old int32
        # cast silently truncated ns-epoch timestamps
        hi = (d >> jnp.int64(32)).astype(jnp.int32)
        lo_u = (d & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        lo = _bitcast(lo_u ^ _SIGN, jnp.int32)
        return hi, lo
    d = d.astype(jnp.int32)
    hi = jnp.where(d < 0, jnp.int32(-1), jnp.int32(0))
    lo = _bitcast(_bitcast(d, jnp.uint32) ^ _SIGN, jnp.int32)
    return hi, lo


def not_limbs(limbs: Tuple[jax.Array, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Per-limb bitwise NOT == int64 bitwise NOT (~v = -v-1): exact strictly
    decreasing remap, used to run 'forward' asof on a backward kernel."""
    hi, lo = limbs
    return ~hi, ~lo


def scalar_limbs(v: int) -> Tuple[np.int32, np.int32]:
    """Limb encoding of a host int (arbitrary precision, sign-correct)."""
    v = int(v)
    return np.int32(v >> 32), np.int32((v & 0xFFFFFFFF) - 2**31)


def limb_le_scalar_count(col: NumCol, boundaries) -> jax.Array:
    """searchsorted(boundaries, col, side='right') for a possibly-wide column:
    per row, the count of boundaries <= value."""
    hi, lo = widen_limbs(col)
    bl = [scalar_limbs(b) for b in boundaries]
    bhi = jnp.asarray(np.array([h for h, _ in bl], dtype=np.int32))
    blo = jnp.asarray(np.array([l for _, l in bl], dtype=np.int32))
    le = (bhi[None, :] < hi[:, None]) | (
        (bhi[None, :] == hi[:, None]) & (blo[None, :] <= lo[:, None])
    )
    return jnp.sum(le, axis=1).astype(jnp.int32)


def host_max_i64(col: NumCol, valid) -> int:
    """Exact int64 max over valid rows via two device reduces (no bulk pull).
    Caller must ensure at least one valid row."""
    hi, lo = widen_limbs(col)
    neg = jnp.int32(-(2**31))
    mh = jnp.max(jnp.where(valid, hi, neg))
    ml = jnp.max(jnp.where(valid & (hi == mh), lo, neg))
    mh, ml = tracing.device_read("time.max", (mh, ml))
    return int(mh) * 2**32 + int(ml) + 2**31


def host_min_i64(col: NumCol, valid) -> int:
    """Exact int64 min over valid rows (mirror of host_max_i64)."""
    hi, lo = widen_limbs(col)
    pos = jnp.int32(2**31 - 1)
    mh = jnp.min(jnp.where(valid, hi, pos))
    ml = jnp.min(jnp.where(valid & (hi == mh), lo, pos))
    mh, ml = tracing.device_read("time.min", (mh, ml))
    return int(mh) * 2**32 + int(ml) + 2**31


def cmp_scalar(col: NumCol, v: int, op: str) -> jax.Array:
    """Elementwise comparison of a possibly-wide int column against a host int."""
    hi, lo = widen_limbs(col)
    vhi, vlo = scalar_limbs(v)
    eq = (hi == vhi) & (lo == vlo)
    lt = (hi < vhi) | ((hi == vhi) & (lo < vlo))
    return {
        "=": eq, "!=": ~eq, "<": lt, "<=": lt | eq, ">": ~(lt | eq), ">=": ~lt,
    }[op]


def host_i64(col: NumCol, valid) -> np.ndarray:
    """Exact int64 host values of the valid rows (one device->host sync)."""
    mask, hi, lo = tracing.device_read("time.values",
                                       (valid, col.hi, col.data))
    if hi is not None:
        hi = hi[mask].astype(np.int64)
        lo = lo[mask].astype(np.int64) + 2**31
        return (hi << np.int64(32)) | lo
    return lo[mask].astype(np.int64)


def rebase_narrow(col: NumCol, valid, base: int, headroom: int = 0) -> NumCol:
    """value - base as an int32 'i' column.  Exact: raises if any valid value
    falls outside [0, 2**31 - headroom) relative to base — the caller keeps
    `headroom` so later window arithmetic (t + size) cannot overflow."""
    hi, lo = widen_limbs(col)
    bhi, blo = scalar_limbs(base)
    lo_u = _bitcast(lo, jnp.uint32) ^ _SIGN        # true unsigned low limb
    blo_u = np.uint32((int(base) & 0xFFFFFFFF))
    diff_lo = lo_u - blo_u                          # wraps mod 2^32
    borrow = (lo_u < blo_u).astype(jnp.int32)
    diff_hi = hi - jnp.int32(int(base) >> 32) - borrow
    rel = _bitcast(diff_lo, jnp.int32)
    limit = jnp.int32(2**31 - 1 - int(headroom))
    ok = (diff_hi == 0) & (rel >= 0) & (rel <= limit)
    if not bool(tracing.device_read("time.rebase_fits",
                                    jnp.all(ok | ~valid))):
        unit = f" {col.unit}" if col.unit else ""
        raise ValueError(
            f"time column spans more than 2^31{unit} units within one stream "
            f"(base={base}); cast to a coarser unit (e.g. ms/s) or enable x64"
        )
    return NumCol(jnp.where(valid, rel, 0), "i")


def add_base(data, base: Optional[int], kind: str, unit: Optional[str]) -> NumCol:
    """Inverse of rebase_narrow: int32 relative values + host base -> NumCol
    (wide if the absolute values need 64 bits)."""
    data = data.astype(jnp.int32)
    if not base:
        return NumCol(data, kind, unit=unit)
    lo_u = _bitcast(data, jnp.uint32)               # data >= 0 so low limb == data
    blo_u = np.uint32(int(base) & 0xFFFFFFFF)
    sum_lo = lo_u + blo_u                            # wraps mod 2^32
    carry = (sum_lo < lo_u).astype(jnp.int32)
    hi = jnp.int32(int(base) >> 32) + carry
    lo = _bitcast(sum_lo ^ _SIGN, jnp.int32)
    return NumCol(lo, kind, hi=hi, unit=unit)
