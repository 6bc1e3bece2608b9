"""Canonical jit-cache signature derivation — the ONE place cache-key
dimensions come from.

Every fused/AOT program in the engine is cached by a structural signature
(padded length, column dtypes, expression text, strategy flags).  That
space used to fragment: 11-15 real compiles per join query during
warmup, because each call site derived its own key from raw batch
properties — one program per 2x padded-length rung, per redundant
kind-char, per exact dictionary size.  This module collapses the key space:

- ``bucket_rows(n)``: the padded-length bucket ladder.  All rungs are
  powers of two (mesh sharding divides by them), but below ``LADDER_KNEE``
  rungs are spaced 4x apart instead of 2x: small intermediates (probe
  slices, partial aggregates, shuffle partitions) are sub-millisecond to
  process at any of those sizes, so the extra padding is free while the
  rung count — and with it the number of distinct compiled programs —
  halves at the small end.  Above the knee rungs stay 2x: padding waste is
  real memory there.  ``QUOKKA_SIG_LADDER=pow2`` restores the legacy pure
  2x ladder.
- ``pow2_dim(n)``: canonical key-space dimensions (dictionary sizes, hash
  buckets) — raw sizes vary per file/batch and would recompile the
  program every time a dictionary grows by one entry.
- ``batch_sig(batch, names)`` / ``col_sig``: the canonical per-column
  signature.  The column ``kind`` char is deliberately absent: traced
  programs rebuild kinds from dtypes (``fuse._infer_kind``), so date vs
  int32 columns compile to the same program and must share a key.
- ``aval_sig(args)``: canonical (shape, dtype) tuple over a pytree of
  arrays — the key half for AOT-compiled kernels (runtime/compileplane).
- ``make_key(kind, *parts)``: assembles the final hashable key AND records
  it in the process-wide ledger, so signature cardinality is observable
  (tests pin a per-query budget; lint QK012 bans keys built from raw
  lengths anywhere else).

No jax import: this module is on the config import path (config.bucket_size
delegates to ``bucket_rows``) and must stay dependency-light.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Sequence, Tuple

MIN_BUCKET = 256
MAX_BUCKET = 1 << 24
# below the knee, ladder rungs are spaced 4x (LADDER_STEP bits); above it 2x
LADDER_KNEE = 1 << 16
LADDER_STEP = 2

_PURE_POW2 = os.environ.get("QUOKKA_SIG_LADDER", "").lower() == "pow2"


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n - 1)).bit_length()


def bucket_rows(n: int) -> int:
    """Smallest ladder bucket that fits n rows.  All rungs are powers of
    two; rungs below LADDER_KNEE come every LADDER_STEP doublings so the
    small-shape compile space stays small."""
    if n <= MIN_BUCKET:
        return MIN_BUCKET
    b = _pow2_ceil(n)
    if b > MAX_BUCKET:
        raise ValueError(f"batch of {n} rows exceeds max bucket {MAX_BUCKET}")
    if _PURE_POW2 or b >= LADDER_KNEE:
        return b
    # snap up to the next rung: rung exponents are MIN_BUCKET's exponent
    # plus a multiple of LADDER_STEP
    base = MIN_BUCKET.bit_length() - 1
    over = (b.bit_length() - 1) - base
    rung = base + ((over + LADDER_STEP - 1) // LADDER_STEP) * LADDER_STEP
    return min(1 << rung, LADDER_KNEE)


def pow2_dim(n: int) -> int:
    """Canonical key-space dimension (dictionary size, bucket count):
    next power of two, so growth recompiles O(log) times, not O(n)."""
    return _pow2_ceil(n)


def col_sig(name: str, col) -> Tuple:
    """Canonical per-column signature: dtype + wide-limb presence decide
    the traced program; the kind char does not (kinds are re-inferred from
    dtypes inside the trace) and exact dictionary contents never do."""
    # StrCol duck-type: dictionary-encoded codes
    if hasattr(col, "codes"):
        return (name, "str")
    return (name, str(col.data.dtype), col.hi is not None)


def batch_sig(batch, names: Sequence[str]) -> Tuple:
    """Structural signature of a batch restricted to ``names`` — padded
    length (already on the canonical ladder by construction) plus each
    column's canonical signature."""
    return (batch.padded_len,) + tuple(
        col_sig(n, batch.columns[n]) for n in names
    )


def aval_sig(args) -> Tuple:
    """Canonical (shape, dtype) signature over a nested tuple of arrays —
    the shape half of an AOT kernel key.  Non-array leaves (ints, bools,
    strings: static parameters) pass through as themselves."""
    if isinstance(args, (tuple, list)):
        return tuple(aval_sig(a) for a in args)
    shape = getattr(args, "shape", None)
    dtype = getattr(args, "dtype", None)
    if shape is None or dtype is None:
        return args
    return (tuple(shape), str(dtype))


# ---------------------------------------------------------------------------
# signature ledger: every distinct program key, by kind — makes cache-key
# cardinality observable (tests pin a budget; prewarm reads it)
# ---------------------------------------------------------------------------

_ledger_lock = threading.Lock()
_LEDGER: Dict[str, set] = {}


def make_key(kind: str, *parts) -> Tuple:
    """Assemble a program cache key and record it in the ledger.  Hot
    path (steady-state kernel dispatch) is a lock-free membership probe —
    dict/set reads are GIL-atomic and the sets only grow; the lock is
    taken only for a genuinely new key."""
    key = (kind,) + tuple(parts)
    s = _LEDGER.get(kind)
    if s is None or key not in s:
        with _ledger_lock:
            _LEDGER.setdefault(kind, set()).add(key)
    return key


def ledger_counts() -> Dict[str, int]:
    """{kind: distinct keys recorded since reset} — the cardinality the
    compile plane exists to keep small."""
    with _ledger_lock:
        return {k: len(v) for k, v in _LEDGER.items()}


def ledger_keys(kind: str) -> Tuple:
    with _ledger_lock:
        return tuple(_LEDGER.get(kind, ()))


def reset_ledger() -> None:
    with _ledger_lock:
        _LEDGER.clear()
