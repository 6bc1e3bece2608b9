"""Jitted relational kernels over DeviceBatch.

Design notes (TPU-first):
- Every kernel is static-shape: batches are padded to buckets (config.bucket_size)
  and carry a validity mask.  Filtering flips mask bits; compaction (which needs
  a host sync for the live count) happens only at batch boundaries (shuffle,
  output), mirroring where the reference engine synchronizes anyway.
- Group-by is "sort, scans, sort" (sorted_groupby): sort rows by key limbs
  with the aggregates' inputs as sort operands, mark group starts, reduce the
  contiguous segments by prefix sums and segmented scans, and compact to dense
  rank order by a second sort.  This replaces the hash-table group-bys Polars
  does on CPU (SURVEY.md section 2.2) with a plan made of XLA's sort and scans:
  on the TPU a sort of 1<<20 rows costs about a millisecond, while a scatter
  or a gather costs 7-9 ns an element whatever the order of its indices.
- Multi-column / string / wide-int keys are lists of 32-bit "limbs"
  (ops/batch.key_limbs); lexicographic multi-operand lax.sort handles them
  without 64-bit device ints.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quokka_tpu import config
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import hashtable
from quokka_tpu.ops.batch import (
    DeviceBatch,
    NumCol,
    StrCol,
    gather_columns,
    key_limbs,
    map_codes,
    rank_table,
)

# ---------------------------------------------------------------------------
# masking / compaction
# ---------------------------------------------------------------------------


def _aot(kind, jit_fn, args, statics=(), form=()):
    """Kernel dispatch through the compile plane (persisted executables,
    canonical aval keys); inlines untouched inside traces."""
    from quokka_tpu.runtime import compileplane

    return compileplane.aot_kernel_call(kind, jit_fn, args, statics, form)


def apply_mask(batch: DeviceBatch, mask: jax.Array) -> DeviceBatch:
    new_valid, num = _aot("mask_count", _mask_and_count, (batch.valid, mask))
    return DeviceBatch(batch.columns, new_valid, None, batch.sorted_by).note_count(num)


@jax.jit
def _mask_and_count(valid, mask):
    v = valid & mask
    return v, jnp.sum(v.astype(jnp.int32))


# Names ``live_indices``' sort body in the key of every persisted program
# that traces it (``compact_idx`` below, ``fused_concat`` in ops/bridge.py,
# ``asof_take`` in ops/asof.py): an edit of the body changes this string, or
# the AOT store goes on running the old executable under the unchanged key.
COMPACT_FORM = "pack_unstable_sort"


def _nonzero_indices(valid, size):
    return jnp.nonzero(valid, size=size, fill_value=0)[0]


def _sorted_indices(valid, size):
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # unique values: a stable sort would carry an iota operand as its tie
    # break, a second operand for nothing
    key = lax.sort(jnp.where(valid, iota, iota + n), is_stable=False)
    key = jnp.concatenate(
        [key, jnp.full(max(size - n, 0), 2 * n, jnp.int32)])[:size]
    return jnp.where(key < n, key, 0)


_LIVE_INDICES = {"nonzero": _nonzero_indices, "sort": _sorted_indices}


def live_indices(valid: jax.Array, size: int, method: str) -> jax.Array:
    """``jnp.nonzero(valid, size=size, fill_value=0)[0]``, value for value.

    ``method`` is the ``compact`` row of the strategy matrix.  ``nonzero``
    is jax's own: a cumsum, a ``bincount`` (a scatter-add of one update a
    mask slot, whatever ``size`` is) and a cumsum.  ``sort`` sorts one int32
    operand, each slot's index with the mask's length added where the slot
    is dead: the live indices come first in their order, the dead ones
    after, and every entry from the mask's length on reads 0 as nonzero's
    fill does.  Works inside a trace; ``size`` and ``method`` are
    static."""
    return _LIVE_INDICES[method](valid, size)


def compact_method(slots: int) -> str:
    """The ``compact`` strategy for a compaction over ``slots`` mask slots,
    recorded as what ran (``strategy.note_used``, and the operator's
    ``compact_sort_slots`` / ``compact_nonzero_slots``)."""
    from quokka_tpu.obs import opstats
    from quokka_tpu.ops import strategy as kstrategy

    method = kstrategy.choice("compact")
    kstrategy.note_used("compact", method)
    opstats.note(**{f"compact_{method}_slots": int(slots)})
    return method


@functools.partial(jax.jit, static_argnames=("out_size", "method"))
def _compact_idx(valid, out_size, method):
    return live_indices(valid, out_size, method)


def compact(batch: DeviceBatch) -> DeviceBatch:
    """Gather valid rows to the front and shrink to the smallest bucket.
    Costs one host sync for the live count."""
    n = batch.count_valid()
    padded = config.bucket_size(n)
    if n == batch.padded_len and padded == batch.padded_len:
        return batch
    idx = _aot("compact_idx", _compact_idx, (batch.valid,),
               (padded, compact_method(batch.padded_len)), (COMPACT_FORM,))
    valid = jnp.arange(padded) < n
    return batch.take(idx, valid, n)


def compact_if_large(batch: DeviceBatch, threshold: int = 1 << 16) -> DeviceBatch:
    """Compact only when the padded region is big enough to matter.  Small
    batches pass through uncompacted — their blocking live-count read (a full
    host round trip) costs far more than the slack rows they carry."""
    if batch.padded_len <= threshold:
        return batch
    return compact(batch)


def head(batch: DeviceBatch, k: int) -> DeviceBatch:
    b = compact(batch)
    n = min(b.count_valid(), k)
    padded = config.bucket_size(n)
    idx = jnp.arange(padded)
    return b.take(idx, idx < n, n)


# ---------------------------------------------------------------------------
# sort-key limbs (order-preserving, unlike hash limbs)
# ---------------------------------------------------------------------------


def sort_limbs(batch: DeviceBatch, cols: Sequence[str], descending=None,
               ranks=None) -> List[jax.Array]:
    """Limbs whose ascending lexicographic order == the requested column order.
    Strings map codes -> dictionary-rank (StringDict.rank, a host argsort of
    the dict), so string sorts are true lexicographic sorts, not hash-order.
    ``ranks``: the rank tables as device arrays by column name, for a caller
    inside a trace (ops/aggtail.py); else each is its dictionary's cached copy."""
    if descending is None:
        descending = [False] * len(cols)
    limbs: List[jax.Array] = []
    for name, desc in zip(cols, descending):
        c = batch.columns[name]
        if isinstance(c, StrCol):
            rank = (ranks[name] if ranks is not None
                    else rank_table(c.dictionary))
            limb = map_codes(c.codes, rank)
            limbs.append(~limb if desc else limb)
        else:
            parts = []
            if c.hi is not None:
                parts.append(c.hi)
            parts.append(c.data)
            for p in parts:
                if desc:
                    # bitwise-not reverses signed-int (and bool) order with
                    # no overflow
                    p = -p if jnp.issubdtype(p.dtype, jnp.floating) else ~p
                limbs.append(p)
    return limbs


# ---------------------------------------------------------------------------
# dense rank (the group-by / join workhorse)
# ---------------------------------------------------------------------------


@jax.jit
def _dense_rank_impl(limbs: Tuple[jax.Array, ...], valid: jax.Array):
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    inv = (~valid).astype(jnp.int32)
    sorted_ops = lax.sort([inv, *limbs, iota], num_keys=1 + len(limbs))
    perm = sorted_ops[-1]
    valid_sorted = sorted_ops[0] == 0
    changed = jnp.zeros(n, dtype=bool)
    for limb_sorted in sorted_ops[1:-1]:
        changed = changed | (limb_sorted != jnp.roll(limb_sorted, 1))
    starts = valid_sorted & (changed | (iota == 0))
    ranks_sorted = jnp.cumsum(starts.astype(jnp.int32)) - 1
    ranks_sorted = jnp.maximum(ranks_sorted, 0)
    num = jnp.max(jnp.where(valid_sorted, ranks_sorted, -1)) + 1
    ranks = jnp.zeros(n, dtype=jnp.int32).at[perm].set(ranks_sorted)
    return ranks, num


def dense_rank(limbs: Sequence[jax.Array], valid: jax.Array):
    """Dense 0..k-1 ids such that two valid rows share an id iff their key limbs
    are equal.  Invalid rows get an arbitrary id; callers must mask."""
    return _dense_rank_impl(tuple(limbs), valid)


# ---------------------------------------------------------------------------
# group-by aggregate
# ---------------------------------------------------------------------------

AGG_OPS = ("sum", "count", "min", "max", "mean", "first")

# Names ``sorted_groupby``'s body in the key of every persisted program that
# traces it (``partial_agg`` in ops/fuse.py, ``agg_recombine`` in
# ops/aggtail.py): program keys carry no version of the code, so an edit of
# the body changes this string, or the AOT store goes on running the old
# executable under the unchanged key.
SORTED_GROUPBY_FORM = "sort_scan_sort"


def _reduce_to_segment_starts(x, rank, combine, ident):
    """Segmented reduction over sorted, contiguous segments as log2(n)
    shifted steps: after the loop the FIRST row of each segment (rows of
    equal ``rank``) holds ``combine`` over the whole segment.  A row only
    ever reads rows of its own segment, so a group's rounding error grows
    with the group (a pairwise tree) and not with the rows before it, and a
    NaN or an inf stays in its group."""
    n = x.shape[0]
    ident = jnp.asarray(ident, x.dtype)
    d = 1
    while d < n:
        same = jnp.concatenate([rank[d:], jnp.full(d, -2, rank.dtype)]) == rank
        ahead = jnp.concatenate([x[d:], jnp.full(d, ident, x.dtype)])
        x = combine(x, jnp.where(same, ahead, ident))
        d *= 2
    return x


def _diff_next(at_starts, live, end):
    """``at_starts[r]`` is a running quantity read where group ``r`` starts
    and ``end`` the same quantity past the last group: a group's own share is
    the next group's reading less its own (0 for the slots past ``live``)."""
    e = jnp.where(live, at_starts, end)
    return jnp.concatenate([e[1:], end[None]]) - e


@functools.partial(jax.jit, static_argnames=("ops",))
def sorted_groupby(limbs: Tuple[jax.Array, ...], arrays: Tuple[jax.Array, ...],
                   ops: Tuple[str, ...], valid: jax.Array):
    """Group-by-aggregate in sorted segment order: sort, scans, sort.

    No indexed op (a ``scatter`` or a ``gather`` costs the TPU 7-9 ns an
    element, sorted ids or not; a sort of 1<<20 rows about a millisecond):

    1. one multi-operand sort by (invalid, *limbs) that carries the row
       index and the aggregates' inputs, so nothing is gathered by the
       permutation.  ``lax.sort`` is stable (``is_stable=True`` is its
       default), so the row index ascends inside a group and the one at a
       segment's first row is the group's least original index: ``rep``;
    2. reductions over the contiguous segments as scans: integer sums and
       counts are a prefix sum differenced at the group boundaries (exact
       under two's-complement wrap whenever the group's own sum fits);
       float sums and every min / max are a segmented scan
       (``_reduce_to_segment_starts``), never a differenced float prefix;
    3. compaction to rank order as a second sort on one key that puts the
       segments' first rows first, in order: slot r holds group r.

    Returns (agg_outputs, counts, rep_indices, num): outputs indexed by dense
    rank and padded to the input length, ``rep`` maps rank -> the least
    original row index of the group (it holds the group's key values)."""
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    inv = (~valid).astype(jnp.int32)
    # what each aggregate reads, (reduction, input), and the inputs that ride
    # the sort, each once; count(*) and the count of an integer column read
    # the segment lengths alone
    wants, slot_of, carried = [], {}, []
    for arr, op in zip(arrays, ops):
        if op not in AGG_OPS:
            raise ValueError(f"unknown agg {op}")
        if op == "count" and not jnp.issubdtype(arr.dtype, jnp.floating):
            wants.append(None)
            continue
        if id(arr) not in slot_of:
            slot_of[id(arr)] = len(carried)
            carried.append(arr)
        kind = {"count": "notnan", "mean": "sum"}.get(op, op)
        wants.append((kind, slot_of[id(arr)]))
    nk = 1 + len(limbs)
    sorted_ops = lax.sort([inv, *limbs, iota, *carried], num_keys=nk)
    valid_s = sorted_ops[0] == 0
    perm = sorted_ops[nk]
    changed = jnp.zeros(n, dtype=bool)
    for limb_sorted in sorted_ops[1:nk]:
        changed = changed | (limb_sorted != jnp.roll(limb_sorted, 1))
    starts = valid_s & (changed | (iota == 0))
    # invalid rows sort last and keep the last group's rank: every column
    # below holds its reduction's identity there
    rank = jnp.cumsum(starts.astype(jnp.int32)) - 1
    num = rank[-1] + 1
    nvalid = jnp.sum(valid_s, dtype=jnp.int32)

    # one column per distinct want, to be read where each segment starts.
    # ``tails[i]`` says how: ("prefix", its reading past the last row) is
    # differenced; ("value", what an empty segment reduces to) is taken
    col_of, cols, tails = {}, [], []
    for want in dict.fromkeys(w for w in wants if w is not None):
        kind, slot = want
        x = sorted_ops[nk + 1 + slot]
        zero = jnp.zeros((), x.dtype)
        floating = jnp.issubdtype(x.dtype, jnp.floating)
        if kind == "first":
            c, tail = x, ("value", zero)
        elif kind == "notnan" or (kind == "sum" and not floating):
            if kind == "notnan":
                x = (valid_s & ~jnp.isnan(x)).astype(jnp.int32)
            else:
                x = jnp.where(valid_s, x, zero)
            incl = jnp.cumsum(x, dtype=x.dtype)
            c, tail = incl - x, ("prefix", incl[-1])
        else:  # a float sum, a min or a max: never a differenced prefix
            combine, ident = {
                "sum": (jnp.add, zero),
                "min": (jnp.minimum, _max_sentinel(x.dtype)),
                "max": (jnp.maximum, _min_sentinel(x.dtype)),
            }[kind]
            c = _reduce_to_segment_starts(
                jnp.where(valid_s, x, ident), rank, combine, ident)
            tail = ("value", ident)
        col_of[want] = len(cols)
        cols.append(c)
        tails.append(tail)

    # keys are unique, so the second sort needs no stability
    order = jnp.where(starts, iota, n + iota)
    pos, rep, *cols = lax.sort([order, perm, *cols], num_keys=1,
                               is_stable=False)
    live = iota < num
    counts = _diff_next(pos, live, nvalid)
    rep = jnp.where(live, rep, n - 1)

    outs = []
    for op, want in zip(ops, wants):
        if want is None:
            outs.append(counts)
            continue
        c, (how, at_end) = cols[col_of[want]], tails[col_of[want]]
        out = (_diff_next(c, live, at_end) if how == "prefix"
               else jnp.where(live, c, at_end))
        if op == "mean":
            out = out / jnp.maximum(counts, 1).astype(out.dtype)
        outs.append(out)
    return tuple(outs), counts, rep, num


def _segment_aggs_body(ranks, valid, arrays: Tuple[jax.Array, ...],
                       ops: Tuple[str, ...]):
    n = ranks.shape[0]
    outs = []
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), ranks, num_segments=n)
    iota = jnp.arange(n, dtype=jnp.int32)
    rep = jnp.full(n, n - 1, dtype=jnp.int32).at[ranks].min(jnp.where(valid, iota, n - 1))
    for arr, op in zip(arrays, ops):
        if op == "count":
            if arr is not None and jnp.issubdtype(arr.dtype, jnp.floating):
                c = jax.ops.segment_sum(
                    (valid & ~jnp.isnan(arr)).astype(jnp.int32), ranks, num_segments=n
                )
            else:
                c = counts
            outs.append(c)
        elif op == "sum":
            x = jnp.where(valid, arr, jnp.zeros((), arr.dtype))
            outs.append(jax.ops.segment_sum(x, ranks, num_segments=n))
        elif op == "mean":
            x = jnp.where(valid, arr, jnp.zeros((), arr.dtype))
            s = jax.ops.segment_sum(x, ranks, num_segments=n)
            outs.append(s / jnp.maximum(counts, 1).astype(s.dtype))
        elif op == "min":
            big = _max_sentinel(arr.dtype)
            x = jnp.where(valid, arr, big)
            outs.append(jax.ops.segment_min(x, ranks, num_segments=n))
        elif op == "max":
            small = _min_sentinel(arr.dtype)
            x = jnp.where(valid, arr, small)
            outs.append(jax.ops.segment_max(x, ranks, num_segments=n))
        elif op == "first":
            outs.append(arr[rep])
        else:
            raise ValueError(f"unknown agg {op}")
    return outs, counts, rep


_segment_aggs_jit = functools.partial(jax.jit, static_argnames=("ops",))(
    _segment_aggs_body
)


def _segment_aggs(ranks, valid, arrays, ops):
    """Jitted at top level, plain body while tracing (see
    hashtable._in_trace for the dispatch-race rationale)."""
    fn = _segment_aggs_body if hashtable._in_trace() else _segment_aggs_jit
    return fn(ranks, valid, tuple(arrays), tuple(ops))


def _max_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _min_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def groupby_limbs(limbs: Tuple[jax.Array, ...], arrays: Tuple[jax.Array, ...],
                  ops: Tuple[str, ...], valid: jax.Array):
    """Group rows by key limbs: the single strategy-dispatch point for every
    group-by consumer (here, FusedPartialAgg).  The per-backend matrix
    (ops/strategy.py) picks hash table vs multi-operand sort; hash_groupby
    itself records a sort fallback when the insert diverges."""
    from quokka_tpu.ops import strategy as kstrategy

    if kstrategy.choice("groupby") == "hashtable":
        return hashtable.hash_groupby(tuple(limbs), arrays, ops, valid)
    kstrategy.note_used("groupby", "sort")
    return sorted_groupby(tuple(limbs), arrays, ops, valid)


def groupby_aggregate(
    batch: DeviceBatch,
    keys: Sequence[str],
    aggs: Sequence[Tuple[str, str, Optional[jax.Array]]],
) -> DeviceBatch:
    """aggs: list of (output_name, op, input_array_or_None_for_count).
    Returns a grouped batch (padded to input size; compact() to shrink)."""
    n = batch.padded_len
    arrays = tuple(
        a if a is not None else jnp.zeros(n, dtype=jnp.int32) for (_, _, a) in aggs
    )
    ops = tuple(op for (_, op, _) in aggs)
    if keys:
        limbs = key_limbs(batch, keys)
        outs, counts, rep, num = groupby_limbs(tuple(limbs), arrays, ops, batch.valid)
    else:
        ranks = jnp.zeros(n, dtype=jnp.int32)
        num = jnp.minimum(jnp.sum(batch.valid), 1).astype(jnp.int32)
        outs, counts, rep = _segment_aggs(ranks, batch.valid, arrays, ops)

    cols = gather_columns({k: batch.columns[k] for k in keys}, rep)
    for (name, _, _), arr in zip(aggs, outs):
        cols[name] = NumCol(arr, "f" if jnp.issubdtype(arr.dtype, jnp.floating) else "i")
    group_valid = jnp.arange(n) < num
    if keys:
        from quokka_tpu.obs import opstats

        opstats.note(groupby_sort_slots=n, groupby_groups_out=num)
    return DeviceBatch(cols, group_valid, None, None).note_count(num)


# ---------------------------------------------------------------------------
# sort / top-k
# ---------------------------------------------------------------------------


@jax.jit
def _sort_perm(limbs: Tuple[jax.Array, ...], valid: jax.Array):
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    inv = (~valid).astype(jnp.int32)
    out = lax.sort([inv, *limbs, iota], num_keys=1 + len(limbs))
    return out[-1]


def sort_batch(batch: DeviceBatch, by: Sequence[str], descending=None) -> DeviceBatch:
    limbs = sort_limbs(batch, by, descending)
    perm = _aot("sort_perm", _sort_perm, (tuple(limbs), batch.valid))
    out = batch.take(perm, batch.valid, batch.nrows)
    # valid rows are now contiguous at the front; derive the mask on device
    # (a host count here would cost a full round trip per sort) and start the
    # count's async host copy so a later compact/head is sync-free
    out.valid, n = _aot("prefix_mask", _prefix_mask, (batch.valid,))
    out.nrows = batch.nrows
    out.sorted_by = list(by)
    return out.note_count(n)


@jax.jit
def _prefix_mask(valid):
    n = jnp.sum(valid.astype(jnp.int32))
    return jnp.arange(valid.shape[0], dtype=jnp.int32) < n, n


def top_k(batch: DeviceBatch, by: Sequence[str], k: int, descending=None) -> DeviceBatch:
    s = sort_batch(batch, by, descending)
    return head(s, k)


# ---------------------------------------------------------------------------
# hash partition (shuffle)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_parts",))
def _partition_ids(limbs: Tuple[jax.Array, ...], n_parts: int):
    h = jnp.zeros(limbs[0].shape[0], dtype=jnp.uint32)
    for limb in limbs:
        if jnp.issubdtype(limb.dtype, jnp.floating):
            limb = limb.astype(jnp.int32)
        elif limb.dtype == jnp.bool_:
            limb = limb.astype(jnp.int32)
        u = limb.astype(jnp.uint32) if limb.dtype != jnp.int64 else limb.astype(jnp.uint32)
        h = h * jnp.uint32(0x9E3779B1) + u
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
    return (h % jnp.uint32(n_parts)).astype(jnp.int32)


def partition_ids(batch: DeviceBatch, keys: Sequence[str], n_parts: int) -> jax.Array:
    limbs = key_limbs(batch, keys)
    return _aot("partition_ids", _partition_ids, (tuple(limbs),), (n_parts,))


@functools.partial(jax.jit, static_argnames=("n_parts",))
def _split_masks(part_ids, valid, n_parts: int):
    """ONE dispatch producing every partition's validity mask plus its live
    count (the masked-split fast path used to dispatch one apply_mask kernel
    per partition)."""
    masks = tuple((part_ids == p) & valid for p in range(n_parts))
    counts = tuple(jnp.sum(m.astype(jnp.int32)) for m in masks)
    return masks, counts


@functools.partial(jax.jit, static_argnames=("n_parts",))
def _partition_plan(part_ids, valid, n_parts: int):
    """ONE dispatch planning a compacted split: a stable permutation grouping
    valid rows by partition id (invalid rows last), per-partition counts and
    start offsets.  Every partition is then a window of ``perm`` — no
    per-partition nonzero scans over the full batch."""
    n = valid.shape[0]
    pid = jnp.where(valid, part_ids, jnp.int32(n_parts))
    counts = jnp.bincount(pid, length=n_parts + 1)[:n_parts]
    iota = jnp.arange(n, dtype=jnp.int32)
    perm = lax.sort([pid.astype(jnp.int32), iota], num_keys=2)[-1]
    offsets = jnp.cumsum(counts) - counts
    return perm, counts, offsets


@functools.partial(jax.jit, static_argnames=("out_size",))
def _part_window(perm, offset, count, out_size: int):
    """Row indices + validity of one partition's window of the plan perm."""
    pos = offset + jnp.arange(out_size, dtype=jnp.int32)
    idx = perm[jnp.clip(pos, 0, perm.shape[0] - 1)]
    return idx, jnp.arange(out_size, dtype=jnp.int32) < count


# Per-query attribution for push-path host syncs: the engine enters a scope
# carrying its ONCE-RESOLVED per-query counter (a creating registry lookup
# here would resurrect a GC'd per-query instrument after TaskGraph.cleanup,
# and diffing the global counter would cross-attribute concurrent queries).
_SYNC_SCOPE = threading.local()


@contextlib.contextmanager
def shuffle_sync_scope(counter):
    prev = getattr(_SYNC_SCOPE, "counter", None)
    _SYNC_SCOPE.counter = counter
    try:
        yield
    finally:
        _SYNC_SCOPE.counter = prev


def _shuffle_sync() -> None:
    """Count a blocking host readback on the shuffle path (the shuffle-smoke
    sentinel asserts this stays flat in steady state)."""
    from quokka_tpu import obs

    obs.REGISTRY.counter("shuffle.host_syncs").inc()
    c = getattr(_SYNC_SCOPE, "counter", None)
    if c is not None:
        c.inc()


def split_by_partition(batch: DeviceBatch, part_ids: jax.Array, n_parts: int,
                       compact: Optional[bool] = None):
    """Split a batch into n per-partition batches.

    Default (masked) mode: parts are VIEWS over the parent's column arrays —
    one fused kernel produces every partition's mask and live count, columns
    are shared (no copies, no gathers) and the counts' host copies start
    asynchronously (note_count), so the push path pays ZERO blocking host
    syncs.  Consumers compact/concat when the counts have long landed.

    Compacted mode (``compact=True``, or auto past SHUFFLE_MASKED_CAP total
    padded rows): one segmented-sort plan kernel groups rows by partition,
    then each partition is a window-gather at its own bucket — n_parts
    window gathers instead of n_parts full-batch nonzero scans, and ONE
    counts readback whose async host copy starts at plan dispatch.  Buckets
    are UNIFORM across partitions when skew allows, so every downstream
    consumer sees one shape per split instead of one per partition."""
    if n_parts == 1:
        return [batch]
    if compact is None:
        from quokka_tpu.ops import strategy as kstrategy

        if kstrategy.choice("shuffle") == "compacted":
            # calibrated-compacted backends still skip the plan kernel on
            # small batches, where its counts readback dominates
            compact = batch.padded_len > (1 << 16)
        else:
            compact = (batch.padded_len > (1 << 16)
                       and n_parts * batch.padded_len > config.SHUFFLE_MASKED_CAP)
        kstrategy.note_used("shuffle", "compacted" if compact else "masked")
    if not compact:
        masks, counts = _aot("split_masks", _split_masks,
                             (part_ids, batch.valid), (n_parts,))
        return [
            DeviceBatch(batch.columns, m, None, batch.sorted_by).note_count(c)
            for m, c in zip(masks, counts)
        ]
    perm, counts, offsets = _aot("partition_plan", _partition_plan,
                                 (part_ids, batch.valid), (n_parts,))
    _shuffle_sync()
    # the one counts readback of a compacted split (device_get starts the
    # copy before it waits; numpy-backed counts pass through)
    host_counts = tracing.device_read("shuffle.counts", counts)
    max_count = int(host_counts.max()) if n_parts else 0
    uniform = config.bucket_size(max_count)
    total = int(host_counts.sum())
    # uniform buckets collapse the downstream shape space to ONE per split;
    # skewed splits fall back to per-partition buckets so device memory
    # stays proportional to the data
    use_uniform = n_parts * uniform <= 2 * config.bucket_size(max(total, 1))
    out = []
    for p in range(n_parts):
        cnt = int(host_counts[p])
        padded = uniform if use_uniform else config.bucket_size(cnt)
        idx, valid = _aot("part_window", _part_window,
                          (perm, offsets[p], counts[p]), (padded,))
        out.append(batch.take(idx, valid, cnt))
    return out


# ---------------------------------------------------------------------------
# whole-batch reductions
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("op",))
def reduce_array(arr: jax.Array, valid: jax.Array, op: str):
    if op == "sum":
        return jnp.sum(jnp.where(valid, arr, jnp.zeros((), arr.dtype)))
    if op == "count":
        return jnp.sum(valid.astype(jnp.int64 if config.x64_enabled() else jnp.int32))
    if op == "min":
        return jnp.min(jnp.where(valid, arr, _max_sentinel(arr.dtype)))
    if op == "max":
        return jnp.max(jnp.where(valid, arr, _min_sentinel(arr.dtype)))
    raise ValueError(op)
