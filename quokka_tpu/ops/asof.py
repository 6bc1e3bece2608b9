"""As-of join kernels.

The reference's SortedAsofExecutor walks trade/quote frontiers sequentially
per batch (pyquokka/executors/ts_executors.py:324-383).  Three strategies
(ops/strategy.py picks per backend; each records what actually ran):

- ``sort``: a merge.  Both sides are concatenated and sorted once by (key,
  time, side, row); two running maxima over the sorted order give every
  trade the position of the latest quote of its own key; a second sort on
  one key brings the trade slots back to the front in their order.  One
  program a flush (``asof_match``), no ``gather`` or ``scatter`` over the
  quote slots, no sequential loop.  Costs by the slot of both sides
  together: the TPU's default (a gather there costs 8-13 ns an element, a
  sort of a million rows a millisecond).
- ``searchsorted``: sort ONLY the quotes by (key, time) — cached on the
  quote batch, so repeated flushes against an unchanged buffer pay it once —
  and resolve every trade with a vectorized lexicographic binary search
  (upper bound for backward, lower bound for forward).  ~log2(q) gathers per
  limb and trade instead of an (n+m)-row multi-operand sort per flush, and
  no concat-sized intermediates.  Fully device-resident: the GPU's default.
- ``host``: the native O(n+m) sequential merge (native/columnar.cpp),
  profitable only where np.asarray of a device array is zero-copy (CPU).

Direction 'backward' matches quotes with time <= trade time (quotes sort
before trades on ties); 'forward' is the mirror.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from quokka_tpu import config
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops.batch import (
    DeviceBatch,
    NumCol,
    StrCol,
    column_arrays,
    flat_arrays,
    key_limbs,
    map_codes,
    rebuild_columns,
)


def _seg_fill_forward(values: jax.Array, seg_start: jax.Array) -> jax.Array:
    """Within each segment (seg_start marks first element), running max of
    `values` — the sliding window's and the shift's fill-forward
    (executors/ts_execs.py, parallel/mesh_exec.py).  The asof match takes two
    plain running maxima instead: positions only grow, so a segment's start
    need not reset them."""

    def combine(a, b):
        av, as_ = a
        bv, bs = b
        v = jnp.where(bs, bv, jnp.maximum(av, bv))
        return v, as_ | bs

    out, _ = lax.associative_scan(combine, (values, seg_start))
    return out


# What the match's traced body is made of.  One persisted program traces it
# (``asof_match``, below): program keys carry no version of the code, so an
# edit of the body changes this string, or the AOT store goes on running the
# old executable under the unchanged key (as kernels.SORTED_GROUPBY_FORM).
ASOF_MATCH_FORM = "sort_cummax_sort"


@functools.partial(jax.jit, static_argnames=("t", "forward_ties"))
def _asof_match(limbs: Tuple[jax.Array, ...], times: Tuple[jax.Array, ...],
                is_trade: jax.Array, valid: jax.Array, t: int,
                forward_ties: bool = False):
    """Returns per-trade-row (quote_row_idx, matched) for backward asof.
    Arrays are the concatenation [trades | quotes]; `t` = trade padded len
    (the trades are rows 0..t-1).  `times` is one array for narrow/float
    time columns, or (hi, lo) limbs for wide int64/ns timestamps (limb
    lexicographic order == numeric order).

    A merge, as sort, running maxima, sort; nothing indexed over the n
    slots (a ``gather`` or a ``scatter`` costs the TPU 7-9 ns an element, a
    sort of a million rows about a millisecond):

    1. one sort by (*limbs, *times, tag) and nothing carried: ``tag`` is the
       side, the validity and the row index in one int32 (every operand
       costs the sort of 8.65 M slots about 8 ms, PERF.md section 6), high
       bits first: valid quote 0, masked quote 1, valid trade 2, masked
       trade 3, then the row.  So quotes stand before trades at equal times
       (backward asof includes same-timestamp quotes) and equal rows in
       original order, with no stability asked of the sort.  Validity is no
       leading key: a masked-out row stands wherever its stale key and time
       put it, inside some key's run or in a run of its own, and is never a
       quote that counts nor a trade that matches;
    2. two running maxima over the sorted order: the position of the latest
       valid quote so far, and the position where the current run of one key
       began (any limb differs from the row before).  A trade is matched iff
       its latest quote lies inside its own run: no key is gathered to be
       compared;
    3. a second sort on one key brings the t trade slots (valid or not:
       holes keep their places) back to the front in chunk order, carrying
       the latest quote's sorted position; the quote's row is gathered for
       those t rows alone.

    Tie-break among quotes sharing (key, time): the maximum takes the quote
    at the LAST sorted position, equal quotes stand in original order, so
    backward picks the last tied quote (pandas/polars).  ``forward_ties``
    (on the caller's negated times) orders them by descending row instead,
    so forward picks the FIRST tied quote, matching pandas and the native
    host merge."""
    n = valid.shape[0]
    bits = (n - 1).bit_length()
    assert bits <= 29, "the side and the row index share one int32"
    iota = jnp.arange(n, dtype=jnp.int32)
    side = 2 * is_trade.astype(jnp.int32) + (~valid).astype(jnp.int32)
    tag = (side << bits) | (n - 1 - iota if forward_ties else iota)
    keys = [*limbs, *times, tag]
    sorted_ops = lax.sort(keys, num_keys=len(keys), is_stable=False)
    side_s = sorted_ops[-1] >> bits
    perm = sorted_ops[-1] & ((1 << bits) - 1)
    if forward_ties:
        perm = n - 1 - perm
    run_start = iota == 0
    for s in sorted_ops[:len(limbs)]:
        run_start = run_start | (s != jnp.roll(s, 1))
    last_quote_pos = lax.cummax(jnp.where(side_s == 0, iota, -1))
    run_begin = lax.cummax(jnp.where(run_start, iota, 0))
    hit = (side_s == 2) & (last_quote_pos >= run_begin)
    # the trades' original rows are 0..t-1, each once; the quotes all get n
    # and follow them in any order
    _, pos = lax.sort([jnp.where(side_s >= 2, perm, n),
                       jnp.where(hit, last_quote_pos, -1)],
                      num_keys=1, is_stable=False)
    pos = pos[:t]
    return perm[jnp.clip(pos, 0, n - 1)], pos >= 0


def _asof_match_sides(t_limbs: Tuple[jax.Array, ...],
                      t_times: Tuple[jax.Array, ...], t_valid: jax.Array,
                      q_limbs: Tuple[jax.Array, ...],
                      q_times: Tuple[jax.Array, ...], q_valid: jax.Array,
                      forward: bool):
    """One flush's match as ONE program: the two sides' key limbs, times
    and masks are concatenated, the quote side cast to the trade side's
    dtypes and (``forward``) the times reversed, here and not as one eager
    launch each over the quote buffer's capacity.  Returns (quote row index
    clipped, matched) aligned to the trade slots."""
    t, q = t_valid.shape[0], q_valid.shape[0]
    cat = lambda a, b: jnp.concatenate([a, b.astype(a.dtype)])  # noqa: E731
    times = tuple(cat(a, b) for a, b in zip(t_times, q_times))
    if forward:
        # run forward on the backward kernel: an exact decreasing remap
        from quokka_tpu.ops import timewide

        times = (timewide.not_limbs(times) if len(times) == 2
                 else (-times[0],))
    match_orig, matched = _asof_match(
        tuple(cat(a, b) for a, b in zip(t_limbs, q_limbs)), times,
        jnp.arange(t + q, dtype=jnp.int32) < t, cat(t_valid, q_valid), t,
        forward_ties=forward)
    return jnp.clip(match_orig - t, 0, q - 1), matched


# The device trace knows a program by its function's name.  The benchmark's
# ``asof_match_roofline`` and PERF.md know the match as ``jit__asof_match``;
# the program a flush launches is the match, whatever wraps it.
_asof_match_sides.__name__ = "_asof_match"


@functools.lru_cache(maxsize=None)
def match_kernel():
    return jax.jit(_asof_match_sides, static_argnames=("forward",))


# ---------------------------------------------------------------------------
# searchsorted strategy: cached quote-side (key, time) sort + vectorized
# lexicographic binary search per trade row.
# ---------------------------------------------------------------------------


def _lex_lt_eq(a: Tuple[jax.Array, ...], b: Tuple[jax.Array, ...]):
    """Elementwise lexicographic (a < b, a == b) over limb tuples (the same
    comparator join._pk_probe_sorted uses)."""
    lt = jnp.zeros(a[0].shape, dtype=bool)
    eq = jnp.ones(a[0].shape, dtype=bool)
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt, eq


@jax.jit
def _ss_sort_quotes(ops: Tuple[jax.Array, ...], valid: jax.Array):
    """Sort the quote side once by (validity, key limbs..., time limbs...);
    returns (sorted_ops, perm, n_valid).  Invalid rows sort last; ties keep
    original order (iota operand), so among equal (key, time) quotes sorted
    position order == original order — the tie-break both directions rely
    on."""
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    inv = (~valid).astype(jnp.int32)
    s = lax.sort([inv, *ops, iota], num_keys=1 + len(ops))
    return tuple(s[1:-1]), s[-1], jnp.sum(valid.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("steps", "upper", "nkey"))
def _ss_probe(sorted_ops: Tuple[jax.Array, ...], perm: jax.Array,
              n_valid: jax.Array, probe_ops: Tuple[jax.Array, ...],
              probe_valid: jax.Array, steps: int, upper: bool, nkey: int):
    """Per-trade binary search over the sorted quotes.  ``upper`` (backward
    asof): upper bound of (key, time) minus one — the LAST quote with key ==
    k and time <= t (among exact (key, time) ties the last original index,
    pandas semantics).  Lower bound (forward): the FIRST quote with key == k
    and time >= t.  Returns (original quote row idx clipped, matched)."""
    p = probe_ops[0].shape[0]
    nq = sorted_ops[0].shape[0]
    lo = jnp.zeros(p, dtype=jnp.int32)
    hi = jnp.broadcast_to(n_valid.astype(jnp.int32), (p,))
    for _ in range(steps):
        mid = (lo + hi) >> 1
        mk = tuple(l[mid] for l in sorted_ops)
        lt, eq = _lex_lt_eq(mk, probe_ops)
        cond = (lt | eq) if upper else lt  # quote[mid] <= probe vs < probe
        go = lo < hi
        lo = jnp.where(go & cond, mid + 1, lo)
        hi = jnp.where(go & ~cond, mid, hi)
    pos = lo - 1 if upper else lo
    in_range = (pos >= 0) & (pos < n_valid)
    cpos = jnp.clip(pos, 0, nq - 1)
    keq = jnp.ones(p, dtype=bool)
    for s_l, p_l in zip(sorted_ops[:nkey], probe_ops[:nkey]):
        keq = keq & (s_l[cpos] == p_l)
    matched = probe_valid & in_range & keq
    return jnp.clip(perm[cpos], 0, nq - 1), matched


def _quote_key_limbs(quotes: DeviceBatch,
                     right_by: Sequence[str]) -> List[jax.Array]:
    """The quote side's key limbs: the ones a ``RowBuffer`` carried beside
    its columns (hashed part by part as they arrived), else hashed here."""
    carried = getattr(quotes, "_asof_key_limbs", None)
    if carried is not None and carried[0] == tuple(right_by):
        return list(carried[1])
    return key_limbs(quotes, list(right_by)) if right_by else []


def _ss_quote_sorted(quotes: DeviceBatch, right_on: str,
                     right_by: Sequence[str], wide: bool, time_dtype):
    """(sorted_ops, perm, n_valid, nkey) for a quote batch, cached ON the
    batch object (the streaming executor probes the same buffer on every
    flush until new quotes concat into a fresh object — same discipline as
    join._build_sorted_cached).  Both directions share one cache entry: the
    search side decides backward vs forward, not the sort.  ``time_dtype``
    (the TRADE side's time dtype, None when wide) is applied to the quote
    time limb BEFORE sorting — the same quote->trade cast the sort kernel
    applies pre-sort, so mixed-dtype comparisons and within-tie ordering
    stay bit-identical to that path (probe-side casts would truncate the
    trade times instead)."""
    from quokka_tpu.runtime import compileplane

    cache = getattr(quotes, "_asof_ss_cache", None)
    if cache is None:
        cache = quotes._asof_ss_cache = {}
    key = (tuple(right_by), right_on, wide, str(time_dtype))
    hit = cache.get(key)
    if hit is None:
        ql = _quote_key_limbs(quotes, right_by)
        qc = quotes.columns[right_on]
        if wide:
            from quokka_tpu.ops import timewide

            qt = tuple(timewide.widen_limbs(qc))
        else:
            qt = (qc.data.astype(time_dtype),)
        ops = tuple(ql) + qt
        sorted_ops, perm, n_valid = compileplane.aot_kernel_call(
            "asof_ss_sort", _ss_sort_quotes, (ops, quotes.valid))
        hit = cache[key] = (sorted_ops, perm, n_valid, len(ql))
    return hit


def _asof_match_searchsorted(trades: DeviceBatch, quotes: DeviceBatch,
                             left_on: str, right_on: str,
                             left_by: Sequence[str],
                             right_by: Sequence[str], direction: str):
    """(quote_idx, matched) aligned to trade rows, fully on device."""
    from quokka_tpu.runtime import compileplane

    tc = trades.columns[left_on]
    qc = quotes.columns[right_on]
    wide = tc.hi is not None or qc.hi is not None
    sorted_ops, perm, n_valid, nkey = _ss_quote_sorted(
        quotes, right_on, list(right_by), wide,
        None if wide else tc.data.dtype)
    lt = key_limbs(trades, list(left_by)) if left_by else []
    assert len(lt) == nkey, "asof by-key column types must match"
    if wide:
        from quokka_tpu.ops import timewide

        tt = tuple(timewide.widen_limbs(tc))
    else:
        tt = (tc.data,)
    probe_ops = tuple(
        l.astype(s.dtype) for l, s in zip(tuple(lt) + tt, sorted_ops)
    )
    steps = max(1, int(np.ceil(np.log2(max(2, quotes.padded_len)))) + 1)
    return compileplane.aot_kernel_call(
        "asof_ss_probe", _ss_probe,
        (sorted_ops, perm, n_valid, probe_ops, trades.valid),
        (steps, direction == "backward", nkey),
    )


def _asof_match_sort(trades: DeviceBatch, quotes: DeviceBatch,
                     left_on: str, right_on: str, left_by: Sequence[str],
                     right_by: Sequence[str], direction: str):
    """(quote_idx, matched) aligned to trade rows: one program a flush,
    keyed on (trade slots, quote slots) like every program of the buffers."""
    from quokka_tpu.ops import timewide
    from quokka_tpu.runtime import compileplane

    tl = key_limbs(trades, list(left_by)) if left_by else []
    ql = _quote_key_limbs(quotes, right_by)
    assert len(tl) == len(ql), "asof by-key column types must match"
    tc = trades.columns[left_on]
    qc = quotes.columns[right_on]
    if tc.hi is not None or qc.hi is not None:
        tt, qt = timewide.widen_limbs(tc), timewide.widen_limbs(qc)
    else:
        tt, qt = (tc.data,), (qc.data,)
    return compileplane.aot_kernel_call(
        "asof_match", match_kernel(),
        (tuple(tl), tuple(tt), trades.valid, tuple(ql), tuple(qt),
         quotes.valid),
        (direction == "forward",), form=(ASOF_MATCH_FORM,))


# ---------------------------------------------------------------------------
# Host fast path (CPU backend): the as-of match is a textbook O(n+m)
# sequential merge; XLA:CPU's variadic sort makes the device kernel ~340
# ns/row while the native walk (native/columnar.cpp qk_asof_backward) runs at
# memory speed.  On the CPU backend np.asarray of a device array is a
# zero-copy view, so "host" costs no transfer.  Accelerators keep a device
# kernel (config.use_host_asof() gates, QUOKKA_HOST_ASOF overrides).
# ---------------------------------------------------------------------------


def _np_time64(col: NumCol) -> np.ndarray:
    """Order-preserving int64 view of a time column on host.  NOTE: float
    columns map through an IEEE bit trick, so the result is only comparable
    against another float column's encoding — _asof_match_host bails when
    the two sides' dtype families differ."""
    hi, d = tracing.device_read("asof.host_time", (col.hi, col.data))
    if hi is not None:
        from quokka_tpu.ops import bridge

        return bridge._limbs_to_int64(hi, d)
    if d.dtype.kind == "f":
        # IEEE total-order bit trick: non-negative floats' bit patterns are
        # already ordered non-negative ints; negatives flip their low 63
        # bits (sign kept) to reverse magnitude order while staying below
        # every positive
        bits = np.ascontiguousarray(d.astype(np.float64)).view(np.int64)
        return np.where(bits < 0, bits ^ np.int64(0x7FFFFFFFFFFFFFFF), bits)
    return d.astype(np.int64)


def _time_family(col: NumCol) -> str:
    if col.hi is not None:
        return "i"
    return "f" if col.data.dtype.kind == "f" else "i"


def _np_key64(batch: DeviceBatch, by: Sequence[str]) -> "np.ndarray | None":
    """Exact int64 key per row from <=2 int32 limbs (or one int64 limb).
    Returns None when the key shape doesn't pack exactly — caller falls back
    to the device kernel."""
    if not by:
        return np.zeros(batch.padded_len, dtype=np.int64)
    limbs = tracing.device_read("asof.host_keys",
                                list(key_limbs(batch, list(by))))
    if any(l.dtype.kind == "f" for l in limbs):
        return None
    if len(limbs) == 1:
        return limbs[0].astype(np.int64)
    if len(limbs) == 2 and all(l.dtype.itemsize <= 4 for l in limbs):
        return (limbs[0].astype(np.int64) << 32) | limbs[1].astype(
            np.uint32
        ).astype(np.int64)
    return None


def _asof_match_host(trades, quotes, left_on, right_on, left_by, right_by,
                     direction):
    """(quote_idx, matched) as numpy arrays aligned to trade rows, or None
    when the native library / key shape doesn't support the fast path."""
    from quokka_tpu.utils import native

    if not native.has_asof():
        return None  # skip all host prep when the merge can't run anyway
    if _time_family(trades.columns[left_on]) != _time_family(
            quotes.columns[right_on]):
        return None  # int vs float encodings are not mutually comparable
    tk = _np_key64(trades, left_by)
    qk = _np_key64(quotes, right_by)
    if tk is None or qk is None:
        return None
    tt = _np_time64(trades.columns[left_on])
    qt = _np_time64(quotes.columns[right_on])
    tv, qv = tracing.device_read("asof.host_valid",
                                 (trades.valid, quotes.valid))
    tidx = np.flatnonzero(tv)
    qidx = np.flatnonzero(qv)
    tt, tk = np.ascontiguousarray(tt[tidx]), np.ascontiguousarray(tk[tidx])
    qt, qk = np.ascontiguousarray(qt[qidx]), np.ascontiguousarray(qk[qidx])
    if not native.is_sorted_i64(tt):
        order = np.argsort(tt, kind="stable")
        tidx, tt, tk = tidx[order], np.ascontiguousarray(tt[order]), \
            np.ascontiguousarray(tk[order])
    if not native.is_sorted_i64(qt):
        order = np.argsort(qt, kind="stable")
        qidx, qt, qk = qidx[order], np.ascontiguousarray(qt[order]), \
            np.ascontiguousarray(qk[order])
    res = native.asof_merge(tt, tk, qt, qk, direction)
    if res is None:
        return None
    quote_idx = np.zeros(trades.padded_len, dtype=np.int32)
    matched = np.zeros(trades.padded_len, dtype=bool)
    hit = res >= 0
    quote_idx[tidx[hit]] = qidx[res[hit]].astype(np.int32)
    matched[tidx[hit]] = True
    return quote_idx, matched


# ---------------------------------------------------------------------------
# Row buffers of a fixed capacity, appended in place.  The streaming asof
# executor keeps its trades and its quotes in one each: every program that
# touches a buffer is keyed on (capacity rung, part or chunk rung), so the
# set of programs a plan asks for follows the plan and the data's row
# counts, never which batches happened to arrive together.
# ---------------------------------------------------------------------------


def _row_mask(live: jax.Array, like: jax.Array) -> jax.Array:
    return live if like.ndim == 1 else live[:, None]


def _append_rows(bufs: Tuple[jax.Array, ...], buf_valid: jax.Array,
                 parts: Tuple[jax.Array, ...], part_valid: jax.Array,
                 tables: Tuple[jax.Array, ...], end: jax.Array):
    """Write one part, every slot of it under its own mask, behind slot
    ``end`` of the buffer: contiguous copies, no gather (a part the exchange
    cut by a mask stays as sparse in the buffer as it came).  ``tables`` has
    one entry per array: a remap table for a string column's codes (the
    part's dictionary in the buffer's), else an empty array.  The caller
    guarantees ``end`` + the part's length fits: a clamped start would
    overwrite live rows."""
    def write(buf, rows):
        return lax.dynamic_update_slice(
            buf, rows.astype(buf.dtype), (end,) + (0,) * (buf.ndim - 1))

    return (tuple(write(buf, map_codes(part, table) if table.shape[0]
                        else part)
                  for buf, part, table in zip(bufs, parts, tables)),
            write(buf_valid, part_valid))


def _take_rows(arrays: Tuple[jax.Array, ...], valid: jax.Array,
               ready: jax.Array, n: jax.Array, chunk: int):
    """The first ``n`` rows of ``ready`` (a subset of ``valid``; ``n`` at
    most ``chunk`` and at most its count) as a chunk of exactly ``chunk``
    slots under its own mask, and the buffer's mask without them.  The rows
    stay where they are: a buffer is a log."""
    idx = jnp.nonzero(ready, size=chunk, fill_value=0)[0]
    live = jnp.arange(chunk) < n
    outs = tuple(
        jnp.where(_row_mask(live, a), a[idx], jnp.zeros((), a.dtype))
        for a in arrays)
    taken = ready & (jnp.cumsum(ready.astype(jnp.int32)) <= n)
    return outs, live, valid & ~taken


@functools.lru_cache(maxsize=None)
def append_kernel():
    # built at first use, not at import.  The buffer's arrays are donated:
    # the write happens in place, and a queue of appends holds one buffer,
    # not one copy of it per part in flight
    return jax.jit(_append_rows, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def take_kernel():
    return jax.jit(_take_rows, static_argnames=("chunk",))


class RowBuffer:
    """Device rows of one schema in arrays of a fixed capacity (a ladder
    rung): ``append`` writes a part, slot for slot under its mask, behind
    the last slot ever written, ``take`` hands out a chunk and masks its
    rows out.  Rows never move, so arrival order is row order (the asof
    tie-break relies on it).  ``rows`` (live) and ``end`` (slots written)
    are host counts.  A buffer that cannot hold a part closes its holes,
    then doubles: the path of a source whose row count the plan did not
    know, or whose parts are sparser than one slot in two.

    ``key``: the join's key columns of a searched buffer.  Where one is a
    string column, each part's key limbs (its rows' value hashes) are
    appended beside the columns, and ``view()`` carries them
    (``_asof_key_limbs``): a flush then sorts the buffer without hashing
    its whole capacity again."""

    def __init__(self, like: DeviceBatch, capacity: int,
                 key: Sequence[str] = ()):
        self.capacity = capacity
        self.key = tuple(key) if any(
            isinstance(like.columns[k], StrCol) for k in key) else ()
        self.limbs: List[jax.Array] = []  # made by the first append
        self.columns = rebuild_columns(like.columns, [
            jnp.zeros((capacity,) + a.shape[1:], a.dtype)
            for a in flat_arrays(like.columns)])
        self.valid = jnp.zeros(capacity, dtype=bool)
        self.sorted_by = like.sorted_by
        self.rows = 0
        self.end = 0
        self._view: "DeviceBatch | None" = None
        self._dict_index: dict = {}  # column -> {value: code} of its dict

    def view(self) -> DeviceBatch:
        """The buffer as a batch: one object until the buffer next changes,
        so what is cached on it (the quote sort) lives as long as it holds."""
        if self._view is None:
            self._view = DeviceBatch(dict(self.columns), self.valid,
                                     self.rows, self.sorted_by)
            if self.key:
                self._view._asof_key_limbs = (self.key, tuple(self.limbs))
        return self._view

    def _arrays(self) -> List[jax.Array]:
        return flat_arrays(self.columns) + self.limbs

    def _set(self, arrays, valid, rows: int) -> None:
        arrays = list(arrays)
        n = len(arrays) - len(self.limbs)
        self.columns = rebuild_columns(self.columns, arrays[:n])
        self.limbs = arrays[n:]
        self._mask(valid, rows)

    def _mask(self, valid, rows: int) -> None:
        self.valid = valid
        self.rows = rows
        self._view = None

    def _remap_table(self, name: str, part: StrCol) -> np.ndarray:
        """The part's codes as codes of the buffer's dictionary, which grows
        at its end only: codes already written keep their meaning."""
        from quokka_tpu.ops.batch import StringDict, pad_table
        from quokka_tpu.ops.sigkey import pow2_dim

        cur = self.columns[name]
        if part.dictionary is cur.dictionary:
            return np.zeros(0, dtype=np.int32)  # the same codes: no remap
        size = pow2_dim(len(part.dictionary))
        index = self._dict_index.get(name)
        if index is None:
            index = self._dict_index[name] = {
                v: i for i, v in enumerate(cur.dictionary.values)}
        grown = len(index)
        remap = np.fromiter(
            (index.setdefault(v, len(index)) for v in part.dictionary.values),
            dtype=np.int32, count=len(part.dictionary))
        if len(index) > grown:
            values = np.empty(len(index), dtype=object)
            for v, i in index.items():
                values[i] = v
            self.columns[name] = StrCol(cur.codes, StringDict(
                values, binary=cur.dictionary.binary or part.dictionary.binary))
            self._view = None
        elif (remap == np.arange(len(remap))).all():
            return np.zeros(0, dtype=np.int32)  # the same values in order
        return pad_table(remap, size)

    def _align(self, part: DeviceBatch):
        """The part's columns in the buffer's order, limb layout and dtypes
        (the buffer widens, eagerly, where a later part is wider)."""
        from quokka_tpu.ops import bridge

        cols = {}
        for name, b in self.columns.items():
            c = part.columns[name]
            if isinstance(b, NumCol):
                b2, c = bridge._align_limbs([b, c])
                dtype = jnp.promote_types(b2.data.dtype, c.data.dtype)
                if b2 is not b or dtype != b.data.dtype:
                    self.columns[name] = NumCol(
                        b2.data.astype(dtype), b2.kind, hi=b2.hi, unit=b2.unit)
                    self._view = None
            cols[name] = c
        return cols

    def _make_room(self, slots: int) -> None:
        if self.rows < self.end:  # dead slots: close them first
            idx = jnp.nonzero(self.valid, size=self.capacity, fill_value=0)[0]
            live = jnp.arange(self.capacity) < self.rows
            self._set([jnp.where(_row_mask(live, a), a[idx],
                                 jnp.zeros((), a.dtype))
                       for a in self._arrays()], live, self.rows)
            self.end = self.rows
        if self.end + slots > self.capacity:
            cap = config.bucket_size(max(2 * self.capacity,
                                         self.end + slots))
            grow = cap - self.capacity
            self._set([jnp.pad(a, ((0, grow),) + ((0, 0),) * (a.ndim - 1))
                       for a in self._arrays()],
                      jnp.pad(self.valid, (0, grow)), self.rows)
            self.capacity = cap

    def append(self, part: DeviceBatch) -> None:
        from quokka_tpu.runtime import compileplane

        limbs = key_limbs(part, list(self.key)) if self.key else []
        if not self.limbs:
            self.limbs = [jnp.zeros(self.capacity, l.dtype) for l in limbs]
        if self.end + part.padded_len > self.capacity:
            self._make_room(part.padded_len)
        empty = np.zeros(0, dtype=np.int32)
        parts, tables = [], []
        for name, c in self._align(part).items():
            arrays = column_arrays(c)
            parts += arrays
            tables += ([self._remap_table(name, c)] if isinstance(c, StrCol)
                       else [empty] * len(arrays))
        arrays, valid = compileplane.aot_kernel_call(
            "asof_write", append_kernel(),
            (tuple(self._arrays()), self.valid, tuple(parts + limbs),
             part.valid, tuple(tables + [empty] * len(limbs)),
             np.int32(self.end)))
        self._set(arrays, valid, self.rows + part.count_valid())
        self.end += part.padded_len

    def take(self, ready: jax.Array, n: int, chunk: int) -> DeviceBatch:
        """The first ``n`` rows of ``ready`` (at most ``chunk``) as a batch
        of ``chunk`` slots; they leave the buffer."""
        from quokka_tpu.runtime import compileplane

        arrays, live, valid = compileplane.aot_kernel_call(
            "asof_take", take_kernel(),
            (tuple(flat_arrays(self.columns)), self.valid, ready,
             np.int32(n)), (chunk,))
        self._mask(valid, self.rows - n)
        return DeviceBatch(rebuild_columns(self.columns, arrays), live, n,
                           self.sorted_by)

    def detach(self, batch: DeviceBatch) -> DeviceBatch:
        """``batch`` with copies of whatever arrays it shares with the
        buffer (a batch cut from ``view()`` without a gather): the next
        append donates the buffer's arrays, and a batch already emitted
        must outlive that."""
        mine = {id(a) for a in self._arrays()}
        arrays = flat_arrays(batch.columns)
        if not any(id(a) in mine for a in arrays):
            return batch
        return DeviceBatch(
            rebuild_columns(batch.columns, [
                jnp.copy(a) if id(a) in mine else a for a in arrays]),
            batch.valid, batch.nrows, batch.sorted_by, batch.nrows_dev)

    def keep(self, mask: jax.Array) -> None:
        """Drop the live rows outside ``mask`` (one blocking count)."""
        valid = self.valid & mask
        self._mask(valid, int(tracing.device_read(
            "asof.buffer_count", jnp.sum(valid.astype(jnp.int32)))))


def asof_join(
    trades: DeviceBatch,
    quotes: DeviceBatch,
    left_on: str,
    right_on: str,
    left_by: Sequence[str],
    right_by: Sequence[str],
    payload: Sequence[str],
    direction: str = "backward",
    strategy: "str | None" = None,
) -> DeviceBatch:
    """Probe-aligned asof join: each valid trade row gains the payload of its
    most recent quote (per key).  Unmatched trades keep NaN/zero payload and a
    false mask is NOT applied (matches polars join_asof semantics: unmatched
    rows survive with null payload — floats become NaN).

    ``strategy`` forces a kernel ("host"/"sort"/"searchsorted"); None
    consults the per-backend matrix (ops/strategy.py).  A host pick that the
    native library / key shape declines falls back to the device
    searchsorted kernel — never a wrong answer, and the fallback is what
    gets recorded as having run."""
    from quokka_tpu.obs import opstats
    from quokka_tpu.ops import strategy as kstrategy

    if direction not in ("backward", "forward"):
        raise ValueError(direction)
    pick = strategy or kstrategy.choice("asof")
    host = None
    if pick == "host":
        host = _asof_match_host(
            trades, quotes, left_on, right_on, left_by, right_by, direction
        )
        if host is None:
            pick = "searchsorted"  # native lib/key shape declined
    if host is not None:
        quote_idx = jnp.asarray(host[0])
        matched = jnp.asarray(host[1])
        kstrategy.note_used("asof", "host")
    elif pick == "searchsorted":
        quote_idx, matched = _asof_match_searchsorted(
            trades, quotes, left_on, right_on, left_by, right_by, direction
        )
        kstrategy.note_used("asof", "searchsorted")
        opstats.note(asof_match_search=1)
    else:
        quote_idx, matched = _asof_match_sort(
            trades, quotes, left_on, right_on, left_by, right_by, direction
        )
        kstrategy.note_used("asof", "sort")
        opstats.note(asof_match_sort=1)
    cols = dict(trades.columns)
    from quokka_tpu.ops.batch import with_nulls

    for name in payload:
        c = quotes.columns[name]
        taken = c.take(quote_idx)
        cols[name] = with_nulls(taken, ~matched)
    cols["__asof_matched__"] = NumCol(matched, "b")
    return DeviceBatch(cols, trades.valid, trades.nrows, trades.sorted_by)
