"""Streaming distinct over a sorted key state: ``DistinctExecutor``'s device
work.

The state is every distinct key seen so far, as normalised key limbs sorted
lexicographically in a buffer, and its count on the host.  A batch is folded
into it by three compiled programs, each a compile-plane kind of its own (so
a trace names them) whose key ends in ``DISTINCT_FORM``:

- ``distinct_dedupe``: one sort of the batch's limbs (invalid rows last, the
  row index as the last key) marks the first row of each key;
- ``distinct_probe``: a search of the sorted batch keys in the sorted state.
  The first key of every chunk of ``CHUNK`` sorted slots, and the last live
  key, are found by binary search.  Where every chunk's keys lie within
  ``NARROW`` chunks' width of state slots from its bound (a batch whose keys
  fall between the state's, as a table clustered on the key sends), each key
  is compared with that window of the state; else every key takes the binary
  search.  The program also brings the new keys' flags back to the rows'
  order (one sort of a packed int32), counts them, and counts the slots it
  and the dedupe sorted or compared;
- ``distinct_merge``: the new keys and the old state written into a buffer at
  the positions the search found: one linear pass, no sort, its scatters'
  indices in order and its prefix sums by rows (``_cumsum``), so that it compiles in
  seconds.

The slots sorted or compared for an input row do not follow the state's
size on the window's route (two sorts and a window of ``NARROW`` chunks a
padded slot), and grow with the log of it on the search's; only the merge's
pass is linear in the state.  One host read a fold: the count of new keys,
which sizes the state and is the emitted batch's count.

Every program's shape is a function of the batches' padded lengths alone,
never of the order they arrive in, so a query compiles the same programs
whichever channel or dispatch sends which batch: a batch longer than
``FOLD_SLOTS`` (what an upstream stage that concatenates scan batches sends)
is folded as pieces of that length, and the buffer grows only where the keys
outgrow it, to the rung that holds the old buffer and one more batch: the
rungs follow from the lengths, where a rung sized to the count would follow
the order the batches arrived in.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from quokka_tpu import config
from quokka_tpu.obs import opstats
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops.join import _lex_lt_eq
from quokka_tpu.runtime import compileplane

# Names the three bodies in their programs' keys: program keys carry no
# version of the code, so an edit of a body changes this string, or the AOT
# store goes on running the old executable under the unchanged key.
DISTINCT_FORM = "sort_window_or_search_sorted_scatter_row_scans"
# sorted slots a chunk of the probe, and its window's width in chunks
CHUNK = 128
NARROW = 2
# the longest piece a fold takes: the reader's batch
FOLD_SLOTS = config.bucket_size(config.DEFAULT_BATCH_ROWS)
# the row of a blocked prefix sum (_cumsum)
SCAN_ROW = 1024

_INT_OF_WIDTH = {2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


def _normalized(limb: jax.Array) -> jax.Array:
    """An integer limb whose equality is the key's: a float's bits with one
    NaN and one zero, a bool as int32, an integer as it is."""
    if jnp.issubdtype(limb.dtype, jnp.floating):
        limb = jnp.where(jnp.isnan(limb), jnp.nan,
                         jnp.where(limb == 0, 0, limb)).astype(limb.dtype)
        return lax.bitcast_convert_type(
            limb, _INT_OF_WIDTH[jnp.dtype(limb.dtype).itemsize])
    if limb.dtype == jnp.bool_:
        return limb.astype(jnp.int32)
    return limb


def distinct_dedupe(limbs: Tuple[jax.Array, ...], valid: jax.Array):
    """(sorted keys, row of each sorted slot, first-of-its-key flags, live
    rows).  An invalid slot reads the largest key and its row plus the
    batch's length as the last sort key, so it sorts last and the sorted keys
    never decrease; the row as that key puts each key's first row first."""
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    limbs = tuple(jnp.where(valid, x, jnp.iinfo(x.dtype).max)
                  for x in map(_normalized, limbs))
    *keys, tag = lax.sort([*limbs, jnp.where(valid, iota, iota + n)],
                          num_keys=1 + len(limbs), is_stable=False)
    live = tag < n
    changed = iota == 0
    for k in keys:
        changed = changed | (k != jnp.roll(k, 1))
    return (tuple(keys), jnp.where(live, tag, tag - n), live & changed,
            jnp.sum(valid, dtype=jnp.int32))


def _lower_bound(state, m, keys):
    """First index in [0, m) whose state key is not less than each key (m
    where none is): one gather a limb a halving."""
    lo = jnp.zeros(keys[0].shape, dtype=jnp.int32)
    hi = jnp.broadcast_to(m, keys[0].shape)
    for _ in range(state[0].shape[0].bit_length()):
        mid = (lo + hi) >> 1
        lt, _ = _lex_lt_eq(tuple(s[mid] for s in state), keys)
        go = lo < hi
        lo = jnp.where(go & lt, mid + 1, lo)
        hi = jnp.where(go & ~lt, mid, hi)
    return lo


def _searched(state, m, keys):
    lb = _lower_bound(state, m, keys)
    at = jnp.minimum(lb, state[0].shape[0] - 1)
    _, eq = _lex_lt_eq(tuple(s[at] for s in state), keys)
    return lb, eq & (lb < m)


def _windowed(state, m, keys, bound, start, hi, chunk, window):
    """Each chunk's keys against the state slots [bound, hi] below m of its
    window [start, start + window): a key's lower bound is its chunk's bound
    plus the slots of that range below it, and the slot at ``hi`` may equal
    the chunk's last keys."""
    nc = bound.shape[0]
    at = start[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
    inside = (at >= bound[:, None]) & (at <= hi[:, None]) & (at < m)
    win = tuple(jax.vmap(lambda s, x=x: lax.dynamic_slice(x, (s,), (window,)))(
        start)[:, None, :] for x in state)
    kc = tuple(k.reshape(nc, chunk)[:, :, None] for k in keys)
    lt, eq = _lex_lt_eq(win, kc)
    inside = inside[:, None, :]
    below = jnp.sum(inside & lt, axis=-1, dtype=jnp.int32)
    found = jnp.any(inside & eq, axis=-1)
    return ((bound[:, None] + below).reshape(-1), found.reshape(-1))


def distinct_probe(state: Tuple[jax.Array, ...], m: jax.Array,
                   keys: Tuple[jax.Array, ...], starts: jax.Array,
                   rows: jax.Array, live: jax.Array, chunk: int):
    """(lower bound of each sorted slot in the state, new-key flags of the
    sorted slots, new-key flags in the rows' order, number of new keys,
    slots sorted or compared).  ``live``: the batch's live rows, which sort
    before its dead slots; a dead slot's bound reads ``m``, so the bounds
    never decrease.  The slots: the dedupe's sort and the flags' sort, a
    padded slot each; the bounds' search, a slot a halving; then a window's
    width a padded slot, or the search's halvings and its last read."""
    n = keys[0].shape[0]
    cap, nc = state[0].shape[0], n // chunk
    window = min(cap, NARROW * chunk)
    steps = cap.bit_length()
    # the bounds of every chunk's first key and of the last live key
    last_live = jnp.maximum(live - 1, 0)
    bounds = _lower_bound(state, m, tuple(
        jnp.concatenate([k[::chunk], k[last_live][None]]) for k in keys))
    bound, end = bounds[:nc], bounds[nc]
    # each chunk's live keys lie between its bound and the next chunk's, or
    # the last live key's; a chunk with none spans nothing
    head = jnp.arange(nc, dtype=jnp.int32) * chunk
    hi = jnp.where(head + chunk < live, bounds[1:],
                   jnp.where(head < live, end, bound))
    start = jnp.clip(bound, 0, cap - window)
    fits = jnp.all(jnp.minimum(hi, m - 1) < start + window)
    lb, found = lax.cond(
        fits,
        lambda: _windowed(state, m, keys, bound, start, hi, chunk, window),
        lambda: _searched(state, m, keys))
    lb = jnp.where(jnp.arange(n, dtype=jnp.int32) < live, lb, m)
    new = starts & ~found
    # the rows are a permutation: one unstable sort of row x 2 + flag puts
    # the flags back in the rows' order
    packed = lax.sort(rows * 2 + new.astype(jnp.int32), is_stable=False)
    slots = 2 * n + (nc + 1) * steps + jnp.where(
        fits, n * window, n * (steps + 1))
    return (lb, new, (packed & 1) == 1, jnp.sum(new, dtype=jnp.int32),
            slots.astype(jnp.int32))


def _cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of ``x`` as rows of ``SCAN_ROW`` slots: each
    row's sum, then the rows' totals summed the same way and added to the
    rows after.  The TPU's compiler takes about 20 s over one ``cumsum`` of
    a million slots in one piece, and about 1 s over it as rows."""
    n = x.shape[0]
    if n <= SCAN_ROW or n % SCAN_ROW:
        return jnp.cumsum(x, dtype=x.dtype)
    rows = jnp.cumsum(x.reshape(-1, SCAN_ROW), axis=1, dtype=x.dtype)
    before = _cumsum(rows[:, -1]) - rows[:, -1]
    return (rows + before[:, None]).reshape(-1)


def distinct_merge(state: Tuple[jax.Array, ...], m: jax.Array,
                   keys: Tuple[jax.Array, ...], new: jax.Array,
                   lb: jax.Array, cap: int):
    """The state with the new keys written in, in a buffer of ``cap`` slots:
    a new key lands at its lower bound plus the new keys before it, an old
    key at its index plus the new keys whose bound is at or before it.
    Both scatters' indices never decrease, which the TPU's compiler takes in
    about a second where an unordered scatter of a million takes ten: the
    old keys' are unique, and every sorted slot of the batch adds at its
    bound plus the new keys before it, zero where its key is not new, so a
    slot that shares its place with a key only adds nothing to it."""
    n_old = state[0].shape[0]
    i = jnp.arange(n_old, dtype=jnp.int32)
    flags = new.astype(jnp.int32)
    upto = _cumsum(flags)
    # the new keys whose bound is at or before each old slot: the new keys
    # counted at their bounds (which never decrease), summed up the state
    shift = _cumsum(jnp.zeros(n_old, jnp.int32).at[lb].add(
        flags, mode="drop", indices_are_sorted=True))
    dest_old = jnp.where(i < m, i + shift, cap + i)
    dest_new = lb + upto - flags
    return tuple(
        jnp.zeros(cap, s.dtype)
        .at[dest_old].set(s, mode="drop", unique_indices=True,
                          indices_are_sorted=True)
        .at[dest_new].add(jnp.where(new, k, 0).astype(s.dtype), mode="drop",
                          indices_are_sorted=True)
        for s, k in zip(state, keys))


_STATICS = {distinct_dedupe: (), distinct_probe: ("chunk",),
            distinct_merge: ("cap",)}


@functools.lru_cache(maxsize=None)
def jitted(body):
    """One of the three bodies, jitted on first use (a jit object built at
    import would be shared across the engine's threads: lint rule QK001)."""
    return jax.jit(body, static_argnames=_STATICS[body])


def _call(body, args, statics=()):
    return compileplane.aot_kernel_call(body.__name__, jitted(body), args,
                                        statics, (DISTINCT_FORM,))


class SortedKeys:
    """The distinct keys seen so far: sorted normalised limbs and their
    count."""

    def __init__(self):
        self.limbs: Optional[Tuple[jax.Array, ...]] = None
        self.count = 0

    def fold(self, limbs, valid) -> Tuple[jax.Array, int]:
        """Fold one batch's key limbs in: (the mask of the rows whose key
        was new, first row of each key, and how many)."""
        n = valid.shape[0]
        if n <= FOLD_SLOTS:
            return self._fold(tuple(limbs), valid)
        emits, added = [], 0
        for at in range(0, n, FOLD_SLOTS):
            emit, new = self._fold(
                tuple(x[at:at + FOLD_SLOTS] for x in limbs),
                valid[at:at + FOLD_SLOTS])
            emits.append(emit)
            added += new
        return jnp.concatenate(emits), added

    def _fold(self, limbs, valid) -> Tuple[jax.Array, int]:
        n = valid.shape[0]
        keys, rows, starts, rows_in = _call(distinct_dedupe, (limbs, valid))
        if self.limbs is None:
            empty = config.bucket_size(0)
            self.limbs = tuple(jnp.zeros(empty, k.dtype) for k in keys)
        m = jnp.asarray(self.count, dtype=jnp.int32)
        lb, new, emit, num, slots = _call(
            distinct_probe, (self.limbs, m, keys, starts, rows, rows_in),
            (min(CHUNK, n & -n),))
        added = int(tracing.device_read("distinct.new", num))
        opstats.note(distinct_rows_in=rows_in, distinct_rows_out=added,
                     distinct_sort_slots=slots)
        if added:
            self.count += added
            cap = self.limbs[0].shape[0]
            if self.count > cap:
                cap = config.bucket_size(cap + n)
            self.limbs = _call(
                distinct_merge, (self.limbs, m, keys, new, lb), (cap,))
        return emit, added
