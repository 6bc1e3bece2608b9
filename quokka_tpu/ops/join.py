"""Device join kernels.

TPU has no pointer-chasing hash tables, so joins are rank-based (SURVEY.md
"Hard parts" #3): concatenate probe+build key limbs, compute dense ranks via a
multi-operand sort (one XLA sort), then match rows that share a rank.  Two
paths:

- ``hash_join_pk``: build side has unique keys (the common TPC-H case —
  dimension/PK build sides).  Output is probe-aligned and mask-based: no host
  sync, stays fully on device.  On the sort branch a build whose key is one
  dense integer limb is probed through a direct-address table (one gather a
  probe row), every other build by binary search.
- ``hash_join_general``: many-to-many.  Output size is computed on device and
  synced to the host once per batch to pick the output bucket, then a jitted
  expansion kernel gathers (probe_idx, build_idx) pairs.

Reference behavior being matched: BuildProbeJoinExecutor semantics
(pyquokka/executors/sql_executors.py:325-378) — inner/left/semi/anti.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quokka_tpu import config
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import kernels
from quokka_tpu.runtime import compileplane
from quokka_tpu.ops.batch import (
    DeviceBatch, NumCol, StrCol, gather_columns, key_limbs, null_mask, with_nulls,
)
from quokka_tpu.ops.kernels import dense_rank


def _nonnull_valid(batch: DeviceBatch, keys) -> jax.Array:
    """Rows with any null join key never match (SQL null-join semantics)."""
    v = batch.valid
    for k in keys:
        v = v & ~null_mask(batch.columns[k])
    return v


@jax.jit
def _count_true(mask: jax.Array):
    return jnp.sum(mask.astype(jnp.int32))


def _concat_limbs(probe: DeviceBatch, build: DeviceBatch, probe_keys, build_keys):
    lp = key_limbs(probe, probe_keys)
    lb = key_limbs(build, build_keys)
    assert len(lp) == len(lb), "join key column types must match"
    limbs = [jnp.concatenate([a, b.astype(a.dtype)]) for a, b in zip(lp, lb)]
    valid = jnp.concatenate(
        [_nonnull_valid(probe, probe_keys), _nonnull_valid(build, build_keys)]
    )
    return limbs, valid


@jax.jit
def _sort_build_keys(limbs: Tuple[jax.Array, ...], valid: jax.Array):
    """Sort the build side's key limbs once (invalid/null-key rows last).
    Returns (sorted_limbs, perm, n_valid) for binary-search probing."""
    n = valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    inv = (~valid).astype(jnp.int32)
    s = lax.sort([inv, *limbs, iota], num_keys=1 + len(limbs))
    return tuple(s[1:-1]), s[-1], jnp.sum(valid.astype(jnp.int32))


def _lex_lt_eq(a: Tuple[jax.Array, ...], b: Tuple[jax.Array, ...]):
    """Elementwise lexicographic (a < b, a == b) over limb tuples."""
    lt = jnp.zeros(a[0].shape, dtype=bool)
    eq = jnp.ones(a[0].shape, dtype=bool)
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt, eq


@functools.partial(jax.jit, static_argnames=("steps",))
def _pk_probe_sorted(sorted_limbs, perm, n_valid, probe_limbs, probe_ok,
                     steps: int):
    """Probe a PRESORTED build with a vectorized lexicographic lower-bound:
    `steps` unrolled halvings, each one gather per limb — ~20 p-sized gathers
    instead of re-sorting probe+build jointly per batch.  The probe for
    every key `_direct_table_cached` cannot hold: several limbs, strings,
    floats, sparse or wide integers."""
    p = probe_limbs[0].shape[0]
    lo = jnp.zeros(p, dtype=jnp.int32)
    hi = jnp.broadcast_to(n_valid.astype(jnp.int32), (p,))
    for _ in range(steps):
        mid = (lo + hi) >> 1
        mk = tuple(l[mid] for l in sorted_limbs)
        lt, _ = _lex_lt_eq(mk, probe_limbs)  # build[mid] < probe row
        go = lo < hi
        lo = jnp.where(go & lt, mid + 1, lo)
        hi = jnp.where(go & ~lt, mid, hi)
    pos = jnp.clip(lo, 0, perm.shape[0] - 1)
    mk = tuple(l[pos] for l in sorted_limbs)
    _, eq = _lex_lt_eq(mk, probe_limbs)
    matched = probe_ok & eq & (lo < n_valid)
    # ties in the build sort kept original order (iota operand), so perm[pos]
    # is the smallest original build index of the key — same pick as
    # _pk_match's segment-min
    build_idx = jnp.clip(perm[pos], 0, perm.shape[0] - 1)
    return build_idx, matched


def _batch_cache(build: DeviceBatch, attr: str) -> dict:
    """A dict kept ON the batch object under `attr`, keyed by key columns:
    what a finalized build derives once and every probe batch reuses."""
    cache = getattr(build, attr, None)
    if cache is None:
        cache = {}
        setattr(build, attr, cache)
    return cache


def _build_sorted_cached(build: DeviceBatch, build_keys: Sequence[str]):
    """Sorted-key view of a build table, cached ON the batch object: the
    probe executor joins the same finalized build against every probe batch
    (sql_execs.BuildProbeJoinExecutor), so the sort is paid once."""
    cache = _batch_cache(build, "_pk_sorted_cache")
    key = tuple(build_keys)
    hit = cache.get(key)
    if hit is None:
        limbs = key_limbs(build, build_keys)
        ok = _nonnull_valid(build, build_keys)
        hit = cache[key] = compileplane.aot_kernel_call(
            "sort_build_keys", _sort_build_keys, (tuple(limbs), ok))
    return hit


# A direct-address table may take this many int32 slots per padded build
# row.  It is memory against gathers: 4 B x 32 = 128 B a build slot, a few
# times the build's own columns, and never more than MAX_BUCKET's 64 MB.
# dbgen's order keys use one value in four and Q3's date filter plus the
# two-way exchange keep one order in four of those per channel (6.0 M of
# span over 524,288 slots: 16), so 32 leaves one ladder rung for a filter
# twice as selective; a build thinner than that is sparse and keeps the
# search, whose ~20 gathers a row then cost less than the table's bytes.
DIRECT_SLOTS_PER_BUILD_ROW = 32


@jax.jit
def _sorted_build_stats(sorted_limbs, n_valid):
    """What the host reads once per finalised build, in one transfer: any
    adjacent equal key pair within the valid prefix of the build sort, the
    valid count, and the first limb's smallest and largest valid value
    (the sort put them first and last)."""
    n = sorted_limbs[0].shape[0]
    eq = jnp.ones(n, dtype=bool)
    for limb in sorted_limbs:
        eq = eq & (limb == jnp.roll(limb, 1))
    iota = jnp.arange(n, dtype=jnp.int32)
    dup = jnp.any(eq & (iota >= 1) & (iota < n_valid))
    first = sorted_limbs[0]
    return dup, n_valid, first[0], first[jnp.maximum(n_valid - 1, 0)]


class _BuildStats(NamedTuple):
    dup: bool        # two valid rows share a key
    n_ok: int        # valid rows with a non-null key
    kmin: object     # the first limb's least and greatest valid value, as
    kmax: object     # host numbers (python ints for an integer limb)
    kmin_dev: jax.Array  # ... and as the device scalars they were read from
    kmax_dev: jax.Array


def _build_stats_cached(build: DeviceBatch,
                        build_keys: Sequence[str]) -> _BuildStats:
    """The one blocking read a finalised build pays on the sort branch,
    cached on the batch and shared by `build_keys_unique` and the probe's
    choice between table and search."""
    cache = _batch_cache(build, "_pk_stats_cache")
    key = tuple(build_keys)
    hit = cache.get(key)
    if hit is None:
        sorted_limbs, _perm, n_valid = _build_sorted_cached(build, build_keys)
        stats = _sorted_build_stats(tuple(sorted_limbs), n_valid)
        dup, n_ok, kmin, kmax = tracing.device_read("join.build_stats", stats)
        hit = cache[key] = _BuildStats(
            bool(dup), int(n_ok), kmin.item(), kmax.item(), *stats[2:])
    return hit


@functools.partial(jax.jit, static_argnames=("size",))
def _pk_direct_build(sorted_key, perm, n_valid, kmin, size: int):
    """slot[key - kmin] = the build row holding `key`, -1 where none does.
    One scatter over the build sort: the valid prefix's offsets ascend and
    are unique (the caller saw no duplicate), and the invalid tail is sent
    past the table's end, ascending too, where `drop` discards it."""
    iota = jnp.arange(sorted_key.shape[0], dtype=jnp.int32)
    slot = jnp.where(iota < n_valid, (sorted_key - kmin).astype(jnp.int32),
                     size + iota)
    return jnp.full(size, -1, dtype=jnp.int32).at[slot].set(
        perm, mode="drop", indices_are_sorted=True, unique_indices=True)


def _direct_table_cached(build: DeviceBatch, build_keys: Sequence[str]):
    """(table, kmin, kmax) for a build whose key is one dense integer limb,
    else None; decided once per build from what `_build_stats_cached` read
    and cached on the batch.  The address is the key, so a probe row pays
    one gather where the search pays ~20."""
    cache = _batch_cache(build, "_pk_direct_cache")
    key = tuple(build_keys)
    if key not in cache:
        cache[key] = _direct_table(build, build_keys)
    return cache[key]


def _direct_table(build: DeviceBatch, build_keys: Sequence[str]):
    col = build.columns[build_keys[0]]
    if not (len(build_keys) == 1 and isinstance(col, NumCol)
            and col.kind in ("i", "d") and col.hi is None
            and jnp.issubdtype(col.data.dtype, jnp.integer)):
        return None
    st = _build_stats_cached(build, build_keys)
    span = st.kmax - st.kmin + 1  # python ints: no overflow
    limit = min(config.MAX_BUCKET,
                DIRECT_SLOTS_PER_BUILD_ROW * build.padded_len)
    # the span first (`bucket_size` refuses one beyond MAX_BUCKET), then
    # its rung: below the ladder's knee the next rung is 4x away
    if st.n_ok == 0 or st.dup or span > limit:
        return None
    size = config.bucket_size(span)
    if size > limit:
        return None
    (sorted_key,), perm, n_valid = _build_sorted_cached(build, build_keys)
    table = compileplane.aot_kernel_call(
        "pk_direct_build", _pk_direct_build,
        (sorted_key, perm, n_valid, st.kmin_dev), (size,))
    return table, st.kmin_dev, st.kmax_dev


def direct_table_nbytes(build: DeviceBatch, build_keys: Sequence[str]) -> int:
    """Device bytes of the direct-address table this build holds (0 without
    one; reads the cache, never builds)."""
    hit = getattr(build, "_pk_direct_cache", {}).get(tuple(build_keys))
    return 0 if hit is None else int(hit[0].nbytes)


@jax.jit
def _pk_probe_direct(table, kmin, kmax, probe_key, probe_ok):
    """Probe a direct-address table: one gather a probe row.  `matched` is
    `_pk_probe_sorted`'s bit for bit, and so is `build_idx` on every matched
    row (an unmatched row reads build row 0 under a false mask)."""
    in_range = probe_ok & (probe_key >= kmin) & (probe_key <= kmax)
    # the difference is only used where it fits the table: a wrap (a key
    # far outside [kmin, kmax]) is masked, never an address
    off = jnp.where(in_range, probe_key - kmin, 0).astype(jnp.int32)
    slot = table[off]
    return jnp.maximum(slot, 0), in_range & (slot >= 0)


@functools.partial(jax.jit, static_argnames=("p",))
def _pk_match(limbs: Tuple[jax.Array, ...], valid: jax.Array, p: int):
    n = valid.shape[0]
    ranks, _ = dense_rank(limbs, valid)
    rp, rb = ranks[:p], ranks[p:]
    vp, vb = valid[:p], valid[p:]
    b = n - p
    iota_b = jnp.arange(b, dtype=jnp.int32)
    first = jnp.full(n, b, dtype=jnp.int32).at[rb].min(jnp.where(vb, iota_b, b))
    cnt = jax.ops.segment_sum(vb.astype(jnp.int32), rb, num_segments=n)
    build_idx = jnp.clip(first[rp], 0, b - 1)
    matched = vp & (cnt[rp] > 0)
    return build_idx, matched


def hash_join_pk(
    probe: DeviceBatch,
    build: DeviceBatch,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    how: str = "inner",
    build_payload: Sequence[str] = (),
) -> DeviceBatch:
    """Join where build keys are unique.  Probe-aligned; the probe path has
    no host sync.  The cached build pays ONE scalar d2h per build batch (the
    hash-table convergence check, hashtable.build_table) — a diverged build
    is remembered on the batch and every probe takes the sort path, whose
    one read per build (`_build_stats_cached`) also decides between the
    direct-address table and the binary search."""
    from quokka_tpu.obs import opstats
    from quokka_tpu.ops import strategy as kstrategy

    probe_limbs = key_limbs(probe, probe_keys)
    probe_ok = _nonnull_valid(probe, probe_keys)
    use_tables = kstrategy.choice("join_build") == "hashtable"
    if use_tables:
        # hashtable is imported at module scope by kernels (imported above):
        # a first-import inside an active trace once mis-primed jit dispatch
        from quokka_tpu.ops import hashtable

        try:
            table = hashtable.build_table(
                build, build_keys, key_limbs,
                lambda: _nonnull_valid(build, build_keys),
            )
        except hashtable.HashTableConvergenceError:
            # unplaced build rows would alias slot 0's key: take the sort
            # path for this build batch instead of joining wrong
            use_tables = False
        else:
            assert len(probe_limbs) == len(table.raw_dtypes), \
                "join key column types must match"
            build_idx, matched = hashtable.pk_probe(
                table, probe_limbs, probe_ok)
            kstrategy.note_used("join_build", "hashtable")
    if not use_tables:
        kstrategy.note_used("join_build", "sort")
        sorted_limbs, perm, n_valid = _build_sorted_cached(build, build_keys)
        assert len(probe_limbs) == len(sorted_limbs), \
            "join key column types must match"
        probe_limbs = tuple(l.astype(s.dtype)
                            for l, s in zip(probe_limbs, sorted_limbs))
        direct = _direct_table_cached(build, build_keys)
        if direct is not None:
            build_idx, matched = compileplane.aot_kernel_call(
                "pk_probe_direct", _pk_probe_direct,
                (*direct, probe_limbs[0], probe_ok))
            opstats.note(join_probe_direct=probe.padded_len)
        else:
            steps = max(1, int(np.ceil(np.log2(max(2, build.padded_len)))) + 1)
            build_idx, matched = compileplane.aot_kernel_call(
                "pk_probe_sorted", _pk_probe_sorted,
                (tuple(sorted_limbs), perm, n_valid, probe_limbs, probe_ok),
                (steps,),
            )
            opstats.note(join_probe_search=probe.padded_len)
    if how == "semi":
        return kernels.apply_mask(probe, matched)
    if how == "anti":
        return kernels.apply_mask(probe, probe.valid & ~matched)
    cols = dict(probe.columns)
    for name, taken in gather_columns(
        {n: build.columns[n] for n in build_payload}, build_idx
    ).items():
        if how == "left":
            taken = with_nulls(taken, ~matched)
        cols[name] = taken
    if how == "inner":
        out_valid = matched
    elif how == "left":
        out_valid = probe.valid
    else:
        raise ValueError(f"how={how}")
    # start the output count's async host copy now: downstream consumers
    # (partial agg, storage filters, concat compaction) read it batches
    # later, when it has long landed — instead of paying a fresh device
    # round trip each
    return DeviceBatch(cols, out_valid, None, probe.sorted_by).note_count(
        _count_true(out_valid))


@functools.partial(jax.jit, static_argnames=("p",))
def _mm_plan(limbs: Tuple[jax.Array, ...], valid: jax.Array, p: int):
    n = valid.shape[0]
    ranks, _ = dense_rank(limbs, valid)
    rp, rb = ranks[:p], ranks[p:]
    vp, vb = valid[:p], valid[p:]
    b = n - p
    cnt = jax.ops.segment_sum(vb.astype(jnp.int32), rb, num_segments=n)
    # build rows grouped by rank: sort build positions by rank
    iota_b = jnp.arange(b, dtype=jnp.int32)
    inv = (~vb).astype(jnp.int32)
    _, _, build_pos_sorted = lax.sort([inv, rb, iota_b], num_keys=2)
    offsets = jnp.cumsum(cnt) - cnt  # start of each rank's run in the sorted build
    match_count = jnp.where(vp, cnt[rp], 0)
    total = jnp.sum(match_count)
    return match_count, total, offsets, build_pos_sorted, rp


@functools.partial(jax.jit, static_argnames=("out_padded",))
def _mm_expand(match_count, offsets, build_pos_sorted, rp, total, out_padded: int):
    p = match_count.shape[0]
    cum = jnp.cumsum(match_count)
    j = jnp.arange(out_padded, dtype=jnp.int32)
    probe_idx = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    probe_idx = jnp.clip(probe_idx, 0, p - 1)
    start = cum[probe_idx] - match_count[probe_idx]
    k = j - start
    bpos = offsets[rp[probe_idx]] + k
    bpos = jnp.clip(bpos, 0, build_pos_sorted.shape[0] - 1)
    build_idx = build_pos_sorted[bpos]
    out_valid = j < total
    return probe_idx, build_idx, out_valid


def mm_plan_for(limbs, valid, p: int, how: str, probe_valid=None):
    """Shared many-to-many planning for the embedded AND mesh join paths:
    per-probe match counts (left joins get a synthetic row for unmatched
    probes), total output rows, and the sorted-build expansion tables."""
    match_count, total, offsets, build_pos_sorted, rp = \
        compileplane.aot_kernel_call(
            "mm_plan", _mm_plan, (tuple(limbs), valid), (p,))
    if how == "left":
        pv = valid[:p] if probe_valid is None else probe_valid
        match_count = jnp.where(pv & (match_count == 0), 1, match_count)
        total = jnp.sum(match_count)
    return match_count, total, offsets, build_pos_sorted, rp


def mm_unmatched(limbs, valid, p: int, probe_idx, match_count):
    """Output-aligned mask of left-join rows with no real build match."""
    return (match_count[probe_idx] == 1) & _is_unmatched_gather(
        tuple(limbs), valid, p, probe_idx
    )


def hash_join_general(
    probe: DeviceBatch,
    build: DeviceBatch,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    how: str = "inner",
    build_payload: Sequence[str] = (),
) -> DeviceBatch:
    """Many-to-many join.  One host sync per batch for the output bucket."""
    from quokka_tpu.obs import opstats

    p = probe.padded_len
    opstats.note(join_probe_general=p)
    limbs, valid = _concat_limbs(probe, build, probe_keys, build_keys)
    if how in ("semi", "anti"):
        match_count, *_ = _mm_plan(tuple(limbs), valid, p)
        matched = match_count > 0
        mask = matched if how == "semi" else (probe.valid & ~matched)
        return kernels.apply_mask(probe, mask)
    match_count, total, offsets, build_pos_sorted, rp = mm_plan_for(
        limbs, valid, p, how, probe_valid=probe.valid
    )
    # host sync: pick output bucket
    ntotal = int(tracing.device_read("join.mm_total", total))
    out_padded = config.bucket_size(ntotal)
    probe_idx, build_idx, out_valid = compileplane.aot_kernel_call(
        "mm_expand", _mm_expand,
        (match_count, offsets, build_pos_sorted, rp, total), (out_padded,)
    )
    cols = gather_columns(probe.columns, probe_idx)
    unmatched = None
    if how == "left":
        unmatched = mm_unmatched(limbs, valid, p, probe_idx, match_count)
    for name, taken in gather_columns(
        {n: build.columns[n] for n in build_payload}, build_idx
    ).items():
        if how == "left":
            taken = with_nulls(taken, unmatched)
        cols[name] = taken
    # out_valid = (iota < total) for BOTH inner and left (mm_plan_for's
    # left adjustment feeds total), so the host count is exact either way
    return DeviceBatch(cols, out_valid, ntotal, None)


@functools.partial(jax.jit, static_argnames=("p",))
def _is_unmatched_gather(limbs, valid, p, probe_idx):
    ranks, _ = dense_rank(tuple(limbs), valid)
    rp, rb = ranks[:p], ranks[p:]
    vp, vb = valid[:p], valid[p:]
    n = valid.shape[0]
    cnt = jax.ops.segment_sum(vb.astype(jnp.int32), rb, num_segments=n)
    # dense_rank gives invalid (incl. null-key) probe rows an arbitrary rank —
    # they must read as unmatched regardless of that rank's build count
    return ((cnt[rp] == 0) | ~vp)[probe_idx]


@jax.jit
def _distinct_from_table(tbl, ok):
    """(# placed keys, # insertable rows) from a converged hash table."""
    from quokka_tpu.ops import hashtable

    return (jnp.sum((tbl != hashtable.EMPTY).astype(jnp.int32)),
            jnp.sum(ok.astype(jnp.int32)))


def build_keys_unique(build: DeviceBatch, build_keys: Sequence[str]) -> bool:
    """Host-synced check whether the build side is PK-unique (decides fast
    path).  Called once per finalized build table, not per probe batch.

    Answered from the SAME cached structure the probe will use — the device
    hash table (distinct == placed slots) or the cached build sort (any
    adjacent equal pair) — instead of a fresh dense-rank sort over the
    build, so the check is nearly free and the probe cache is warm before
    the first probe batch arrives.  Null-key rows match the dense-rank
    semantics this replaces: all nulls collapse into one key, so uniqueness
    additionally requires at most one null/NaN-key row."""
    from quokka_tpu.ops import strategy as kstrategy

    nvalid = build.count_valid()
    if kstrategy.choice("join_build") == "hashtable":
        from quokka_tpu.ops import hashtable

        try:
            table = hashtable.build_table(
                build, build_keys, key_limbs,
                lambda: _nonnull_valid(build, build_keys),
            )
        except hashtable.HashTableConvergenceError:
            table = None  # diverged build: the sort fallback below decides
        if table is not None:
            raw = key_limbs(build, build_keys)
            ok = _nonnull_valid(build, build_keys) & ~hashtable.nan_rows(raw)
            distinct, n_ok = _distinct_from_table(table.tbl, ok)
            distinct, n_ok = map(int, tracing.device_read(
                "join.hash_distinct", (distinct, n_ok)))
            return distinct == n_ok and nvalid - n_ok <= 1
    st = _build_stats_cached(build, build_keys)
    unique = (not st.dup) and nvalid - st.n_ok <= 1
    if unique:
        # the table the probes will use, built before the first one arrives
        _direct_table_cached(build, build_keys)
    return unique
