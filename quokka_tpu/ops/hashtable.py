"""Device hash table for equality-keyed kernels (hash group-by, PK join probe).

The sort-based kernels (`kernels.sorted_groupby`, `join._pk_probe_sorted`)
remain the default on TPU, where random-order scatters serialize badly and
the multi-operand sort is the idiomatic grouping primitive (SURVEY.md "Hard
parts" #3).  On CPU/GPU backends the opposite holds: XLA scatter/gather are
fast and an O(n) table pass beats the O(n log n) sort by 3-10x on the
high-cardinality group-bys that dominate TPC-H Q3-class queries (measured:
1M-row 3-operand lax.sort ~485 ms vs insert+segment ~175 ms on one CPU core).
`config.use_hash_tables()` picks per backend; env QUOKKA_HASH_TABLES=1|0
overrides.

Design: open addressing over a power-of-two capacity with a double-hash odd
stride.  The insert loop runs all rows in lockstep (`lax.while_loop`); each
round every unplaced row scatter-mins its row id into its current candidate
slot, then reads the slot back: the winner is placed, rows whose key equals
the occupant's key are placed on the same slot (duplicate keys CONVERGE —
the slot doubles as a group id), and everyone else steps by its key's
stride.  Rows of equal keys share hash, stride and therefore probe sequence,
so they always meet the same occupant and can never split into two groups.
The scatter-min makes the winner (and thus the whole table) deterministic —
a replay of the same batch reproduces byte-identical groups, which the
lineage tape asserts (runtime/engine.py replay-determinism checks).

Reference parity: this plays the role of the in-memory hash structures
polars uses inside the reference's groupby/join executors
(pyquokka/executors/sql_executors.py:325-378) — here as a pure XLA program.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from quokka_tpu.analysis import compat
from quokka_tpu.obs import spans as tracing

EMPTY = jnp.int32(2**31 - 1)


class HashTableConvergenceError(RuntimeError):
    """The lockstep insert failed to place every valid row (load factor or
    probe-chain pathology).  Callers fall back to the sort-based kernels —
    never proceed: unplaced rows silently alias slot 0's group."""

_M1 = jnp.uint32(0x85EBCA6B)
_M2 = jnp.uint32(0xC2B2AE35)
_M3 = jnp.uint32(0x9E3779B1)


def capbits_for(n: int) -> int:
    """Capacity exponent giving load factor <= 0.5 (min 256 slots)."""
    bits = 8
    while (1 << bits) < 2 * max(n, 1):
        bits += 1
    return bits


def canonical_limbs(limbs: Sequence[jax.Array],
                    nan_unique: bool = True) -> Tuple[jax.Array, ...]:
    """Equality-preserving int32 form of key limbs.  64-bit limbs (the x64
    CPU regime stores ints as one int64 limb and floats as float64) expand
    to TWO int32 limbs each — truncating would silently merge keys that
    differ only above bit 31.

    Float limbs are bitcast after canonicalizing -0.0 to +0.0 (IEEE == says
    they are one key; their bit patterns differ).  NaN handling follows the
    sort path's IEEE-compare semantics (NaN != NaN):

    - group-by (`nan_unique=True`): each NaN row must become its own group,
      so every float limb carries a companion limb that is 0 for non-NaN
      rows and a per-row unique id for NaN rows (a full int32 limb — a
      NaN-space bit pattern would overflow the 23-bit mantissa at
      MAX_BUCKET-sized batches).  Spreading NaNs across slots also breaks up
      what would otherwise be one giant shared probe chain.
    - join (`nan_unique=False`): NaN keys never match ANY row, including
      other NaNs; callers must mask NaN rows out of validity (`nan_rows`).
    """
    out = []
    for l in limbs:
        if jnp.issubdtype(l.dtype, jnp.floating):
            if l.dtype == jnp.float64:
                f = jnp.where(l == 0.0, jnp.float64(0.0), l)
                isnan = jnp.isnan(l)
                f = jnp.where(isnan, jnp.float64(jnp.nan), f)  # one NaN pattern
                pair = lax.bitcast_convert_type(f, jnp.int32)  # [..., 2]
                out.append(pair[..., 0])
                out.append(pair[..., 1])
            else:
                f = l.astype(jnp.float32)
                f = jnp.where(f == 0.0, jnp.float32(0.0), f)
                isnan = jnp.isnan(f)
                f = jnp.where(isnan, jnp.float32(jnp.nan), f)
                out.append(lax.bitcast_convert_type(f, jnp.int32))
            if nan_unique:
                rid = jnp.arange(l.shape[0], dtype=jnp.int32)
                out.append(jnp.where(isnan, rid + 1, jnp.int32(0)))
        elif l.dtype == jnp.int32:
            out.append(l)
        elif l.dtype in (jnp.int64, jnp.uint64):
            u = l.astype(jnp.uint64)
            out.append((u >> 32).astype(jnp.int32))
            out.append(u.astype(jnp.uint32).astype(jnp.int32))
        else:
            out.append(l.astype(jnp.int32))
    return tuple(out)


def nan_rows(limbs: Sequence[jax.Array]) -> jax.Array:
    """Rows with a NaN in any float limb (excluded from join matching)."""
    m = jnp.zeros(limbs[0].shape, dtype=bool)
    for l in limbs:
        if jnp.issubdtype(l.dtype, jnp.floating):
            m = m | jnp.isnan(l)
    return m


def _hash_stride(limbs: Tuple[jax.Array, ...], mask: int):
    h = jnp.full(limbs[0].shape, jnp.uint32(0x9747B28C))
    for l in limbs:
        h = (h ^ l.astype(jnp.uint32)) * _M3
        h ^= h >> 16
    h = (h ^ (h >> 13)) * _M1
    h = (h ^ (h >> 16)) * _M2
    slot = (h ^ (h >> 15)) & jnp.uint32(mask)
    stride = ((h >> 7) | jnp.uint32(1)) & jnp.uint32(mask)  # odd: full cycle
    return slot, stride


def _eq_at(limbs: Tuple[jax.Array, ...], idx: jax.Array,
           other: Tuple[jax.Array, ...]) -> jax.Array:
    eq = jnp.ones(idx.shape, dtype=bool)
    for l, o in zip(limbs, other):
        eq = eq & (l[idx] == o)
    return eq


_RID_BITS = 24  # rid < 2^24 always holds: config.MAX_BUCKET == 1 << 24
_RID_MASK = (1 << _RID_BITS) - 1


def _in_trace() -> bool:
    """True while tracing inside another jit.  The table kernels are called
    both nested (FusedPartialAgg's fused program, mesh programs) and at top
    level (executors); routing traced calls to the PLAIN bodies — which
    trace to the identical jaxpr a nested pjit would inline — sidesteps a
    jit-dispatch race observed when the engine's threads hit the same pjit
    object from both contexts (spurious 'Execution supplied N buffers but
    compiled program expected M buffers' on the 1-core CPU backend).

    The probe goes through the version-guarded shim: a jax upgrade that
    moves the private API fails the package at import (analysis/compat.py)
    instead of a swallowed exception silently answering False — which would
    re-enable the dispatch race this helper exists to avoid."""
    return not compat.trace_state_clean()


def _insert_body(limbs: Tuple[jax.Array, ...], valid: jax.Array, capbits: int):
    """Insert all valid rows; returns (slot_for_row, table, converged).

    slot_for_row[i] is the slot holding row i's key (all equal keys share
    it); table[s] packs (claim_round << 24 | row_id) for the row that
    claimed slot s, or EMPTY.  Use `table_rid` to decode.  Invalid rows get
    slot 0 — callers mask by `valid`.  `converged` is a scalar bool: every
    valid row placed before the round cap — when False the unplaced rows'
    myslot=0 silently aliases slot 0's group, so untraced callers MUST
    check it and fall back to the sort path (build_table raises
    HashTableConvergenceError; hash_groupby reruns sorted_groupby).  With
    load <= 0.5 and full-cycle double hashing non-convergence is
    astronomically unlikely — but its failure mode is silent wrong
    results, which is exactly what must never fail silently.

    The scatter must be claim-stable: a plain scatter-min of row ids would
    let a LATER round's smaller rid clobber an earlier claim, breaking the
    open-addressing invariant that slots a row probed past stay occupied
    (observed as ~2% of keys silently vanishing from the table).  Packing
    the round number above the rid makes earlier claims always win; ties
    within a round resolve to the smallest rid, so the table — and every
    group id derived from it — is deterministic.  Rounds saturate at 126
    (prio must stay below EMPTY); with load <= 0.5 and double hashing,
    probe chains are ~6-10 rounds in practice.
    """
    cap = 1 << capbits
    mask = cap - 1
    n = valid.shape[0]
    slot0, stride = _hash_stride(limbs, mask)
    rid = jnp.arange(n, dtype=jnp.int32)

    def body(c):
        tbl, slot, placed, myslot, it = c
        active = ~placed
        prio = (jnp.minimum(it, 126) << _RID_BITS) | rid
        cand = jnp.where(active, slot, jnp.uint32(0)).astype(jnp.int32)
        tbl = tbl.at[cand].min(jnp.where(active, prio, EMPTY))
        occ_prio = tbl[slot.astype(jnp.int32)]
        occ_row = jnp.clip(occ_prio & _RID_MASK, 0, n - 1)
        same = (occ_prio != EMPTY) & _eq_at(limbs, occ_row, limbs)
        newly = active & ((occ_prio == prio) | same)
        myslot = jnp.where(newly, slot.astype(jnp.int32), myslot)
        placed = placed | newly
        slot = jnp.where(placed, slot, (slot + stride) & jnp.uint32(mask))
        return tbl, slot, placed, myslot, it + 1

    def cond(c):
        return (~c[2].all()) & (c[4] < 2 * cap)

    tbl = jnp.full(cap, EMPTY)
    init = (tbl, slot0, ~valid, jnp.zeros(n, dtype=jnp.int32), jnp.int32(0))
    tbl, _, placed, myslot, _ = lax.while_loop(cond, body, init)
    return myslot, tbl, placed.all()


_insert_jit = functools.partial(jax.jit, static_argnames=("capbits",))(_insert_body)


def _insert(limbs, valid, capbits: int):
    """(myslot, table, converged).  Traced calls cannot host-check the
    converged flag; it stays an array for the caller's program (build_table,
    the only untraced consumer, checks it and raises)."""
    if _in_trace():
        return _insert_body(limbs, valid, capbits)
    from quokka_tpu.runtime import compileplane

    return compileplane.aot_kernel_call(
        "ht_insert", _insert_jit, (limbs, valid), (capbits,))


def table_rid(tbl: jax.Array) -> jax.Array:
    """Decode a table's packed entries to row ids (EMPTY stays EMPTY)."""
    return jnp.where(tbl == EMPTY, EMPTY, tbl & _RID_MASK)


def _probe_body(table: jax.Array, build_limbs: Tuple[jax.Array, ...],
                probe_limbs: Tuple[jax.Array, ...], probe_ok: jax.Array,
                capbits: int):
    """Walk each probe row's sequence until its key or an empty slot.
    Returns (build_idx clipped to range, matched)."""
    mask = (1 << capbits) - 1
    slot0, stride = _hash_stride(probe_limbs, mask)
    p = probe_ok.shape[0]
    b = max(build_limbs[0].shape[0], 1)

    def body(c):
        slot, done, res, ok = c
        entry = table[slot.astype(jnp.int32)]
        empty = entry == EMPTY
        rid = entry & _RID_MASK
        hit = (~empty) & _eq_at(build_limbs, jnp.clip(rid, 0, b - 1), probe_limbs)
        res = jnp.where(hit & ~done, rid, res)
        ok = ok | (hit & ~done)
        done = done | hit | empty
        slot = jnp.where(done, slot, (slot + stride) & jnp.uint32(mask))
        return slot, done, res, ok

    def cond(c):
        return ~c[1].all()

    init = (slot0, ~probe_ok, jnp.zeros(p, dtype=jnp.int32),
            jnp.zeros(p, dtype=bool))
    _, _, res, ok = lax.while_loop(cond, body, init)
    return jnp.clip(res, 0, b - 1), ok & probe_ok


_probe_jit = functools.partial(jax.jit, static_argnames=("capbits",))(_probe_body)


def _probe(table, build_limbs, probe_limbs, probe_ok, capbits: int):
    if _in_trace():
        return _probe_body(table, build_limbs, probe_limbs, probe_ok, capbits)
    from quokka_tpu.runtime import compileplane

    return compileplane.aot_kernel_call(
        "ht_probe", _probe_jit, (table, build_limbs, probe_limbs, probe_ok),
        (capbits,))


def hash_groupby(limbs: Tuple[jax.Array, ...], arrays: Tuple[jax.Array, ...],
                 ops: Tuple[str, ...], valid: jax.Array):
    """Drop-in for `kernels.sorted_groupby` — same (outs, counts, rep, num)
    contract, except group ids come out in hash order rather than key order
    (no consumer depends on group order; ORDER BY is an explicit node).

    Non-convergence of the insert (silent wrong groups otherwise): untraced
    calls check the flag on host — one scalar d2h sync per batch, the price
    of never answering wrong — and rerun through the sort path; traced
    calls (fused/mesh programs) cannot host-branch, so they accept the
    residual risk documented on `_insert_body` — the executors' untraced
    batches are where the table strategy actually runs today."""
    capbits = capbits_for(valid.shape[0])
    if _in_trace():
        outs, counts, rep, num, _ = _hash_groupby_body(
            tuple(limbs), tuple(arrays), ops, valid, capbits)
        return outs, counts, rep, num
    from quokka_tpu.ops import strategy as kstrategy

    outs, counts, rep, num, converged = _hash_groupby_jit(
        tuple(limbs), tuple(arrays), ops, valid, capbits)
    if not bool(tracing.device_read("groupby.hash_converged", converged)):
        from quokka_tpu.ops import kernels

        kstrategy.note_used("groupby", "sort")  # the fallback is what ran
        return kernels.sorted_groupby(tuple(limbs), tuple(arrays), ops, valid)
    kstrategy.note_used("groupby", "hashtable")
    return outs, counts, rep, num


def _hash_groupby_body(limbs, arrays, ops, valid, capbits):
    from quokka_tpu.ops import kernels

    climbs = canonical_limbs(limbs)
    myslot, tbl, converged = _insert_body(climbs, valid, capbits)
    flag = (tbl != EMPTY).astype(jnp.int32)
    rank_of_slot = jnp.cumsum(flag) - flag
    ranks = rank_of_slot[myslot]
    num = jnp.sum(flag)
    outs, counts, rep = kernels._segment_aggs_body(ranks, valid, arrays, ops)
    return tuple(outs), counts, rep, num, converged


_hash_groupby_jit = functools.partial(
    jax.jit, static_argnames=("ops", "capbits")
)(_hash_groupby_body)


class _TableCache:
    """Hash table of a finalized build batch, cached on the batch object
    (same discipline as join._build_sorted_cached: one build serves every
    probe batch, so the insert — and the build-side null-mask work — is
    paid once, on the cache miss only)."""

    __slots__ = ("tbl", "limbs", "raw_dtypes", "capbits")

    def __init__(self, tbl, limbs, raw_dtypes, capbits):
        self.tbl = tbl
        self.limbs = limbs
        self.raw_dtypes = raw_dtypes
        self.capbits = capbits


# negative-cache sentinel: a diverged build is remembered on the batch so a
# long probe stream does not re-run the whole failed insert loop per probe
_DIVERGED = object()


def build_table(build, build_keys: Sequence[str], key_limbs_fn,
                valid_fn) -> _TableCache:
    cache = getattr(build, "_ht_cache", None)
    if cache is None:
        cache = build._ht_cache = {}
    key = tuple(build_keys)
    hit = cache.get(key)
    if hit is _DIVERGED:
        raise HashTableConvergenceError(
            "hash-table build previously failed to converge for this build "
            "batch (cached); take the sort-based probe")
    if hit is None:
        raw = key_limbs_fn(build, build_keys)
        limbs = canonical_limbs(raw, nan_unique=False)
        capbits = capbits_for(build.padded_len)
        _, tbl, converged = _insert(limbs, valid_fn() & ~nan_rows(raw),
                                    capbits)
        if not bool(tracing.device_read("join.hash_converged", converged)):
            cache[key] = _DIVERGED
            raise HashTableConvergenceError(
                f"hash-table build did not place every row "
                f"(capbits={capbits}, n={build.padded_len}); caller must "
                "fall back to the sort-based probe")
        hit = cache[key] = _TableCache(
            tbl, limbs, tuple(l.dtype for l in raw), capbits
        )
    return hit


def pk_probe(table: _TableCache, probe_limbs: Sequence[jax.Array],
             probe_ok: jax.Array):
    """PK-join probe against a cached build table: (build_idx, matched).
    Equal-key build rows converged on one slot holding the SMALLEST build
    row id — the same pick as the sort path's segment-min.  Probe limbs are
    coerced to the build's raw limb dtypes first (the sort path's
    `astype(s.dtype)` discipline), so an int probe key matches a float
    build key by value."""
    coerced = [l.astype(dt) for l, dt in zip(probe_limbs, table.raw_dtypes)]
    climbs = canonical_limbs(coerced, nan_unique=False)
    ok = probe_ok & ~nan_rows(coerced)
    return _probe(table.tbl, table.limbs, climbs, ok, table.capbits)
