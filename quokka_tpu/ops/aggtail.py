"""The aggregation tail as compiled programs.

After the per-batch partial aggregate, what is left of a group-by is small:
PartialAggExecutor folds a handful of few-row partials into its state, the
exchange hands them to FinalAggExecutor, which folds again and then applies
the final expressions, HAVING, ORDER BY and LIMIT.  Run op by op that tail is
hundreds of one-op device launches with blocking live-count reads between
them (TPC-H Q1 on the chip: ~590 launches, 419 ms of a 650 ms request, the
device idle half of the time).  Here it is two programs, acquired through
the compile plane (persisted, prewarmed, named by ``compile.acquire``):

- ``agg_recombine`` (``recombine``): one merge.  Remaps string codes into
  the merged dictionary, concatenates the parts at their PADDED lengths under
  their masks (no compaction, so no live count is read), groups by the key
  limbs, gathers the representatives' keys and returns the group mask and the
  device count.  The host merges dictionaries, builds nothing on the device
  and reads nothing back.
- ``agg_final_tail`` (``final_tail``): the plan's finals, the HAVING mask,
  the projection, and ORDER BY / LIMIT as one sort permutation and a prefix
  mask.

Both adapt on a shape and take no option: they run when the parts' summed
padded rows are at most ``SMALL_ROWS`` (the threshold ``compact_if_large``
uses).  Above it (raw passthrough batches, high-cardinality group-bys)
compaction saves real device work and memory, and the callers' general path
runs as before.  The choice depends on shapes only, never on timing or on a
device count, so tape replay and checkpoint/restore reproduce it.

The program set is a function of the plan, not of arrival: how many partials
sit in a buffer at a merge follows timing, so every run of same-bucket parts
is filled up to a rung of ``_RUNGS`` with cached all-invalid parts, and a
plan asks for one ``agg_recombine`` per (column signature, row buckets).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quokka_tpu import config
from quokka_tpu.ops import bridge, expr_compile, kernels, sigkey
from quokka_tpu.ops import strategy as kstrategy
from quokka_tpu.ops.batch import (
    DEVICE_TABLES,
    DeviceBatch,
    NumCol,
    StrCol,
    StringDict,
    code_hash_limbs,
    hash_tables,
    map_codes,
    pad_table,
    rank_table,
)
from quokka_tpu.ops.fuse import _dispatch_program, _infer_kind, _pad_tail, _ShimBatch

# the compiled tail's upper bound on summed padded rows (and on a merged
# dictionary's entries): kernels.compact_if_large's threshold
SMALL_ROWS = 1 << 16

# part-count rungs: a run of n same-bucket buffered parts is filled to the
# smallest rung that holds it (PartialAggExecutor merges every 8 batches,
# FinalAggExecutor every 32), beyond the last to a multiple of it; but never
# beyond SMALL_ROWS rows, so a run of large parts is not blown up
_RUNGS = (8, 32)


def _rung(n: int, rows: int) -> int:
    cap = max(n, SMALL_ROWS // rows)
    for r in _RUNGS:
        if n <= r:
            return min(r, cap)
    return min(-(-n // _RUNGS[-1]) * _RUNGS[-1], cap)


def note_path(compiled: bool) -> None:
    """One merge or final tail took the compiled or the general path: the
    operator's record (-> the query record's ``agg_merges_*``) and the
    process-wide counters."""
    from quokka_tpu import obs
    from quokka_tpu.obs import opstats

    if compiled:
        opstats.note(agg_merges_compiled=1)
        obs.REGISTRY.counter("agg.merges_compiled").inc()
    else:
        opstats.note(agg_merges_general=1)
        obs.REGISTRY.counter("agg.merges_general").inc()


# ---------------------------------------------------------------------------
# cached device arrays: fillers, and the remap tables of a dictionary merge
# ---------------------------------------------------------------------------

_FILLERS: Dict[Tuple[int, str], jax.Array] = {}


def _filler(rows: int, dtype) -> jax.Array:
    """A cached all-zero device array (an all-invalid part's column, or its
    mask): a copy from the host, never an eager device op."""
    key = (rows, str(dtype))
    arr = _FILLERS.get(key)
    if arr is None:
        arr = _FILLERS[key] = jax.device_put(np.zeros(rows, dtype=dtype))
    return arr


def _merged_remap(dicts: List[Optional[StringDict]]):
    """For one string key, the parts' dictionaries (None: a filler part)
    -> (merged dictionary, [parts, T] remap table on the device, T a power
    of two).  The dictionary merge runs on the host (bridge.merge_dicts);
    the table is copied to the device once per set of dictionaries and the
    merged dictionary keeps its identity, so its own tables
    (batch.hash_tables) and the next level's lookup are found again too."""
    real = [d for d in dicts if d is not None]

    def build():
        merged, remaps = bridge.merge_dicts(real)
        size = sigkey.pow2_dim(max(len(d) for d in real + [merged]))
        identity = np.arange(size, dtype=np.int32)
        rows, it = [], iter(remaps)
        for d in dicts:
            remap = None if d is None else next(it)
            rows.append(np.zeros(size, dtype=np.int32) if d is None
                        else identity if remap is None
                        else pad_table(remap, size))
        return (merged, jax.device_put(np.stack(rows))), len(dicts) * size

    # a filler is part of the layout: None keys as id(None), a constant
    return DEVICE_TABLES.get("remap", dicts, build)


# ---------------------------------------------------------------------------
# agg_recombine
# ---------------------------------------------------------------------------


def _filler_like(part: DeviceBatch, names: Sequence[str]) -> DeviceBatch:
    """An all-invalid part of ``part``'s row bucket and column layout, of
    cached zero arrays; its string columns carry no dictionary."""
    rows, cols = part.padded_len, {}
    for name in names:
        c = part.columns[name]
        if isinstance(c, StrCol):
            cols[name] = StrCol(_filler(rows, np.int32), None)
        else:
            cols[name] = NumCol(
                _filler(rows, c.data.dtype), c.kind, unit=c.unit,
                hi=None if c.hi is None else _filler(rows, np.int32))
    return DeviceBatch(cols, _filler(rows, np.bool_), 0)


def _fill(buffer: List[DeviceBatch], names: Sequence[str]) -> List[DeviceBatch]:
    """The buffered parts in order, each run of one row bucket filled up to
    its rung with all-invalid parts.  Order is kept: a stable sort then
    orders valid rows as it orders the compacted concat, so sums come out
    bit-equal to the general path's."""
    out: List[DeviceBatch] = []
    for rows, run in itertools.groupby(buffer, key=lambda p: p.padded_len):
        run = list(run)
        out.extend(run)
        out.extend([_filler_like(run[0], names)]
                   * (_rung(len(run), rows) - len(run)))
    return out


def _uniform(parts: List[DeviceBatch], names: Sequence[str],
             keys: Sequence[str]) -> bool:
    """Can these parts run as one program?  Every column a NumCol or StrCol
    of one dtype and limb layout across parts (a stream may mix int32 and
    two-limb batches: bridge._align_limbs promotes those on the general
    path), aggregate inputs narrow, dictionaries small."""
    first = parts[0]
    for name in names:
        c0 = first.columns.get(name)
        for p in parts:
            c = p.columns.get(name)
            if isinstance(c0, StrCol):
                if not isinstance(c, StrCol) or len(c.dictionary) > SMALL_ROWS:
                    return False
            elif isinstance(c0, NumCol):
                if (not isinstance(c, NumCol) or c.data.dtype != c0.data.dtype
                        or (c.hi is None) != (c0.hi is None)):
                    return False
                if c.hi is not None and name not in keys:
                    return False
            else:
                return False
    return True


def recombine(keys: Sequence[str], ops: Sequence[Tuple[str, str]],
              buffer: List[DeviceBatch],
              state: Optional[DeviceBatch]) -> DeviceBatch:
    """Fold buffered partials (and the running state, last) into one grouped
    batch of ``keys`` + the recombined columns ``ops`` [(name, op)]: the one
    implementation behind PartialAggExecutor's and FinalAggExecutor's
    ``_merge``."""
    parts = buffer + ([state] if state is not None else [])
    names = list(keys) + [p for p, _ in ops]
    small = sum(p.padded_len for p in parts) <= SMALL_ROWS
    if small and _uniform(parts, names, keys):
        note_path(True)
        filled = _fill(buffer, names) + ([state] if state is not None else [])
        return _recombine_compiled(list(keys), list(ops), filled)
    note_path(False)
    parts = [kernels.compact(p) for p in parts]
    merged = bridge.concat_batches(parts) if len(parts) > 1 else parts[0]
    aggs = [(p, op, merged.columns[p].data) for (p, op) in ops]
    return kernels.groupby_aggregate(merged, keys, aggs).select(names)


def _recombine_compiled(keys, ops, parts: List[DeviceBatch]) -> DeviceBatch:
    first = parts[0]
    names = keys + [p for p, _ in ops]
    total = sum(p.padded_len for p in parts)
    data_parts, hi_parts, tables, merged_dicts = [], [], [], {}
    # groups cannot outnumber the rows, nor the combinations of the string
    # keys' (merged) dictionary entries and their null code
    group_bound = 1
    for name in names:
        cols = [p.columns[name] for p in parts]
        if isinstance(cols[0], StrCol):
            merged, remap = _merged_remap([c.dictionary for c in cols])
            merged_dicts[name] = merged
            tables.append((remap, *hash_tables(merged)))
            group_bound *= len(merged) + 1
            data_parts.append(tuple(c.codes for c in cols))
            hi_parts.append(())
            continue
        if name in keys:
            group_bound = total  # a numeric key: no bound short of the rows
        data_parts.append(tuple(c.data for c in cols))
        hi_parts.append(() if cols[0].hi is None
                        else tuple(c.hi for c in cols))
    # output rows: a function of shapes and dictionary sizes, never of a
    # device count
    out_rows = config.bucket_size(min(total, group_bound))
    gb_choice = kstrategy.choice("groupby")
    op_names = tuple(op for _, op in ops)
    sig = sigkey.make_key(
        "agg_recombine",
        tuple((rows, len(list(run))) for rows, run in itertools.groupby(
            p.padded_len for p in parts)),
        tuple(sigkey.col_sig(n, first.columns[n]) for n in names),
        tuple((int(t[0].shape[1]), int(t[1].shape[0])) for t in tables),
        len(keys), op_names, out_rows, gb_choice,
        kernels.SORTED_GROUPBY_FORM,  # the traced group-by body
    )
    kstrategy.note_used("groupby", gb_choice)
    str_keys = tuple(isinstance(first.columns[k], StrCol) for k in keys)
    builder = lambda: _build_recombine(  # noqa: E731 — deferred to a miss
        str_keys, op_names, out_rows)
    key_outs, agg_outs, gvalid, num = _dispatch_program(sig, builder, (
        tuple(data_parts), tuple(hi_parts), tuple(tables),
        tuple(p.valid for p in parts)))
    if keys:
        from quokka_tpu.obs import opstats

        opstats.note(groupby_sort_slots=total, groupby_groups_out=num)
    cols = {}
    for name, (data, hi) in zip(keys, key_outs):
        c0 = first.columns[name]
        if isinstance(c0, StrCol):
            cols[name] = StrCol(data, merged_dicts[name])
        else:
            cols[name] = NumCol(data, c0.kind, hi=hi, unit=c0.unit)
    for (pname, _), arr in zip(ops, agg_outs):
        cols[pname] = NumCol(arr, _infer_kind(arr))
    return DeviceBatch(cols, gvalid, None, None).note_count(num)


def _build_recombine(str_keys: Tuple[bool, ...], ops: Tuple[str, ...],
                     out_rows: int):
    n_keys = len(str_keys)

    @jax.jit
    def agg_recombine(data_parts, hi_parts, tables, valids):
        valid = jnp.concatenate(valids)
        n = valid.shape[0]
        tables_it = iter(tables)
        limbs, key_arrays = [], []
        for is_str, data, hi in zip(str_keys, data_parts, hi_parts):
            if is_str:
                remap, hh, hl = next(tables_it)
                codes = jnp.concatenate(
                    [map_codes(c, remap[i]) for i, c in enumerate(data)])
                limbs.extend(code_hash_limbs(codes, hh, hl))
                key_arrays.append((codes, None))
            else:
                d = jnp.concatenate(data)
                h = jnp.concatenate(hi) if hi else None
                if h is not None:
                    limbs.append(h)
                limbs.append(d)
                key_arrays.append((d, h))
        arrays = tuple(jnp.concatenate(d) for d in data_parts[n_keys:])
        if n_keys:
            outs, _counts, rep, num = kernels.groupby_limbs(
                tuple(limbs), arrays, ops, valid)
        else:
            ranks = jnp.zeros(n, dtype=jnp.int32)
            num = jnp.minimum(jnp.sum(valid), 1).astype(jnp.int32)
            outs, _counts, rep = kernels._segment_aggs(ranks, valid, arrays, ops)
        key_outs = tuple(
            (_pad_tail(d[rep], out_rows),
             None if h is None else _pad_tail(h[rep], out_rows))
            for d, h in key_arrays)
        gvalid = jnp.arange(out_rows, dtype=jnp.int32) < num
        return (key_outs, tuple(_pad_tail(o, out_rows) for o in outs),
                gvalid, num)

    return agg_recombine


# ---------------------------------------------------------------------------
# agg_final_tail
# ---------------------------------------------------------------------------

# signature -> [(output name, kind, unit)] of the program's numeric outputs,
# or None where the plan's finals cannot run under a trace.  Kinds are host
# metadata a persisted executable does not carry: one abstract evaluation
# (jax.eval_shape: a trace, no compile) per signature and process finds them.
_TAIL_META: Dict[Tuple, Optional[List[Tuple]]] = {}


def final_tail(g: DeviceBatch, keys: Sequence[str], plan, having,
               order_by: Optional[List[Tuple[str, bool]]],
               limit: Optional[int]) -> Optional[DeviceBatch]:
    """FinalAggExecutor.done's tail over the folded state ``g`` as one
    program: ``plan.finals``, the HAVING mask, the projection to keys +
    finals, ORDER BY and LIMIT.  None where it does not apply (a large state;
    an expression that needs dictionary VALUES, which live on the host; finals
    that cannot compile): the caller runs its general path."""
    if g.padded_len > SMALL_ROWS:
        return None
    out_names = list(dict.fromkeys(list(keys) + [n for n, _ in plan.finals]))
    final_names = {n for n, _ in plan.finals}
    num_names = [n for n, c in g.columns.items() if isinstance(c, NumCol)]
    str_names = [n for n in out_names if n not in final_names
                 and isinstance(g.columns.get(n), StrCol)]
    strings = {n for n, c in g.columns.items() if not isinstance(c, NumCol)}
    exprs = [e for _, e in plan.finals] + ([] if having is None else [having])
    by = [n for n, _ in order_by or ()]
    known = final_names | set(num_names) | set(str_names)
    if (any(e.required_columns() & strings for e in exprs)
            or any(n not in known for n in out_names)
            or any(n not in out_names for n in by)):
        return None
    ranked = [n for n in by if n in str_names]
    ranks = tuple(rank_table(g.columns[n].dictionary) for n in ranked)
    desc = tuple(bool(d) for _, d in order_by or ())
    out_rows = g.padded_len if limit is None else min(
        g.padded_len, config.bucket_size(limit))
    num_meta = tuple(
        (n, str(g.columns[n].data.dtype), g.columns[n].hi is not None,
         g.columns[n].kind, g.columns[n].unit) for n in num_names)
    sig = sigkey.make_key(
        "agg_final_tail", g.padded_len, num_meta, tuple(str_names),
        tuple((n, e.sql()) for n, e in plan.finals),
        None if having is None else having.sql(),
        tuple(zip(by, desc)), limit, tuple(int(r.shape[0]) for r in ranks),
    )
    args = (
        tuple(g.columns[n].data for n in num_names),
        tuple(_filler(0, np.int32) if g.columns[n].hi is None
              else g.columns[n].hi for n in num_names),
        tuple(g.columns[n].codes for n in str_names),
        ranks,
        g.valid,
    )
    meta_out: List[Tuple] = []
    body = _tail_body(num_meta, str_names, plan.finals, having, out_names,
                      by, desc, ranked, limit, out_rows, meta_out)
    if sig not in _TAIL_META:
        try:
            jax.eval_shape(body, *args)
            _TAIL_META[sig] = list(meta_out)
        except (expr_compile.CompileError, jax.errors.JAXTypeError):
            _TAIL_META[sig] = None
    meta = _TAIL_META[sig]
    if meta is None:
        return None
    outs, valid, num = _dispatch_program(sig, lambda: jax.jit(body), args)
    cols, outs = {}, iter(outs)
    kinds = {n: (kind, unit) for n, kind, unit in meta}
    for name in out_names:
        data, hi = next(outs)
        if name in kinds:
            kind, unit = kinds[name]
            cols[name] = NumCol(data, kind, hi=hi, unit=unit)
        else:
            cols[name] = StrCol(data, g.columns[name].dictionary)
    sorted_by = by if by else g.sorted_by
    return DeviceBatch(cols, valid, None, sorted_by).note_count(num)


def _tail_body(num_meta, str_names, finals, having, out_names, by, desc,
               ranked, limit, out_rows, meta_out):
    def agg_final_tail(num_arrays, hi_arrays, str_codes, ranks, valid):
        n = valid.shape[0]
        cols = {}
        for (name, _dt, _wide, kind, unit), arr, hi in zip(
                num_meta, num_arrays, hi_arrays):
            cols[name] = NumCol(arr, kind, hi=hi if hi.shape[0] else None,
                                unit=unit)
        shim = _ShimBatch(cols, n, valid)
        for name, e in finals:
            col = expr_compile.evaluate_to_column(e, shim)
            if not isinstance(col, NumCol):
                raise expr_compile.CompileError(
                    "string-valued final in the compiled tail")
            cols[name] = col
        if having is not None:
            # HAVING runs before the projection: it may reference partial
            # columns (aggregates rewritten by plan.rewrite) the output drops
            valid = valid & expr_compile.evaluate_predicate(having, shim)
        for name, codes in zip(str_names, str_codes):
            cols[name] = StrCol(codes, None)
        del meta_out[:]
        meta_out.extend((name, cols[name].kind, cols[name].unit)
                        for name in out_names if name not in str_names)
        out = [(cols[n].codes, None) if n in str_names
               else (cols[n].data, cols[n].hi) for n in out_names]
        num = jnp.sum(valid.astype(jnp.int32))
        if by or limit is not None:
            # one stable sort: ORDER BY's limbs, or none (LIMIT alone takes
            # the first valid rows in their order); valid rows come first
            limbs = kernels.sort_limbs(
                _ShimBatch(cols, n, valid), by, list(desc),
                ranks=dict(zip(ranked, ranks)))
            perm = kernels._sort_perm(tuple(limbs), valid)
            if limit is not None:
                num = jnp.minimum(num, limit)
            perm = perm[:out_rows]
            out = [(d[perm], None if h is None else h[perm]) for d, h in out]
            valid = jnp.arange(out_rows, dtype=jnp.int32) < num
        return tuple(out), valid, num

    return agg_final_tail
