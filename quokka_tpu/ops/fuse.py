"""Whole-pipeline fusion: run a batch's expression+aggregate work as ONE
jitted XLA program.

Why: per-op jit dispatch costs dominate on TPU (each call is a host->device
round trip; over a remote runtime each is milliseconds).  XLA wants one big
program it can fuse (SURVEY.md build plan: "let XLA fuse — don't hand-schedule").

Two-phase design:
- HOST PREPASS (per batch): anything that depends on string dictionary VALUES
  (LIKE/contains/equality masks, in-lists, string transforms) is evaluated
  once over the (small) dictionary and gathered by code into a device array,
  which becomes an extra input column.  The expression tree is rewritten to
  reference these bound columns.  Key string columns contribute their hash
  limb arrays the same way.
- TRACED PHASE: the rewritten, now purely-numeric expression graph plus the
  sort/segment group-by runs inside a single jit, cached per
  (padded_len, column signature, plan id).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from quokka_tpu.expression import (
    Agg,
    Alias,
    BinOp,
    Case,
    Cast,
    ColRef,
    DateLit,
    DtField,
    Expr,
    Func,
    InList,
    IntervalLit,
    IsNull,
    Literal,
    StrOp,
    UnaryOp,
    _rebuild,
)
import numpy as np

from quokka_tpu import config
from quokka_tpu.obs import opstats
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import expr_compile, kernels, sigkey
from quokka_tpu.ops import strategy as kstrategy
from quokka_tpu.ops.batch import DeviceBatch, NumCol, StrCol, gather_columns
from quokka_tpu.runtime import compileplane


def _is_string_dependent(e: Expr, batch: DeviceBatch) -> bool:
    """Does evaluating e require dictionary VALUES (host data)?"""
    if isinstance(e, (StrOp,)):
        return True
    if _is_string_cast(e):
        return True
    if isinstance(e, UnaryOp) and e.op == "not":
        # bind the whole NOT subtree, not just its string child: evaluate()'s
        # 3VL null guard lives inside the NOT handling, and `not __bound`
        # would re-invert null rows back to True
        return _is_string_dependent(e.operand, batch)
    if isinstance(e, InList):
        return _refs_string(e.expr, batch)
    if isinstance(e, IsNull):
        return _refs_string(e.expr, batch)
    if isinstance(e, BinOp) and e.op in ("=", "!=", "<", "<=", ">", ">="):
        if _refs_string(e.left, batch) or _refs_string(e.right, batch):
            return True
    return False


def _is_string_cast(e: Expr) -> bool:
    """cast(x as varchar) builds a dictionary on the HOST — it can never run
    inside a traced (fused) program, even over numeric inputs."""
    return isinstance(e, Cast) and e.to.startswith(("varchar", "string", "text"))


def _refs_string(e: Expr, batch: DeviceBatch) -> bool:
    if isinstance(e, ColRef):
        return isinstance(batch.columns.get(e.name), StrCol)
    if isinstance(e, Literal):
        return isinstance(e.value, str)
    if _is_string_cast(e):
        return True
    return any(_refs_string(c, batch) for c in e.children())


class Prepass:
    """Rewrites expressions against a concrete batch: string-dependent
    subtrees are evaluated NOW (host dict work + one gather) and replaced by
    references to bound device columns."""

    def __init__(self, batch: DeviceBatch):
        self.batch = batch
        self.bound: Dict[str, jnp.ndarray] = {}
        self._memo: Dict[str, str] = {}

    def rewrite(self, e: Expr) -> Expr:
        if isinstance(e, Alias):
            return Alias(self.rewrite(e.expr), e.name)
        if _is_string_dependent(e, self.batch):
            return ColRef(self._bind(e))
        kids = e.children()
        if not kids:
            return e
        return _rebuild(e, [self.rewrite(k) for k in kids])

    def _bind(self, e: Expr) -> str:
        key = e.sql()
        if key in self._memo:
            return self._memo[key]
        col = expr_compile.evaluate_to_column(e, self.batch)
        if isinstance(col, StrCol):
            # string-valued transform: bind its hash limbs? not needed for
            # numeric pipelines; fall back to codes (equality-safe only within
            # this batch) — callers needing more go through the unfused path
            raise expr_compile.CompileError("string-valued expr in fused pipeline")
        name = f"__b{len(self.bound)}"
        self.bound[name] = col.data
        self._memo[key] = name
        return name


class _ShimBatch:
    """Duck-typed DeviceBatch over traced arrays for expr_compile.evaluate."""

    def __init__(self, columns: Dict[str, object], padded_len: int, valid):
        self.columns = columns
        self._padded = padded_len
        self.valid = valid

    @property
    def padded_len(self):
        return self._padded

    @property
    def names(self):
        return list(self.columns.keys())


# Fused programs are cached GLOBALLY by full structural signature so separate
# executor instances (and separate queries) reuse the same jitted callable —
# jax's trace cache is keyed by function identity, so per-instance closures
# would recompile on every query.  The dict is the compile plane's program
# store: signatures derive through ops/sigkey (canonical ladder, normalized
# column signatures) and misses resolve through compileplane.acquire, which
# loads a persisted executable when one exists and AOT-compiles otherwise.
_FUSED_PROGRAMS: Dict[Tuple, object] = compileplane.PROGRAMS


def _dispatch_program(sig, builder, args):
    """Hot-path program dispatch: one dict get per batch; misses go through
    the compile plane (persisted-executable load, else explicit AOT
    compile + background persist).  A pre-warmed executable whose shapes
    drift from this call rebuilds in place instead of erroring."""
    fn = _FUSED_PROGRAMS.get(sig)
    if fn is None:
        fn = compileplane.acquire(sig, builder, args)
    else:
        # record the use under the current plan even on a warm hit (a new
        # plan reusing another's programs must still prewarm them all)
        compileplane.note_program(sig)
    try:
        return fn(*args)
    except compileplane.AotMismatch:
        fn = builder()
        _FUSED_PROGRAMS[sig] = fn
        return fn(*args)


# Small-key group-by: the one-hot operand the MXU matmul contracts over is
# materialized n x (B+1); bound its footprint so a big batch can't blow HBM.
_SMALL_GROUPBY_MAX_BUCKETS = 256
_SMALL_GROUPBY_MAX_BYTES = 512 << 20


class FusedPartialAgg:
    """One-jit partial group-by-aggregate, compiled per batch signature.

    Two strategies inside the jit:
    - SMALL-KEY FAST PATH: when every group key is a dictionary-encoded string
      and the product of dictionary sizes is tiny (TPC-H Q1's
      returnflag x linestatus = a dozen groups), the group id is computed
      directly from the codes and every partial reduces over ONE one-hot
      mask of the rows' buckets: float sums and count(*) via one matmul on
      the MXU, integer sums via an exact masked reduction in the column's
      own dtype (a float32 matmul is exact only to 2^24).  Integer partials
      are common, not rare: every avg(x) and count(x) plans a
      sum(__nncount(x)) of 0/1 flags, and sum() of an integer column is one
      too (Q1 has three).  No sort and no scatter, and the output batch is
      a 256-row bucket instead of the input's padded length (so everything
      downstream — shuffle, concat, recombine — shrinks by ~4000x).
    - GENERAL PATH: ``kernels.sorted_groupby``: sort, scans, sort.  One stable
      multi-operand lax.sort on the key limbs carries the aggregates'
      inputs, the contiguous segments reduce by prefix sums and segmented
      scans, a second sort on one key compacts the groups to rank order.
      On a v5e a 1<<20-slot batch with three sums is 4.99 ms, the two sorts
      2.34 and 1.92 of it (PERF.md section 5, ``h2o_g1_1e7.q5_s2``, PR 33);
      as segment reductions (scatters at 8.8-9.2 ms per 1<<20 updates,
      sorted ids or not) and gathers by the permutation (7.3-8.8 ms each)
      it was 61.4.  Its program is
      ``fused_groupby`` (module ``jit_fused_groupby``), apart from the
      small-key path's and the predicate's ``fused``."""

    def __init__(self, keys: List[str], plan):
        self.keys = keys
        self.plan = plan

    def _small_dims(self, batch: DeviceBatch, use_tables: bool):
        """Per-key bucket counts (dict size + a null slot) when the small-key
        path applies, else None.  Dims are CANONICALIZED to the next power
        of two: raw dictionary sizes vary per file/batch, and keying the
        fused program on the exact size would recompile the whole small-key
        program every time a scan chunk's dictionary grows by one entry —
        the bucket ladder discipline, applied to the signature space."""
        if not self.keys:
            return None
        if not all(isinstance(batch.columns[k], StrCol) for k in self.keys):
            return None
        if not all(op in ("sum", "count") for _, op, _ in self.plan.partials):
            return None
        dims = tuple(
            _pow2(len(batch.columns[k].dictionary.values) + 1)
            for k in self.keys
        )
        n_buckets = int(np.prod(dims)) + 1  # + the invalid-row dump bucket
        itemsize = 8 if config.x64_enabled() else 4
        if n_buckets > _SMALL_GROUPBY_MAX_BUCKETS:
            return None
        if not use_tables:
            # matmul-strategy gates only: the scatter strategy materializes
            # no n x B one-hot and accumulates exactly
            if batch.padded_len * n_buckets * itemsize > _SMALL_GROUPBY_MAX_BYTES:
                return None
            # float32 matmul accumulation is exact only up to 2^24: beyond
            # that, counts (and integer-valued sums) can silently lose units
            if not config.x64_enabled() and batch.padded_len > (1 << 24):
                return None
        return dims

    def __call__(self, batch: DeviceBatch) -> DeviceBatch:
        pre = Prepass(batch)
        pre_exprs = [(name, pre.rewrite(e)) for name, e in self.plan.pre]
        # inputs: numeric columns referenced + bound columns + key limbs
        needed = set()
        for _, e in pre_exprs:
            needed |= e.required_columns()
        num_inputs = {}
        for n in sorted(needed):
            c = batch.columns.get(n)
            if c is None:
                continue  # bound column
            assert isinstance(c, NumCol), n
            num_inputs[n] = c
        # the group-by strategy is resolved ONCE per dispatch and baked
        # into the program signature (ops/strategy.py); a warm program's
        # choice is recorded as having run without re-tracing
        gb_choice = kstrategy.choice("groupby")
        use_tables = gb_choice == "hashtable"
        dims = self._small_dims(batch, use_tables)
        if dims is not None:
            kstrategy.note_used("groupby", gb_choice)
            return self._call_small(batch, pre, pre_exprs, num_inputs, dims,
                                    use_tables)
        key_limbs: List[jnp.ndarray] = []
        for k in self.keys:
            c = batch.columns[k]
            if isinstance(c, StrCol):
                # within one batch, dictionary codes ARE the key identity:
                # one limb instead of two hash limbs (cross-batch identity is
                # restored at recombine time via hash limbs on the small
                # partial batches)
                key_limbs.append(c.codes)
            else:
                if c.hi is not None:
                    key_limbs.append(c.hi)
                key_limbs.append(c.data)
        sig = sigkey.make_key(
            "partial_agg",
            sigkey.batch_sig(batch, list(num_inputs)),
            tuple(sorted(pre.bound)),
            tuple(str(l.dtype) for l in key_limbs),
            tuple((n, e.sql()) for n, e in pre_exprs),
            tuple((p, op, tmp) for p, op, tmp in self.plan.partials),
            bool(self.keys),
            use_tables,  # strategy is baked into the program
            # names the program's function: keys carry no version of the
            # code, so a persisted executable under the key without this
            # part goes on showing a trace the module name ``jit_fused``
            "fused_groupby",
            kernels.SORTED_GROUPBY_FORM,  # the traced group-by body
        )
        kstrategy.note_used("groupby", gb_choice)
        builder = lambda: self._build(  # noqa: E731 — deferred to cache miss
            pre_exprs, list(num_inputs), sorted(pre.bound), len(key_limbs))
        with tracing.span("groupby.partial"):
            out = self._invoke(
                sig, builder, batch, pre, num_inputs, tuple(key_limbs),
                batch.padded_len,
            )
        if self.keys:  # a global aggregate sorts nothing
            opstats.note(groupby_sort_slots=batch.padded_len,
                         groupby_groups_out=out.nrows_dev)
        return out

    def _invoke(self, sig, builder, batch, pre, num_inputs, key_arrays,
                out_pad):
        """Shared dispatch tail: run the fused program and assemble the
        partial-aggregate output batch (used by both strategies)."""
        hi_arrays = tuple(
            c.hi if c.hi is not None else jnp.zeros(0, jnp.int32)
            for c in num_inputs.values()
        )
        outs = _dispatch_program(sig, builder, (
            tuple(c.data for c in num_inputs.values()),
            hi_arrays,
            tuple(pre.bound[k] for k in sorted(pre.bound)),
            key_arrays,
            batch.valid,
        ))
        *agg_arrays, rep, num = outs
        cols = gather_columns({k: batch.columns[k] for k in self.keys}, rep)
        for (pname, _, _), arr in zip(self.plan.partials, agg_arrays):
            cols[pname] = NumCol(
                arr, "f" if jnp.issubdtype(arr.dtype, jnp.floating) else "i"
            )
        gvalid = jnp.arange(out_pad) < num
        return DeviceBatch(cols, gvalid, None, None).note_count(num)

    def _build(self, pre_exprs, num_names, bound_names, n_limbs):
        plan = self.plan
        has_keys = bool(self.keys)

        @jax.jit
        def fused_groupby(num_arrays, hi_arrays, bound_arrays, limbs, valid):
            n = valid.shape[0]
            cols = {}
            for name, arr, hi in zip(num_names, num_arrays, hi_arrays):
                cols[name] = NumCol(arr, _infer_kind(arr), hi=hi if hi.shape[0] else None)
            for name, arr in zip(bound_names, bound_arrays):
                cols[name] = NumCol(arr, _infer_kind(arr))
            shim = _ShimBatch(cols, n, valid)
            pre_cols = {}
            for name, e in pre_exprs:
                pre_cols[name] = expr_compile.evaluate_to_column(e, shim)
            arrays = tuple(
                pre_cols[tmp].data if tmp is not None else jnp.zeros(n, jnp.int32)
                for (_, _, tmp) in plan.partials
            )
            ops = tuple(op for (_, op, _) in plan.partials)
            if has_keys:
                outs, counts, rep, num = kernels.groupby_limbs(
                    tuple(limbs), arrays, ops, valid
                )
            else:
                ranks = jnp.zeros(n, dtype=jnp.int32)
                num = jnp.minimum(jnp.sum(valid), 1).astype(jnp.int32)
                outs, counts, rep = kernels._segment_aggs(ranks, valid, arrays, ops)
            return (*outs, rep, num)

        return fused_groupby

    def _call_small(self, batch, pre, pre_exprs, num_inputs, dims,
                    use_tables: bool):
        codes = tuple(batch.columns[k].codes for k in self.keys)
        out_pad = config.bucket_size(int(np.prod(dims)))
        sig = sigkey.make_key(
            "partial_agg_small",
            sigkey.batch_sig(batch, list(num_inputs)),
            tuple(sorted(pre.bound)),
            dims,
            tuple((n, e.sql()) for n, e in pre_exprs),
            tuple((p, op, tmp) for p, op, tmp in self.plan.partials),
            use_tables,  # strategy is baked into the program
            # names the body's reduction form: program keys carry no
            # version of the code, so an edit of _build_small's body changes
            # this part, else a persisted executable (<cache>/aot) under the
            # old key goes on running the old body
            "onehot_reduce",
        )
        builder = lambda: self._build_small(  # noqa: E731 — on cache miss
            pre_exprs, list(num_inputs), sorted(pre.bound), dims, out_pad,
            use_tables)
        return self._invoke(sig, builder, batch, pre, num_inputs, codes,
                            out_pad)

    def _build_small(self, pre_exprs, num_names, bound_names, dims, out_pad,
                     use_tables: bool):
        plan = self.plan
        n_groups = int(np.prod(dims))
        strides = []
        s = 1
        for d in reversed(dims):
            strides.append(s)
            s *= d
        strides = tuple(reversed(strides))
        if use_tables:
            # CPU/GPU: scatter segment-sums by bucket id — no n x B one-hot,
            # exact accumulation, and none of the matmul memory gates.  TPU
            # keeps the one-hot matmul (the MXU reduces all agg columns in
            # one pass; random scatters serialize there).
            return self._build_small_scatter(
                pre_exprs, num_names, bound_names, strides, n_groups, out_pad
            )

        @jax.jit
        def fused(num_arrays, hi_arrays, bound_arrays, codes, valid):
            n = valid.shape[0]
            cols = {}
            for name, arr, hi in zip(num_names, num_arrays, hi_arrays):
                cols[name] = NumCol(
                    arr, _infer_kind(arr), hi=hi if hi.shape[0] else None
                )
            for name, arr in zip(bound_names, bound_arrays):
                cols[name] = NumCol(arr, _infer_kind(arr))
            shim = _ShimBatch(cols, n, valid)
            pre_cols = {}
            for name, e in pre_exprs:
                pre_cols[name] = expr_compile.evaluate_to_column(e, shim)
            gid = jnp.zeros(n, dtype=jnp.int32)
            for c, st in zip(codes, strides):
                # code -1 = null -> slot 0 of that key (SQL: nulls form one group)
                gid = gid + (c.astype(jnp.int32) + 1) * jnp.int32(st)
            gid = jnp.where(valid, gid, jnp.int32(n_groups))  # dump bucket
            fdt = config.float_dtype()
            onehot = gid[:, None] == jnp.arange(n_groups + 1, dtype=jnp.int32)[None, :]
            mat_cols = []  # columns reduced by the one matmul
            int_results = {}  # partial idx -> bucket array (integer sums)
            for j, (pname, op, tmp) in enumerate(plan.partials):
                if op == "count":
                    mat_cols.append((j, valid.astype(fdt)))
                    continue
                v = pre_cols[tmp].data
                if jnp.issubdtype(v.dtype, jnp.floating):
                    # invalid (padded) rows may hold NaN garbage, which would
                    # poison the whole bucket column through NaN * 0
                    mat_cols.append(
                        (j, jnp.where(valid, v, jnp.zeros((), v.dtype)))
                    )
                else:
                    # integer sums stay exact in v's own dtype: a masked
                    # reduction over the one-hot (it fuses into one pass
                    # over the rows; a segment_sum lowers to a scatter-add
                    # that the TPU runs one update at a time).  Invalid
                    # rows sit in the dump bucket, dropped with the slice.
                    zero = jnp.zeros((), v.dtype)
                    int_results[j] = jnp.sum(
                        jnp.where(onehot[:, :n_groups], v[:, None], zero),
                        axis=0, dtype=v.dtype,
                    )
            sums = None
            if mat_cols:
                stacked = jnp.stack([c for _, c in mat_cols], axis=1)
                # HIGHEST: the TPU MXU's default f32 matmul truncates operands
                # to bf16 (~8 mantissa bits) — sums must keep f32 precision to
                # match the segment-reduce path
                sums = jnp.matmul(
                    onehot.astype(fdt).T, stacked,
                    precision=jax.lax.Precision.HIGHEST,
                )[:n_groups]
            iota = jnp.arange(n, dtype=jnp.int32)
            rep_b = jnp.min(
                jnp.where(onehot[:, :n_groups], iota[:, None], jnp.int32(n)),
                axis=0,
            )
            live = rep_b < n
            num = jnp.sum(live.astype(jnp.int32))
            bidx = jnp.arange(n_groups, dtype=jnp.int32)
            order = jnp.argsort(jnp.where(live, bidx, jnp.int32(n_groups) + bidx))
            outs = []
            k = 0
            for j, (pname, op, tmp) in enumerate(plan.partials):
                if j in int_results:
                    arr = int_results[j]
                else:
                    arr = sums[:, k]
                    k += 1
                    if op == "count":
                        # counts <= n <= 2**24 are exact in float32
                        arr = arr.astype(jnp.int32)
                arr = arr[order]
                outs.append(_pad_tail(arr, out_pad))
            rep_d = jnp.minimum(rep_b[order], jnp.int32(n - 1))
            return (*outs, _pad_tail(rep_d, out_pad), num)

        return fused

    def _build_small_scatter(self, pre_exprs, num_names, bound_names,
                             strides, n_groups, out_pad):
        """Scatter strategy of the small-key fast path: identical contract
        and bucket-id scheme as the matmul strategy, but every aggregate is
        one segment reduce over (n_groups + 1) buckets."""
        plan = self.plan

        @jax.jit
        def fused(num_arrays, hi_arrays, bound_arrays, codes, valid):
            n = valid.shape[0]
            cols = {}
            for name, arr, hi in zip(num_names, num_arrays, hi_arrays):
                cols[name] = NumCol(
                    arr, _infer_kind(arr), hi=hi if hi.shape[0] else None
                )
            for name, arr in zip(bound_names, bound_arrays):
                cols[name] = NumCol(arr, _infer_kind(arr))
            shim = _ShimBatch(cols, n, valid)
            pre_cols = {}
            for name, e in pre_exprs:
                pre_cols[name] = expr_compile.evaluate_to_column(e, shim)
            gid = jnp.zeros(n, dtype=jnp.int32)
            for c, st in zip(codes, strides):
                # code -1 = null -> slot 0 of that key (SQL: nulls form one group)
                gid = gid + (c.astype(jnp.int32) + 1) * jnp.int32(st)
            gid = jnp.where(valid, gid, jnp.int32(n_groups))  # dump bucket
            iota = jnp.arange(n, dtype=jnp.int32)
            rep_b = jax.ops.segment_min(
                jnp.where(valid, iota, jnp.int32(n)), gid,
                num_segments=n_groups + 1,
            )[:n_groups]
            live = rep_b < n
            num = jnp.sum(live.astype(jnp.int32))
            bidx = jnp.arange(n_groups, dtype=jnp.int32)
            order = jnp.argsort(jnp.where(live, bidx, jnp.int32(n_groups) + bidx))
            outs = []
            for pname, op, tmp in plan.partials:
                if op == "count":
                    x = valid.astype(jnp.int32)
                else:
                    v = pre_cols[tmp].data
                    x = jnp.where(valid, v, jnp.zeros((), v.dtype))
                arr = jax.ops.segment_sum(x, gid, num_segments=n_groups + 1)
                outs.append(_pad_tail(arr[:n_groups][order], out_pad))
            rep_d = jnp.minimum(rep_b[order], jnp.int32(n - 1))
            return (*outs, _pad_tail(rep_d, out_pad), num)

        return fused


def _pow2(n: int) -> int:
    return sigkey.pow2_dim(n)


def _pad_tail(arr, padded):
    from quokka_tpu.ops.bridge import _pad_device

    return _pad_device(arr, padded)


def _infer_kind(arr):
    if arr.dtype == jnp.bool_:
        return "b"
    if jnp.issubdtype(arr.dtype, jnp.floating):
        return "f"
    return "i"


class FusedPredicate:
    """One-jit filter mask evaluation (plus prepass-bound string masks)."""

    def __init__(self, expr: Expr):
        self.expr = expr

    def __call__(self, batch: DeviceBatch) -> DeviceBatch:
        pre = Prepass(batch)
        try:
            e = pre.rewrite(self.expr)
        except expr_compile.CompileError:
            mask = expr_compile.evaluate_predicate(self.expr, batch)
            return kernels.apply_mask(batch, mask)
        needed = sorted(
            n for n in e.required_columns() if n in batch.columns
        )
        num_inputs = {}
        ok = True
        for n in needed:
            c = batch.columns[n]
            if not isinstance(c, NumCol) or c.hi is not None:
                ok = False
                break
            num_inputs[n] = c
        if not ok:
            mask = expr_compile.evaluate_predicate(self.expr, batch)
            return kernels.apply_mask(batch, mask)
        sig = sigkey.make_key(
            "predicate",
            sigkey.batch_sig(batch, list(num_inputs)),
            tuple(sorted(pre.bound)),
            e.sql(),
        )

        def builder():
            names, bnames = list(num_inputs), sorted(pre.bound)

            @jax.jit
            def fused(arrays, barrays, valid):
                cols = {}
                for name, arr in zip(names, arrays):
                    cols[name] = NumCol(arr, _infer_kind(arr))
                for name, arr in zip(bnames, barrays):
                    cols[name] = NumCol(arr, _infer_kind(arr))
                shim = _ShimBatch(cols, valid.shape[0], valid)
                m = valid & expr_compile.evaluate_predicate(e, shim)
                return m, jnp.sum(m.astype(jnp.int32))

            return fused

        mask, num = _dispatch_program(sig, builder, (
            tuple(num_inputs[n].data for n in num_inputs),
            tuple(pre.bound[k] for k in sorted(pre.bound)),
            batch.valid,
        ))
        return DeviceBatch(batch.columns, mask, None, batch.sorted_by).note_count(num)
