"""Whole-stage fusion: run a maximal linear operator chain as ONE exec actor.

Why: per-operator dispatch tax dominates Q3/Q5 — every filter→project→probe→
partial-agg hop used to round-trip through a separate task dispatch, a store
push, and a re-densify on the consumer side.  The optimizer's ``fuse_stages``
pass (optimizer.py) rewrites single-consumer, same-placement, non-blocking
chains into one ``FusedStageNode`` which lowers to ONE actor running a
``FusedStageExecutor``: a producer's output feeds the next operator in-process
with zero intermediate batch materialization, zero extra bridge/densify, and
zero added host syncs (Flare's whole-stage compilation, TQP's tensor-runtime
lowering — ROADMAP item 1).

Two layers:

- ``FusedElementwise``: consecutive filter/project/expression-map members
  collapse into ONE jitted program through the existing ops/fuse.py prepass +
  compile-plane machinery (sigkey-canonicalized signature, AOT-persisted,
  pre-warmable).  The output keeps the input's columns with a lazily-applied
  combined mask (the FusedPredicate discipline) — no densify between members.
- ``FusedStageExecutor``: the actor-level chain container.  Stream 0 cascades
  through the member executors; build streams (join builds) route to their
  owning member.  Lineage, checkpoint, and tape boundaries sit at STAGE
  granularity: the stage checkpoints as one unit (a list of member snapshots)
  and the engine's tape records stage-level inputs/outputs, so chaos/recovery
  replay stays bit-exact.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from quokka_tpu.expression import Expr, substitute_columns
from quokka_tpu.ops import expr_compile, kernels, sigkey
from quokka_tpu.ops.batch import DeviceBatch, NumCol
from quokka_tpu.ops.fuse import (
    Prepass,
    _dispatch_program,
    _infer_kind,
    _refs_string,
    _ShimBatch,
)
from quokka_tpu.executors.base import Executor


class FusedElementwise:
    """Picklable fused filter/project/map pipeline: ONE jit program per batch
    signature computes the combined row mask plus every derived column.

    ``steps`` is the chain segment in execution order:
      ("filter", Expr) | ("project", [cols]) | ("map", [(name, Expr), ...])
    Map/filter expressions are inlined at plan time (later steps substitute
    earlier map definitions), so the program evaluates everything against the
    ORIGINAL input columns — filters and maps commute freely because masks
    only ever narrow ``valid`` and expressions are evaluated over all lanes
    anyway (the engine-wide padded-lane discipline)."""

    def __init__(self, steps: Sequence[Tuple]):
        self.steps = [tuple(s) for s in steps]
        env: Dict[str, Expr] = {}
        conjuncts: List[Expr] = []
        visible: Optional[List[str]] = None  # None -> passthrough-all
        for kind, payload in self.steps:
            if kind == "filter":
                conjuncts.append(substitute_columns(payload, env))
            elif kind == "map":
                for name, e in payload:
                    env[name] = substitute_columns(e, env)
                if visible is not None:
                    visible += [n for n, _ in payload if n not in visible]
            elif kind == "project":
                visible = list(payload)
            else:  # pragma: no cover - plan construction bug
                raise ValueError(f"unknown stagefuse step {kind!r}")
        self._env = env
        self._conjuncts = conjuncts
        self._visible = visible
        # computed outputs the program must produce (projection may drop some)
        names = visible if visible is not None else list(env)
        self._outputs = [(n, env[n]) for n in names if n in env]

    def sql(self) -> str:
        """Stable structural text (compile-plane fingerprints stop recursing
        at sql(); without this, deep factory nesting would hit _describe's
        depth cutoff and stop discriminating between elementwise pipelines)."""
        parts = []
        for kind, payload in self.steps:
            if kind == "filter":
                parts.append(f"filter:{payload.sql()}")
            elif kind == "map":
                parts.append(
                    "map:" + ",".join(f"{n}={e.sql()}" for n, e in payload))
            else:
                parts.append("project:" + ",".join(payload))
        return "elemwise[" + ";".join(parts) + "]"

    # -- sequential fallback (string-valued exprs, wide-int inputs) ----------
    def _sequential(self, batch: DeviceBatch) -> DeviceBatch:
        b = batch
        for kind, payload in self.steps:
            if kind == "filter":
                mask = expr_compile.evaluate_predicate(payload, b)
                b = kernels.apply_mask(b, mask)
            elif kind == "map":
                for name, e in payload:
                    b = b.with_column(name, expr_compile.evaluate_to_column(e, b))
            else:
                b = b.select([c for c in payload if c in b.columns])
        return b

    def __call__(self, batch: DeviceBatch) -> DeviceBatch:
        pre = Prepass(batch)
        try:
            conjuncts = [pre.rewrite(e) for e in self._conjuncts]
            outputs = [(n, pre.rewrite(e)) for n, e in self._outputs]
        except expr_compile.CompileError:
            return self._sequential(batch)
        if any(_refs_string(e, batch) for e in conjuncts) or any(
                _refs_string(e, batch) for _, e in outputs):
            # string material survived the rewrite (e.g. CASE with string
            # branches): evaluating it builds a host dictionary, which can
            # never happen inside a trace — run the per-step path
            return self._sequential(batch)
        needed = set()
        for e in conjuncts:
            needed |= e.required_columns()
        for _, e in outputs:
            needed |= e.required_columns()
        num_inputs: Dict[str, NumCol] = {}
        for n in sorted(needed):
            c = batch.columns.get(n)
            if c is None:
                continue  # prepass-bound column
            if not isinstance(c, NumCol) or c.hi is not None:
                # wide-int / string inputs: the per-step executors handle
                # them; identical values either way (masks are exact)
                return self._sequential(batch)
            num_inputs[n] = c
        sig = sigkey.make_key(
            "stage_elemwise",
            sigkey.batch_sig(batch, list(num_inputs)),
            tuple(sorted(pre.bound)),
            tuple(e.sql() for e in conjuncts),
            tuple((n, e.sql()) for n, e in outputs),
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
                m = valid
                for e in conjuncts:
                    m = m & expr_compile.evaluate_predicate(e, shim)
                outs = []
                for _, e in outputs:
                    c = expr_compile.evaluate_to_column(e, shim)
                    outs.append((c.data,
                                 c.hi if c.hi is not None
                                 else jnp.zeros(0, jnp.int32)))
                return m, jnp.sum(m.astype(jnp.int32)), tuple(outs)

            return fused

        try:
            mask, num, out_arrays = _dispatch_program(sig, builder, (
                tuple(num_inputs[n].data for n in num_inputs),
                tuple(pre.bound[k] for k in sorted(pre.bound)),
                batch.valid,
            ))
        except expr_compile.CompileError:
            # an expression form evaluate() supports eagerly but not under
            # trace — identical values either way, just per-step dispatch
            return self._sequential(batch)
        computed = {}
        for (name, _), (arr, hi) in zip(outputs, out_arrays):
            computed[name] = NumCol(arr, _infer_kind(arr),
                                    hi=hi if hi.shape[0] else None)
        if self._visible is None:
            # with_column replaces in place: a recomputed existing column
            # keeps its position, new names append in definition order
            names = list(batch.columns)
            names += [n for n in computed if n not in batch.columns]
        else:
            names = self._visible
        cols = {}
        for n in names:
            cols[n] = computed[n] if n in computed else batch.columns[n]
        sorted_by = batch.sorted_by
        if sorted_by is not None and not all(s in cols for s in sorted_by):
            sorted_by = None
        return DeviceBatch(cols, mask, None, sorted_by).note_count(num)


class StageSpec:
    """Picklable description of a fused stage: the member executor steps in
    chain order plus the fused-actor stream routing.  Exposes sql() so the
    plan fingerprint captures the FULL chain structure."""

    def __init__(self, steps: Sequence[Tuple[str, Callable]],
                 routing: Dict[int, Tuple[int, int]]):
        self.steps = [tuple(s) for s in steps]
        self.routing = dict(routing)

    def sql(self) -> str:
        from quokka_tpu.runtime.compileplane import _describe

        parts = [f"{label}:{_describe(factory)}" for label, factory in self.steps]
        routes = ",".join(f"{s}->{m}.{ss}"
                          for s, (m, ss) in sorted(self.routing.items()))
        return "stage[" + ";".join(parts) + "|" + routes + "]"


class FusedStageExecutor(Executor):
    """One actor running a whole fused stage.  Stream 0 (the chain's main
    input) cascades through every member; build streams route to their owning
    join member.  Emission decisions stay content-deterministic — each member
    already decides emits without inspecting device data, and the cascade is
    a pure function of those decisions — so tape replay at stage granularity
    reproduces the exact emit sequence."""

    # one fused dispatch does the work of the whole member chain: drain a
    # wider slice of the ready queue per task than the per-operator default
    # (a stage that holds a join takes the join's cap: see __init__)
    MAX_PIPELINE_BATCHES = 32

    def __init__(self, spec: StageSpec):
        self.spec = spec
        self.steps = [factory() for _, factory in spec.steps]
        self.labels = [label for label, _ in spec.steps]
        self.routing = spec.routing
        self.OP_NAME = "FusedStage[" + ">".join(self.labels) + "]"
        # the tightest cap a member states is the stage's: the head member
        # receives what the stage was dispatched with
        caps = [m.MAX_PIPELINE_BATCHES for m in self.steps
                if hasattr(m, "MAX_PIPELINE_BATCHES")]
        if caps:
            self.MAX_PIPELINE_BATCHES = min(caps)

    @property
    def SUPPORTS_CHECKPOINT(self) -> bool:
        # the stage checkpoints as ONE unit; that is only sound when every
        # member either snapshots real state or carries none at all.  Reading
        # the members' flags per call keeps runtime downgrades visible (the
        # grace join flips its instance flag off when it enters disk mode).
        return all(
            getattr(m, "SUPPORTS_CHECKPOINT", False)
            or getattr(m, "STATELESS", False)
            for m in self.steps
        )

    def _note_rows(self, idx: int, out: Optional[DeviceBatch]) -> None:
        """Per-logical-operator row accounting on the fused actor's opstats
        record (host-known rows only — never a device sync)."""
        if out is None:
            return
        from quokka_tpu.obs import opstats

        rows = out.nrows if out.nrows is not None else out.padded_len
        opstats.note(**{f"fused{idx}_{self.labels[idx]}_rows": rows})

    def _cascade(self, start: int, out: Optional[DeviceBatch],
                 channel: int) -> Optional[DeviceBatch]:
        for i in range(start, len(self.steps)):
            if out is None:
                return None
            out = self.steps[i].execute([out], 0, channel)
            self._note_rows(i, out)
        return out

    def execute(self, batches, stream_id, channel):
        idx, sub_stream = self.routing.get(stream_id, (0, 0))
        if sub_stream == 0:
            from quokka_tpu.obs.metrics import REGISTRY

            REGISTRY.counter("stagefuse.exec").inc()
        out = self.steps[idx].execute(batches, sub_stream, channel)
        self._note_rows(idx, out)
        return self._cascade(idx + 1, out, channel)

    def source_done(self, stream_id, channel):
        idx, sub_stream = self.routing.get(stream_id, (0, 0))
        out = self.steps[idx].source_done(sub_stream, channel)
        self._note_rows(idx, out)
        return self._cascade(idx + 1, out, channel)

    def done(self, channel):
        # interior members learn "main input exhausted" here: each member's
        # done() output feeds the remaining chain before the next member
        # finalizes, preserving per-operator flush order exactly as the
        # unfused actor pipeline would have delivered it
        pending: List[DeviceBatch] = []
        for i, m in enumerate(self.steps):
            outs: List[DeviceBatch] = []
            for b in pending:
                o = m.execute([b], 0, channel)
                self._note_rows(i, o)
                if o is not None:
                    outs.append(o)
            d = m.done(channel)
            if d is not None:
                for o in ([d] if isinstance(d, DeviceBatch) else d):
                    if o is not None:
                        self._note_rows(i, o)
                        outs.append(o)
            pending = outs
        return pending or None

    def checkpoint(self):
        return [
            m.checkpoint() if getattr(m, "SUPPORTS_CHECKPOINT", False) else None
            for m in self.steps
        ]

    def restore(self, state) -> None:
        if not state:
            return
        for m, s in zip(self.steps, state):
            if s is not None:
                m.restore(s)
