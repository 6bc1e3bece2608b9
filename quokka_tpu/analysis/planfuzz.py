"""Differential optimizer fuzzer (planfuzz): seeded random logical plans,
planned under the full pass pipeline vs every cumulative pass prefix vs
``QK_STAGE_FUSE=0``, with each variant both statically verified (planck
QK021-QK024) and executed on tiny in-memory data by a reference
interpreter — results must match the unoptimized plan bit-exactly (all
fuzz data is int64, so sums/mins/maxes are order-independent and avg is
an exact ratio of exact ints).

Any failing seed is shrunk with ddmin (analysis/shrink.py) to a
1-minimal op list: removing ANY single op from the repro makes the
failure disappear.  The generator builds plans by folding an op list
over a DataStream, *skipping inapplicable ops* (a join whose key was
projected away, an agg with no value column), so every ddmin
subsequence still builds — the property ddmin's chunk removal needs.

Known-bug injection (``BREAKERS``) wires a deliberately wrong rewrite
into the pipeline so tests can prove the harness actually catches
optimizer bugs end-to-end, differentially and statically:

- ``drop-filter``     splices a FilterNode out of the plan (statically
                      clean — only the differential run catches it)
- ``phantom-column``  appends a column the node never computes (QK021)
- ``claim-order``     marks a filter sorted over an unordered input (QK024)

CLI::

    python -m quokka_tpu.analysis.planfuzz --seeds 200
    python -m quokka_tpu.analysis.planfuzz --seed 7 --breaker drop-filter
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import random
import time
from typing import Callable, List, Optional, Sequence, Tuple

from quokka_tpu import logical, optimizer
from quokka_tpu.analysis import planck
from quokka_tpu.analysis.shrink import ddmin
from quokka_tpu.expression import (
    Alias,
    BinOp,
    ColRef,
    Expr,
    Func,
    Literal,
    UnaryOp,
    col,
)

# ---------------------------------------------------------------------------
# deterministic tiny tables (int64 only: exact, order-independent arithmetic)
# ---------------------------------------------------------------------------

_TABLES = None


def _tables():
    global _TABLES
    if _TABLES is None:
        import numpy as np
        import pyarrow as pa

        r = np.random.default_rng(0)
        n = 40
        fact = pa.table({
            "r": np.arange(n, dtype=np.int64),  # unique: deterministic top-k
            "k": r.integers(0, 6, n).astype(np.int64),
            "j": r.integers(0, 4, n).astype(np.int64),
            "x": r.integers(0, 100, n).astype(np.int64),
            "v": r.integers(0, 1000, n).astype(np.int64),
        })
        dim = pa.table({  # k=5 missing: inner joins genuinely drop rows
            "k": np.arange(5, dtype=np.int64),
            "w": r.integers(0, 10, 5).astype(np.int64),
        })
        dim2 = pa.table({
            "j": np.arange(4, dtype=np.int64),
            "z": r.integers(0, 10, 4).astype(np.int64),
        })
        _TABLES = (fact, dim, dim2)
    return _TABLES


# ---------------------------------------------------------------------------
# op-list grammar
# ---------------------------------------------------------------------------

_OP_KINDS = ("filter", "project", "with_columns", "join_k", "join_j",
             "agg", "distinct", "sort", "topk")


def gen_ops(seed: int) -> List[Tuple[str, int, int]]:
    """Deterministic op list for a seed: (kind, a, b) triples whose params
    are resolved against whatever columns exist when the op applies."""
    rng = random.Random(seed)
    n = rng.randint(3, 8)
    return [(rng.choice(_OP_KINDS), rng.randrange(1 << 16), rng.randrange(1 << 16))
            for _ in range(n)]


def build(qc, ops: Sequence[Tuple[str, int, int]]):
    """Fold the op list over a DataStream, skipping inapplicable ops so any
    subsequence (ddmin!) still builds.  Returns the final DataStream."""
    fact, dim, dim2 = _tables()
    ds = qc.from_arrow(fact)
    joined = set()
    uniq = 0  # with_columns name counter: unique within one build
    for kind, a, b in ops:
        cols = list(ds.schema)
        if kind == "filter":
            c = cols[a % len(cols)]
            ds = ds.filter(col(c) > (b % 50))
        elif kind == "project":
            keep = [c for i, c in enumerate(cols) if (a >> (i % 16)) & 1]
            if not keep:
                keep = [cols[a % len(cols)]]
            ds = ds.select(keep)
        elif kind == "with_columns":
            c1 = cols[a % len(cols)]
            c2 = cols[b % len(cols)]
            ds = ds.with_columns({f"e{uniq}": col(c1) * 2 + col(c2)})
            uniq += 1
        elif kind == "join_k":
            if "k" in cols and "join_k" not in joined:
                ds = ds.join(qc.from_arrow(dim), on="k")
                joined.add("join_k")
        elif kind == "join_j":
            if "j" in cols and "join_j" not in joined:
                ds = ds.join(qc.from_arrow(dim2), on="j")
                joined.add("join_j")
        elif kind == "agg":
            keys = [c for c in ("k", "j", "w", "z") if c in cols]
            if not keys:
                continue
            key = keys[a % len(keys)]
            vals = [c for c in cols if c != key]
            if not vals:
                continue
            val = vals[b % len(vals)]
            fn = ("sum", "min", "max", "avg", "count")[(a + b) % 5]
            ds = ds.groupby(key).agg_sql(
                f"{fn}({val}) as a{uniq}, count(*) as n{uniq}")
            uniq += 1
        elif kind == "distinct":
            ds = ds.distinct([cols[a % len(cols)]])
        elif kind == "sort":
            ds = ds.sort(cols[a % len(cols)])
        elif kind == "topk":
            if "r" in cols:  # unique column: tie-free, deterministic
                ds = ds.top_k("r", 5, descending=[bool(a % 2)])
    return ds


# ---------------------------------------------------------------------------
# reference interpreter: pandas semantics of the LOGICAL plan
# ---------------------------------------------------------------------------


def _eval(e: Expr, df):
    import numpy as np

    if isinstance(e, Alias):
        return _eval(e.expr, df)
    if isinstance(e, ColRef):
        return df[e.name]
    if isinstance(e, Literal):
        return e.value
    if isinstance(e, BinOp):
        l, r = _eval(e.left, df), _eval(e.right, df)
        if e.op == "+":
            return l + r
        if e.op == "-":
            return l - r
        if e.op == "*":
            return l * r
        if e.op == "/":
            return l / r
        if e.op == "//":
            return l // r
        if e.op == "%":
            return l % r
        if e.op == "=":
            return l == r
        if e.op == "!=":
            return l != r
        if e.op == "<":
            return l < r
        if e.op == "<=":
            return l <= r
        if e.op == ">":
            return l > r
        if e.op == ">=":
            return l >= r
        if e.op == "and":
            return l & r
        if e.op == "or":
            return l | r
        raise NotImplementedError(f"planfuzz interp: binop {e.op}")
    if isinstance(e, UnaryOp):
        v = _eval(e.operand, df)
        if e.op == "not":
            return ~v
        if e.op == "-":
            return -v
        raise NotImplementedError(f"planfuzz interp: unaryop {e.op}")
    if isinstance(e, Func):
        if e.name in ("__nn0", "__nnhigh", "__nnlow"):
            return _eval(e.args[0], df)  # null-identity wrappers: int data
        if e.name == "__nncount":
            a = _eval(e.args[0], df)
            return a.notna().astype("int64")
        if e.name == "sqrt":
            return np.sqrt(_eval(e.args[0], df))
        raise NotImplementedError(f"planfuzz interp: func {e.name}")
    raise NotImplementedError(f"planfuzz interp: {type(e).__name__}")


def _interp_node(node: logical.Node, inputs):
    import pandas as pd

    if isinstance(node, logical.SourceNode):
        df = node.reader.table.to_pandas()
        if node.predicate is not None:
            df = df[_eval(node.predicate, df).astype(bool)]
        if node.projection is not None:
            df = df[list(node.projection)]
        return df[list(node.schema)]
    if isinstance(node, logical.FusedStageNode):
        builds = iter(inputs[1:])
        cur = inputs[0]
        for m in node.members:
            if isinstance(m, logical.JoinNode):
                cur = _interp_node(m, [cur, next(builds)])
            else:
                cur = _interp_node(m, [cur])
        return cur[list(node.schema)]
    if isinstance(node, logical.FilterNode):
        df = inputs[0]
        return df[_eval(node.predicate, df).astype(bool)][list(node.schema)]
    if isinstance(node, logical.ProjectionNode):
        return inputs[0][list(node.schema)]
    if isinstance(node, logical.MapNode):
        df = inputs[0].copy()
        if node.exprs is not None:
            for k, e in node.exprs.items():
                df[k] = _eval(e, df)
            return df[list(node.schema)]
        if node.rename is not None:
            return df.rename(columns=node.rename)[list(node.schema)]
        raise NotImplementedError("planfuzz interp: opaque MapNode")
    if isinstance(node, logical.JoinNode):
        if node.how != "inner":
            raise NotImplementedError(f"planfuzz interp: {node.how} join")
        left, right = inputs
        rename = node.rename or {}
        payload = [c for c in right.columns if c not in set(node.right_on)]
        r2 = right[list(node.right_on) + payload].copy()
        r2.columns = list(node.left_on) + [rename.get(c, c) for c in payload]
        out = left.merge(r2, on=list(node.left_on), how="inner")
        return out[list(node.schema)]
    if isinstance(node, logical.AggNode):
        if node.order_by or node.limit is not None:
            raise NotImplementedError("planfuzz interp: order/limit agg")
        df = inputs[0].copy()
        plan = node.plan
        for tmp, e in plan.pre:
            df[tmp] = _eval(e, df)
        keys = list(node.keys)
        parts = {}
        if keys:
            g = df.groupby(keys, sort=True)
            for pname, op, tmp in plan.partials:
                if op == "count":
                    parts[pname] = g.size()
                elif op == "sum":
                    parts[pname] = g[tmp].sum()
                elif op == "min":
                    parts[pname] = g[tmp].min()
                elif op == "max":
                    parts[pname] = g[tmp].max()
                else:
                    raise NotImplementedError(f"planfuzz interp: partial {op}")
            pdf = pd.DataFrame(parts).reset_index()
        else:
            for pname, op, tmp in plan.partials:
                if op == "count":
                    parts[pname] = len(df)
                elif op == "sum":
                    parts[pname] = df[tmp].sum()
                elif op == "min":
                    parts[pname] = df[tmp].min()
                elif op == "max":
                    parts[pname] = df[tmp].max()
                else:
                    raise NotImplementedError(f"planfuzz interp: partial {op}")
            pdf = pd.DataFrame({k: [v] for k, v in parts.items()})
        for out_name, e in plan.finals:
            pdf[out_name] = _eval(e, pdf)
        if node.having is not None:
            pdf = pdf[_eval(node.having, pdf).astype(bool)]
        return pdf[list(node.schema)]
    if isinstance(node, logical.DistinctNode):
        return inputs[0][list(node.keys)].drop_duplicates()[list(node.schema)]
    if isinstance(node, logical.SortNode):
        asc = [not d for d in (node.descending or [False] * len(node.by))]
        return inputs[0].sort_values(list(node.by), ascending=asc)[
            list(node.schema)]
    if isinstance(node, logical.TopKNode):
        asc = [not d for d in (node.descending or [False] * len(node.by))]
        return inputs[0].sort_values(list(node.by), ascending=asc).head(
            node.k)[list(node.schema)]
    if isinstance(node, logical.SinkNode):
        return inputs[0][list(node.schema)]
    raise NotImplementedError(f"planfuzz interp: {type(node).__name__}")


def interpret(sub, sink_id):
    """Execute the logical plan bottom-up on pandas frames."""
    done = {}
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        done[nid] = _interp_node(node, [done[p] for p in node.parents])
    return done[sink_id]


def canon(df):
    """Order-free, dtype-normalized form for bit-exact comparison."""
    import pandas as pd

    df = df.copy()
    cols = sorted(df.columns)
    df = df[cols]
    for c in cols:
        if pd.api.types.is_integer_dtype(df[c]):
            df[c] = df[c].astype("int64")
        elif pd.api.types.is_float_dtype(df[c]):
            df[c] = df[c].astype("float64")
    return df.sort_values(cols, kind="mergesort").reset_index(drop=True)


# ---------------------------------------------------------------------------
# known-bug injection
# ---------------------------------------------------------------------------


def _break_drop_filter(sub, sink_id):
    """Splice the first FilterNode out of the plan — schemas stay valid
    (statically clean); only differential execution notices missing rows."""
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if isinstance(node, logical.FilterNode):
            pid = node.parents[0]
            for other in sub.values():
                other.parents = [pid if p == nid else p for p in other.parents]
            del sub[nid]
            return


def _break_phantom_column(sub, sink_id):
    """Append a column a node never computes (QK021 schema propagation)."""
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if isinstance(node, (logical.FilterNode, logical.JoinNode)):
            node.schema = list(node.schema) + ["__phantom"]
            return


def _break_claim_order(sub, sink_id):
    """Mark a filter as sorted over an unordered input (QK024)."""
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if isinstance(node, logical.FilterNode) and \
                sub[node.parents[0]].sorted_by is None:
            node.sorted_by = [node.schema[0]]
            return


# breaker name -> (inject after this pass, rewrite)
BREAKERS = {
    "drop-filter": ("push_filters", _break_drop_filter),
    "phantom-column": ("early_projection", _break_phantom_column),
    "claim-order": ("push_filters", _break_claim_order),
}


# ---------------------------------------------------------------------------
# variant runner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FuzzResult:
    seed: int
    ok: bool
    kind: Optional[str] = None      # "static" | "diff" | "error"
    variant: Optional[str] = None
    detail: str = ""
    ops: Optional[List[tuple]] = None
    shrunk: Optional[List[tuple]] = None

    def summary(self) -> str:
        if self.ok:
            return f"seed {self.seed}: ok"
        s = (f"seed {self.seed}: {self.kind} failure in variant "
             f"{self.variant}: {self.detail}")
        if self.shrunk is not None:
            s += f"\n  1-minimal repro ({len(self.shrunk)} ops): {self.shrunk}"
        return s


def _plan(ops, breaker=None, upto: Optional[int] = None):
    """Build ops into a plan and run the first `upto` optimizer passes
    (None = all), injecting `breaker` after its target pass."""
    from quokka_tpu.context import QuokkaContext

    qc = QuokkaContext(optimize=False)
    ds = build(qc, ops)
    sub, sink_id = qc._prepare_plan(ds.node_id)
    pipeline = optimizer.pass_pipeline(exec_channels=qc.exec_channels)
    for name, fn in pipeline[:len(pipeline) if upto is None else upto]:
        fn(sub, sink_id)
        if breaker is not None and breaker[0] == name:
            breaker[1](sub, sink_id)
    return sub, sink_id


def check_ops(ops, breaker=None, static_only=False) -> Optional[Tuple[str, str, str]]:
    """Run every variant of the op list; return (kind, variant, detail) for
    the first failure, None when all variants agree and verify clean."""
    names = [n for n, _ in optimizer.pass_pipeline()]
    variants = [("v0", 0, None)]
    variants += [(f"prefix:{names[i - 1]}", i, None)
                 for i in range(1, len(names) + 1)]
    variants += [("nofuse", len(names), "0")]

    reference = None
    for vname, upto, fuse_env in variants:
        old_fuse = os.environ.get("QK_STAGE_FUSE")
        if fuse_env is not None:
            os.environ["QK_STAGE_FUSE"] = fuse_env
        try:
            sub, sink_id = _plan(ops, breaker=breaker, upto=upto)
        finally:
            if fuse_env is not None:
                if old_fuse is None:
                    os.environ.pop("QK_STAGE_FUSE", None)
                else:
                    os.environ["QK_STAGE_FUSE"] = old_fuse
        try:
            planck.verify_plan(sub, sink_id, where=f"fuzz:{vname}")
        except planck.PlanInvariantError as e:
            return ("static", vname, str(e))
        if static_only:
            continue
        try:
            got = canon(interpret(sub, sink_id))
        except Exception as e:  # interp gap or genuinely broken plan
            return ("error", vname, f"{type(e).__name__}: {e}")
        if reference is None:
            reference = got
        elif not reference.equals(got):
            return ("diff", vname,
                    f"result mismatch vs v0 "
                    f"({len(got)} rows vs {len(reference)} rows, "
                    f"cols {list(got.columns)})")
    return None


def run_seed(seed: int, breaker=None, static_only=False,
             shrink: bool = True) -> FuzzResult:
    if isinstance(breaker, str):
        breaker = BREAKERS[breaker]
    ops = gen_ops(seed)
    failure = check_ops(ops, breaker=breaker, static_only=static_only)
    if failure is None:
        return FuzzResult(seed=seed, ok=True, ops=ops)
    kind, variant, detail = failure
    shrunk = None
    if shrink:
        shrunk = ddmin(ops, lambda cand: check_ops(
            list(cand), breaker=breaker, static_only=static_only) is not None)
    return FuzzResult(seed=seed, ok=False, kind=kind, variant=variant,
                      detail=detail, ops=ops, shrunk=shrunk)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m quokka_tpu.analysis.planfuzz",
        description="differential optimizer fuzzer: random plans, full "
                    "pipeline vs pass prefixes vs QK_STAGE_FUSE=0, verified "
                    "statically (planck) and executed on tiny data")
    p.add_argument("--seeds", type=int, default=200,
                   help="number of seeds to run (0..N-1)")
    p.add_argument("--seed", type=int, default=None,
                   help="run exactly one seed")
    p.add_argument("--breaker", choices=sorted(BREAKERS), default=None,
                   help="inject a known optimizer bug (harness self-test)")
    p.add_argument("--static-only", action="store_true")
    args = p.parse_args(argv)

    t0 = time.perf_counter()
    seeds = [args.seed] if args.seed is not None else list(range(args.seeds))
    failures = 0
    for seed in seeds:
        r = run_seed(seed, breaker=args.breaker,
                     static_only=args.static_only)
        if not r.ok:
            failures += 1
            print(r.summary())
    dt = time.perf_counter() - t0
    print(f"planfuzz: {len(seeds) - failures}/{len(seeds)} seeds clean "
          f"in {dt:.1f}s"
          + (f" (breaker={args.breaker})" if args.breaker else ""))
    if args.breaker and failures == 0:
        print("planfuzz: breaker injected but NO seed caught it — harness gap")
        return 1
    return 1 if (failures and not args.breaker) else 0


if __name__ == "__main__":  # pragma: no cover
    from quokka_tpu.analysis import planfuzz as _canonical

    raise SystemExit(_canonical.main())
