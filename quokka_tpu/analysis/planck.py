"""Plan-invariant verifier (planck): typed invariants over the logical DAG.

Every optimizer pass is a hand-written in-place rewrite of the plan's node
dict, and until now nothing checked that a pass preserved anything: stale
interior schemas were silently tolerated by defensive executors,
``unfuse_stages`` was *trusted* to invert ``fuse_stages``, and exchange
edges were trusted to partition on columns the producer actually emits.
The next roadmap items (fusion through the exchange, adaptive re-planning
mid-query) rewrite plans far more aggressively — this module is the
correctness net they run inside, the same way the protocol verifier
(QK014-QK017) was built before streaming GC leaned on it.

Zero-baseline rules (no suppression file — a violation fails tier-1):

- **QK021 schema propagation** — every node's output schema must be EXACTLY
  derivable from its parents' schemas plus its own metadata
  (``Node.derive_schema``), including through every ``FusedStageNode``
  member; derived schemas must be non-empty and duplicate-free, and a
  source's pushed predicate may reference only columns the source reads.
- **QK022 exchange-key coverage** — every exchange edge's partition
  function references only columns its producer emits: hash-join key lists
  align positionally and exist on both inputs, stateful-operator
  partitioners name live columns of the right parent, a range-partitioned
  sort's boundaries match its channel fan-out.
- **QK023 fusion legality** — fused chains contain only fusible,
  placement-free, unordered members; interior joins are broadcast; an agg
  terminates the chain; absorbed member ids are gone from the plan and
  referenced by nobody else; and ``unfuse_stages(fuse_stages(p))`` is
  structurally identical to ``p`` — VERIFIED against a pre-pass digest
  (or by re-fusing the unfused plan when no 'before' exists), not trusted.
- **QK024 streaming legality** — order metadata stays monotone-safe: a
  node's ``sorted_by`` columns exist in its schema, order-inheriting verbs
  (filter/projection/map) only claim order their input has, time-series
  operators (asof join, window agg, shift) sit on inputs ordered by their
  time key, an UNBOUNDED source keeps the single-channel streaming
  discipline, and no checkpoint-barrier member hides inside a fused stage
  (a fused stage checkpoints as ONE unit).
- **QK025 resume-fingerprint restart-stability** — the structural
  fingerprint ``runtime/resume.py`` verifies at batch resume must be
  IDENTICAL when the same prepared plan is pickled (the manifest's plan
  payload) and re-lowered into a fresh context and control store — the
  exact round trip ``QueryService.recover_orphans`` performs after a crash
  — and its preimage must be free of object addresses and size-dependent
  buckets (a source file may grow between restarts).  Checked over live
  lowerings in the CLI corpus run, not statically.
- **QK026 adaptive-exchange legality** — ``adapt_salt`` (the mark that lets
  the runtime re-partition a skewed build exchange mid-query,
  planner/decide.py) sits only where the salt+replicate rewrite provably
  keeps every inner match exactly-once: INNER hash joins, non-broadcast,
  no claimed output order; and the reserved runtime salt column never
  appears in any node's schema.

Pass-level instrumentation lives in ``optimizer.optimize``: under
``QK_PLAN_VERIFY=1`` (default-on in tests) every pass's
(before, after) plan pair is verified and a violation raises
``PlanInvariantError`` naming the pass and the offending node.  All checks
run at PLAN time — never on the push path.

CLI::

    python -m quokka_tpu.analysis.planck            # corpus of query shapes
    python -m quokka_tpu.analysis.planck --seeds 50 # + fuzzer-generated plans
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from quokka_tpu import logical
from quokka_tpu.optimizer import _reachable, fuse_stages, unfuse_stages
from quokka_tpu.target_info import (
    HashPartitioner,
    RangePartitioner,
)

RULES = {
    "QK021": "schema propagation: declared output schema == derived schema",
    "QK022": "exchange-key coverage: partition keys exist on the producer",
    "QK023": "fusion legality: fusible members + exact unfuse round-trip",
    "QK024": "streaming legality: monotone order metadata, 1-channel "
             "unbounded sources, no checkpoint barrier inside a stage",
    "QK025": "resume-fingerprint restart-stability: a durable batch "
             "plan's structural fingerprint survives pickle + fresh-"
             "process re-lowering, address- and size-hint-free",
    "QK026": "adaptive-exchange legality: adapt_salt only on inner "
             "non-broadcast unordered joins; salt column reserved",
}

# plan-time verification cost, read by optimizer.optimize and the tests
# (acceptance: <= 5 ms per query at plan time)
VERIFY_STATS = {"plans": 0, "checks": 0, "ms_total": 0.0, "ms_last_plan": 0.0}
_CUR_MS = [0.0]


def enabled() -> bool:
    """QK_PLAN_VERIFY gate, read dynamically (config.py env-knob idiom)."""
    return os.environ.get("QK_PLAN_VERIFY", "0") not in ("0", "false", "no", "")


@dataclasses.dataclass
class PlanViolation:
    rule: str
    node_id: int
    node: str          # node.describe() of the offender
    message: str

    def render(self) -> str:
        return f"{self.rule} node {self.node_id} [{self.node}]: {self.message}"


class PlanInvariantError(AssertionError):
    """An optimizer pass (or a hand-built plan) broke a plan invariant."""

    def __init__(self, where: str, violations: Sequence[PlanViolation]):
        self.where = where
        self.violations = list(violations)
        lines = "\n  ".join(v.render() for v in self.violations)
        super().__init__(f"plan invariants violated after {where}:\n  {lines}")


# ---------------------------------------------------------------------------
# structural digest
# ---------------------------------------------------------------------------


def _node_sig(node: logical.Node) -> tuple:
    sig = (
        type(node).__name__,
        tuple(node.parents),
        tuple(node.schema),
        node.describe(),
        node.channels,
        tuple(node.sorted_by or ()),
        tuple(getattr(node, "boundaries", None) or ()),
        tuple(sorted((getattr(node, "rename", None) or {}).items())),
        bool(getattr(node, "folded", False)),
        bool(getattr(node, "adapt_salt", False)),
    )
    if isinstance(node, logical.FusedStageNode):
        sig += (tuple(_node_sig(m) for m in node.members),)
    return sig


def digest(sub: Dict[int, logical.Node], sink_id: int) -> tuple:
    """Structural identity of the reachable plan: node ids, types, links,
    schemas, and per-type metadata.  Two plans with equal digests lower to
    identical actor graphs; the QK023 round-trip check compares these."""
    t0 = time.perf_counter()
    out = tuple(
        (nid, _node_sig(sub[nid])) for nid in sorted(_reachable(sub, sink_id))
    )
    _account(time.perf_counter() - t0)
    return out


def _account(seconds: float) -> None:
    ms = seconds * 1e3
    VERIFY_STATS["ms_total"] += ms
    VERIFY_STATS["checks"] += 1
    _CUR_MS[0] += ms


def finish_plan() -> None:
    """Roll per-pass accounting into per-plan stats (called by optimize)."""
    VERIFY_STATS["plans"] += 1
    VERIFY_STATS["ms_last_plan"] = _CUR_MS[0]
    _CUR_MS[0] = 0.0


# ---------------------------------------------------------------------------
# rule checks
# ---------------------------------------------------------------------------


def collect(sub: Dict[int, logical.Node], sink_id: int) -> List[PlanViolation]:
    """Run QK021-QK024 + QK026 over the reachable plan; return all
    violations."""
    out: List[PlanViolation] = []
    order = _reachable(sub, sink_id)
    consumers: Dict[int, List[int]] = {nid: [] for nid in order}
    for nid in order:
        for p in sub[nid].parents:
            consumers.setdefault(p, []).append(nid)
    for nid in order:
        node = sub[nid]
        parents = [list(sub[p].schema) for p in node.parents]
        out += _qk021_schema(nid, node, parents)
        out += _qk022_exchange(nid, node, parents)
        if isinstance(node, logical.FusedStageNode):
            out += _qk023_fusion(sub, nid, node, consumers)
        out += _qk024_streaming(sub, nid, node)
        out += _qk026_adaptive(nid, node)
    return out


def _qk021_schema(nid, node, parents) -> List[PlanViolation]:
    out = []

    def bad(msg):
        out.append(PlanViolation("QK021", nid, node.describe(), msg))

    schema = list(node.schema)
    if not schema:
        bad("empty output schema")
    if len(set(schema)) != len(schema):
        dupes = sorted({c for c in schema if schema.count(c) > 1})
        bad(f"duplicate output columns {dupes}")
    if not all(isinstance(c, str) for c in schema):
        bad(f"non-string column names in {schema}")
    try:
        derived = node.derive_schema(parents)
    except ValueError as e:
        bad(str(e))
        return out
    if derived is not None and list(derived) != schema:
        bad(f"declared schema {schema} != derived {list(derived)}")
    if isinstance(node, logical.SourceNode):
        if node.predicate is not None:
            missing = sorted(node.predicate.required_columns() - set(schema))
            if missing:
                bad(f"pushed predicate references pruned columns {missing}")
        if node.projection is not None and list(node.projection) != schema:
            bad(f"projection {node.projection} != schema {schema}")
    return out


def _qk022_exchange(nid, node, parents) -> List[PlanViolation]:
    out = []

    def bad(msg):
        out.append(PlanViolation("QK022", nid, node.describe(), msg))

    if isinstance(node, logical.JoinNode):
        if not node.left_on or len(node.left_on) != len(node.right_on):
            bad(f"join key arity mismatch {node.left_on} vs {node.right_on}")
        # key presence on both inputs is QK021's derive_schema _require;
        # re-check here so a QK022 report stands alone for exchange edges
        for keys, side in ((node.left_on, 0), (node.right_on, 1)):
            missing = [k for k in keys if k not in set(parents[side])]
            if missing:
                bad(f"exchange keys {missing} not produced by input {side} "
                    f"{parents[side]}")
    if isinstance(node, logical.StatefulNode):
        for i, part in (node.partitioners or {}).items():
            if i >= len(parents):
                bad(f"partitioner on missing input {i}")
                continue
            if isinstance(part, HashPartitioner):
                missing = [k for k in part.keys if k not in set(parents[i])]
                if missing:
                    bad(f"hash partition keys {missing} not produced by "
                        f"input {i} {parents[i]}")
            if isinstance(part, RangePartitioner) and part.key not in set(parents[i]):
                bad(f"range partition key {part.key!r} not produced by "
                    f"input {i} {parents[i]}")
    if isinstance(node, logical.AggNode) and node.keys:
        # the partial->final exchange hashes on the group keys; the partial
        # half always emits them, so only key sanity is checkable here
        if len(set(node.keys)) != len(node.keys):
            bad(f"duplicate group keys {node.keys}")
    if isinstance(node, logical.SortNode) and node.boundaries is not None:
        n = node.channels or 0
        if n < 2:
            bad(f"range-partitioned sort with {n} channel(s)")
        elif len(node.boundaries) != n - 1:
            bad(f"{len(node.boundaries)} boundaries for {n} channels "
                "(need channels-1)")
        if len(node.by) != 1:
            bad(f"range partition on multi-column sort {node.by}")
    return out


_FUSIBLE = (logical.FilterNode, logical.ProjectionNode, logical.MapNode,
            logical.JoinNode, logical.AggNode)


def _qk023_fusion(sub, nid, node: logical.FusedStageNode, consumers) -> List[PlanViolation]:
    out = []

    def bad(msg):
        out.append(PlanViolation("QK023", nid, "FusedStage", msg))

    members = node.members
    if len(members) < 2:
        bad(f"{len(members)}-member stage (fusion must be a real chain)")
    joins = 0
    for i, m in enumerate(members):
        if not isinstance(m, _FUSIBLE):
            bad(f"member {i} ({type(m).__name__}) is not a fusible operator")
        if m.placement is not None:
            bad(f"member {i} ({m.describe()}) carries a placement strategy")
        if m.sorted_by is not None:
            bad(f"member {i} ({m.describe()}) is order-carrying")
        if isinstance(m, logical.JoinNode):
            joins += 1
            if i > 0 and not m.broadcast:
                bad(f"interior member {i} is a non-broadcast hash join")
        if isinstance(m, logical.AggNode) and i != len(members) - 1:
            bad(f"agg member {i} does not terminate the chain")
        if m.channels is not None and node.channels is not None \
                and m.channels != node.channels:
            bad(f"member {i} pinned to {m.channels} channels, stage has "
                f"{node.channels}")
    if joins != len(node.parents) - 1:
        bad(f"{joins} join member(s) but {len(node.parents) - 1} build input(s)")
    # absorbed interior ids must be gone and unreferenced (single-consumer)
    interior = [m.parents[0] for m in members[1:]]
    for mid in interior:
        if mid in sub:
            bad(f"absorbed member id {mid} still present in the plan")
        for other, cons in consumers.items():
            if other == mid and cons:
                bad(f"absorbed member id {mid} still consumed by {cons}")
    refs = [
        (onid, mid)
        for onid, other in sub.items()
        for mid in interior
        if onid != nid and mid in other.parents
    ]
    for onid, mid in refs:
        bad(f"absorbed member id {mid} referenced by node {onid}")
    return out


def _qk024_streaming(sub, nid, node) -> List[PlanViolation]:
    out = []

    def bad(msg):
        out.append(PlanViolation("QK024", nid, node.describe(), msg))

    if node.sorted_by is not None:
        missing = [c for c in node.sorted_by if c not in set(node.schema)]
        if missing:
            bad(f"sorted_by columns {missing} not in output schema "
                f"{list(node.schema)}")
        # order-inheriting verbs can't invent order their input lacks
        if isinstance(node, (logical.FilterNode, logical.ProjectionNode,
                             logical.MapNode)):
            parent = sub[node.parents[0]]
            if parent.sorted_by is None:
                bad(f"claims order {node.sorted_by} over an unordered input "
                    f"({parent.describe()})")
        # hash-exchange operators have no order contract at all: their
        # key-partitioned shuffle interleaves channels arbitrarily
        if isinstance(node, (logical.JoinNode, logical.AggNode,
                             logical.DistinctNode)):
            bad(f"hash-exchange operator claims order {node.sorted_by}")
    if isinstance(node, logical.AsofJoinNode):
        for side, key in ((0, node.left_on), (1, node.right_on)):
            psort = sub[node.parents[side]].sorted_by or []
            if not psort or psort[0] != key:
                bad(f"asof input {side} ordered by {psort or None}, join "
                    f"needs {key!r} first")
    elif isinstance(node, (logical.WindowAggNode, logical.ShiftNode)):
        psort = sub[node.parents[0]].sorted_by or []
        if not psort or psort[0] != node.time_col:
            bad(f"time-series input ordered by {psort or None}, operator "
                f"needs {node.time_col!r} first")
    if isinstance(node, logical.SourceNode) and \
            getattr(node.reader, "UNBOUNDED", False):
        if node.channels != 1:
            bad(f"unbounded source with channels={node.channels} "
                "(streaming v1 discipline is exactly 1)")
    if isinstance(node, logical.FusedStageNode):
        for i, m in enumerate(node.members):
            if getattr(m, "checkpoint_barrier", False) or \
                    isinstance(m, logical.StatefulNode):
                bad(f"checkpoint barrier (member {i}, {m.describe()}) inside "
                    "a fused stage — the stage checkpoints as one unit")
    return out


def _qk026_adaptive(nid, node) -> List[PlanViolation]:
    out = []

    def bad(msg):
        out.append(PlanViolation("QK026", nid, node.describe(), msg))

    # the runtime salting rewrite owns this name on the wire; a plan that
    # emits it would collide with adapted exchanges (decide.SALT_COLUMN)
    from quokka_tpu.planner.decide import SALT_COLUMN

    if SALT_COLUMN in set(node.schema):
        bad(f"reserved salt column {SALT_COLUMN!r} in output schema")
    marked = [node]
    if isinstance(node, logical.FusedStageNode):
        marked += list(node.members)
    for m in marked:
        if not getattr(m, "adapt_salt", False):
            continue
        if not isinstance(m, logical.JoinNode):
            bad(f"adapt_salt on non-join {type(m).__name__}")
            continue
        if m.how != "inner":
            bad(f"adapt_salt on {m.how!r} join — only inner joins keep "
                "exactly-once matching under salt+replicate")
        if m.broadcast:
            bad("adapt_salt on a broadcast join (no build exchange to salt)")
        if m.sorted_by:
            bad(f"adapt_salt on an order-carrying join (sorted_by="
                f"{list(m.sorted_by)}) — replicated probe slices interleave")
    return out


# ---------------------------------------------------------------------------
# entry points used by optimizer.optimize
# ---------------------------------------------------------------------------


def verify_plan(sub, sink_id: int, where: str = "plan") -> None:
    """Check all invariants; additionally prove the fuse/unfuse involution
    for already-fused plans (no 'before' digest exists here, so the check
    is unfuse -> re-fuse -> identical digest)."""
    t0 = time.perf_counter()
    violations = collect(sub, sink_id)
    if any(isinstance(n, logical.FusedStageNode) for n in sub.values()) \
            and not violations:
        unfused = unfuse_stages(sub)
        refused = dict(unfused)
        fuse_stages(refused, sink_id)
        if _raw_digest(refused, sink_id) != _raw_digest(sub, sink_id):
            violations.append(PlanViolation(
                "QK023", sink_id, "plan",
                "fuse_stages(unfuse_stages(p)) != p (round-trip drift)"))
    _account(time.perf_counter() - t0)
    if violations:
        raise PlanInvariantError(where, violations)


def verify_pass(sub, sink_id: int, pass_name: str, before: Optional[tuple]) -> None:
    """Post-pass check: all invariants, plus — for the fusion pass — the
    exact round-trip ``unfuse_stages(after) == before`` (QK023)."""
    t0 = time.perf_counter()
    violations = collect(sub, sink_id)
    if pass_name == "fuse_stages" and before is not None and not violations:
        unfused = unfuse_stages(sub)
        if _raw_digest(unfused, sink_id) != before:
            violations.append(PlanViolation(
                "QK023", sink_id, "plan",
                "unfuse_stages(fuse_stages(p)) is not structurally "
                "identical to p"))
    _account(time.perf_counter() - t0)
    if violations:
        raise PlanInvariantError(f"pass {pass_name}", violations)


def _raw_digest(sub, sink_id) -> tuple:
    return tuple(
        (nid, _node_sig(sub[nid])) for nid in sorted(_reachable(sub, sink_id))
    )


# ---------------------------------------------------------------------------
# CLI corpus: every plannable query shape the tests/bench exercise
# ---------------------------------------------------------------------------


def _tables():
    import numpy as np
    import pyarrow as pa

    r = np.random.default_rng(7)
    n = 64
    fact = pa.table({
        "k": r.integers(0, 6, n).astype(np.int64),
        "j": r.integers(0, 4, n).astype(np.int64),
        "x": r.integers(0, 100, n).astype(np.int64),
        "v": r.normal(size=n),
    })
    dim = pa.table({
        "k": np.arange(6, dtype=np.int64),
        "name": np.array([f"k{i}" for i in range(6)]),
        "w": r.integers(0, 10, 6).astype(np.int64),
    })
    dim2 = pa.table({
        "j": np.arange(4, dtype=np.int64),
        "x": r.integers(0, 10, 4).astype(np.int64),  # clashes with fact.x
    })
    t = np.sort(r.integers(0, 10_000, n)).astype(np.int64)
    ticks = pa.table({
        "time": t,
        "symbol": r.integers(0, 3, n).astype(np.int64),
        "size": r.integers(1, 9, n).astype(np.int64),
    })
    return fact, dim, dim2, ticks


def corpus() -> List[Tuple[str, "callable"]]:
    """(name, build(qc) -> DataStream) for every plannable query shape in
    the tier-1 tests and the benchmark's cells — the CLI plans each one
    with the full pass pipeline and verifies every intermediate plan."""
    from quokka_tpu.expression import col
    from quokka_tpu.windows import TumblingWindow

    fact, dim, dim2, ticks = _tables()

    def filter_agg(qc):
        return (qc.from_arrow(fact).filter(col("x") > 10)
                .groupby("k").agg_sql("sum(x) as sx, avg(v) as av"))

    def q3_shape(qc):
        f = qc.from_arrow(fact).filter(col("x") > 5)
        d = qc.from_arrow(dim)
        return (f.join(d, on="k").groupby("name")
                .agg_sql("sum(x) as revenue").top_k("revenue", 3,
                                                    descending=[True]))

    def join_chain(qc):
        f = qc.from_arrow(fact)
        return (f.join(qc.from_arrow(dim), on="k")
                .join(qc.from_arrow(dim2), on="j", suffix="_d2")
                .select(["k", "name", "x_d2"]))

    def broadcast_dim(qc):
        return (qc.from_arrow(fact)
                .broadcast_join(qc.from_arrow(dim), on="k")
                .select(["k", "w"]).sum("w"))

    def semi_anti(qc):
        f = qc.from_arrow(fact)
        d = qc.from_arrow(dim).filter(col("w") > 3)
        return f.join(d, on="k", how="semi").union(
            f.join(d, on="k", how="anti")).select(["k", "x"])

    def suffix_clash(qc):
        return (qc.from_arrow(fact)
                .join(qc.from_arrow(dim2), on="j")
                .select(["k", "x_2"]))

    def union_prune(qc):
        # regression shape: each union side prunes differently (left keeps
        # a pushed predicate's column), the union schema must re-derive
        a = qc.from_arrow(fact).filter(col("x") > 50)
        b = qc.from_arrow(fact)
        return a.union(b).select(["k"]).distinct()

    def map_chain(qc):
        return (qc.from_arrow(fact)
                .with_columns({"x2": col("x") * 2})
                .rename({"v": "value"})
                .transform(lambda df: df.head(5), ["k", "j", "x", "value", "x2"])
                .select(["k", "x2"]))

    def order_verbs(qc):
        s = qc.from_arrow(fact).sort("x").filter(col("k") > 1)
        return s.head(10)

    def count_distinct(qc):
        return qc.from_arrow(fact).groupby("k").agg_sql(
            "count(distinct j) as dj")

    def asof(qc):
        t = qc.from_arrow_sorted(ticks, sorted_by="time")
        q = qc.from_arrow_sorted(ticks, sorted_by="time")
        return t.join_asof(q, on="time", by="symbol")

    def window(qc):
        t = qc.from_arrow_sorted(ticks, sorted_by="time")
        return t.window_agg(TumblingWindow(1000), "sum(size) as vol",
                            by="symbol")

    def shift(qc):
        t = qc.from_arrow_sorted(ticks, sorted_by="time")
        return t.shift("size", n=1, by="symbol")

    def quantile(qc):
        return qc.from_arrow(fact).approximate_quantile("x", [0.5, 0.9])

    return [
        ("filter_agg", filter_agg),
        ("q3_shape", q3_shape),
        ("join_chain", join_chain),
        ("broadcast_dim", broadcast_dim),
        ("semi_anti", semi_anti),
        ("suffix_clash", suffix_clash),
        ("union_prune", union_prune),
        ("map_chain", map_chain),
        ("order_verbs", order_verbs),
        ("count_distinct", count_distinct),
        ("asof", asof),
        ("window", window),
        ("shift", shift),
        ("quantile", quantile),
    ]


def check_corpus(progress=None) -> List[Tuple[str, PlanInvariantError]]:
    """Plan every corpus query with the full (instrumented) pipeline and a
    final whole-plan verify; returns (name, error) for failures.  ``progress``
    is an optional ``callable(line: str)`` invoked once per corpus query
    (the CLI passes ``print``)."""
    from quokka_tpu.context import QuokkaContext

    old = os.environ.get("QK_PLAN_VERIFY")
    os.environ["QK_PLAN_VERIFY"] = "1"
    failures: List[Tuple[str, PlanInvariantError]] = []
    try:
        for name, build in corpus():
            qc = QuokkaContext()
            try:
                ds = build(qc)
                sub, sink_id = qc._prepare_plan(ds.node_id)
                verify_plan(sub, sink_id, where=f"corpus:{name}")
            except PlanInvariantError as e:
                failures.append((name, e))
            if progress is not None:
                status = "FAIL" if failures and failures[-1][0] == name else "ok"
                progress(f"  {name:<16} {status}")
    finally:
        if old is None:
            os.environ.pop("QK_PLAN_VERIFY", None)
        else:
            os.environ["QK_PLAN_VERIFY"] = old
    return failures


def check_resume_fingerprints(progress=None) -> List[Tuple[str, str]]:
    """QK025, run over live lowerings: for each shape, prepare the plan,
    pickle it exactly like ``QueryService.submit(durable=True)`` does,
    then unpickle + lower TWICE into fresh contexts/stores (two simulated
    process restarts).  Both fingerprints must equal each other AND the
    original submit-side lowering's, and every preimage part must be free
    of memory addresses.  Returns (name, problem) failures."""
    import pickle

    import numpy as np
    import pyarrow as pa

    from quokka_tpu.context import QuokkaContext
    from quokka_tpu.runtime import resume as bresume
    from quokka_tpu.runtime.engine import TaskGraph
    from quokka_tpu.runtime.tables import ControlStore

    r = np.random.default_rng(7)
    n = 256
    fact = pa.table({
        "k": r.integers(0, 6, n).astype(np.int64),
        "v": r.integers(0, 100, n).astype(np.float64),
    })
    dim = pa.table({
        "k": np.arange(6, dtype=np.int64),
        "w": r.integers(0, 10, 6).astype(np.int64),
    })
    shapes = [
        ("agg", lambda qc: qc.from_arrow(fact)
            .groupby("k").agg_sql("sum(v) as s, count(*) as n")),
        ("join_agg", lambda qc: qc.from_arrow(fact)
            .join(qc.from_arrow(dim), on="k")
            .groupby("w").agg_sql("sum(v) as s")),
        ("filter_proj", lambda qc: qc.from_arrow(fact)
            .filter_sql("v > 10").select(["k"])),
    ]
    failures: List[Tuple[str, str]] = []
    for name, build in shapes:
        qc = QuokkaContext()
        ds = build(qc)
        sub, sink_id = qc._prepare_plan(ds.node_id)
        blob = pickle.dumps({"sub": sub, "sink_id": sink_id,
                             "exec_channels": qc.exec_channels})
        g0 = TaskGraph(qc.exec_config, store=ControlStore())
        qc._lower_plan(sub, sink_id, g0)
        fps, parts = [], []
        for _restart in range(2):
            payload = pickle.loads(blob)
            ctx = QuokkaContext()
            ctx.exec_channels = payload.get("exec_channels",
                                            ctx.exec_channels)
            g = TaskGraph(ctx.exec_config, store=ControlStore())
            ctx._lower_plan(payload["sub"], payload["sink_id"], g)
            fps.append(bresume.structural_fingerprint(g))
            parts.append(bresume.structural_parts(g))
        if len({bresume.structural_fingerprint(g0), *fps}) != 1:
            failures.append((name, f"fingerprint drifted across simulated "
                                   f"restarts: submit="
                                   f"{bresume.structural_fingerprint(g0)} "
                                   f"relowered={fps}"))
        addressed = [p for p in parts[0] if "0x" in p]
        if addressed:
            failures.append((name, "fingerprint preimage contains object "
                                   f"addresses: {addressed}"))
        if progress is not None:
            status = ("FAIL" if failures and failures[-1][0] == name
                      else "ok")
            progress(f"  resume-fp {name:<12} {status}")
    return failures


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m quokka_tpu.analysis.planck",
        description="verify plan invariants QK021-QK024 over the corpus of "
                    "plannable query shapes (plus fuzzer-generated plans)")
    p.add_argument("--seeds", type=int, default=0,
                   help="additionally verify N fuzzer-generated plans "
                        "(static checks only; see planfuzz for differential)")
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args(argv)

    t0 = time.perf_counter()
    failures = check_corpus(progress=print if args.verbose else None)
    n_corpus = len(corpus())
    print(f"planck: corpus {n_corpus - len(failures)}/{n_corpus} plans clean "
          f"({VERIFY_STATS['checks']} checks, "
          f"{VERIFY_STATS['ms_total']:.1f} ms total, "
          f"last plan {VERIFY_STATS['ms_last_plan']:.2f} ms)")
    for name, e in failures:
        print(f"FAIL {name}:\n{e}")

    fp_failures = check_resume_fingerprints(
        progress=print if args.verbose else None)
    print(f"planck: resume fingerprints (QK025) "
          f"{3 - len({n for n, _ in fp_failures})}/3 shapes restart-stable")
    for name, problem in fp_failures:
        print(f"FAIL resume-fp {name}: {problem}")
    failures = failures + fp_failures

    if args.seeds:
        from quokka_tpu.analysis import planfuzz

        fuzz_failures = 0
        for seed in range(args.seeds):
            r = planfuzz.run_seed(seed, static_only=True)
            if not r.ok:
                fuzz_failures += 1
                print(f"FAIL fuzz seed {seed}: {r.summary()}")
        print(f"planck: fuzz {args.seeds - fuzz_failures}/{args.seeds} "
              "seeded plans clean")
        if fuzz_failures:
            return 1
    print(f"planck: done in {time.perf_counter() - t0:.1f}s")
    return 1 if failures else 0


if __name__ == "__main__":  # pragma: no cover
    # dispatch through the canonical module so VERIFY_STATS is shared with
    # the optimizer's instrumentation (python -m runs this file as __main__)
    from quokka_tpu.analysis import planck as _canonical

    raise SystemExit(_canonical.main())
