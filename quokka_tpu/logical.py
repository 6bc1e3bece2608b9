"""Logical plan nodes.

Role of pyquokka/logical.py: the DataStream API builds a DAG of these; the
optimizer rewrites it; ``lower()`` emits physical actors into the runtime
TaskGraph.  Each node records its parents, output schema, and (assigned by
stage analysis) its execution stage; every consumer edge carries a TargetInfo
describing partitioning and any folded-in predicate/projection/batch functions.
"""

from __future__ import annotations

import functools

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from quokka_tpu.expression import Expr
from quokka_tpu.ops import kernels
from quokka_tpu.ops.expr_compile import AggPlan, evaluate_predicate, evaluate_to_column
from quokka_tpu.target_info import (
    BroadcastPartitioner,
    HashPartitioner,
    PassThroughPartitioner,
    RangePartitioner,
    TargetInfo,
)


class Node:
    def __init__(self, parents: List[int], schema: List[str]):
        self.parents = parents
        self.schema = schema
        self.stage = 0
        self.channels: Optional[int] = None  # None -> context default
        # build_parents: indices into self.parents whose subtree must complete
        # before this node's streaming side runs (join build sides)
        self.build_parents: List[int] = []
        self.sorted_by: Optional[List[str]] = None
        # runtime/placement.py strategy: fixes the channel count at lowering
        # and pins channels to workers in the distributed runtime
        self.placement = None

    def lower(self, ctx, graph, actor_of: Dict[int, int], node_id: int) -> None:
        raise NotImplementedError

    def derive_schema(self, parents: List[List[str]]) -> Optional[List[str]]:
        """Output columns derivable from the parents' schemas plus this
        node's own metadata (keys, expressions, rename maps, ...).

        Returns None when the DECLARED schema is the source of truth (sources
        and opaque user executors); otherwise returns the derived column list
        and raises ValueError when a parent is missing a column this node
        requires — the contract the plan verifier (analysis/planck.py QK021)
        checks node-by-node and optimizer.early_projection uses to keep
        interior schemas exact after source pruning."""
        return None

    def describe(self) -> str:
        return type(self).__name__


def _require(cols, parent: List[str], what: str) -> None:
    missing = [c for c in cols if c not in set(parent)]
    if missing:
        raise ValueError(f"{what} references columns {missing} not in input {parent}")


class SourceNode(Node):
    def __init__(self, reader, schema: List[str], sorted_by=None):
        super().__init__([], schema)
        self.reader = reader
        self.sorted_by = sorted_by
        self.predicate: Optional[Expr] = None  # pushed-down filter
        self.projection: Optional[List[str]] = None  # pushed-down column set

    def lower(self, ctx, graph, actor_of, node_id):
        reader = self.reader
        if self.predicate is not None and hasattr(reader, "predicate"):
            reader.predicate = self.predicate  # row-group pruning
        if self.projection is not None and hasattr(reader, "columns"):
            reader.columns = list(self.projection)
        actor_of[node_id] = graph.new_input_reader_node(
            reader,
            self.channels or ctx.io_channels,
            self.stage,
            self.sorted_by,
            predicate=self.predicate,
            projection=self.projection,
        )
        # plan-independent scan identity: the cardprofile records this
        # scan's measured rows/bytes under it, and the cost model
        # (planner/cost.py) looks the figure up at the NEXT plan time —
        # before any fingerprint for the next plan can exist
        from quokka_tpu.planner.cost import source_signature

        graph.actors[actor_of[node_id]].src_sig = source_signature(
            reader, self.predicate, self.projection)

    def describe(self):
        d = f"Source({type(self.reader).__name__}"
        if self.predicate is not None:
            d += f", filter={self.predicate.sql()}"
        if self.projection is not None:
            d += f", cols={self.projection}"
        return d + ")"


def _passthrough_edge():
    return TargetInfo(PassThroughPartitioner())


@dataclasses.dataclass
class SelectFn:
    """Picklable per-batch projection (executor factories must cross process
    boundaries for the multi-worker runtime)."""

    cols: List[str]

    def __call__(self, b):
        return b.select(self.cols)


@dataclasses.dataclass
class RenameFn:
    mapping: Dict[str, str]

    def __call__(self, b):
        return b.rename(self.mapping)


@dataclasses.dataclass
class WithColumnsFn:
    """Picklable with_columns map: compiles its expressions per batch."""

    exprs: Dict[str, Expr]

    def __call__(self, b):
        for name, e in self.exprs.items():
            b = b.with_column(name, evaluate_to_column(e, b))
        return b


class FilterNode(Node):
    def __init__(self, parents, schema, predicate: Expr):
        super().__init__(parents, schema)
        self.predicate = predicate

    def derive_schema(self, parents):
        _require(self.predicate.required_columns(), parents[0], "filter predicate")
        return list(parents[0])

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import UDFExecutor
        from quokka_tpu.ops.fuse import FusedPredicate

        pred = self.predicate
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(UDFExecutor, FusedPredicate(pred)),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            self.channels or ctx.exec_channels,
            self.stage,
            sorted_actor=self.sorted_by is not None,
        )

    def describe(self):
        return f"Filter({self.predicate.sql()})"


class ProjectionNode(Node):
    def __init__(self, parents, schema):
        super().__init__(parents, schema)

    def derive_schema(self, parents):
        _require(self.schema, parents[0], "projection")
        return list(self.schema)

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import UDFExecutor

        cols = list(self.schema)
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(UDFExecutor, SelectFn(cols)),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            self.channels or ctx.exec_channels,
            self.stage,
            sorted_actor=self.sorted_by is not None,
        )

    def describe(self):
        return f"Projection({self.schema})"


class MapNode(Node):
    """with_columns / rename / transform: a per-batch device function.
    ``exprs`` (when set) makes the map foldable by the optimizer.

    Every MapNode must carry EXPLICIT output-schema metadata — one of
    ``exprs`` (with_columns), ``rename`` (a column-rename map), or
    ``declared=True`` (an opaque UDF whose declared schema is trusted).  A
    bare fn with none of the three has no derivable output schema and fails
    plan verification (QK021)."""

    def __init__(self, parents, schema, fn: Callable, exprs: Optional[Dict[str, Expr]] = None,
                 rename: Optional[Dict[str, str]] = None, declared: bool = False):
        super().__init__(parents, schema)
        self.fn = fn
        self.exprs = exprs
        self.rename = rename
        self.declared = declared
        self.folded = False  # set by optimizer.fold_maps: ride the edge

    def derive_schema(self, parents):
        if self.exprs is not None:
            for k, e in self.exprs.items():
                _require(e.required_columns(), parents[0], f"map expr {k}")
            # with_column replaces in place when present, appends when new —
            # mirror DeviceBatch.with_column exactly
            return list(parents[0]) + [k for k in self.exprs if k not in set(parents[0])]
        if self.rename is not None:
            # a mapping key absent from the input is a no-op (matches
            # DeviceBatch.rename), so only the output list is derived
            return [self.rename.get(c, c) for c in parents[0]]
        if self.declared:
            return None  # opaque UDF: the declared schema is the contract
        raise ValueError("MapNode without exprs/rename/declared schema metadata")

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import UDFExecutor

        fn = self.fn
        if self.folded:
            # no actor: the map becomes a batch_func on every edge leaving
            # the parent's actor (optimizer.fold_maps guarantees this node is
            # the parent's only consumer)
            src = actor_of[self.parents[0]]
            actor_of[node_id] = src
            graph.add_pending_batch_fn(src, fn)
            return
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(UDFExecutor, fn),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            self.channels or ctx.exec_channels,
            self.stage,
            sorted_actor=self.sorted_by is not None,
        )

    def describe(self):
        label = "FoldedMap" if self.folded else "Map"
        if self.exprs:
            return f"{label}(" + ", ".join(f"{k}={v.sql()}" for k, v in self.exprs.items()) + ")"
        return f"{label}(udf)"


class StatefulNode(Node):
    """User-provided executor (stateful_transform / custom operators)."""

    def __init__(self, parents, schema, executor_factory, partitioners=None, sorted_output=None):
        super().__init__(parents, schema)
        self.executor_factory = executor_factory
        self.partitioners = partitioners or {}
        self.sorted_by = sorted_output

    def lower(self, ctx, graph, actor_of, node_id):
        self._lower_with(self.executor_factory, ctx, graph, actor_of, node_id)

    def _lower_with(self, executor_factory, ctx, graph, actor_of, node_id):
        sources = {}
        for i, p in enumerate(self.parents):
            part = self.partitioners.get(i, PassThroughPartitioner())
            sources[i] = (actor_of[p], TargetInfo(part))
        actor_of[node_id] = graph.new_exec_node(
            executor_factory,
            sources,
            self.channels or ctx.exec_channels,
            self.stage,
            sorted_actor=self.sorted_by is not None,
        )

    def describe(self):
        return "Stateful"


class AsofJoinNode(StatefulNode):
    """As-of join (OrderedStream.join_asof).  A StatefulNode for the engine
    path (SortedAsofExecutor does streaming frontier matching), but carries
    the join parameters so the mesh path can run it as one shard_map program
    (hash-shuffle both sides by the `by` keys over ICI, then the
    data-parallel sort+scan asof kernel per shard — parallel/mesh_exec.
    mesh_asof).  Reference: pyquokka/orderedstream.py:37 join_asof."""

    def __init__(self, parents, schema, executor_factory, partitioners,
                 sorted_output, *, left_on, right_on, left_by, right_by,
                 suffix, direction):
        super().__init__(parents, schema, executor_factory, partitioners,
                         sorted_output)
        self.left_on = left_on
        self.right_on = right_on
        self.left_by = list(left_by)
        self.right_by = list(right_by)
        self.suffix = suffix
        self.direction = direction

    def derive_schema(self, parents):
        _require([self.left_on] + self.left_by, parents[0], "asof left keys")
        _require([self.right_on] + self.right_by, parents[1], "asof right keys")
        rpayload = [c for c in parents[1]
                    if c not in set(self.right_by) and c != self.right_on]
        return list(parents[0]) + [
            c + self.suffix if c in set(parents[0]) else c for c in rpayload
        ]

    def lower(self, ctx, graph, actor_of, node_id):
        """The executor learns what the plan knows of its two sources: a
        ladder rung over each reader's row count (an upper bound on what
        any channel can be sent), or None where the parent is no reader or
        the reader cannot say.  Its buffers take that capacity, so the
        programs it asks for follow the plan (``SortedAsofExecutor``)."""
        from quokka_tpu import config

        def rung(parent):
            fn = getattr(graph.actors[actor_of[parent]].reader, "num_rows",
                         None)
            rows = None if fn is None else fn()
            if rows is None or rows > config.MAX_BUCKET:
                return None  # unknown, or past the ladder: buffers double
            return config.bucket_size(rows)

        capacity = tuple(rung(p) for p in self.parents)
        self._lower_with(
            functools.partial(self.executor_factory, capacity=capacity),
            ctx, graph, actor_of, node_id)

    def describe(self):
        return f"AsofJoin({self.direction} on {self.left_on})"


class WindowAggNode(StatefulNode):
    """Window aggregation (OrderedStream.window_agg).  A StatefulNode for the
    streaming engine path, carrying window parameters so the mesh path can
    run tumbling/hopping windows as a window-id group-by in one shard_map
    (parallel/mesh_exec.mesh_window_agg).  Reference: pyquokka/datastream.py
    windowed_transform + windowtypes compilation."""

    def __init__(self, parents, schema, executor_factory, partitioners,
                 sorted_output, *, time_col, by, window, plan, trigger):
        super().__init__(parents, schema, executor_factory, partitioners,
                         sorted_output)
        self.time_col = time_col
        self.by = list(by)
        self.window = window
        self.plan = plan
        self.trigger = trigger

    def derive_schema(self, parents):
        from quokka_tpu import windows as W

        _require([self.time_col] + self.by, parents[0], "window keys")
        for name, e in self.plan.pre:
            _require(e.required_columns(), parents[0], f"window agg input {name}")
        finals = [n for n, _ in self.plan.finals]
        if isinstance(self.window, W.SlidingWindow):
            return list(parents[0]) + finals
        if isinstance(self.window, W.SessionWindow):
            extra = ["session_start", "session_end"]
        else:
            extra = ["window_start", "window_end"]
        return list(self.by) + extra + finals

    def describe(self):
        return f"WindowAgg({type(self.window).__name__})"


class ShiftNode(StatefulNode):
    """Per-key lag (OrderedStream.shift).  StatefulNode for the streaming
    engine (ShiftExecutor carries per-key tails across batches); the mesh
    path runs it as one shard_map (shuffle by key, per-shard sort + segment
    shift — parallel/mesh_exec.mesh_shift).  Reference:
    pyquokka/orderedstream.py:13."""

    def __init__(self, parents, schema, executor_factory, partitioners,
                 sorted_output, *, time_col, by, columns, n):
        super().__init__(parents, schema, executor_factory, partitioners,
                         sorted_output)
        self.time_col = time_col
        self.by = list(by)
        self.columns = list(columns)
        self.n = n

    def derive_schema(self, parents):
        _require([self.time_col] + self.by + self.columns, parents[0], "shift")
        return list(parents[0]) + [f"{c}_shifted_{self.n}" for c in self.columns]

    def describe(self):
        return f"Shift(n={self.n})"


class JoinNode(Node):
    """Binary hash join; parents[0] = probe (stream 0), parents[1] = build."""

    def __init__(self, parents, schema, left_on, right_on, how="inner", suffix="_2",
                 broadcast=False, rename=None):
        super().__init__(parents, schema)
        self.left_on = list(left_on)
        self.right_on = list(right_on)
        self.how = how
        self.suffix = suffix
        self.broadcast = broadcast
        # plan-time build-column renames (so runtime behavior is stable even
        # when the optimizer prunes the clashing probe column)
        self.rename = rename
        self.build_parents = [1]
        # planner/decide.plan_adaptive_exchanges: this join's build edge may
        # be salted mid-query when the runtime observes partition skew
        # (inner non-broadcast joins only — see QK026)
        self.adapt_salt = False

    def derive_schema(self, parents):
        _require(self.left_on, parents[0], "join left keys")
        _require(self.right_on, parents[1], "join right keys")
        if self.how in ("semi", "anti"):
            return list(parents[0])
        rename = self.rename or {}
        rpayload = [c for c in parents[1] if c not in set(self.right_on)]
        return list(parents[0]) + [rename.get(c, c) for c in rpayload]

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import BuildProbeJoinExecutor

        left_on, right_on, how, suffix = self.left_on, self.right_on, self.how, self.suffix
        rename = self.rename
        out_schema = list(self.schema)
        if self.broadcast:
            edges = {
                0: (actor_of[self.parents[0]], _passthrough_edge()),
                1: (actor_of[self.parents[1]], TargetInfo(BroadcastPartitioner())),
            }
        else:
            edges = {
                0: (actor_of[self.parents[0]], TargetInfo(HashPartitioner(left_on))),
                1: (actor_of[self.parents[1]], TargetInfo(HashPartitioner(right_on))),
            }
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(BuildProbeJoinExecutor,
                left_on, right_on, how, suffix, rename, out_schema=out_schema
            ),
            edges,
            self.channels or ctx.exec_channels,
            self.stage,
        )
        if not self.broadcast and getattr(self, "adapt_salt", False):
            graph.adapt_edges[(actor_of[self.parents[1]],
                               actor_of[node_id])] = {
                "probe_src": actor_of[self.parents[0]],
            }

    def describe(self):
        k = "BroadcastJoin" if self.broadcast else "HashJoin"
        return f"{k}({self.how}, {self.left_on}={self.right_on})"


class AggNode(Node):
    """Decomposed group-by aggregate: a partial-agg actor on the parent's
    channels feeds a key-hash-partitioned final-agg actor.  (The TPU-first
    replacement for batch_funcs partial agg + SQLAggExecutor concat-DuckDB.)"""

    def __init__(self, parents, schema, keys: List[str], plan: AggPlan,
                 having=None, order_by=None, limit=None):
        super().__init__(parents, schema)
        self.keys = keys
        self.plan = plan
        self.having = having
        self.order_by = order_by
        self.limit = limit

    def derive_schema(self, parents):
        _require(self.keys, parents[0], "groupby keys")
        for name, e in self.plan.pre:
            _require(e.required_columns(), parents[0], f"aggregate input {name}")
        return list(self.keys) + [
            n for n, _ in self.plan.finals if n not in set(self.keys)
        ]

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import FinalAggExecutor, PartialAggExecutor

        keys, plan = self.keys, self.plan
        having, order_by, limit = self.having, self.order_by, self.limit
        partial = graph.new_exec_node(
            functools.partial(PartialAggExecutor, keys, plan),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            self.channels or ctx.exec_channels,
            self.stage,
        )
        n_final = (self.channels or ctx.exec_channels) if keys else 1
        part = HashPartitioner(keys) if keys else PassThroughPartitioner()
        final = graph.new_exec_node(
            functools.partial(FinalAggExecutor, keys, plan, having, order_by, limit),
            {0: (partial, TargetInfo(part))},
            n_final,
            self.stage,
        )
        if (order_by or limit is not None) and n_final > 1:
            # per-channel order/limit is local; merge to the global result
            from quokka_tpu.executors.sql_execs import SortExecutor, TopKExecutor

            names = [n for n, _ in (order_by or [])]
            desc = [d for _, d in (order_by or [])]
            if limit is not None:
                merge_factory = functools.partial(TopKExecutor, names, limit, desc)
            else:
                merge_factory = functools.partial(SortExecutor, names, desc)
            final = graph.new_exec_node(
                merge_factory,
                {0: (final, TargetInfo(PassThroughPartitioner()))},
                1,
                self.stage,
            )
        actor_of[node_id] = final

    def describe(self):
        return f"Agg(keys={self.keys}, out={[n for n, _ in self.plan.finals]})"


class FusedStageNode(Node):
    """A maximal fusible linear chain rewritten into ONE exec actor
    (optimizer.fuse_stages).  parents[0] is the chain head's main input;
    parents[1:] are the member joins' build sides in chain order.  Lowers to
    a single FusedStageExecutor actor (ops/stagefuse.py): consecutive
    filter/project/expression-map members collapse into one jitted
    elementwise program, and a tail AggNode contributes its partial half
    in-stage with the final-agg actors emitted exactly as AggNode.lower
    would."""

    def __init__(self, members: List[Node], parents: List[int],
                 schema: List[str]):
        super().__init__(parents, schema)
        self.members = members
        self.build_parents = list(range(1, len(parents)))

    def derive_schema(self, parents):
        # replay the member chain: member i's main input is member i-1's
        # derived output; join members consume build sides in chain order
        builds = iter(parents[1:])
        cur = list(parents[0])
        for m in self.members:
            if isinstance(m, JoinNode):
                cur = m.derive_schema([cur, list(next(builds))])
            else:
                d = m.derive_schema([cur])
                cur = list(m.schema) if d is None else d
        leftover = list(builds)
        if leftover:
            raise ValueError(
                f"fused stage has {len(leftover)} build inputs with no join member")
        return cur

    def describe(self):
        inner = "\n".join("  " + m.describe() for m in self.members)
        return "FusedStage(\n" + inner + "\n)"

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import (
            BuildProbeJoinExecutor,
            FinalAggExecutor,
            PartialAggExecutor,
            UDFExecutor,
        )
        from quokka_tpu.ops.stagefuse import (
            FusedElementwise,
            FusedStageExecutor,
            StageSpec,
        )

        steps: List[Tuple[str, Callable]] = []
        routing: Dict[int, Tuple[int, int]] = {}
        sources: Dict[int, Tuple[int, TargetInfo]] = {}
        builds = iter(self.parents[1:])
        elem: List[Tuple] = []
        agg: Optional[AggNode] = None

        def flush_elem():
            if elem:
                steps.append(("Elemwise", functools.partial(
                    UDFExecutor, FusedElementwise(list(elem)))))
                elem.clear()

        head = self.members[0]
        if isinstance(head, JoinNode) and not head.broadcast:
            sources[0] = (actor_of[head.parents[0]],
                          TargetInfo(HashPartitioner(head.left_on)))
        else:
            sources[0] = (actor_of[head.parents[0]], _passthrough_edge())
        for m in self.members:
            if isinstance(m, FilterNode):
                elem.append(("filter", m.predicate))
            elif isinstance(m, ProjectionNode):
                elem.append(("project", list(m.schema)))
            elif isinstance(m, MapNode) and m.exprs:
                elem.append(("map", list(m.exprs.items())))
            elif isinstance(m, MapNode):
                flush_elem()
                steps.append(("Map", functools.partial(UDFExecutor, m.fn)))
            elif isinstance(m, JoinNode):
                flush_elem()
                part = (BroadcastPartitioner() if m.broadcast
                        else HashPartitioner(m.right_on))
                stream = len(sources)
                sources[stream] = (actor_of[next(builds)], TargetInfo(part))
                routing[stream] = (len(steps), 1)
                label = "BroadcastJoin" if m.broadcast else "HashJoin"
                steps.append((label, functools.partial(
                    BuildProbeJoinExecutor, m.left_on, m.right_on, m.how,
                    m.suffix, m.rename, out_schema=list(m.schema))))
            elif isinstance(m, AggNode):
                flush_elem()
                steps.append(("PartialAgg", functools.partial(
                    PartialAggExecutor, m.keys, m.plan)))
                agg = m
            else:  # pragma: no cover - fuse_stages only admits the above
                raise TypeError(f"unfusible member {type(m).__name__}")
        flush_elem()
        fused = graph.new_exec_node(
            functools.partial(FusedStageExecutor, StageSpec(steps, routing)),
            sources,
            self.channels or ctx.exec_channels,
            self.stage,
        )
        # fuse_stages only admits a non-broadcast hash join at the chain
        # HEAD; its build is the fused actor's stream-1 source, so the
        # adaptive-exchange mark survives fusion as a runtime edge
        if (isinstance(head, JoinNode) and not head.broadcast
                and getattr(head, "adapt_salt", False) and 1 in sources):
            graph.adapt_edges[(sources[1][0], fused)] = {
                "probe_src": sources[0][0],
            }
        if agg is None:
            actor_of[node_id] = fused
            return
        # the tail agg's final half: identical actors to AggNode.lower, fed
        # by the fused stage's in-stage partials
        keys, plan = agg.keys, agg.plan
        n_final = (self.channels or ctx.exec_channels) if keys else 1
        part = HashPartitioner(keys) if keys else PassThroughPartitioner()
        final = graph.new_exec_node(
            functools.partial(FinalAggExecutor, keys, plan, agg.having,
                              agg.order_by, agg.limit),
            {0: (fused, TargetInfo(part))},
            n_final,
            self.stage,
        )
        if (agg.order_by or agg.limit is not None) and n_final > 1:
            from quokka_tpu.executors.sql_execs import SortExecutor, TopKExecutor

            names = [n for n, _ in (agg.order_by or [])]
            desc = [d for _, d in (agg.order_by or [])]
            if agg.limit is not None:
                merge_factory = functools.partial(
                    TopKExecutor, names, agg.limit, desc)
            else:
                merge_factory = functools.partial(SortExecutor, names, desc)
            final = graph.new_exec_node(
                merge_factory,
                {0: (final, TargetInfo(PassThroughPartitioner()))},
                1,
                self.stage,
            )
        actor_of[node_id] = final


class DistinctNode(Node):
    def __init__(self, parents, schema, keys):
        super().__init__(parents, schema)
        self.keys = keys

    def derive_schema(self, parents):
        _require(self.keys, parents[0], "distinct keys")
        return list(self.keys)

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import DistinctExecutor

        keys = self.keys
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(DistinctExecutor, keys),
            {0: (actor_of[self.parents[0]], TargetInfo(HashPartitioner(keys)))},
            self.channels or ctx.exec_channels,
            self.stage,
        )

    def describe(self):
        return f"Distinct({self.keys})"


class TopKNode(Node):
    def __init__(self, parents, schema, by, k, descending):
        super().__init__(parents, schema)
        self.by = by
        self.k = k
        self.descending = descending

    def derive_schema(self, parents):
        _require(self.by, parents[0], "top_k keys")
        return list(parents[0])

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import TopKExecutor

        by, k, desc = self.by, self.k, self.descending
        local = graph.new_exec_node(
            functools.partial(TopKExecutor, by, k, desc),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            self.channels or ctx.exec_channels,
            self.stage,
        )
        actor_of[node_id] = graph.new_exec_node(
            functools.partial(TopKExecutor, by, k, desc),
            {0: (local, _passthrough_edge())},
            1,
            self.stage,
        )

    def describe(self):
        return f"TopK({self.by}, k={self.k})"


class SortNode(Node):
    """Global sort.  When the upstream chain is sampleable, boundaries come
    from a sample and the sort runs range-partitioned in parallel (channel i
    owns value range i; ordered channel concat is globally sorted — the
    parallel discipline of SuperFastSortExecutor, sql_executors.py:88).
    Otherwise falls back to a single-channel blocking sort."""

    def __init__(self, parents, schema, by, descending):
        super().__init__(parents, schema)
        self.by = by
        self.descending = descending
        self.boundaries = None  # filled by the optimizer/sampling when possible

    def derive_schema(self, parents):
        _require(self.by, parents[0], "sort keys")
        return list(parents[0])

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import SortExecutor

        by, desc = self.by, self.descending
        n = self.channels or ctx.exec_channels
        if self.boundaries is not None and n > 1:
            bounds = list(self.boundaries)
            # descending: reversed range ownership keeps channel-order concat
            # equal to the requested global order
            edge = TargetInfo(
                RangePartitioner(by[0], bounds, descending=bool(desc and desc[0]))
            )
            actor_of[node_id] = graph.new_exec_node(
                functools.partial(SortExecutor, by, desc),
                {0: (actor_of[self.parents[0]], edge)},
                n,
                self.stage,
                # consumers must drain channel 0's whole range before channel
                # 1's — channel-major delivery (SAT's (seq, channel)
                # interleave breaks once a spilled sort emits multiple seqs)
                channel_major=True,
            )
        else:
            actor_of[node_id] = graph.new_exec_node(
                functools.partial(SortExecutor, by, desc),
                {0: (actor_of[self.parents[0]], _passthrough_edge())},
                1,
                self.stage,
                sorted_actor=True,
            )
        self.sorted_by = list(by)

    def describe(self):
        par = f", parallel x{self.channels or '?'}" if self.boundaries else ""
        return f"Sort({self.by}{par})"


class SinkNode(Node):
    """Blocking collect target (DataSetNode in the reference)."""

    def __init__(self, parents, schema):
        super().__init__(parents, schema)

    def derive_schema(self, parents):
        # the sink SELECTS its declared columns (SelectingStorageExecutor);
        # a superset input is legal, a missing column is not
        _require(self.schema, parents[0], "collect")
        return list(self.schema)

    def lower(self, ctx, graph, actor_of, node_id):
        from quokka_tpu.executors.sql_execs import SelectingStorageExecutor

        actor_of[node_id] = graph.new_exec_node(
            functools.partial(SelectingStorageExecutor, list(self.schema)),
            {0: (actor_of[self.parents[0]], _passthrough_edge())},
            1,
            self.stage,
            blocking=True,
        )

    def describe(self):
        return "Collect"
