"""Logical-plan optimizer.

Pass lineup mirrors the reference driver (pyquokka/df.py:887-907):
  1. push_filters      — predicate pushdown per CNF conjunct, through
                         projections/maps/joins down into source readers
                         (df.py:1029-1139 + parquet pushdown); a filter
                         over a group-by becomes its HAVING
  2. early_projection  — column-requirement analysis; prunes the column set
                         each source actually reads (df.py:1141-1262)
  3. choose_broadcast  — catalog-estimated small build sides switch their
                         shuffle join to a broadcast join (the cardinality
                         role of df.py:1401-1513's join ordering)
Stage assignment (df.py:1530-1621) runs afterwards in context._assign_stages.
All passes are pure rewrites of the node dict; nodes a rewrite disconnects
are garbage-collected between passes (pass_pipeline), so the dict always
holds exactly the live graph.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

from quokka_tpu import logical
from quokka_tpu.expression import Expr, conjoin, rename_columns, split_conjuncts, substitute_columns

BROADCAST_THRESHOLD = 65_536  # build rows below this skip the probe-side shuffle


def pass_pipeline(exec_channels: int = 2):
    """The canonical pass lineup as (name, fn(sub, sink_id)) pairs — the
    unit of pass-level verification (analysis/planck.py) and of the plan
    fuzzer's pass-subset differential (analysis/planfuzz.py)."""
    def wrap(fn):
        def run(sub, sid):
            fn(sub, sid)
            # rewrites leave disconnected leftovers behind (a pushed
            # filter's original node); collect them so the plan dict holds
            # exactly the live graph — EXPLAIN and the plan verifier scan it
            live = _reachable(sub, sid)
            for nid in set(sub) - set(live):
                del sub[nid]
            # structural passes may stale interior schemas (a swapped
            # filter, a pruned source): re-derive so declared stays exact
            _recompute_schemas(sub, live)

        return run

    # cost-fed passes live in planner/decide.py; imported lazily because
    # decide consumes this module's chain-walk and catalog helpers
    from quokka_tpu.planner import decide

    return [
        (name, wrap(fn))
        for name, fn in [
            ("push_filters", push_filters),
            ("early_projection", early_projection),
            ("reorder_joins", decide.reorder_joins_cost),
            ("choose_broadcast", decide.choose_broadcast_cost),
            ("size_channels",
             lambda sub, sid: decide.size_channels(sub, sid, exec_channels)),
            ("plan_adaptive_exchanges", decide.plan_adaptive_exchanges),
            ("plan_parallel_sorts",
             lambda sub, sid: plan_parallel_sorts(sub, sid, exec_channels)),
            ("push_ann", push_ann),
            ("fold_maps", fold_maps),
            ("fuse_stages", fuse_stages),
        ]
    ]


def optimize(sub: Dict[int, logical.Node], sink_id: int,
             exec_channels: int = 2) -> int:
    """Run the full pass pipeline.  Under QK_PLAN_VERIFY=1 every pass's
    (before, after) pair is checked against the plan invariants QK021-QK024;
    a violation raises PlanInvariantError naming the pass and the offending
    node (never on the push path — this is all plan-time)."""
    from quokka_tpu.analysis import planck

    verify = planck.enabled()
    if verify:
        planck.verify_plan(sub, sink_id, where="pre-optimize")
    for name, fn in pass_pipeline(exec_channels):
        before = planck.digest(sub, sink_id) if verify else None
        fn(sub, sink_id)
        if verify:
            planck.verify_pass(sub, sink_id, name, before)
    if verify:
        planck.finish_plan()
    return sink_id


def push_ann(sub: Dict[int, logical.Node], sink_id: int) -> None:
    """Approximate nearest-neighbor pushdown (df.py:1264-1352 push_ann):
    an opted-in nearest_neighbors over an IVF-indexed Parquet source prunes
    the scan to row groups owning the queries' closest cells."""
    # readers are shared with the user's plan object: reset first so a prior
    # approximate query can't leak pruning into a later exact one
    for nid in _reachable(sub, sink_id):
        node = sub[nid]
        if isinstance(node, logical.SourceNode) and hasattr(node.reader, "ann_prune"):
            node.reader.ann_prune = None
    cons = _consumers(sub, sink_id)
    for nid in _reachable(sub, sink_id):
        node = sub[nid]
        info = getattr(node, "ann_info", None)
        if info is None:
            continue
        # the walked chain (including the source) must feed ONLY this ANN
        # branch — pruning a shared source would drop rows from exact branches
        cur_id = node.parents[0]
        ok = True
        guard = 0
        while guard < 16:
            guard += 1
            if len(cons.get(cur_id, [])) > 1:
                ok = False
                break
            cur = sub[cur_id]
            if isinstance(cur, (logical.ProjectionNode, logical.FilterNode)):
                cur_id = cur.parents[0]
                continue
            break
        if not ok:
            continue
        cur = sub[cur_id]
        if isinstance(cur, logical.SourceNode) and hasattr(cur.reader, "ann_prune"):
            cur.reader.ann_prune = (info["queries"], info["nprobe"])


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _consumers(sub: Dict[int, logical.Node], sink_id: int) -> Dict[int, List[int]]:
    cons: Dict[int, List[int]] = {nid: [] for nid in _reachable(sub, sink_id)}
    for nid in list(cons):
        for p in sub[nid].parents:
            cons[p].append(nid)
    return cons


def _reachable(sub: Dict[int, logical.Node], sink_id: int) -> List[int]:
    out, seen = [], set()

    def rec(nid):
        if nid in seen:
            return
        seen.add(nid)
        for p in sub[nid].parents:
            rec(p)
        out.append(nid)

    rec(sink_id)
    return out


def _relink(sub, sink_id, old: int, new: int) -> None:
    """Point every consumer of `old` at `new` (removing `old` from the plan)."""
    for nid in _reachable(sub, sink_id):
        node = sub[nid]
        node.parents = [new if p == old else p for p in node.parents]


# ---------------------------------------------------------------------------
# 1. predicate pushdown
# ---------------------------------------------------------------------------


def push_filters(sub: Dict[int, logical.Node], sink_id: int) -> None:
    changed = True
    rounds = 0
    while changed and rounds < 100:
        changed = False
        rounds += 1
        for nid in _reachable(sub, sink_id):
            node = sub.get(nid)
            if not isinstance(node, logical.FilterNode):
                continue
            cons = _consumers(sub, sink_id)
            if not cons.get(nid):
                continue  # a root filter cannot be removed after its push
            parent = sub[node.parents[0]]
            if _try_push_one(sub, sink_id, nid, node, node.parents[0], parent, cons):
                changed = True
                break


def _try_push_one(sub, sink_id, fid, fnode, pid, parent, cons) -> bool:
    pred = fnode.predicate
    parent_shared = len(cons.get(pid, [])) > 1

    if isinstance(parent, logical.FilterNode):
        parent_pred = parent.predicate
        if parent_shared:
            return False
        fnode.predicate = conjoin([parent_pred, pred])
        fnode.parents = list(parent.parents)
        return True

    if isinstance(parent, logical.SourceNode):
        if parent_shared:
            return False
        parent.predicate = (
            pred if parent.predicate is None else conjoin([parent.predicate, pred])
        )
        _relink(sub, sink_id, fid, pid)
        return True

    if isinstance(parent, (logical.ProjectionNode, logical.SortNode, logical.DistinctNode)):
        if parent_shared:
            return False
        # swap: filter below, parent above.  The filter now sees the
        # parent's INPUT: inherit that node's order metadata (pushing below
        # a sort means the filter's input is no longer sorted — QK024)
        fnode.parents = list(parent.parents)
        fnode.sorted_by = _copy_order(sub[fnode.parents[0]])
        parent.parents = [fid]
        _relink_except(sub, sink_id, fid, pid, skip=pid)
        return True

    if isinstance(parent, logical.AggNode) and parent.limit is None:
        if parent_shared:
            return False
        # a filter over a group-by's output is its HAVING: the final
        # aggregate applies it before its ORDER BY and compacts what it keeps
        # (as a filter it masked the final's whole padded output)
        parent.having = (pred if parent.having is None
                         else conjoin([parent.having, pred]))
        _relink(sub, sink_id, fid, pid)
        return True

    if isinstance(parent, logical.MapNode) and parent.exprs is not None:
        if parent_shared:
            return False
        new_pred = substitute_columns(pred, parent.exprs)
        fnode.predicate = new_pred
        fnode.parents = list(parent.parents)
        fnode.sorted_by = _copy_order(sub[fnode.parents[0]])
        parent.parents = [fid]
        _relink_except(sub, sink_id, fid, pid, skip=pid)
        return True

    if isinstance(parent, logical.JoinNode):
        left_schema = set(sub[parent.parents[0]].schema)
        right = sub[parent.parents[1]]
        rename = parent.rename or {}
        unsuffix = {}
        for c in right.schema:
            if c in set(parent.right_on):
                continue
            unsuffix[rename.get(c, c)] = c
        remaining = []
        pushed = False
        for conj in split_conjuncts(pred):
            req = conj.required_columns()
            if req <= left_schema and parent.how in ("inner", "left", "semi", "anti"):
                _insert_filter_above(sub, parent, 0, conj)
                pushed = True
            elif req <= set(unsuffix) and parent.how == "inner":
                _insert_filter_above(sub, parent, 1, rename_columns(conj, unsuffix))
                pushed = True
            else:
                remaining.append(conj)
        if not pushed:
            return False
        if remaining:
            fnode.predicate = conjoin(remaining)
        else:
            _relink(sub, sink_id, fid, pid)
        return True

    return False


def _copy_order(node: logical.Node):
    return list(node.sorted_by) if node.sorted_by is not None else None


def _relink_except(sub, sink_id, fid, pid, skip):
    """After swapping filter below `pid`: consumers of fid (other than pid)
    should now consume pid."""
    for nid in _reachable(sub, sink_id):
        if nid in (fid, skip):
            continue
        node = sub[nid]
        node.parents = [pid if p == fid else p for p in node.parents]


def _insert_filter_above(sub, join_node: logical.JoinNode, side: int, conj: Expr):
    parent_id = join_node.parents[side]
    new_id = max(sub) + 1
    sub[new_id] = logical.FilterNode([parent_id], list(sub[parent_id].schema), conj)
    join_node.parents[side] = new_id


# ---------------------------------------------------------------------------
# 2. early projection
# ---------------------------------------------------------------------------


def early_projection(sub: Dict[int, logical.Node], sink_id: int) -> None:
    order = _reachable(sub, sink_id)
    req: Dict[int, Set[str]] = {nid: set() for nid in order}
    req[sink_id] = set(sub[sink_id].schema)
    for nid in reversed(order):
        node = sub[nid]
        need = req[nid] | set()
        if isinstance(node, logical.SinkNode):
            need = set(node.schema)
        # a with_columns output nobody consumes must be PRUNED, not just
        # skipped in the requirement walk: the runtime map computes every
        # expr it carries, so its inputs would otherwise need to survive
        # source pruning (planfuzz-found: dead expr over a pruned column)
        if isinstance(node, logical.MapNode) and node.exprs is not None \
                and any(k not in need for k in node.exprs):
            node.exprs = {k: e for k, e in node.exprs.items() if k in need}
            node.fn = logical.WithColumnsFn(node.exprs)
        for i, pid in enumerate(node.parents):
            req[pid] |= _needed_from_parent(sub, node, i, need)
    for nid in order:
        node = sub[nid]
        if isinstance(node, logical.SourceNode):
            keep = set(req[nid])
            if node.predicate is not None:
                keep |= node.predicate.required_columns()
            # a sorted source's order columns stay readable: downstream
            # ordered operators key off them and the plan invariant
            # (QK024) requires sorted_by ⊆ schema
            keep |= set(node.sorted_by or [])
            needed = [c for c in node.schema if c in keep]
            if 0 < len(needed) < len(node.schema):
                node.projection = needed
                node.schema = needed
    _recompute_schemas(sub, order)


def _recompute_schemas(sub: Dict[int, logical.Node], order: List[int]) -> None:
    """Re-derive interior output schemas after source pruning, so every
    node's declared schema stays EXACTLY what the runtime will produce
    (planck QK021 checks declared == derived).  Nodes whose declared schema
    is the source of truth (sources, opaque UDFs) return None and keep it;
    a derivation error here is left for the plan verifier to report."""
    for nid in order:
        node = sub[nid]
        if not node.parents:
            continue
        try:
            d = node.derive_schema([list(sub[p].schema) for p in node.parents])
        except (ValueError, KeyError):
            continue
        if d is not None:
            node.schema = d


def _needed_from_parent(sub, node: logical.Node, i: int, need: Set[str]) -> Set[str]:
    parent_schema = set(sub[node.parents[i]].schema)
    if isinstance(node, logical.FilterNode):
        return (need | node.predicate.required_columns()) & parent_schema
    if isinstance(node, logical.ProjectionNode):
        return set(node.schema) & parent_schema
    if isinstance(node, logical.MapNode):
        if node.exprs is None:
            return parent_schema  # opaque UDF: keep everything
        out = set()
        for c in need:
            if c in node.exprs:
                out |= node.exprs[c].required_columns()
            else:
                out.add(c)
        return out & parent_schema
    if isinstance(node, logical.AggNode):
        out = set(node.keys)
        for _, e in node.plan.pre:
            out |= e.required_columns()
        return out & parent_schema
    if isinstance(node, logical.JoinNode):
        if i == 0:
            return ((need & parent_schema) | set(node.left_on)) & parent_schema
        right = sub[node.parents[1]]
        rename = node.rename or {}
        out = set(node.right_on)
        for c in right.schema:
            if rename.get(c, c) in need:
                out.add(c)
        return out & parent_schema
    if isinstance(node, (logical.SortNode, logical.TopKNode)):
        return (need | set(node.by)) & parent_schema
    if isinstance(node, logical.DistinctNode):
        return set(node.keys) & parent_schema
    if isinstance(node, logical.StatefulNode):
        return parent_schema
    return need & parent_schema if need else parent_schema


# ---------------------------------------------------------------------------
# 3. broadcast join selection
# ---------------------------------------------------------------------------


_CATALOG = None


def _get_catalog():
    from quokka_tpu.catalog import Catalog

    global _CATALOG
    if _CATALOG is None:
        _CATALOG = Catalog()
    return _CATALOG


def choose_broadcast(sub: Dict[int, logical.Node], sink_id: int) -> None:
    cat = _get_catalog()
    for nid in _reachable(sub, sink_id):
        node = sub[nid]
        if not isinstance(node, logical.JoinNode) or node.broadcast:
            continue
        if node.how not in ("inner", "semi", "anti", "left"):
            continue
        est = _estimate_subtree(sub, node.parents[1], cat)
        if est is not None and est <= BROADCAST_THRESHOLD:
            node.broadcast = True


def fold_maps(sub: Dict[int, logical.Node], sink_id: int) -> None:
    """Fold expression-only MapNodes into their consumer edges
    (df.py:1354-1399 fold_map): instead of a separate actor hop, the map runs
    as a TargetInfo.batch_func inside the producer's partition function
    (engine executes batch_funcs at push time, runtime/engine.py).  Safe only
    when the map's parent has no OTHER consumer — the map rides every edge
    leaving the parent's actor."""
    cons = _consumers(sub, sink_id)
    for nid in _reachable(sub, sink_id):
        node = sub.get(nid)
        if not isinstance(node, logical.MapNode) or node.exprs is None:
            continue
        if getattr(node, "folded", False):
            continue
        pid = node.parents[0]
        if len(cons.get(pid, [])) != 1:
            continue
        parent = sub[pid]
        if isinstance(parent, logical.SourceNode):
            continue  # the source predicate path already fuses; keep readers lean
        node.folded = True


def _fusible_member(node: logical.Node) -> bool:
    """May this node live inside a fused stage?  Non-blocking, streaming,
    unordered, placement-free operators only — exactly the set
    FusedStageNode.lower knows how to turn into in-stage steps."""
    if node.sorted_by is not None or node.placement is not None:
        return False
    return isinstance(node, (
        logical.FilterNode,
        logical.ProjectionNode,
        logical.MapNode,
        logical.JoinNode,
        logical.AggNode,
    ))


def fuse_stages(sub: Dict[int, logical.Node], sink_id: int) -> None:
    """Whole-stage fusion (ROADMAP item 1, ops/stagefuse.py): rewrite each
    maximal single-consumer linear chain of fusible operators into ONE
    FusedStageNode, so the whole chain runs inside one exec dispatch with no
    store round-trip between members.  Chain rules:

    - extension follows the consumer's MAIN input (parents[0]) only, and only
      while the producer has exactly one consumer;
    - a non-broadcast hash join may only HEAD a chain (its probe-side hash
      edge partitions the stage's stream 0); interior joins must be broadcast
      — a hash build mid-chain would need the probe re-partitioned by a
      different key than the stage's input edge delivers;
    - an AggNode terminates the chain (its partial half fuses in-stage, the
      final half stays a separate key-partitioned actor);
    - blocking operators (sort, top-k, distinct, sinks) and stateful/ordered
      nodes never fuse;
    - 1-member "chains" are left untouched.

    Runs LAST: it consumes the shapes the earlier passes settle (broadcast
    choices, folded maps, reordered joins).  QK_STAGE_FUSE=0 disables it.
    """
    from quokka_tpu import config

    if not config.stage_fuse_enabled():
        return
    cons = _consumers(sub, sink_id)
    absorbed: Set[int] = set()
    for nid in _reachable(sub, sink_id):
        if nid in absorbed:
            continue
        node = sub.get(nid)
        if node is None or not _fusible_member(node):
            continue
        if isinstance(node, logical.AggNode):
            continue  # terminal-only: an agg heads nothing
        members = [node]
        ids = [nid]
        cur = nid
        while True:
            c = cons.get(cur, [])
            if len(c) != 1:
                break
            nxt = sub[c[0]]
            if nxt.parents[0] != cur:
                break  # we feed a build side, not the main input
            if not _fusible_member(nxt):
                break
            if isinstance(nxt, logical.JoinNode) and not nxt.broadcast:
                break
            members.append(nxt)
            ids.append(c[0])
            cur = c[0]
            if isinstance(nxt, logical.AggNode):
                break
        if len(members) < 2:
            continue
        chans = {m.channels for m in members if m.channels is not None}
        if len(chans) > 1:
            continue  # members pinned to conflicting widths
        tail = members[-1]
        tail_id = ids[-1]
        parents = [members[0].parents[0]] + [
            m.parents[1] for m in members if isinstance(m, logical.JoinNode)
        ]
        fused = logical.FusedStageNode(members, parents, list(tail.schema))
        fused.channels = chans.pop() if chans else None
        # the tail's id survives so consumers' parent links stay valid
        sub[tail_id] = fused
        for i in ids[:-1]:
            del sub[i]
        absorbed.update(ids)
        cons = _consumers(sub, sink_id)


def unfuse_stages(sub: Dict[int, logical.Node]) -> Dict[int, logical.Node]:
    """Inverse of fuse_stages, for executors that lower logical nodes
    themselves (the mesh SPMD path): expand every FusedStageNode back into
    its member chain.  Fusion never rewrote the members' own parent links —
    member[i].parents[0] still names member[i-1]'s pre-fusion id and the
    tail kept its id — so the original graph is recoverable exactly.
    Returns a new dict; the caller's (fused) plan is untouched."""
    out = dict(sub)
    for nid, node in sub.items():
        if not isinstance(node, logical.FusedStageNode):
            continue
        ids = [m.parents[0] for m in node.members[1:]] + [nid]
        for mid, m in zip(ids, node.members):
            out[mid] = m
    return out


def reorder_joins(sub: Dict[int, logical.Node], sink_id: int,
                  estimate=None, on_reorder=None, basis_of=None) -> None:
    """Greedy cardinality ordering of left-deep inner-join chains
    (df.py:1401-1513 merged multi-joins + 1570-1594 ordering): collect the
    chain's build subtrees, estimate each, and re-attach them smallest-first
    subject to key availability (snowflake joins whose keys come from an
    earlier dimension keep their dependency order).  Only applies when no
    column renames are involved and payload names are globally unique, so
    output schemas are order-independent.

    ``estimate(nid) -> Optional[float]`` overrides the catalog sampler
    (planner/decide.py feeds cost-model figures through here);
    ``on_reorder(chain_ids, before, after, basis)`` observes each applied
    reorder, with ``basis_of(nid)`` labelling the estimates' provenance."""
    cat = _get_catalog()
    if estimate is None:
        estimate = lambda nid: _estimate_subtree(sub, nid, cat)  # noqa: E731
    cons = _consumers(sub, sink_id)

    def chain_join(nid) -> bool:
        n = sub.get(nid)
        return (
            isinstance(n, logical.JoinNode)
            and n.how == "inner"
            and not n.broadcast
            and not (n.rename or {})
        )

    for nid in _reachable(sub, sink_id):
        if not chain_join(nid):
            continue
        # only start from the TOP of a chain
        c = cons.get(nid, [])
        if (
            len(c) == 1
            and chain_join(c[0])
            and sub[c[0]].parents[0] == nid
        ):
            continue
        chain: List[int] = []  # top-down join node ids
        cur = nid
        while chain_join(cur):
            chain.append(cur)
            pid = sub[cur].parents[0]
            if not chain_join(pid) or len(cons.get(pid, [])) != 1:
                break
            cur = pid
        if len(chain) < 2:
            continue
        base_id = sub[chain[-1]].parents[0]
        base_schema = list(sub[base_id].schema)
        levels = []  # bottom-up original order
        names = set(base_schema)
        ok = True
        for jid in reversed(chain):
            j = sub[jid]
            payload = [c for c in sub[j.parents[1]].schema if c not in set(j.right_on)]
            if any(p in names for p in payload):
                ok = False
                break
            names |= set(payload)
            est = estimate(j.parents[1])
            if est is None:
                ok = False
                break
            levels.append({
                "build": j.parents[1], "left_on": list(j.left_on),
                "right_on": list(j.right_on), "payload": payload, "est": est,
            })
        if not ok:
            continue
        # greedy: among joins whose keys are available, take the smallest build
        avail = set(base_schema)
        remaining = levels[:]
        order = []
        while remaining:
            cands = [lv for lv in remaining if set(lv["left_on"]) <= avail]
            if not cands:
                order = None
                break
            pick = min(cands, key=lambda lv: lv["est"])
            order.append(pick)
            remaining.remove(pick)
            avail |= set(pick["payload"])
        if order is None or order == levels:
            continue
        if on_reorder is not None:
            basis = "sampled"
            if basis_of is not None:
                ranks = {"hint": 0, "sampled": 1, "measured": 2}
                basis = min((basis_of(lv["build"]) for lv in levels),
                            key=lambda b: ranks.get(b, 0))
            on_reorder(
                chain, [lv["build"] for lv in levels],
                [lv["build"] for lv in order], basis)
        # reuse the chain's node ids positionally (bottom-up) so the top node
        # keeps its id and consumers stay untouched
        prev_id, prev_schema = base_id, base_schema
        for jid, lv in zip(reversed(chain), order):
            j = sub[jid]
            j.parents = [prev_id, lv["build"]]
            j.left_on = lv["left_on"]
            j.right_on = lv["right_on"]
            j.schema = prev_schema + lv["payload"]
            prev_id, prev_schema = jid, list(j.schema)


def plan_parallel_sorts(sub: Dict[int, logical.Node], sink_id: int,
                        exec_channels: int) -> None:
    """Give global sorts range boundaries from a source sample so they run
    partitioned across channels instead of on one."""
    if exec_channels < 2:
        return
    _get_catalog()
    for nid in _reachable(sub, sink_id):
        node = sub[nid]
        if not isinstance(node, logical.SortNode) or node.boundaries is not None:
            continue
        if len(node.by) != 1:
            continue
        col = node.by[0]
        sample = _sample_subtree(sub, node.parents[0], _CATALOG)
        if sample is None or sample.num_rows < 4 * exec_channels:
            continue
        if col not in sample.column_names:
            continue
        import numpy as np
        import pyarrow as pa

        arr = sample.column(col)
        t = arr.type
        if not (pa.types.is_integer(t) or pa.types.is_floating(t)
                or pa.types.is_date32(t)):
            continue  # string/timestamp boundaries: single-channel fallback
        vals = arr.combine_chunks().drop_null().cast(
            pa.int64() if pa.types.is_date32(t) else t
        ).to_numpy(zero_copy_only=False)
        if pa.types.is_floating(t):
            vals = vals[~np.isnan(vals)]
        if len(vals) < 4 * exec_channels:
            continue
        qs = np.quantile(vals, [i / exec_channels for i in range(1, exec_channels)])
        if pa.types.is_integer(t) or pa.types.is_date32(t):
            qs = np.unique(qs.astype(np.int64))
        else:
            qs = np.unique(qs)
        # spread sanity: degenerate/clustered samples (all quantiles at one
        # extreme) would route everything to one channel — fall back instead
        if (
            len(qs) == exec_channels - 1
            and vals.min() < qs[0]
            and qs[-1] < vals.max()
        ):
            node.boundaries = qs.tolist()
            node.channels = exec_channels


def _sample_subtree(sub, nid: int, cat):
    """Sample rows flowing out of a Filter/Projection/Map chain over a source
    (applies the chain's predicates to the sample)."""
    node = sub[nid]
    preds = []
    guard = 0
    while guard < 64:
        guard += 1
        if isinstance(node, logical.SourceNode):
            sample = cat._sample(node.reader)
            if sample is None or sample.num_rows == 0:
                return None
            all_preds = preds + (
                [node.predicate] if node.predicate is not None else []
            )
            if all_preds:
                from quokka_tpu.ops import bridge, kernels
                from quokka_tpu.ops.expr_compile import evaluate_predicate

                # project down before bridging: the full schema may contain
                # columns the bridge can't represent (structs/lists) that the
                # query never touches
                import numpy as np
                import pyarrow as pa

                needed = set()
                for p in all_preds:
                    needed |= p.required_columns()
                keep = [c for c in sample.column_names if c in needed]
                try:
                    b = bridge.arrow_to_device(sample.select(keep))
                    for p in all_preds:
                        b = kernels.apply_mask(b, evaluate_predicate(p, b))
                    mask = np.asarray(b.valid)[: sample.num_rows]
                    sample = sample.filter(pa.array(mask))
                except Exception:
                    # sampling is advisory; any failure means "no estimate"
                    return None
            return sample
        if isinstance(node, logical.FilterNode):
            preds.append(node.predicate)
            node = sub[node.parents[0]]
            continue
        if isinstance(node, (logical.ProjectionNode, logical.MapNode)):
            node = sub[node.parents[0]]
            continue
        return None
    return None


def _estimate_subtree(sub, nid: int, cat) -> Optional[float]:
    """Estimate rows flowing out of a Filter/Projection/Map chain over one
    source; None when the shape is more complex."""
    node = sub[nid]
    preds: List[Expr] = []
    guard = 0
    while guard < 64:
        guard += 1
        if isinstance(node, logical.SourceNode):
            pred = conjoin(preds + ([node.predicate] if node.predicate is not None else []))
            return cat.estimate_source(node.reader, pred)
        if isinstance(node, logical.FilterNode):
            preds.append(node.predicate)
            node = sub[node.parents[0]]
            continue
        if isinstance(node, (logical.ProjectionNode, logical.MapNode)):
            node = sub[node.parents[0]]
            continue
        return None
    return None
