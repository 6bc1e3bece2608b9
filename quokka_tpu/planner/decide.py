"""Optimizer passes that consume the cost model (planner/cost.py).

Four passes, spliced into ``optimizer.pass_pipeline`` (and therefore into
planck's per-pass verification and planfuzz's cumulative-prefix matrix):

- ``choose_broadcast_cost`` — broadcast-vs-partition by MEASURED build-side
  bytes (``QK_BROADCAST_BYTES``) when the cardprofile has seen this exact
  scan before; cold plans keep the legacy sampled-row threshold
  (``optimizer.BROADCAST_THRESHOLD``) so a fresh process plans identically
  to the pre-planner pipeline.
- ``reorder_joins_cost`` — the greedy smallest-build-first chain ordering
  (optimizer.reorder_joins), fed by cost-model estimates instead of raw
  catalog samples.  Hint-only estimates decline to reorder: a guess is not
  evidence.
- ``size_channels`` — shrink the channel fan-out of exchanges whose
  measured row volume cannot use the default parallelism (fewer channels =
  fewer partitions, fewer per-channel compiles, denser buckets).
- ``plan_adaptive_exchanges`` — mark the join edges where mid-query skew
  re-partitioning (planner/adapt.py) is semantically safe, so the runtime
  trigger never has to reason about plan shape.

Every choice is recorded through a thread-local decision log — begun by
``context._prepare_plan``, attached to the lowered TaskGraph, surfaced in
``explain()`` as the "planner decisions" section — with the measured vs
hinted figures that drove it.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional

from quokka_tpu import config, logical, optimizer
from quokka_tpu.planner import cost as cost_mod

# a channel is worth its compile/dispatch overhead only past this many rows
ROWS_PER_CHANNEL = 1 << 17

# reserved by the runtime salting rewrite; no user plan may emit it
SALT_COLUMN = "__qk_salt"

# ---------------------------------------------------------------------------
# decision log (thread-local: optimize() runs on the submitting thread)
# ---------------------------------------------------------------------------

_TL = threading.local()


def begin_decisions() -> None:
    """Start collecting decisions for the plan being optimized."""
    _TL.log = []


def record(kind: str, **fields) -> None:
    log = getattr(_TL, "log", None)
    if log is not None:
        log.append({"kind": kind, **fields})


def take_decisions() -> List[dict]:
    """Return and clear the collected decisions (empty when collection was
    never begun — direct optimize() calls in tests and the fuzzer)."""
    log = getattr(_TL, "log", None)
    _TL.log = None
    return list(log or [])


def _model(sub: Dict[int, logical.Node]) -> cost_mod.CostModel:
    return cost_mod.CostModel(sub, catalog=optimizer._get_catalog())


# ---------------------------------------------------------------------------
# broadcast vs partition
# ---------------------------------------------------------------------------


def choose_broadcast_cost(sub: Dict[int, logical.Node], sink_id: int) -> None:
    """Measured build bytes under QK_BROADCAST_BYTES -> broadcast; measured
    above -> partition (even when a stale sample says otherwise).  No
    measurement -> the legacy sampled-rows threshold, unchanged."""
    model = _model(sub)
    cat = optimizer._get_catalog()
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if not isinstance(node, logical.JoinNode) or node.broadcast:
            continue
        if node.how not in ("inner", "semi", "anti", "left"):
            continue
        est = model.build_bytes(node.parents[1])
        if est.basis == cost_mod.BASIS_MEASURED:
            limit = config.broadcast_bytes_threshold()
            node.broadcast = est.bytes is not None and est.bytes <= limit
            record("broadcast", node=node.describe(),
                   choice="broadcast" if node.broadcast else "partition",
                   basis=est.basis, build_rows=round(est.rows),
                   build_bytes=round(est.bytes or 0),
                   threshold_bytes=limit)
            continue
        rows = optimizer._estimate_subtree(sub, node.parents[1], cat)
        if rows is not None and rows <= optimizer.BROADCAST_THRESHOLD:
            node.broadcast = True
        record("broadcast", node=node.describe(),
               choice="broadcast" if node.broadcast else "partition",
               basis=est.basis if rows is not None else "unknown",
               build_rows=round(rows) if rows is not None else None,
               threshold_rows=optimizer.BROADCAST_THRESHOLD)


# ---------------------------------------------------------------------------
# join order
# ---------------------------------------------------------------------------


def reorder_joins_cost(sub: Dict[int, logical.Node], sink_id: int) -> None:
    """optimizer.reorder_joins with cost-model estimates.  The estimator
    returns None for hint-only figures, which makes the chain walk bail
    exactly like the legacy sampler does when it cannot sample."""
    model = _model(sub)

    def estimate(nid: int) -> Optional[float]:
        est = model.estimate(nid)
        if est.basis == cost_mod.BASIS_HINT:
            return None
        return est.rows

    def on_reorder(chain_ids, before, after, basis):
        record("join_order", chain=[sub[j].describe() for j in chain_ids],
               before=[sub[b].describe() for b in before],
               after=[f"{sub[a].describe()}"
                      f" (~{round(model.estimate(a).rows)} rows)"
                      for a in after],
               basis=basis)

    optimizer.reorder_joins(sub, sink_id, estimate=estimate,
                            on_reorder=on_reorder,
                            basis_of=lambda nid: model.estimate(nid).basis)


# ---------------------------------------------------------------------------
# channel sizing
# ---------------------------------------------------------------------------


def size_channels(sub: Dict[int, logical.Node], sink_id: int,
                  exec_channels: int = 2) -> None:
    """Shrink exchange fan-out where MEASURED volume cannot feed the
    default channel count.  Only ever sizes DOWN, only on measured figures
    (cold plans are untouched), and never touches nodes with an explicit
    channel count or a placement pin."""
    if exec_channels < 2:
        return
    model = _model(sub)
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if not isinstance(node, (logical.JoinNode, logical.AggNode,
                                 logical.DistinctNode)):
            continue
        if node.channels is not None or node.placement is not None:
            continue
        if isinstance(node, logical.AggNode) and not node.keys:
            continue  # keyless aggs already collapse to one final channel
        est = model.estimate(nid)
        if est.basis != cost_mod.BASIS_MEASURED:
            continue
        want = max(1, min(exec_channels,
                          math.ceil(est.rows / ROWS_PER_CHANNEL)))
        if want < exec_channels:
            node.channels = want
            record("channels", node=node.describe(), basis=est.basis,
                   rows=round(est.rows), channels=want,
                   default=exec_channels)


# ---------------------------------------------------------------------------
# adaptive-exchange eligibility
# ---------------------------------------------------------------------------


def plan_adaptive_exchanges(sub: Dict[int, logical.Node],
                            sink_id: int) -> None:
    """Mark joins whose build exchange may be salted mid-query.

    Eligibility is decided HERE, over the logical plan, so the runtime
    trigger (planner/adapt.py) only ever fires on edges proven safe:

    - inner hash joins only.  Salting scatters one build partition across
      every channel and replicates the matching probe slice, which keeps
      inner matches exactly-once but breaks the per-channel completeness
      that left/semi/anti unmatched-tracking needs.
    - non-broadcast (a broadcast build has no partition to salt), and
    - no claimed output order (QK026: replicated probe slices interleave).
    """
    if not config.adapt_enabled():
        return
    eligible = []
    for nid in optimizer._reachable(sub, sink_id):
        node = sub[nid]
        if not isinstance(node, logical.JoinNode):
            continue
        if SALT_COLUMN in node.schema:
            continue
        if (node.how == "inner" and not node.broadcast
                and not node.sorted_by):
            node.adapt_salt = True
            eligible.append(node.describe())
    if eligible:
        record("adapt_mark", joins=eligible,
               skew_ratio=_skew_threshold())


def _skew_threshold() -> float:
    from quokka_tpu.obs import opstats

    return opstats.skew_ratio_threshold()
