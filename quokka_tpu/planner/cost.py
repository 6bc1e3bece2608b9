"""Calibrated cost model over the logical DAG.

Per-node cardinality/byte estimates with a strict precedence the rest of
the planner (and the README knob table) promises:

    measured  >  sampled  >  hint

- **measured** — the opstats cardprofile's per-source table
  (``obs/opstats.py record_cardinalities``), keyed by a plan-independent
  *source signature* (reader identity + pushed predicate + projection).
  Plan fingerprints are only known after lowering, so they cannot key a
  figure the optimizer needs; the source signature is computable from the
  logical ``SourceNode`` at plan time and survives every downstream
  rewrite of the plan.  Measured rows are post-predicate actuals; measured
  ``rows_raw`` (pre-predicate scan rows) gives the observed selectivity.
- **sampled** — ``catalog.Catalog.estimate_source``: predicate selectivity
  measured on an 8K-row sample, scaled to the footer row count.
- **hint** — reader ``size_hint()`` bytes over an assumed row width.

Interior nodes propagate with textbook defaults exactly where no
measurement can exist at plan time (the cardprofile records per-plan
operator rows under the *plan* fingerprint, which a different join order
invalidates): filters keep the parent's basis at FILTER_SELECTIVITY,
joins assume FK-into-PK (output ~= probe side), aggregates reduce by
GROUP_REDUCTION.  Every estimate carries its ``basis`` so decisions made
from it are auditable in the explain output.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional

from quokka_tpu import logical

# textbook defaults, used exactly where nothing measured or sampled exists
FILTER_SELECTIVITY = 0.25
GROUP_REDUCTION = 0.1
DEFAULT_COL_BYTES = 8.0  # one device column is a fixed-width vector

BASIS_MEASURED = "measured"
BASIS_SAMPLED = "sampled"
BASIS_HINT = "hint"
_RANK = {BASIS_MEASURED: 2, BASIS_SAMPLED: 1, BASIS_HINT: 0}

def _weaker(a: str, b: str) -> str:
    """The weaker of two bases — a derived figure is only as strong as its
    weakest input."""
    return a if _RANK.get(a, 0) <= _RANK.get(b, 0) else b


def _reader_identity(reader) -> str:
    """A stable, path-level identity for a source reader.  Deliberately
    ignores mutable scan state (pushed predicate/columns live on the
    signature separately) so the same table scanned by two queries shares
    one identity."""
    parts = [type(reader).__name__]
    path = getattr(reader, "path", None)
    if path is not None:
        if isinstance(path, (list, tuple)):
            parts += [str(p) for p in path]
        else:
            parts.append(str(path))
    else:
        table = getattr(reader, "table", None)
        if table is not None:
            parts.append(",".join(table.schema.names))
            parts.append(str(table.num_rows))
    return "|".join(parts)


def source_signature(reader, predicate=None,
                     projection=None) -> str:
    """Plan-independent key for one (reader, pushed predicate, projection)
    scan.  Computable both at plan time (from the logical SourceNode) and
    at lowering (from ActorInfo), so measured figures recorded under it in
    one run are addressable by the optimizer in the next — regardless of
    what the rest of that plan looked like."""
    pred_sql = predicate.sql() if predicate is not None else ""
    cols = ",".join(sorted(projection)) if projection else "*"
    raw = f"{_reader_identity(reader)}\x00{pred_sql}\x00{cols}"
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


@dataclasses.dataclass
class Estimate:
    """One node's estimated output: rows, bytes, and the basis that
    produced them (``measured``/``sampled``/``hint``)."""

    rows: float
    bytes: Optional[float]
    basis: str

    def width(self, ncols: int) -> float:
        if self.bytes and self.rows > 0:
            return self.bytes / self.rows
        return DEFAULT_COL_BYTES * max(ncols, 1)


def _hint_bytes(reader) -> Optional[int]:
    fn = getattr(reader, "size_hint", None)
    if fn is None:
        return None
    try:
        return int(fn())
    except (OSError, ValueError, TypeError):
        return None


class CostModel:
    """Memoized per-node estimator over one logical plan.

    ``catalog`` is shared with the optimizer so sampling work is paid once
    per (reader, predicate); ``profile`` defaults to the persisted
    cardprofile's source table (measured_sources) and can be injected for
    tests."""

    def __init__(self, sub: Dict[int, logical.Node], catalog=None,
                 profile: Optional[Dict[str, dict]] = None):
        self.sub = sub
        self.cat = catalog
        if profile is None:
            from quokka_tpu.obs import opstats

            profile = opstats.measured_sources()
        self.profile = profile or {}
        self._memo: Dict[int, Estimate] = {}

    # -- sources -------------------------------------------------------------

    def _estimate_source(self, node: logical.SourceNode) -> Estimate:
        sig = source_signature(node.reader, node.predicate, node.projection)
        rec = self.profile.get(sig)
        if rec and rec.get("rows") is not None:
            return Estimate(float(rec["rows"]),
                            float(rec["bytes"]) if rec.get("bytes") else None,
                            BASIS_MEASURED)
        # a measurement of the bare scan (no predicate) still beats a
        # sample: scale its actual rows by the sampled selectivity
        if node.predicate is not None:
            bare = self.profile.get(
                source_signature(node.reader, None, node.projection))
        else:
            bare = None
        if self.cat is not None:
            sampled = self.cat.estimate_source(node.reader, node.predicate)
        else:
            sampled = None
        if bare and bare.get("rows") is not None and sampled is not None:
            raw = self.cat.estimate_source(node.reader, None)
            if raw and raw > 0:
                sel = min(1.0, sampled / raw)
                rows = float(bare["rows"]) * sel
                b = float(bare["bytes"]) * sel if bare.get("bytes") else None
                return Estimate(rows, b, BASIS_SAMPLED)
        if sampled is not None:
            width = DEFAULT_COL_BYTES * max(len(node.schema), 1)
            return Estimate(float(sampled), float(sampled) * width,
                            BASIS_SAMPLED)
        hint = _hint_bytes(node.reader)
        width = DEFAULT_COL_BYTES * max(len(node.schema), 1)
        if hint:
            rows = float(hint) / width
            sel = FILTER_SELECTIVITY if node.predicate is not None else 1.0
            return Estimate(rows * sel, float(hint) * sel, BASIS_HINT)
        return Estimate(0.0, None, BASIS_HINT)

    # -- interior propagation -------------------------------------------------

    def estimate(self, nid: int) -> Estimate:
        if nid in self._memo:
            return self._memo[nid]
        # seed the memo against (impossible) cycles, then overwrite
        self._memo[nid] = est = self._derive(self.sub[nid])
        return est

    def _derive(self, node: logical.Node) -> Estimate:
        if isinstance(node, logical.SourceNode):
            return self._estimate_source(node)
        if not node.parents:
            return Estimate(0.0, None, BASIS_HINT)
        parent = self.estimate(node.parents[0])
        ncols = max(len(node.schema), 1)
        if isinstance(node, logical.FilterNode):
            return Estimate(parent.rows * FILTER_SELECTIVITY,
                            (parent.bytes * FILTER_SELECTIVITY
                             if parent.bytes else None), parent.basis)
        if isinstance(node, logical.JoinNode):
            build = self.estimate(node.parents[1])
            basis = _weaker(parent.basis, build.basis)
            if node.how in ("semi", "anti"):
                rows = parent.rows * 0.5
            else:
                # FK-into-PK: each probe row matches ~one build row
                rows = max(parent.rows, 1.0)
            width = (parent.width(len(self.sub[node.parents[0]].schema))
                     + build.width(len(self.sub[node.parents[1]].schema)))
            return Estimate(rows, rows * width, basis)
        if isinstance(node, logical.FusedStageNode):
            return self._derive_fused(node)
        if isinstance(node, (logical.AggNode, logical.DistinctNode)):
            keys = getattr(node, "keys", None)
            rows = parent.rows * GROUP_REDUCTION if keys else 1.0
            limit = getattr(node, "limit", None)
            if limit is not None:
                rows = min(rows, float(limit))
            return Estimate(rows, rows * DEFAULT_COL_BYTES * ncols,
                            parent.basis)
        if isinstance(node, logical.TopKNode):
            rows = min(parent.rows, float(node.k))
            return Estimate(rows, rows * parent.width(ncols), parent.basis)
        if isinstance(node, logical.ProjectionNode):
            pcols = max(len(self.sub[node.parents[0]].schema), 1)
            frac = min(1.0, ncols / pcols)
            return Estimate(parent.rows,
                            parent.bytes * frac if parent.bytes else None,
                            parent.basis)
        # Map / Sort / Window / Asof / Shift / Sink: row-preserving (asof
        # probe-aligned; windows row-preserving) — keep the parent's figure
        return Estimate(parent.rows, parent.bytes, parent.basis)

    def _derive_fused(self, node: logical.FusedStageNode) -> Estimate:
        """Replay the member chain the way derive_schema does: member i's
        main input is member i-1's output, joins consume build parents in
        chain order."""
        cur = self.estimate(node.parents[0])
        builds = iter(node.parents[1:])
        for m in node.members:
            if isinstance(m, logical.JoinNode):
                build = self.estimate(next(builds))
                basis = _weaker(cur.basis, build.basis)
                rows = (cur.rows * 0.5 if m.how in ("semi", "anti")
                        else max(cur.rows, 1.0))
                cur = Estimate(rows, rows * cur.width(len(m.schema)), basis)
            elif isinstance(m, logical.FilterNode):
                cur = Estimate(cur.rows * FILTER_SELECTIVITY,
                               (cur.bytes * FILTER_SELECTIVITY
                                if cur.bytes else None), cur.basis)
            elif isinstance(m, logical.AggNode):
                rows = cur.rows * GROUP_REDUCTION if m.keys else 1.0
                cur = Estimate(rows,
                               rows * DEFAULT_COL_BYTES * len(m.schema),
                               cur.basis)
        return cur

    # -- convenience ----------------------------------------------------------

    def build_bytes(self, nid: int) -> Estimate:
        """The estimate decisions quote for a join build side: rows plus a
        bytes figure synthesized from width when the basis carried none."""
        est = self.estimate(nid)
        if est.bytes is None:
            ncols = max(len(self.sub[nid].schema), 1)
            est = Estimate(est.rows, est.rows * est.width(ncols), est.basis)
        return est
