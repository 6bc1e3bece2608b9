"""Core relational executors on device kernels.

Functional parity targets (reference: pyquokka/executors/sql_executors.py):
UDFExecutor:3, CountExecutor:69, StorageExecutor:24, BuildProbeJoinExecutor:325,
DistinctExecutor:517, SQLAggExecutor:556 (split here into PartialAgg/FinalAgg so
aggregation is decomposed partial->shuffle->final instead of concat-then-DuckDB),
ConcatThenSQLExecutor:45 (TopK/Sort below).
"""

from __future__ import annotations

import copy
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from quokka_tpu import config
from quokka_tpu.obs import spans as tracing
from quokka_tpu.ops import aggtail, bridge, kernels
from quokka_tpu.ops import distinct as distinct_ops
from quokka_tpu.ops import join as join_ops
from quokka_tpu.ops.batch import DeviceBatch, NumCol, key_limbs
from quokka_tpu.ops.expr_compile import AggPlan, evaluate_predicate, evaluate_to_column
from quokka_tpu.executors.base import Executor


def _coalesce(live: List[DeviceBatch], cap_rows: int) -> List[DeviceBatch]:
    """Concat a dispatch's ready batches into few compacted batches so the
    group-by's per-batch kernel chain runs once over a bucketed whole
    instead of once per per-partition slice.  Bounded by accumulated PADDED
    rows: only batches small enough that the chain's launches cost more than
    the concat are joined (the join probe takes every batch alone)."""
    if len(live) <= 1:
        return live
    groups: List[List[DeviceBatch]] = []
    cur: List[DeviceBatch] = []
    acc = 0
    for b in live:
        if cur and acc + b.padded_len > cap_rows:
            groups.append(cur)
            cur, acc = [], 0
        cur.append(b)
        acc += b.padded_len
    groups.append(cur)
    return [bridge.concat_batches(g) if len(g) > 1 else g[0] for g in groups]


# `kernels.compact_if_large`'s threshold: below it the blocking read of the
# live count costs more than the slack rows
SHRINK_ABOVE = 1 << 16


# the build row of each probe row, carried through `_shrunk` by an inner PK
# join whose payload is gathered after it (BuildProbeJoinExecutor)
_BUILD_IDX = "__qk_build_idx"


def _shrunk(batch: DeviceBatch) -> DeviceBatch:
    """A large batch whose live rows would fit a bucket a quarter of its
    padded length or smaller (what a selective join or filter leaves of a
    scan batch), compacted to that bucket; every other batch as it is.  The
    compaction (one mask scan, one gather a column at the small bucket) is
    repaid by the first gather or search over the padded length it saves;
    at half the length it is not.  The count is the batch's own, so the
    shape is a function of the table and the plan."""
    if (batch.padded_len <= SHRINK_ABOVE or
            4 * config.bucket_size(batch.count_valid()) > batch.padded_len):
        return batch
    return kernels.compact(batch)


class UDFExecutor(Executor):
    """Stateless per-batch transform (DataStream.transform)."""

    # carries no cross-batch state: a fused stage containing one of these
    # checkpoints without snapshotting it (ops/stagefuse.py) — tape replay
    # already relies on transform purity engine-wide
    STATELESS = True

    def __init__(self, fn: Callable[[DeviceBatch], DeviceBatch]):
        self.fn = fn

    def execute(self, batches, stream_id, channel):
        out = [self.fn(b) for b in batches if b is not None]
        out = [b for b in out if b is not None]
        if not out:
            return None
        return bridge.concat_batches(out) if len(out) > 1 else out[0]


class CountExecutor(Executor):
    def __init__(self):
        self.count = 0

    def execute(self, batches, stream_id, channel):
        self.count += sum(b.count_valid() for b in batches)

    def done(self, channel):
        import pyarrow as pa

        return bridge.arrow_to_device(pa.table({"count": [self.count]}))




# ---------------------------------------------------------------------------
# spill-directory registry: executors that never reach done() (failed query,
# killed worker) must not leak dirs under config.SPILL_DIR forever
_SPILL_DIRS: set = set()

# process-wide count of operators that crossed a spill threshold (one per
# spilling operator instance) — tests assert production-threshold runs
# actually exercised the disk tier
SPILL_EVENTS = 0


def _new_spill_dir(prefix: str) -> str:
    global SPILL_EVENTS
    SPILL_EVENTS += 1
    import atexit
    import os
    import tempfile

    os.makedirs(config.SPILL_DIR, exist_ok=True)
    if not _SPILL_DIRS:
        atexit.register(_purge_spill_dirs)
    d = tempfile.mkdtemp(prefix=prefix, dir=config.SPILL_DIR)
    _SPILL_DIRS.add(d)
    return d


def _drop_spill_dir(d: str) -> None:
    import shutil

    shutil.rmtree(d, ignore_errors=True)
    _SPILL_DIRS.discard(d)


def _purge_spill_dirs() -> None:
    for d in list(_SPILL_DIRS):
        _drop_spill_dir(d)


class StorageExecutor(Executor):
    """Pass batches through unchanged (terminal collect node)."""

    def execute(self, batches, stream_id, channel):
        live = [b for b in batches if b is not None and b.count_valid() > 0]
        if not live:
            return None
        return bridge.concat_batches(live) if len(live) > 1 else live[0]


class SelectingStorageExecutor(StorageExecutor):
    """Terminal collect that also projects to the plan schema (picklable —
    the sink factory crosses process boundaries in the multi-worker runtime)."""

    # one result batch a dispatch: nothing downstream reads a concatenated
    # whole (each batch is copied to the host and the frames are joined
    # there), and which of the channels' results are ready together follows
    # timing, so a concat here is a program whose shape follows arrival
    MAX_PIPELINE_BATCHES = 1

    def __init__(self, schema: Sequence[str]):
        self.schema = list(schema)

    def execute(self, batches, stream_id, channel):
        out = StorageExecutor.execute(self, batches, stream_id, channel)
        if out is None:
            return None
        return out.select([c for c in self.schema if c in out.columns])


class _FoldsPartials:
    """What the two aggregators share: buffered partials folded into one
    running state (``keys``, ``plan``, ``state``, ``_buffer``)."""

    # NOTE: a merge of small parts is one compiled program that reads nothing
    # back (ops/aggtail.py).  Above its threshold the parts are compacted
    # first, which blocks on every live count that has not landed: the async
    # copies start at partial creation and merges run batches later, so on
    # the CPU those reads are from host memory; on the chip a pool thread
    # waits in them (sync.count_valid, PERF.md section 5)

    def _merge(self) -> None:
        if not self._buffer:
            return  # state alone is already folded
        parts, self._buffer = self._buffer, []
        with tracing.span("groupby.merge"):
            self.state = aggtail.recombine(
                self.keys, self.plan.recombine, parts, self.state)


class PartialAggExecutor(_FoldsPartials, Executor):
    SUPPORTS_CHECKPOINT = True
    """Per-channel partial group-by: maintains one running partial-aggregate
    batch; emits it at done.  Sits upstream of the hash shuffle."""

    # merge cadence: per-batch partials are buffered (uncompacted, with an
    # async live-count already in flight) and folded into the running state
    # every K batches — by merge time the counts have landed on the host, so
    # compaction costs no blocking device round trip.  A merge holds exactly
    # K partials (the last one of a channel what is left): how many batches
    # a dispatch happened to carry decides nothing, so the merges' shapes
    # follow the channel's batch count and the partials' group counts, which
    # the plan and the table fix
    MERGE_EVERY = 8

    # adaptive bailout: when the FIRST batch's group count is close to its
    # row count (near-unique keys — e.g. TPC-H Q3's order-level group-by),
    # per-batch partial sorts reduce almost nothing while costing the
    # engine's dominant kernel; switch to PASSTHROUGH: map rows to partial
    # FORM (pre-exprs + count columns, purely elementwise) and emit them
    # immediately for the final agg to reduce.  DuckDB's partial-agg
    # abandonment, TPU-style.  The decision depends only on batch 1's
    # content, so tape replay reproduces it deterministically.
    PASSTHROUGH_RATIO = 0.7

    def __init__(self, keys: Sequence[str], plan: AggPlan):
        self.keys = list(keys)
        self.plan = plan
        self.state: Optional[DeviceBatch] = None
        self._buffer: List[DeviceBatch] = []
        self._passthrough: Optional[bool] = None  # undecided until batch 1
        from quokka_tpu.ops.fuse import FusedPartialAgg

        self._fused = FusedPartialAgg(self.keys, plan)

    def _partial(self, batch: DeviceBatch) -> DeviceBatch:
        from quokka_tpu.ops.expr_compile import CompileError

        try:
            g = self._fused(batch)
        except CompileError:
            b = batch
            for name, e in self.plan.pre:
                b = b.with_column(name, evaluate_to_column(e, b))
            aggs = [
                (p, op, None if tmp is None else b.columns[tmp].data)
                for (p, op, tmp) in self.plan.partials
            ]
            g = kernels.groupby_aggregate(b, self.keys, aggs)
        return g.select(self.keys + [p for p, _, _ in self.plan.partials])

    def _partial_form(self, batch: DeviceBatch) -> DeviceBatch:
        """Raw rows -> partial-FORM rows (count columns = 1 per valid row,
        value columns = the pre-expression inputs) with NO grouping: the
        recombine ops downstream aggregate them exactly like grouped
        partials."""
        b = batch
        for name, e in self.plan.pre:
            b = b.with_column(name, evaluate_to_column(e, b))
        cols = {k: b.columns[k] for k in self.keys}
        for pname, op, tmp in self.plan.partials:
            if op == "count":
                cols[pname] = NumCol(b.valid.astype(jnp.int32), "i")
            else:
                cols[pname] = b.columns[tmp]
        return DeviceBatch(cols, b.valid, b.nrows, None, b.nrows_dev)

    def execute(self, batches, stream_id, channel):
        outs = []
        live = [b for b in batches if b is not None]
        if not self._passthrough:
            # one group-by over a dispatch's SMALL batches (per-partition
            # slices) instead of a launch chain each; deterministic under
            # tape replay (the same recorded batch set coalesces
            # identically).  Large batches stay apart: to concatenate two
            # 1<<20-row scan batches costs the chip 129 ms, their partial
            # aggregates 27.7 ms each (PERF.md section 6, PR 27), and which
            # batches are ready together follows timing, so the concat's
            # and the aggregate's shapes would too
            live = _coalesce(live, cap_rows=aggtail.SMALL_ROWS)
        for b in live:
            if self._passthrough:
                outs.append(self._partial_form(b))
                continue
            g = self._partial(b)
            if self._passthrough is None:
                rows = b.count_valid()
                # tiny batches can't decide (a selective first chunk must
                # not pin the mode for a stream of millions of rows): stay
                # undecided until a big-enough batch arrives — still
                # deterministic under tape replay (content-driven)
                if rows > 4096:
                    groups = g.count_valid()
                    self._passthrough = (
                        groups >= self.PASSTHROUGH_RATIO * rows
                    )
            self._buffer.append(g)
            if len(self._buffer) >= self.MERGE_EVERY:
                self._merge()
        if not outs:
            return None
        return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]

    def done(self, channel):
        self._merge()
        out, self.state = self.state, None
        # state after a merge is already bucket-sized; only compact when the
        # trailing merge left a large padded region (avoids a blocking count)
        return None if out is None else kernels.compact_if_large(out)

    def checkpoint(self):
        self._merge()  # state-folding is semantics-preserving
        table = None if self.state is None else bridge.device_to_arrow(self.state)
        return {"passthrough": self._passthrough, "state": table}

    def restore(self, state):
        self._buffer = []
        if isinstance(state, dict):
            self._passthrough = state.get("passthrough")
            state = state.get("state")
        else:
            self._passthrough = None  # legacy checkpoint blob: re-decide
        self.state = None if state is None else bridge.arrow_to_device(state)


class FinalAggExecutor(_FoldsPartials, Executor):
    """Downstream of the key shuffle: recombines partials for its key range,
    then applies final expressions, HAVING, ORDER BY and LIMIT at done."""

    def __init__(
        self,
        keys: Sequence[str],
        plan: AggPlan,
        having=None,
        order_by: Optional[List[Tuple[str, bool]]] = None,
        limit: Optional[int] = None,
    ):
        self.keys = list(keys)
        self.plan = plan
        self.having = having
        self.order_by = order_by
        self.limit = limit
        self.state: Optional[DeviceBatch] = None
        self._buffer: List[DeviceBatch] = []

    MERGE_EVERY = 32  # incoming partials are small (post-shuffle compacted)
    # a passthrough upstream (PartialAggExecutor bailout) ships FULL-SIZE row
    # batches instead of compacted partials: also fold on accumulated padded
    # rows so the buffer can't hold 32 raw batches on device at once
    MERGE_ROWS = 1 << 21

    def execute(self, batches, stream_id, channel):
        self._buffer.extend(b for b in batches if b is not None)
        if (
            len(self._buffer) >= self.MERGE_EVERY
            or sum(p.padded_len for p in self._buffer) >= self.MERGE_ROWS
        ):
            self._merge()
        return None

    def done(self, channel):
        self._merge()
        if self.state is not None:
            self.state = kernels.compact_if_large(self.state)
        if self.state is None:
            if self.keys:
                return None
            # SQL semantics: a global aggregate over zero rows yields one row
            # (count = 0, sum = 0, min/max = null)
            import numpy as np
            import pyarrow as pa

            cols = {}
            for pname, op, _tmp in self.plan.partials:
                if op == "count":
                    cols[pname] = np.array([0], dtype=np.int64)
                elif op == "sum":
                    cols[pname] = np.array([0.0])
                else:
                    cols[pname] = np.array([np.nan])
            self.state = bridge.arrow_to_device(pa.table(cols))
        g, self.state = self.state, None
        with tracing.span("groupby.final"):
            out = aggtail.final_tail(g, self.keys, self.plan, self.having,
                                     self.order_by, self.limit)
            aggtail.note_path(out is not None)
            return out if out is not None else self._tail_general(g)

    def _tail_general(self, g: DeviceBatch) -> DeviceBatch:
        """The tail op by op: a large state, or finals that need the host."""
        for name, e in self.plan.finals:
            g = g.with_column(name, evaluate_to_column(e, g))
        # HAVING runs before the projection: it may reference partial columns
        # (aggregates rewritten by plan.rewrite) that the output drops
        if self.having is not None:
            g = kernels.compact(kernels.apply_mask(g, evaluate_predicate(self.having, g)))
        # dedupe (a key may also be an output)
        cols = list(dict.fromkeys(self.keys + [n for n, _ in self.plan.finals]))
        g = g.select(cols)
        if self.order_by or self.limit is not None:
            from quokka_tpu.obs import opstats

            opstats.note(agg_merge_slots=g.padded_len)
        if self.order_by:
            names = [n for n, _ in self.order_by]
            desc = [d for _, d in self.order_by]
            if self.limit is not None:
                g = kernels.top_k(g, names, self.limit, desc)
            else:
                g = kernels.sort_batch(g, names, desc)
        elif self.limit is not None:
            g = kernels.head(g, self.limit)
        return g


class BuildProbeJoinExecutor(Executor):
    SUPPORTS_CHECKPOINT = True

    """Streamed hash join: stream 1 is the build side (buffered until its
    stage completes), stream 0 probes.  Stage scheduling guarantees build
    completes before the first probe batch arrives (the reference asserts the
    same invariant, sql_executors.py:357)."""

    # one probe batch a dispatch, so one output a dispatch and no concat of
    # outputs: which batches are ready together follows timing, and a
    # program over their concat would have a shape that does too
    MAX_PIPELINE_BATCHES = 1

    def __init__(
        self,
        left_on: Sequence[str],
        right_on: Sequence[str],
        how: str = "inner",
        suffix: str = "_2",
        rename: Optional[Dict[str, str]] = None,
        out_schema: Optional[List[str]] = None,
    ):
        self.left_on = list(left_on)
        self.right_on = list(right_on)
        self.how = how
        self.suffix = suffix
        # plan-time output schema: lets a left join emit all-null payload even
        # when this channel never saw a single build batch (schema unknown)
        self.out_schema = list(out_schema) if out_schema else None
        # plan-time rename of clashing build columns; None -> detect at
        # runtime from the first probe batch (raw TaskGraph usage)
        self.planned_rename = rename
        self.build_parts: List[DeviceBatch] = []
        self.build: Optional[DeviceBatch] = None
        self.build_done = False
        self.probe_buffer: List[DeviceBatch] = []
        self.build_unique: Optional[bool] = None
        self.payload: Optional[List[str]] = None
        self.rename: Dict[str, str] = {}
        # grace-join spill tier (DiskBuildProbeJoinExecutor,
        # sql_executors.py:456-515): past SPILL_JOIN_BUILD_ROWS accumulated
        # build rows, both sides hash-partition to disk and done() joins
        # partition-by-partition in bounded memory
        self.spill_rows = config.SPILL_JOIN_BUILD_ROWS
        self.fanout = config.SPILL_JOIN_FANOUT
        self._disk = False
        self._build_rows = 0
        self._spill_dir: Optional[str] = None
        self._writers: Dict[Tuple[str, int], object] = {}
        self._files: Dict[Tuple[str, int], str] = {}
        self._build_arrow_schema = None

    def _finalize_build(self, probe_cols: List[str]):
        if not self.build_parts:
            self.build = None
            return
        b = (
            bridge.concat_batches(self.build_parts)
            if len(self.build_parts) > 1
            else self.build_parts[0]
        )
        self.build_parts = []
        # payload = build columns minus its join keys; rename clashes
        payload = [c for c in b.names if c not in self.right_on]
        if self.planned_rename is not None:
            self.rename = {c: n for c, n in self.planned_rename.items() if c in payload}
        else:
            self.rename = {c: c + self.suffix for c in payload if c in probe_cols}
        if self.rename:
            b = b.rename(self.rename)
            payload = [self.rename.get(c, c) for c in payload]
        self.payload = payload
        self.build = b
        self.build_unique = join_ops.build_keys_unique(b, self.right_on)
        # build-side hash state is the largest single device residency a
        # join pins (the batch and, for a dense integer key, its
        # direct-address table); ledger it (query attribution happens at
        # graph level — executors do not know their query id) and retire
        # in done()
        from quokka_tpu.obs import memplane
        from quokka_tpu.runtime.cache import _batch_nbytes

        memplane.LEDGER.track(
            ("join_build", id(self)), memplane.SITE_BUILD,
            _batch_nbytes(b) + join_ops.direct_table_nbytes(b, self.right_on))
        # the strategy that will serve every probe batch of this build is
        # decided here — stamp it into the flight timeline so critpath
        # can attribute the probe pipeline to the kernel family
        # that actually ran (ops/strategy.py matrix)
        from quokka_tpu.obs import RECORDER
        from quokka_tpu.ops import strategy as kstrategy

        RECORDER.record(
            "strategy", "join_build",
            choice=kstrategy.choice("join_build") if self.build_unique
            else "sort", unique=bool(self.build_unique),
        )
        # EXPLAIN ANALYZE: the finalized build size on the operator's
        # record (padded length — host-known, never a device sync)
        from quokka_tpu.obs import opstats

        opstats.note(join_build_rows=b.padded_len, join_builds=1)

    def execute(self, batches, stream_id, channel):
        live = [b for b in batches if b is not None]
        if not live:
            return None
        if stream_id == 1:
            assert self.build is None, "build batch arrived after probing began"
            if self._disk:
                for b in live:
                    self._spill(b, "build", self.right_on)
                return None
            self.build_parts.extend(live)
            # padded length is a free upper bound on live rows: the real
            # counts (a blocking device read per batch when the producer
            # filtered device-side) are only paid once the bound crosses
            # the spill threshold
            self._build_rows += sum(b.padded_len for b in live)
            if self._build_rows > self.spill_rows:
                rows = sum(b.count_valid() for b in self.build_parts)
                if rows > self.spill_rows:
                    self._enter_disk_mode()
                else:
                    self._build_rows = rows
            return None
        if self._disk:
            for b in live:
                self._spill(b, "probe", self.left_on)
            return None
        # probe: if the build stream hasn't been declared exhausted yet
        # (stage-tie cases like self-joins), buffer and flush on source_done
        if not self.build_done:
            self.probe_buffer.extend(live)
            return None
        return self._probe(live)

    # -- grace-join spill tier -------------------------------------------------
    def _enter_disk_mode(self):
        self._disk = True
        # interval checkpoints can't capture on-disk partition state cheaply;
        # recovery falls back to full lineage-tape replay (deterministic)
        self.SUPPORTS_CHECKPOINT = False
        parts, self.build_parts = self.build_parts, []
        self._build_rows = 0
        for b in parts:
            self._spill(b, "build", self.right_on)
        # stage-tie probes buffered before build completion spill too
        buffered, self.probe_buffer = self.probe_buffer, []
        for b in buffered:
            self._spill(b, "probe", self.left_on)

    def _spill(self, batch: DeviceBatch, side: str, keys) -> None:
        import os
        import tempfile

        import pyarrow as pa

        if self._spill_dir is None:
            self._spill_dir = _new_spill_dir("join-")
        pids = kernels.partition_ids(batch, list(keys), self.fanout)
        # compacted split: each partition converts to Arrow right here, so
        # masked views would pay fanout-times the d2h bytes
        for p, part in enumerate(
                kernels.split_by_partition(batch, pids, self.fanout,
                                           compact=True)):
            if part.count_valid() == 0:
                continue
            table = bridge.device_to_arrow(part)
            if side == "build" and self._build_arrow_schema is None:
                # remember the build schema: probe-only partitions still need
                # a schema'd (empty) build for typed left-join null payloads
                self._build_arrow_schema = table.schema
            key = (side, p)
            w = self._writers.get(key)
            if w is None:
                path = os.path.join(self._spill_dir, f"{side}-{p}.arrow")
                self._files[key] = path
                sink = pa.OSFile(path, "wb")
                w = pa.ipc.new_file(sink, table.schema)
                self._writers[key] = (w, sink)
            self._writers[key][0].write_table(table)

    def _disk_join(self):
        import pyarrow as pa

        for w, sink in self._writers.values():
            w.close()
            sink.close()
        self._writers = {}
        try:
            for p in range(self.fanout):
                probe_path = self._files.get(("probe", p))
                if probe_path is None:
                    continue  # no probe rows in this partition -> no output
                build_path = self._files.get(("build", p))
                inner = BuildProbeJoinExecutor(
                    self.left_on, self.right_on, self.how, self.suffix,
                    rename=self.planned_rename, out_schema=self.out_schema,
                )
                inner.build_done = True
                if build_path is not None:
                    with pa.ipc.open_file(build_path) as r:
                        inner.build_parts = [
                            bridge.arrow_to_device(
                                pa.Table.from_batches([r.get_batch(i)])
                            )
                            for i in range(r.num_record_batches)
                        ]
                elif self._build_arrow_schema is not None:
                    # probe-only partition: a schema'd empty build keeps
                    # left-join null payloads correctly typed
                    inner.build_parts = [
                        bridge.arrow_to_device(self._build_arrow_schema.empty_table())
                    ]
                with pa.ipc.open_file(probe_path) as r:
                    for i in range(r.num_record_batches):
                        chunk = bridge.arrow_to_device(
                            pa.Table.from_batches([r.get_batch(i)])
                        )
                        o = inner._probe([chunk])
                        if o is not None and o.count_valid() > 0:
                            yield o
                # each partition's build state dies with its inner executor
                # — retire its ledger entry so a high-fanout grace join does
                # not read as fanout simultaneous build residencies
                from quokka_tpu.obs import memplane

                memplane.LEDGER.retire(("join_build", id(inner)))
        finally:
            if self._spill_dir is not None:
                _drop_spill_dir(self._spill_dir)

    def source_done(self, stream_id, channel):
        if stream_id != 1 or self.build_done:
            return None
        self.build_done = True
        buffered, self.probe_buffer = self.probe_buffer, []
        if self._disk:
            for b in buffered:
                self._spill(b, "probe", self.left_on)
            return None
        if buffered:
            return self._probe(buffered)
        return None

    def done(self, channel):
        from quokka_tpu.obs import memplane

        memplane.LEDGER.retire(("join_build", id(self)))
        if self._disk:
            return self._disk_join()
        # what the probes emitted shares nothing with the build: release it
        # (and the key sort and direct-address table cached on it) now, not
        # when a collector finds the finished query's graph
        self.build = None
        return None

    def _probe(self, live):
        if self.build is None and self.build_parts:
            with tracing.span("join.build"):
                self._finalize_build(live[0].names)
        from quokka_tpu.obs import opstats

        opstats.note(join_probe_rows=sum(
            b.nrows if b.nrows is not None else b.padded_len for b in live))
        if self.build is None:
            # No build batch ever arrived on this channel.  Engine.push always
            # delivers every hash partition (even zero-valid ones), so this
            # only happens when the build SOURCE emitted zero batches — i.e.
            # consistently on every channel.  Payload kinds are unknowable
            # then; all-null float columns stand in (documented limitation:
            # a string payload column degrades to float nulls in this case).
            if self.how in ("inner", "semi"):
                return None
            if self.how == "anti":
                out = live
                return bridge.concat_batches(out) if len(out) > 1 else out[0]
            if self.out_schema is None:
                raise RuntimeError(
                    "left join: build side produced no batches and no plan "
                    "schema was provided (pass out_schema=)"
                )
                outs = []
            for probe in live:
                payload = [c for c in self.out_schema if c not in probe.columns]
                b = probe
                for c in payload:
                    b = b.with_column(
                        c,
                        NumCol(jnp.full(b.padded_len, jnp.nan, config.float_dtype()), "f"),
                    )
                outs.append(b)
            return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]
        if self.build.count_valid() == 0 and self.how in ("inner", "semi"):
            return None
        # empty-but-schema'd build: anti/left fall through — the general join
        # kernel handles a zero-valid build (every probe row unmatched)
        # every batch is probed alone, at a shape its own content fixes: a
        # concat of whatever one dispatch carried would make the probe's and
        # every later program's shape follow arrival
        outs = []
        with tracing.span("join.probe"):
            for probe in map(_shrunk, live):
                if self.build_unique and self.how in ("inner", "semi", "anti"):
                    if self.how == "inner" and self.payload:
                        out = self._probe_pk_late_payload(probe)
                    else:
                        out = join_ops.hash_join_pk(
                            probe, self.build, self.left_on, self.right_on,
                            self.how, self.payload)
                else:
                    out = join_ops.hash_join_general(
                        probe, self.build, self.left_on, self.right_on,
                        self.how, self.payload)
                if out is not None:
                    outs.append(out)
        if not outs:
            return None
        return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]

    def _probe_pk_late_payload(self, probe: DeviceBatch) -> DeviceBatch:
        """An inner PK join with a payload: the matches, with the build
        index carried as a column, go through `_shrunk` first (the count
        read the next operator's `_shrunk` would make) and the payload is
        gathered after, at the length that leaves.  A selective join over a
        large probe thus gathers its payload over its matches' rung and not
        over every probe slot; `_shrunk` alone decides, so a probe of at
        most `SHRINK_ABOVE` slots gathers over every slot and reads no
        count."""
        build_idx, matched = join_ops.pk_match(
            probe, self.build, self.left_on, self.right_on)
        out = _shrunk(kernels.apply_mask(
            probe.with_column(_BUILD_IDX, NumCol(build_idx, "i")), matched))
        cols = dict(out.columns)
        idx = cols.pop(_BUILD_IDX).data
        cols.update(join_ops.pk_payload(self.build, self.payload, idx))
        return DeviceBatch(cols, out.valid, out.nrows, out.sorted_by,
                           out.nrows_dev)

    def checkpoint(self):
        build = self.build
        if build is None and self.build_parts:
            build = bridge.concat_batches(self.build_parts)
        return {
            "build": None if build is None else bridge.device_to_arrow(build),
            # without these, a restore past the build's source_done event
            # would buffer every probe batch forever (build_done False) and
            # silently emit nothing
            "build_done": self.build_done,
            "finalized": self.build is not None,
            "rename": self.rename,
            "payload": self.payload,
            "probe_buffer": [bridge.device_to_arrow(b) for b in self.probe_buffer],
        }

    def restore(self, state):
        if state is None:
            return
        if not isinstance(state, dict):  # legacy: bare build table
            self.build_parts = [bridge.arrow_to_device(state)]
            return
        if state["build"] is not None:
            b = bridge.arrow_to_device(state["build"])
            if state["finalized"]:
                self.build = b
                self.rename = state["rename"]
                self.payload = state["payload"]
                self.build_unique = join_ops.build_keys_unique(b, self.right_on)
            else:
                self.build_parts = [b]
        self.build_done = state["build_done"]
        self.probe_buffer = [
            bridge.arrow_to_device(t) for t in state["probe_buffer"]
        ]


class BroadcastJoinExecutor(BuildProbeJoinExecutor):
    """Small side broadcast to every channel (reference sql_executors.py:275):
    identical device logic; only the partitioner differs (Broadcast)."""


class DistinctExecutor(Executor):
    """Streaming distinct: emit each batch's rows whose key was not seen
    before, the first row of each key (reference sql_executors.py:517).  The
    keys seen so far stay sorted on the device and each batch is folded in:
    one sort of the batch, a search of the state, a linear merge
    (ops/distinct.py), never a sort of the state."""

    # one batch a dispatch, so what it emits has its input's shape and no
    # concat of outputs follows which batches were ready together
    MAX_PIPELINE_BATCHES = 1

    def __init__(self, keys: Sequence[str]):
        self.keys = list(keys)
        self.seen = distinct_ops.SortedKeys()

    def execute(self, batches, stream_id, channel):
        outs = []
        for b in batches:
            if b is None:
                continue
            with tracing.span("distinct.batch"):
                emit, added = self.seen.fold(key_limbs(b, self.keys), b.valid)
            if added:
                outs.append(_shrunk(DeviceBatch(
                    {k: b.columns[k] for k in self.keys}, emit, added)))
        if not outs:
            return None
        return bridge.concat_batches(outs) if len(outs) > 1 else outs[0]


class TopKExecutor(Executor):
    """Running top-k by sort keys (reference expresses this via
    ConcatThenSQLExecutor; here the running state is never larger than k)."""

    def __init__(self, by: List[str], k: int, descending: List[bool]):
        self.by = by
        self.k = k
        self.descending = descending
        self.state: Optional[DeviceBatch] = None

    def execute(self, batches, stream_id, channel):
        parts = [b for b in batches if b is not None]
        if self.state is not None:
            parts.append(self.state)
        if not parts:
            return None
        merged = bridge.concat_batches(parts) if len(parts) > 1 else parts[0]
        self.state = kernels.top_k(merged, self.by, self.k, self.descending)
        return None

    def done(self, channel):
        out, self.state = self.state, None
        return out


class SortExecutor(Executor):
    """Blocking sort with an external-merge spill tier.

    Small inputs: accumulate and sort once at done (the original path).
    Past config.SPILL_SORT_ROWS accumulated rows, each bucket is sorted on
    device and written to disk as a sorted RUN (Arrow IPC, chunked); done()
    k-way-merges the runs in bounded memory and emits a LIST of batches.
    Reference: SuperFastSortExecutor, sql_executors.py:88-188 — same
    sorted-run + merge design, with the device doing every sort.

    Merge invariant: after device-sorting the in-memory buffers, every row at
    or before the FIRST buffer-tail row (the min over live runs of each run's
    last buffered row) is globally final — later chunks of every run sort
    after their run's tail.  Rows are tagged (__run, __pos) so that boundary
    is found by identity, not by re-comparing keys on the host."""

    def __init__(self, by: List[str], descending: List[bool],
                 spill_rows: Optional[int] = None,
                 chunk_rows: Optional[int] = None):
        self.by = by
        self.descending = descending
        self.parts: List[DeviceBatch] = []
        self.rows = 0
        self.spill_rows = spill_rows or config.SPILL_SORT_ROWS
        self.chunk_rows = chunk_rows or config.SPILL_MERGE_CHUNK_ROWS
        self.runs: List[str] = []
        self._dir: Optional[str] = None

    def execute(self, batches, stream_id, channel):
        for b in batches:
            if b is None:
                continue
            self.parts.append(b)
            self.rows += b.count_valid()
        if self.rows >= self.spill_rows:
            self._spill_run()

    def _spill_run(self):
        import os
        import tempfile

        import pyarrow as pa

        if not self.parts:
            return
        if self._dir is None:
            self._dir = _new_spill_dir("sort-")
        merged = bridge.concat_batches(self.parts) if len(self.parts) > 1 else self.parts[0]
        s = kernels.sort_batch(merged, self.by, self.descending)
        table = bridge.device_to_arrow(s)
        path = os.path.join(self._dir, f"run-{len(self.runs)}.arrow")
        with pa.OSFile(path, "wb") as f:
            with pa.ipc.new_file(f, table.schema) as w:
                w.write_table(table, max_chunksize=self.chunk_rows)
        self.runs.append(path)
        self.parts = []
        self.rows = 0

    def done(self, channel):
        if not self.runs:
            if not self.parts:
                return None
            merged = bridge.concat_batches(self.parts) if len(self.parts) > 1 else self.parts[0]
            self.parts = []
            return kernels.sort_batch(merged, self.by, self.descending)
        self._spill_run()
        return self._merge_and_cleanup()

    def _merge_and_cleanup(self):
        try:
            yield from self._merge_runs()
        finally:
            _drop_spill_dir(self._dir)

    def _merge_runs(self):
        import numpy as np
        import pyarrow as pa

        readers = [pa.ipc.open_file(p) for p in self.runs]
        n_chunks = [r.num_record_batches for r in readers]
        next_chunk = [0] * len(readers)
        next_pos = [0] * len(readers)
        buffers: List[Optional[DeviceBatch]] = [None] * len(readers)
        # bounds[i]: (run, pos) tag of run i's last READ row.  While set, no
        # row sorting after it may be emitted (unread rows of run i all sort
        # after it).  None <=> the run is fully read AND its tail was emitted.
        bounds: List[Optional[Tuple[int, int]]] = [None] * len(readers)
        carry: Optional[DeviceBatch] = None

        def load(i) -> None:
            if next_chunk[i] >= n_chunks[i]:
                bounds[i] = None  # exhausted
                return
            rb = readers[i].get_batch(next_chunk[i])
            next_chunk[i] += 1
            t = pa.Table.from_batches([rb])
            b = bridge.arrow_to_device(t)
            n = b.padded_len
            b = b.with_column("__run", NumCol(jnp.full(n, i, dtype=jnp.int32), "i"))
            b = b.with_column(
                "__pos",
                NumCol(jnp.arange(next_pos[i], next_pos[i] + n, dtype=jnp.int32), "i"),
            )
            next_pos[i] += t.num_rows
            bounds[i] = (i, next_pos[i] - 1)
            buffers[i] = b

        for i in range(len(readers)):
            load(i)
        while True:
            parts = [b for b in buffers if b is not None]
            if carry is not None and carry.count_valid() > 0:
                parts.append(carry)
            if not parts:
                break
            merged = bridge.concat_batches(parts) if len(parts) > 1 else parts[0]
            s = kernels.sort_batch(merged, self.by, self.descending)
            nvalid = s.count_valid()
            run_arr, pos_arr = tracing.device_read(
                "sort.merge_runs",
                (s.columns["__run"].data, s.columns["__pos"].data))
            run_arr, pos_arr = run_arr[:nvalid], pos_arr[:nvalid]
            pending = [b for b in bounds if b is not None]
            if pending:
                cut = min(
                    int(np.nonzero((run_arr == r) & (pos_arr == p))[0][0])
                    for (r, p) in pending
                ) + 1
            else:
                cut = nvalid
            yield kernels.head(s, cut).drop(["__run", "__pos"])
            rest_mask = s.valid & (jnp.arange(s.padded_len) >= cut)
            rest = kernels.compact(kernels.apply_mask(s, rest_mask))
            carry = rest if rest.count_valid() > 0 else None
            # all buffered rows now live in carry (or were emitted); reload
            # any run whose tail row was emitted — only then can its next
            # chunk contribute to the frontier
            emitted_runs = {int(r) for r in run_arr[:cut]}
            for i in range(len(readers)):
                buffers[i] = None
                if bounds[i] is not None and bounds[i][0] in emitted_runs:
                    r, p = bounds[i]
                    if (run_arr[:cut] == r).any() and (
                        pos_arr[:cut][run_arr[:cut] == r].max() >= p
                    ):
                        load(i)


class CogroupExecutor(Executor):
    """Cogroup two key-partitioned streams (reference datastream.py:2073):
    buffer both sides, then per distinct key call fn(key, left_df, right_df)
    with host DataFrames (either may be empty) and emit the concatenated
    results.  Keys are colocated per channel by the hash-partitioned edges."""

    def __init__(self, left_on: str, right_on: str, fn: Callable,
                 out_schema: Sequence[str],
                 left_schema: Optional[Sequence[str]] = None,
                 right_schema: Optional[Sequence[str]] = None):
        self.left_on = left_on
        self.right_on = right_on
        self.fn = fn
        self.out_schema = list(out_schema)
        # plan-time schemas: a channel that received zero rows on one side
        # must still hand fn an empty frame WITH that side's columns
        self.left_schema = list(left_schema) if left_schema else None
        self.right_schema = list(right_schema) if right_schema else None
        self.left_parts: List[DeviceBatch] = []
        self.right_parts: List[DeviceBatch] = []

    def execute(self, batches, stream_id, channel):
        live = [b for b in batches if b is not None and b.count_valid() > 0]
        (self.left_parts if stream_id == 0 else self.right_parts).extend(live)
        return None

    def done(self, channel):
        import pandas as pd
        import pyarrow as pa

        def to_df(parts):
            if not parts:
                return None
            return pd.concat(
                [bridge.to_pandas(b) for b in parts], ignore_index=True
            )

        ldf, rdf = to_df(self.left_parts), to_df(self.right_parts)
        self.left_parts, self.right_parts = [], []
        if ldf is None and rdf is None:
            return None
        keys = set()
        if ldf is not None:
            keys |= set(ldf[self.left_on].dropna().unique().tolist())
        if rdf is not None:
            keys |= set(rdf[self.right_on].dropna().unique().tolist())
        outs = []
        empty_l = (ldf.iloc[0:0] if ldf is not None
                   else pd.DataFrame(columns=self.left_schema or []))
        empty_r = (rdf.iloc[0:0] if rdf is not None
                   else pd.DataFrame(columns=self.right_schema or []))
        for k in sorted(keys):
            lg = ldf[ldf[self.left_on] == k] if ldf is not None else empty_l
            rg = rdf[rdf[self.right_on] == k] if rdf is not None else empty_r
            out = self.fn(k, lg, rg)
            if out is not None and len(out):
                outs.append(out)
        if not outs:
            return None
        res = pd.concat(outs, ignore_index=True)[self.out_schema]
        return bridge.arrow_to_device(pa.Table.from_pandas(res, preserve_index=False))
