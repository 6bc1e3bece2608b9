"""The embedded push-based runtime: TaskGraph + TaskManager + Coordinator.

This single-process engine carries the reference's full runtime semantics —
push-based pipelined execution, per-actor channels, partitioned shuffles,
stage-gated build-before-probe scheduling, consumption-watermark backpressure
(pyquokka/core.py exec/IO loops, coordinator.py stage advancement,
quokka_runtime.py TaskGraph) — against the embedded ControlStore and an
in-memory device BatchCache.  Multi-host deployment replaces the store with a
served ControlStore and the cache with the gRPC data plane, without changing
this scheduling logic.

Key invariants preserved from the reference:
- outputs of each (actor, channel) carry contiguous seq numbers; consumers
  request contiguous runs per source channel (flight.py do_get semantics);
- a source is exhausted for a consumer when its channel is in DST and the
  consumer's next needed seq exceeds the source's last produced seq (LIT);
- input generation throttles to at most `max_pipeline` batches ahead of the
  slowest consumer (EWT watermark, core.py:919-925);
- executors at stage s never run before every actor at stages < s is done
  (coordinator.py:106-128).
"""

from __future__ import annotations

import copy
import os
import pickle
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from quokka_tpu import config
from quokka_tpu.expression import Expr
from quokka_tpu.ops import bridge, kernels
from quokka_tpu.ops.batch import DeviceBatch
from quokka_tpu.ops.expr_compile import evaluate_predicate
from quokka_tpu.runtime.cache import BatchCache
from quokka_tpu.runtime.dataset import ResultDataset
from quokka_tpu.runtime.errors import CorruptArtifactError
from quokka_tpu.runtime.tables import ControlStore
from quokka_tpu.runtime.task import (
    ExecutorTask,
    ReplayTask,
    TapedExecutorTask,
    TapedInputTask,
)
from quokka_tpu import obs
from quokka_tpu.obs import memplane, opstats
from quokka_tpu.obs import spans as tracing
from quokka_tpu.planner import adapt as adapt_mod
from quokka_tpu.target_info import (
    BroadcastPartitioner,
    FunctionPartitioner,
    HashPartitioner,
    PassThroughPartitioner,
    RangePartitioner,
    TargetInfo,
)


def new_query_id() -> str:
    """Fresh query namespace id: short, unique per process lifetime, and
    alphanumeric (it embeds in HBQ spill and checkpoint filenames)."""
    import uuid

    return "q" + uuid.uuid4().hex[:10]


class LostObjectError(RuntimeError):
    """A tape input that probed available vanished before the replay reached
    it (e.g. the peer serving its HBQ copy died mid-replay).  Retryable: the
    caller requeues the TapedExecutorTask and the next attempt rebuilds from
    the checkpoint."""

    def __init__(self, name):
        super().__init__(f"lost object {name} vanished during replay")
        self.name = name


class ActorInfo:
    def __init__(self, actor_id, kind, channels, stage=0, sorted_actor=False,
                 channel_major=False):
        self.id = actor_id
        self.kind = kind  # 'input' | 'exec'
        self.channels = channels
        self.stage = stage
        self.sorted_actor = sorted_actor
        self.channel_major = channel_major  # range-partitioned sort output
        self.reader = None
        self.executor_factory = None
        self.targets: Dict[int, TargetInfo] = {}  # tgt_actor -> TargetInfo
        self.source_streams: Dict[int, int] = {}  # src_actor -> stream_id
        self.blocking_dataset: Optional[ResultDataset] = None
        self.sorted_by: Optional[List[str]] = None
        self.predicate = None  # pushed-down source filter (device mask post-read)
        self.projection: Optional[List[str]] = None
        # runtime/placement.py strategy pinning channels to workers (None ->
        # round-robin spread, the reference default)
        self.placement = None
        # plan-independent scan identity (planner/cost.source_signature),
        # stamped by SourceNode.lower on input actors: keys this scan's
        # measured rows/bytes in the persisted cardprofile
        self.src_sig: Optional[str] = None


class TaskGraph:
    """Physical plan builder (quokka_runtime.py:18-392 equivalent).

    ``query_id`` namespaces everything the graph writes — control-store
    tables (through a NamespacedStore view), HBQ spill filenames, checkpoint
    names, metrics keys — so many graphs can share one long-lived store and
    spill dir (the query service).  ``store``/``cache``/``spill_dir`` let
    the service hand in its shared, already-warm instances; a graph built
    without them owns fresh ones, exactly as before."""

    def __init__(self, exec_config: Optional[dict] = None, *,
                 store: Optional[ControlStore] = None,
                 cache: Optional[BatchCache] = None,
                 query_id: Optional[str] = None,
                 spill_dir: Optional[str] = None):
        self.query_id = query_id
        self.root_store = store if store is not None else ControlStore()
        self.store = (
            self.root_store.namespace(query_id) if query_id is not None
            else self.root_store
        )
        self.cache = cache if cache is not None else BatchCache(owner=query_id)
        self.exec_config = dict(config.DEFAULT_EXEC_CONFIG)
        if exec_config:
            self.exec_config.update(exec_config)
        self.actors: Dict[int, ActorInfo] = {}
        self._next_actor = 0
        # adaptive-exchange eligibility (planner/decide.py, registered by
        # JoinNode.lower / FusedStageNode.lower): (build_src_actor,
        # join_actor) -> {"probe_src": actor}.  The engine's skew trigger
        # only ever fires on edges listed here.
        self.adapt_edges: Dict[Tuple[int, int], dict] = {}
        # folded maps (optimizer.fold_maps): batch_funcs to prepend on every
        # edge whose source is this actor
        self._pending_batch_fns: Dict[int, List[Callable]] = {}
        self.hbq = None
        self.ckpt_dir = None
        self._private_spill = False  # True -> this graph owns its spill dirs
        if self.exec_config.get("fault_tolerance"):
            from quokka_tpu.runtime.hbq import HBQ

            if spill_dir is not None and query_id is not None:
                # service mode: one SHARED spill dir; filename namespaces
                # keep concurrent queries' spill + checkpoints apart
                os.makedirs(spill_dir, exist_ok=True)
                self.hbq = HBQ(spill_dir, namespace=query_id)
                self.ckpt_dir = os.path.join(spill_dir, "ckpt")
                os.makedirs(self.ckpt_dir, exist_ok=True)
            else:
                import tempfile

                base = self.exec_config.get("hbq_path",
                                            "/tmp/quokka_tpu_spill/")
                os.makedirs(base, exist_ok=True)
                # unique per run: id()-style keys repeat across (and within)
                # processes and would replay another run's spill files
                self.hbq = HBQ(tempfile.mkdtemp(prefix="run-", dir=base),
                               namespace=query_id)
                self.ckpt_dir = tempfile.mkdtemp(prefix="ckpt-", dir=base)
                self._private_spill = True

    def cleanup(self, preserve_durable: bool = False) -> None:
        """``preserve_durable``: keep the on-disk recovery trio (HBQ spill,
        checkpoint snapshots, stream resume manifest) while still GC'ing
        every in-memory namespace.  Set by the service for a standing query
        torn down by failure/shutdown, whose stream a restarted replica will
        resume from the manifest."""
        import shutil

        if self.hbq is not None and not preserve_durable:
            self.hbq.wipe()  # namespaced: only this query's files go
            if self._private_spill:
                shutil.rmtree(self.hbq.path, ignore_errors=True)
        if self.ckpt_dir is not None and self._private_spill \
                and not preserve_durable:
            shutil.rmtree(self.ckpt_dir, ignore_errors=True)
            # un-namespaced checkpoints die with the dir; their ledger
            # entries go with them (wipe_namespace covers namespaced ones)
            memplane.LEDGER.retire_prefix(("ckpt", self.ckpt_dir))
        if self.query_id is not None and not preserve_durable:
            # GC this query's checkpoints from wherever they actually went:
            # exec_config["checkpoint_store"] (an external/shared root that
            # outlives the graph) wins over the spill-dir default — a
            # persistent service would otherwise leak one ckpt-<qid> set
            # per query into the external store forever
            ckpt_root = self.exec_config.get("checkpoint_store")
            if ckpt_root is None and not self._private_spill:
                ckpt_root = self.ckpt_dir  # private dirs died in the rmtree
            if ckpt_root is not None:
                from quokka_tpu.runtime.ckptstore import CheckpointStore

                CheckpointStore(ckpt_root,
                                namespace=self.query_id).wipe_namespace()
            # a cleanly finished query is complete: no resume.  Both
            # manifest kinds (standing-query stream manifest, durable-batch
            # resume manifest) only survive via preserve_durable above.
            import contextlib

            for attr in ("stream_manifest", "resume_manifest"):
                manifest = getattr(self, attr, None)
                if manifest:
                    with contextlib.suppress(OSError):
                        os.remove(manifest)
        if self.query_id is not None:
            # the one-shot path and the service both land here: a finished
            # query's tables, queues, metrics and cache accounting all GC
            self.snapshot_metrics()  # metrics() keeps answering post-GC
            self.root_store.drop_namespace(self.query_id)
            from quokka_tpu import obs
            from quokka_tpu.runtime import scancache

            scancache.GLOBAL.drop_query(self.query_id)
            # memory plane: whatever the cache still holds is freed by this
            # teardown (retire, not leak), the measured peak persists under
            # the plan fingerprint for admission, and anything STILL in the
            # ledger after that is a named leak report.  A durably-preserved
            # standing query keeps its spill entries (the files survive for
            # resume) and only drops the per-query accounting.
            self.cache.release_ledger()
            if preserve_durable:
                memplane.LEDGER.drop_query(self.query_id)
            else:
                memplane.LEDGER.on_query_gc(
                    self.query_id, plan_fp=getattr(self, "plan_fp", None))
            # progress plane: final snapshot stashed, fraction gauges GC'd
            # (idempotent — the service path already finalized in finish();
            # must run BEFORE opstats GC while its ledger view still exists)
            from quokka_tpu.obs import progress

            progress.TRACKER.on_query_gc(self.query_id)
            # operator-stats plane: final snapshot, measured cardinalities
            # persisted under the plan fingerprint, per-query gauges GC'd
            opstats.OPSTATS.on_query_gc(
                self.query_id, plan_fp=getattr(self, "plan_fp", None))
            obs.REGISTRY.remove(f"cache.plan_hit.{self.query_id}",
                                f"cache.plan_miss.{self.query_id}",
                                f"task.latency_s.{self.query_id}",
                                f"shuffle.bytes.{self.query_id}",
                                f"shuffle.host_syncs.{self.query_id}",
                                f"compile.cache_hit.{self.query_id}",
                                f"compile.miss.{self.query_id}",
                                f"compile.prewarm_hit.{self.query_id}",
                                f"stream.panes.{self.query_id}",
                                f"stream.late_dropped.{self.query_id}",
                                f"stream.watermark_lag_s.{self.query_id}",
                                f"mem.live_bytes.{self.query_id}",
                                f"mem.peak_bytes.{self.query_id}",
                                f"mem.spill_resident_bytes.{self.query_id}")
        # persist this query's program set under its plan fingerprint so the
        # NEXT submit of the same plan shape pre-warms from disk
        fp = getattr(self, "plan_fp", None)
        if fp is not None:
            from quokka_tpu.runtime import compileplane

            compileplane.flush_plan(fp)

    def _new_actor(self, kind, channels, stage, sorted_actor=False) -> ActorInfo:
        info = ActorInfo(self._next_actor, kind, channels, stage, sorted_actor)
        self.actors[self._next_actor] = info
        self._next_actor += 1
        return info

    def new_input_reader_node(
        self,
        reader,
        channels: int,
        stage: int = 0,
        sorted_by: Optional[List[str]] = None,
        predicate=None,
        projection: Optional[List[str]] = None,
    ) -> int:
        info = self._new_actor("input", channels, stage, sorted_actor=sorted_by is not None)
        info.reader = reader
        info.sorted_by = sorted_by
        if predicate is not None:
            from quokka_tpu.ops.fuse import FusedPredicate

            info.predicate = FusedPredicate(predicate)
        info.projection = projection
        tapes = reader.get_own_state(channels)
        for ch in range(channels):
            lineages = tapes.get(ch, [])
            for seq, lineage in enumerate(lineages):
                self.store.tset("LT", (info.id, ch, seq), lineage)
            self.store.tset("LIT", (info.id, ch), len(lineages) - 1)
            self.store.ntt_push(info.id, TapedInputTask(info.id, ch, list(range(len(lineages)))))
        if info.sorted_actor:
            self.store.sadd("SAT", info.id)
        self.store.tset("AST", info.id, stage)
        return info.id

    def new_exec_node(
        self,
        executor_factory: Callable[[], object],
        sources: Dict[int, Tuple[int, TargetInfo]],  # stream_id -> (src_actor, edge spec)
        channels: int,
        stage: int = 0,
        blocking: bool = False,
        sorted_actor: bool = False,
        channel_major: bool = False,
    ) -> int:
        # per-source routing state is keyed by src_actor, so two streams from
        # the SAME actor (direct self-join / self-union) would collide; give
        # each extra stream its own pass-through relay actor
        seen_srcs = set()
        deduped = {}
        for stream_id in sorted(sources):
            src_actor, tinfo = sources[stream_id]
            if src_actor in seen_srcs:
                src_actor = self._relay_actor(src_actor, stage)
            seen_srcs.add(src_actor)
            deduped[stream_id] = (src_actor, tinfo)
        sources = deduped
        info = self._new_actor("exec", channels, stage, sorted_actor)
        info.channel_major = channel_major
        info.executor_factory = executor_factory
        self.store.tset("AST", info.id, stage)
        if sorted_actor:
            self.store.sadd("SAT", info.id)
        if channel_major:
            self.store.sadd("CMT", info.id)
        if blocking:
            info.blocking_dataset = ResultDataset(f"ds-{info.id}")
        for stream_id, (src_actor, tinfo) in sources.items():
            src = self.actors[src_actor]
            pending = self._pending_batch_fns.get(src_actor)
            if pending:
                tinfo = copy.copy(tinfo)
                tinfo.batch_funcs = list(pending) + list(tinfo.batch_funcs)
            src.targets[info.id] = tinfo
            info.source_streams[src_actor] = stream_id
            self.store.tset("PFT", (src_actor, info.id), tinfo)
        for ch in range(channels):
            reqs = {}
            for stream_id, (src_actor, tinfo) in sources.items():
                src = self.actors[src_actor]
                reqs[src_actor] = {
                    sch: 0
                    for sch in range(src.channels)
                    if _feeds(tinfo.partitioner, sch, ch, channels)
                }
            # IRT at state 0: the recovery planner's starting point
            self.store.tset("IRT", (info.id, ch, 0), copy.deepcopy(reqs))
            self.store.ntt_push(info.id, ExecutorTask(info.id, ch, 0, 0, reqs))
        return info.id

    def add_pending_batch_fn(self, src_actor: int, fn: Callable) -> None:
        self._pending_batch_fns.setdefault(src_actor, []).append(fn)

    def _relay_actor(self, src_actor: int, stage: int) -> int:
        from quokka_tpu.executors.sql_execs import StorageExecutor
        from quokka_tpu.target_info import PassThroughPartitioner

        return self.new_exec_node(
            StorageExecutor,
            {0: (src_actor, TargetInfo(PassThroughPartitioner()))},
            self.actors[src_actor].channels,
            stage,
        )

    def run(self, max_batches: Optional[int] = None):
        try:
            Engine(self).run(max_batches=max_batches)
        finally:
            self.cleanup()

    def result(self, actor_id: int) -> ResultDataset:
        return self.actors[actor_id].blocking_dataset

    def metrics(self) -> Dict:
        """Per-(actor, channel) progress counters flushed by engines/workers:
        {(actor, ch): {"tasks": n, "rows": n, "bytes": n}}, plus a "compile"
        entry (utils/compilestats.snapshot()) proving kernel reuse — actor
        keys are tuples, subsystem keys are strings."""
        saved = getattr(self, "_saved_metrics", None)
        out, workers = self._store_metrics() if saved is None else saved
        from quokka_tpu.utils import compilestats

        # kernel-reuse proof: real_compiles flat across runs == no churn;
        # worker processes report their own counters via the flush channel
        out = dict(out)
        out["compile"] = compilestats.snapshot()
        if workers:
            out["compile"]["workers"] = workers
        return out

    def _store_metrics(self) -> Tuple[Dict, Dict]:
        """Aggregate the flushed per-worker snapshots from the store.
        Namespaced graphs flush under ``("metrics", query_id, worker)``,
        plain graphs under ``("metrics", worker)``."""
        out: Dict = {}
        workers: Dict = {}
        want = 2 if self.query_id is None else 3
        for key, snap in list(self.root_store.kv.items()):
            if not (isinstance(key, tuple) and len(key) == want
                    and key[0] == "metrics"):
                continue
            if self.query_id is not None and key[1] != self.query_id:
                continue
            for k, v in snap.items():
                if k == "__compile__":
                    if key[-1] != "embedded":  # embedded == this process
                        workers[key[-1]] = v
                    continue
                agg = out.setdefault(k, {"tasks": 0, "rows": 0, "bytes": 0})
                for f in agg:
                    agg[f] += v[f]
        return out, workers

    def snapshot_metrics(self) -> None:
        """Capture the flushed metrics before drop_namespace sweeps them
        (metrics() keeps answering after cleanup)."""
        self._saved_metrics = self._store_metrics()


def ckpt_candidates(store, a: int, ch: int) -> List[Tuple[int, int, int]]:
    """A channel's recovery-point history: the recorded checkpoint triples
    ``(state_seq, out_seq, tape_pos)`` plus the always-available ``(0,0,0)``
    (state 0 + full tape replay needs no snapshot).  The single source for
    every covering-checkpoint selection (plan_rewinds, corrupt-checkpoint
    fallback, forced producer rewind) — the covering rule is correctness-
    critical and must not fork."""
    return [(0, 0, 0)] + [
        tuple(h) for h in (store.tget("LT", ("ckpts", a, ch)) or [])
    ]


def plan_rewinds(store, dead_exec: List[Tuple[int, int]]) -> Dict[Tuple[int, int], Tuple[int, int, int]]:
    """Need-driven checkpoint selection for a set of simultaneously lost exec
    channels (the reference's rewind requests, coordinator.py:221-229,274-334).

    Default = each channel's latest checkpoint.  But when channel X's replay
    tape consumes an object produced by co-dead channel Y at an output seq
    BELOW Y's chosen checkpoint out_seq, no surviving copy of that object may
    exist (HBQ spill is producer-local and died with Y's worker) — Y must
    rewind to a checkpoint old enough to regenerate it.

    The same covering rule applies PAST the tape: once X's tape is exhausted
    its live execution resumes consuming at its post-replay input frontier
    (IRT at the chosen state, advanced through the tape slice).  A co-dead
    producer restored past that frontier leaves a seq gap no surviving copy
    fills — the consumer-side cache copies died with X's worker and the
    producer-side async spill died with Y's — so X's exec task spins on
    plan_get forever while the stall report blames the dead worker's stale
    heartbeat (the TestKill9Recovery wedge; reproduce with
    `python -m quokka_tpu.analysis.schedex`).  Covering the frontier too
    closes it: over-rewinding is idempotent (re-emissions are seq-keyed,
    consumers ignore seqs below their frontier) and a finished producer is
    never rewound past its end (its checkpoint out_seqs never exceed the
    frontier a consumer could still need).  Iterate to fixpoint; choices
    only move backward, bounded by (0, 0, 0), so this terminates."""
    dead = set(dead_exec)
    choice: Dict[Tuple[int, int], Tuple[int, int, int]] = {}
    for (a, ch) in dead:
        lct = store.tget("LCT", (a, ch))
        choice[(a, ch)] = tuple(lct) if lct is not None else (0, 0, 0)

    def _rewind_to_cover(key: Tuple[int, int], seq: int) -> bool:
        if choice[key][1] <= seq:
            return False  # producer's replay regenerates it
        hist = ckpt_candidates(store, *key)
        best = tuple(
            max((h for h in hist if h[1] <= seq), key=lambda h: h[0])
        )
        if best == choice[key]:
            return False
        choice[key] = best
        return True

    changed = True
    while changed:
        changed = False
        for (a, ch) in dead:
            state_seq, _out_seq, tape_pos = choice[(a, ch)]
            irt = store.tget("IRT", (a, ch, state_seq)) or {}
            frontier = {s: dict(c) for s, c in irt.items()}
            for ev in store.tape_slice(a, ch, tape_pos):
                if ev[0] != "exec":
                    continue
                for name in ev[2]:
                    key = (name[0], name[1])
                    seq = name[2]
                    chans = frontier.setdefault(name[0], {})
                    if chans.get(name[1], 0) <= seq:
                        chans[name[1]] = seq + 1
                    if key not in dead:
                        continue  # producer alive: its HBQ still serves it
                    if _rewind_to_cover(key, seq):
                        changed = True
            # live-phase needs: the first seq consumed after the tape ends
            # must also be regenerated by any co-dead producer
            for sa, chans in frontier.items():
                for sch, nxt in chans.items():
                    key = (sa, sch)
                    if key not in dead:
                        continue
                    if _rewind_to_cover(key, nxt):
                        changed = True
    return choice


def _feeds(partitioner, src_ch: int, tgt_ch: int, n_tgt: int) -> bool:
    if isinstance(partitioner, PassThroughPartitioner):
        return src_ch % n_tgt == tgt_ch
    return True  # hash/broadcast/range/function: every source channel


# ---------------------------------------------------------------------------

# Guards lazily-created per-engine state (emit pool, prefetch pool, metrics,
# service scheduling state) against double-init when the query service drives
# one Engine from several dispatch threads.  Module-level so the distributed
# Worker (which bypasses Engine.__init__) is covered too.  Reentrant:
# _service_prepare holds it across _warm_prefetch -> _ensure_prefetch_pool.
_LAZY_INIT_LOCK = threading.RLock()

# Per-dispatch observability note (thread-local: service pools dispatch one
# engine from many threads).  dispatch_task opens a dict, handlers annotate
# the task's causal identity through it (seqs consumed/produced), and the
# finished dict rides the task's flight-recorder event — what the
# critical-path profiler (obs/critpath.py) rebuilds the DAG from.
_OBS_NOTE = threading.local()


def _note(**kw) -> None:
    d = getattr(_OBS_NOTE, "d", None)
    if d is not None:
        d.update(kw)


def _note_out(seq: int) -> None:
    d = getattr(_OBS_NOTE, "d", None)
    if d is not None:
        d.setdefault("outs", []).append(seq)


class Engine:
    """TaskManager + Coordinator for the embedded runtime."""

    def __init__(self, graph: TaskGraph):
        self.g = graph
        self.store = graph.store
        self.cache = graph.cache
        self._init_latency_hists(graph)
        self.max_batches = graph.exec_config.get("max_pipeline_batches", 8)
        self.execs: Dict[Tuple[int, int], object] = {}
        self._partition_fns: Dict[Tuple[int, int], Callable] = {}
        # adaptive-exchange state (planner/adapt.py): the edge->record map
        # mirrors the durable ADT table (re-read on every recovery path);
        # the row histograms and last-pushed sequences feed the trigger
        self._adapt: Dict[Tuple[int, int], dict] = dict(
            self.store.titems("ADT"))
        self._adapt_rows: Dict[Tuple[int, int], Dict[int, int]] = {}
        self._push_seqs: Dict[Tuple[int, int], int] = {}
        for info in graph.actors.values():
            if info.kind == "exec":
                for ch in range(info.channels):
                    self.execs[(info.id, ch)] = self._bind_executor(
                        info.executor_factory())
        # upgrade the plan's exec labels to the bound executor class names
        # (register_plan already ran in _init_latency_hists); executors may
        # carry an OP_NAME override — a fused stage labels itself with its
        # member chain so opstats rows stay legible per logical operator
        opstats.OPSTATS.register_plan(
            graph, op_names={aid: getattr(ex, "OP_NAME", type(ex).__name__)
                             for (aid, ch), ex in self.execs.items()})

    def _bind_executor(self, executor):
        """Streaming executors resolve their pane/late counters (global +
        per-query twins) against the live registry here — after the
        per-channel factory copy, so instruments are never deep-copied and
        never ride a checkpoint."""
        if hasattr(executor, "bind_query"):
            executor.bind_query(getattr(self.g, "query_id", None))
        return executor

    # -- partition function lowering (quokka_runtime.py:215-312) ------------
    def _partition_fn(self, src_actor: int, tgt_actor: int) -> Callable:
        key = (src_actor, tgt_actor)
        if key in self._partition_fns:
            return self._partition_fns[key]
        tinfo: TargetInfo = self.store.tget("PFT", key)
        n_tgt = self.g.actors[tgt_actor].channels
        part = tinfo.partitioner

        fused_pred = None
        if tinfo.predicate is not None:
            from quokka_tpu.ops.fuse import FusedPredicate

            fused_pred = FusedPredicate(tinfo.predicate)

        range_state = None
        if isinstance(part, RangePartitioner):
            # boundaries land on device ONCE per edge, not once per batch
            # (the per-batch jnp.asarray upload used to sit on the push hot
            # path).  The device copy is built lazily on the first narrow-
            # column batch: wide (int64-limb) columns never upload — their
            # boundaries exceed int32 without x64 — and use the host ints.
            range_state = {"host": [int(b) for b in part.boundaries],
                           "dev": None}

        def fn(batch: DeviceBatch, src_ch: int,
               seq: int = 0) -> Dict[int, DeviceBatch]:
            if fused_pred is not None:
                batch = fused_pred(batch)
            for f in tinfo.batch_funcs:
                batch = f(batch)
                if batch is None:
                    return {}
            if isinstance(part, PassThroughPartitioner):
                out = {src_ch % n_tgt: batch}
            elif isinstance(part, BroadcastPartitioner):
                out = {ch: batch for ch in range(n_tgt)}
            elif isinstance(part, HashPartitioner):
                if n_tgt == 1:
                    out = {0: batch}
                else:
                    # mid-query adaptation (planner/adapt.py): an ADT
                    # record rewrites this edge's routing — salt the fat
                    # build partition from its recorded sequence on, or
                    # replicate the fat probe partition to every channel.
                    # Looked up per call: the record can appear mid-run.
                    ad = self._adapt_map().get(key)
                    pids = kernels.partition_ids(batch, part.keys, n_tgt)
                    if ad is not None and ad["mode"] == "replicate":
                        out = dict(enumerate(adapt_mod.replicate_parts(
                            batch, pids, ad["fat"], n_tgt)))
                    else:
                        if (ad is not None and ad["mode"] == "salt"
                                and seq >= ad["from_seq"].get(src_ch, 0)):
                            pids = adapt_mod.salt_pids(pids, ad["fat"],
                                                       n_tgt)
                        out = dict(enumerate(kernels.split_by_partition(
                            batch, pids, n_tgt)))
            elif isinstance(part, RangePartitioner):
                out = self._range_split(batch, part, n_tgt, range_state)
            elif isinstance(part, FunctionPartitioner):
                out = part.fn(batch, src_ch, n_tgt)
            else:
                raise NotImplementedError(type(part))
            if tinfo.projection is not None:
                out = {ch: b.select(list(tinfo.projection)) for ch, b in out.items()}
            return out

        self._partition_fns[key] = fn
        return fn

    def _range_split(self, batch, part: RangePartitioner, n_tgt: int,
                     range_state=None):
        import jax.numpy as jnp

        if range_state is None:  # direct callers (tests): uncached
            range_state = {"host": [int(b) for b in part.boundaries],
                           "dev": None}
        col = batch.columns[part.key]
        if getattr(col, "hi", None) is not None:
            from quokka_tpu.ops import timewide

            pids = timewide.limb_le_scalar_count(col, range_state["host"])
        else:
            if range_state["dev"] is None:
                range_state["dev"] = jnp.asarray(part.boundaries)
            pids = jnp.searchsorted(
                range_state["dev"], col.data, side="right").astype(jnp.int32)
        if part.descending:
            pids = (n_tgt - 1) - pids  # channel 0 owns the highest range
        return dict(enumerate(kernels.split_by_partition(batch, pids, n_tgt)))

    # -- adaptive exchanges (planner/adapt.py) -------------------------------
    def _adapt_map(self) -> Dict[Tuple[int, int], dict]:
        """Edge -> adaptation record.  Lazy because the distributed Worker
        bypasses Engine.__init__ (it never TRIGGERS adaptations, but its
        partition fns must honor records a coordinator run persisted)."""
        m = getattr(self, "_adapt", None)
        if m is None:
            m = self._adapt = {}
            self._adapt_refresh()
        return m

    def _adapt_refresh(self) -> None:
        """Re-read the durable ADT table into the local map — recovery
        paths call this so replayed pushes route exactly as the adapted
        run did (an engine-local map alone would forget records written
        before a simulated kill)."""
        m = self._adapt_map()
        try:
            m.update(dict(self.store.titems("ADT")))
        except Exception as e:  # a served store mid-failover: keep local
            # view; the next recovery path re-reads, so note, don't wedge
            obs.RECORDER.record("adapt", "refresh-deferred", err=repr(e))

    def _adapt_consider(self, edge: Tuple[int, int], src_channels: int,
                        n_tgt: int) -> None:
        """Evaluate the skew trigger for one eligible build edge; on fire,
        persist the (build, probe) ADT records BEFORE any batch ships under
        the new routing, then install them locally."""
        hist = self._adapt_rows.get(edge, {})
        fat = adapt_mod.skewed_channel(hist, n_tgt,
                                       opstats.skew_ratio_threshold())
        if fat is None:
            return
        src, tgt = edge
        probe = self.g.adapt_edges[edge]["probe_src"]
        probe_edge = (probe, tgt)
        # safety net on top of build-before-probe stage gating: replicating
        # the fat probe partition is only exactly-once if NO probe batch
        # shipped under the old routing
        if any(a == probe for (a, _ch) in self._push_seqs):
            del self.g.adapt_edges[edge]  # too late for this run
            return
        tinfo = self.store.tget("PFT", probe_edge)
        if tinfo is None or not isinstance(tinfo.partitioner,
                                           HashPartitioner):
            del self.g.adapt_edges[edge]
            return
        from_seq = {ch: self._push_seqs.get((src, ch), -1) + 1
                    for ch in range(src_channels)}
        build_rec, probe_rec = adapt_mod.build_records(fat, from_seq)
        with self.store.transaction():
            self.store.tset("ADT", edge, build_rec)
            self.store.tset("ADT", probe_edge, probe_rec)
        m = self._adapt_map()
        m[edge] = build_rec
        m[probe_edge] = probe_rec
        total = sum(hist.values())
        mean = total / max(n_tgt, 1)
        opstats.OPSTATS.note_adaptation(
            getattr(self.g, "query_id", None),
            {"kind": "adapt_runtime", "edge": f"a{src}->a{tgt}",
             "fat_channel": int(fat), "fat_rows": int(hist.get(fat, 0)),
             "mean_rows": round(mean), "total_rows": int(total),
             "ratio": round(hist.get(fat, 0) / mean, 2) if mean else None,
             "action": f"salt build partition {fat} across {n_tgt} "
                       f"channels, replicate probe partition {fat}"})
        obs.RECORDER.record("adapt", f"a{src}->a{tgt}", fat=int(fat),
                            total_rows=int(total))
        obs.REGISTRY.counter("adapt.fired").inc()

    # -- push (core.py:276-376) ---------------------------------------------
    def push(self, actor: int, channel: int, seq: int, batch: DeviceBatch) -> None:
        _note_out(seq)  # producer side of a critical-path data edge
        info = self.g.actors[actor]
        from quokka_tpu.runtime.cache import _batch_nbytes

        # streaming plane: persist the batch's watermark under its seq (SWM)
        # so recovery replay re-presents the same watermark trail, and stamp
        # every partition (splits build new DeviceBatch objects)
        stream_wm = getattr(batch, "_stream_wm", None)
        if stream_wm is not None:
            self.store.tset("SWM", (actor, channel, seq), stream_wm)
        # the sync scope carries this engine's once-resolved per-query
        # counter, so a split blocking inside the partition fn attributes to
        # THIS query even when neighbors dispatch concurrently
        adapt_edges = getattr(self.g, "adapt_edges", None) or {}
        with kernels.shuffle_sync_scope(self._shuffle_syncs_q):
            for tgt_actor in info.targets:
                fn = self._partition_fn(actor, tgt_actor)
                parts = fn(batch, channel, seq)
                if stream_wm is not None:
                    for part in parts.values():
                        part._stream_wm = stream_wm
                        part._stream_ch = channel
                if len(parts) > 1:
                    # shuffle volume: bytes entering a real exchange
                    # (fan-out > 1), counted once per edge from the parent
                    nb = _batch_nbytes(batch)
                    self._shuffle_bytes.inc(nb)
                    if self._shuffle_bytes_q is not None:
                        self._shuffle_bytes_q.inc(nb)
                # skew-trigger accounting, only while an eligible build
                # edge is still unadapted (and only on the embedded engine
                # — the distributed Worker lacks the serial-order guarantee
                # the trigger's determinism rides on)
                edge = (actor, tgt_actor)
                track = None
                if (edge in adapt_edges and config.adapt_enabled()
                        and hasattr(self, "_adapt_rows")
                        and edge not in self._adapt_map()):
                    track = self._adapt_rows.setdefault(edge, {})
                qid = getattr(self.g, "query_id", None)
                for tgt_ch, part in parts.items():
                    # delivered rows per (edge, target channel): the skew
                    # histogram.  Host count when known; else the part's
                    # async nrows_dev scalar (resolved at flush) — never a
                    # fresh device sync
                    opstats.OPSTATS.edge(
                        qid, actor, tgt_actor, tgt_ch,
                        part.nrows if part.nrows is not None
                        else part.nrows_dev)
                    if track is not None:
                        # the trigger's histogram may block on the tiny
                        # count scalar — a kernel-queue wait on an already-
                        # dispatched reduction, not a shuffle host sync
                        n = (part.nrows if part.nrows is not None
                             else int(tracing.device_read(
                                 "shuffle.adapt_count", part.nrows_dev))
                             if part.nrows_dev is not None else 0)
                        track[tgt_ch] = track.get(tgt_ch, 0) + int(n)
                    name = (actor, channel, seq, tgt_actor, actor, tgt_ch)
                    if self.g.hbq is not None:
                        # spill post-partition (core.py:311-313): replayable
                        # without recomputing the producer.  The d2h copy +
                        # checksummed write run on the background spill
                        # pool, overlapped with compute; recovery/checkpoint
                        # boundaries flush it (_flush_spills).
                        self._spill_submit(name, part)
                    self._cache_put(name, part)
                if track is not None:
                    self._push_seqs[(actor, channel)] = seq
                    self._adapt_consider(
                        edge, info.channels,
                        self.g.actors[tgt_actor].channels)
        if hasattr(self, "_push_seqs"):
            self._push_seqs[(actor, channel)] = seq

    # -- async HBQ spill ------------------------------------------------------
    # The HBQ write used to sit synchronously inside push: a full d2h sync +
    # framed disk write per partition per batch, serializing the producer
    # behind the disk.  It now runs on a bounded background pool; the
    # fault-tolerance contract is preserved by flush barriers at every point
    # recovery consults the spill (checkpoint record, failure simulation,
    # tape replay, object replay) and at engine teardown.  QK_SPILL_ASYNC=0
    # restores the synchronous path.

    def _spill_submit(self, name: Tuple, part: DeviceBatch) -> None:
        if not config.SPILL_ASYNC:
            self._spill_one(name, part)
            return
        pool = getattr(self, "_spill_pool", None)
        if pool is None:
            with _LAZY_INIT_LOCK:
                pool = getattr(self, "_spill_pool", None)
                if pool is None:
                    from concurrent.futures import ThreadPoolExecutor

                    self._spill_futs = []
                    self._spill_lock = threading.Lock()
                    pool = self._spill_pool = ThreadPoolExecutor(
                        max_workers=max(1, config.SPILL_POOL),
                        thread_name_prefix="quokka-spill",
                    )
        with self._spill_lock:
            self._spill_futs.append(
                pool.submit(self._offthread, self._spill_one, name, part))
        while True:
            with self._spill_lock:
                if len(self._spill_futs) <= config.SPILL_INFLIGHT:
                    break
                f = self._spill_futs.pop(0)
            # bound device memory pinned by pending spills
            tracing.device_wait("spill.backlog", f.result)

    def _offthread(self, fn, *args):
        """Run ``fn`` on a helper thread (prefetch pool, emitter, spill
        writer) as this query's off-thread work: the spans it closes carry
        the query id and ``p="offthread"`` (obs/spans.py)."""
        with tracing.offthread(getattr(self.g, "query_id", None)):
            return fn(*args)

    def _spill_one(self, name: Tuple, part: DeviceBatch) -> None:
        with tracing.span("spill.hbq"):
            # masked-view parts compact here (counts have landed by spill
            # time) so the d2h copy and the disk bytes stay proportional to
            # the partition, not the parent batch
            if part.padded_len > (1 << 16):
                part = kernels.compact(part)
            table = bridge.device_to_arrow(part, site="spill.part")
            self.g.hbq.put(name, table)
        obs.REGISTRY.counter("shuffle.spill_bytes").inc(table.nbytes)

    def _flush_spills(self) -> None:
        futs = getattr(self, "_spill_futs", None)
        if futs:
            with self._spill_lock:
                futs, self._spill_futs = self._spill_futs, []
            for f in futs:
                # propagate the first spill error loudly
                tracing.device_wait("spill.flush", f.result)

    def _shutdown_spill(self) -> None:
        pool = getattr(self, "_spill_pool", None)
        if pool is not None:
            try:
                self._flush_spills()
            finally:
                self._spill_pool = None
                pool.shutdown(wait=True)

    def _cache_put(self, name: Tuple, part: DeviceBatch) -> None:
        """Deliver a partition to its consumer channel's cache.  The embedded
        engine has one cache; the distributed worker overrides this to route
        by the channel-location table (CLT) over the socket data plane."""
        self.cache.put(name, part)

    # -- input task (core.py:824-965) ----------------------------------------
    # Reader IO overlaps device compute: while the engine executes other
    # tasks, a one-slot background thread per input channel pre-reads the
    # NEXT lineage (VERDICT r1: the serial loop left IO, h2d and compute
    # strictly sequential).  reader.execute is pure per lineage, so the
    # prefetched table is byte-identical to a synchronous read — replay
    # determinism is unaffected.
    def _read_and_bridge(self, info, channel: int, lineage) -> DeviceBatch:
        """Read one lineage and land it on device: decode -> (projection) ->
        dictionary-encode/pack -> one device_put.  Runs on the prefetch
        threads so host decode + the h2d transfer overlap device compute
        (reader.execute is pure per lineage, so a prefetched batch is
        byte-identical to a synchronous read — replay determinism holds).

        Hot segments come from the device scan cache (buffer-pool role,
        runtime/scancache.py): a warm re-scan of an unchanged file skips
        decode, encode and the h2d transfer entirely."""
        from quokka_tpu.runtime import scancache

        ckey = None
        key_fn = getattr(info.reader, "cache_key", None)
        if key_fn is not None and scancache.GLOBAL.enabled:
            base = key_fn(channel, lineage)
            if base is not None:
                ckey = (
                    base,
                    tuple(info.projection or ()),
                    tuple(info.sorted_by or ()),
                    config.x64_enabled(),  # dtype regime changes device layout
                )
                cached = scancache.GLOBAL.get(
                    ckey, query=getattr(self.g, "query_id", None))
                if cached is not None:
                    return cached
        with tracing.span("reader.execute"):
            table = info.reader.execute(channel, lineage)
        if info.projection is not None:
            keep = [c for c in info.projection if c in table.column_names]
            table = table.select(keep)
        with tracing.span("bridge.to_device"):
            # an h2d transfer is where HBM exhaustion actually surfaces:
            # capture the ledger state in a forensics bundle before the
            # allocator error propagates
            with memplane.alloc_guard(memplane.SITE_READER):
                batch = bridge.arrow_to_device(table,
                                               sorted_by=info.sorted_by)
        if ckey is not None:
            scancache.GLOBAL.put(ckey, batch)
        return batch

    def _ensure_prefetch_pool(self):
        if getattr(self, "_prefetch", None) is None:
            with _LAZY_INIT_LOCK:
                if getattr(self, "_prefetch", None) is None:
                    import concurrent.futures

                    self._prefetch_pool = (
                        concurrent.futures.ThreadPoolExecutor(
                            max_workers=self._io_threads(),
                            thread_name_prefix="quokka-io"))
                    self._prefetch = {}
        return self._prefetch

    def _take_prefetched(self, info, task, seq):
        pf = self._ensure_prefetch_pool()
        key = (task.actor, task.channel)
        fut = pf.pop(key, None)
        batch = None
        if fut is not None:
            want, f = fut
            if want == seq:
                with tracing.span("prefetch.wait"):
                    batch = f.result()
            else:
                f.cancel()
        if batch is None:
            lineage = self.store.tget("LT", (task.actor, task.channel, seq))
            batch = self._read_and_bridge(info, task.channel, lineage)
        # schedule the next seq while this batch computes
        nxt = task.peek_next_seq() if hasattr(task, "peek_next_seq") else None
        if nxt is not None:
            lineage_n = self.store.tget("LT", (task.actor, task.channel, nxt))
            if lineage_n is not None:
                pf[key] = (
                    nxt,
                    self._prefetch_pool.submit(
                        self._offthread, self._read_and_bridge, info,
                        task.channel, lineage_n
                    ),
                )
        return batch

    def handle_input_task(self, task: TapedInputTask) -> bool:
        info = self.g.actors[task.actor]
        seq = task.current_seq()
        if seq is None:
            # unbounded sources never exhaust their tape: poll for appended
            # segments until a stop flag turns the channel finite
            streamed = self._stream_advance(info, task)
            if streamed is not None:
                return streamed
            self.store.sadd("DST", (task.actor, task.channel), "done")
            return True
        if self._throttled(info, task.channel, seq):
            self.store.ntt_push(task.actor, task)
            return False
        batch = self._take_prefetched(info, task, seq)
        rows_raw = self._rows_of(batch)  # pre-predicate: what the reader read
        if info.predicate is not None:
            # the source is the operator a predicate's notes belong to
            with tracing.span("source.predicate"), \
                    opstats.OPSTATS.current_op(
                        getattr(self.g, "query_id", None), task.actor,
                        task.channel):
                batch = info.predicate(batch)
        if getattr(info.reader, "UNBOUNDED", False):
            batch = self._stamp_input_wm(info, task.actor, task.channel,
                                         seq, batch)
        with tracing.span("push.input"):
            self.push(task.actor, task.channel, seq, batch)
        from quokka_tpu.runtime.cache import _batch_nbytes

        # counters use the host-known row count only: count_valid() would add
        # a device sync per batch when a source predicate filtered device-side
        rows = batch.nrows if batch.nrows is not None else 0
        self._metric(task.actor, task.channel, rows, _batch_nbytes(batch))
        opstats.OPSTATS.scan(
            getattr(self.g, "query_id", None), task.actor, task.channel,
            rows_raw, self._rows_of(batch), _batch_nbytes(batch),
            batch.padded_len)
        with self.store.transaction():
            self.store.sadd("GIT", (task.actor, task.channel), seq)
        nxt = task.advance()
        if nxt.tape:
            self.store.ntt_push(task.actor, nxt)
        elif (getattr(info.reader, "UNBOUNDED", False)
              and not self.store.tget("SST", task.actor)):
            # exhausted tape on an un-stopped standing source: requeue so
            # the next dispatch polls for appended segments
            self.store.ntt_push(task.actor, nxt)
        else:
            self.store.sadd("DST", (task.actor, task.channel), "done")
        return True

    def _throttled(self, info: ActorInfo, src_ch: int, seq: int) -> bool:
        max_pipeline = self.g.exec_config["max_pipeline"]
        if not info.targets:
            return False
        if not self.cache.puttable():
            return True
        watermark = None
        for tgt_actor, tinfo in info.targets.items():
            tgt = self.g.actors[tgt_actor]
            for tgt_ch in range(tgt.channels):
                if not _feeds(tinfo.partitioner, src_ch, tgt_ch, tgt.channels):
                    continue
                w = self.store.tget("EWT", (info.id, src_ch, tgt_actor, tgt_ch), -1)
                watermark = w if watermark is None else min(watermark, w)
        return watermark is not None and seq > watermark + max_pipeline

    # -- streaming plane (quokka_tpu/streaming/) ------------------------------
    # An input actor whose reader declares UNBOUNDED never finishes on its
    # own: when its tape runs dry the engine polls the reader for appended
    # segments (recording each discovery in the control store, so recovery
    # and the resume manifest see the same frozen lineage) until a stop flag
    # (SST, set by StreamingHandle.stop) turns the channel finite and the
    # normal end-of-input finalization drains every open pane.

    def _stream_advance(self, info: ActorInfo, task: TapedInputTask):
        """Returns None (not streaming / stopped -> finite end-of-input),
        True (new segments discovered and queued: progress), or False
        (nothing new: requeued, idle)."""
        reader = info.reader
        if info.kind != "input" or not getattr(reader, "UNBOUNDED", False):
            return None
        a, ch = task.actor, task.channel
        if self.store.tget("SST", a):
            return None
        polls = getattr(self, "_stream_poll_at", None)
        if polls is None:
            with _LAZY_INIT_LOCK:
                polls = getattr(self, "_stream_poll_at", None)
                if polls is None:
                    polls = self._stream_poll_at = {}
        now = time.time()
        if now - polls.get((a, ch), 0.0) < config.STREAM_POLL_S:
            self.store.ntt_push(a, task)
            return False
        polls[(a, ch)] = now
        new = reader.poll(ch)  # StreamTruncatedError propagates LOUDLY
        if not new:
            self._stream_lag_update(a, ch, advanced=False)
            self.store.ntt_push(a, task)
            return False
        last = self.store.tget("LIT", (a, ch), -1)
        with self.store.transaction():
            for i, lineage in enumerate(new):
                self.store.tset("LT", (a, ch, last + 1 + i), lineage)
            self.store.tset("LIT", (a, ch), last + len(new))
        self.store.ntt_push(
            a, TapedInputTask(a, ch,
                              list(range(last + 1, last + 1 + len(new)))))
        obs.RECORDER.record("stream.segments", f"a{a}c{ch}", a=a, c=ch,
                            n=len(new), **(
                                {"q": self.g.query_id}
                                if getattr(self.g, "query_id", None) else {}))
        return True

    def _stamp_input_wm(self, info: ActorInfo, a: int, ch: int, seq: int,
                        batch: DeviceBatch) -> DeviceBatch:
        """Attach the channel's event-time watermark to an unbounded
        source's batch.  Derived host-side from the lineage's recorded max
        event time (never a device sync), persisted per seq (SWM) so
        recovery replay re-presents the identical watermark sequence, and
        monotone per channel (SWMC high-water)."""
        wm = self.store.tget("SWM", (a, ch, seq))
        if wm is None:
            lineage = self.store.tget("LT", (a, ch, seq))
            delay = float(getattr(info.reader, "watermark_delay", 0.0))
            wm = float(info.reader.lineage_time_max(lineage)) - delay
            prev = self.store.tget("SWMC", (a, ch))
            if prev is not None:
                wm = max(wm, prev)
            with self.store.transaction():
                self.store.tset("SWM", (a, ch, seq), wm)
                self.store.tset("SWMC", (a, ch), wm)
            self._stream_lag_update(a, ch, advanced=True)
        batch._stream_wm = wm
        batch._stream_ch = ch
        return batch

    def _stream_lag_update(self, a: int, ch: int, advanced: bool) -> None:
        """stream.watermark_lag_s gauge: wall seconds since the source
        watermark last ADVANCED (0 while it moves) — the standing query's
        staleness signal.  Instruments resolved once per engine, same
        no-resurrection discipline as the latency histograms."""
        gauges = getattr(self, "_stream_lag_gauges", None)
        if gauges is None:
            with _LAZY_INIT_LOCK:
                gauges = getattr(self, "_stream_lag_gauges", None)
                if gauges is None:
                    qid = getattr(self.g, "query_id", None)
                    insts = [obs.REGISTRY.gauge("stream.watermark_lag_s")]
                    if qid is not None:
                        insts.append(obs.REGISTRY.gauge(
                            f"stream.watermark_lag_s.{qid}"))
                    self._stream_wm_advanced_at = {}
                    gauges = self._stream_lag_gauges = insts
        now = time.time()
        if advanced or (a, ch) not in self._stream_wm_advanced_at:
            self._stream_wm_advanced_at[(a, ch)] = now
        lag = now - min(self._stream_wm_advanced_at.values())
        for g in gauges:
            g.set(lag)

    def _stamp_exec_wm(self, executor, out, channel: int) -> None:
        """Streaming executors' emissions carry the operator watermark so
        chained streaming stages clock off their upstream."""
        if out is None:
            return
        fn = getattr(executor, "current_watermark", None)
        if fn is None:
            return
        wm = fn(channel)
        if wm is not None and wm != float("-inf"):
            out._stream_wm = wm
            out._stream_ch = channel

    def _attach_stream_wm(self, name: Tuple, b):
        """Replay/recovery resolution path: re-attach the watermark recorded
        for this object's producing seq (batch attrs do not survive the
        arrow round trip through the HBQ spill)."""
        if b is None:
            return b
        wm = self.store.tget("SWM", (name[0], name[1], name[2]))
        if wm is not None:
            b._stream_wm = wm
            b._stream_ch = name[1]
        return b

    # -- exec task (core.py:484-700) -----------------------------------------
    def handle_exec_task(self, task: ExecutorTask) -> bool:
        info = self.g.actors[task.actor]
        executor = self.execs[(task.actor, task.channel)]
        qid = getattr(self.g, "query_id", None)
        # prune exhausted sources against DST/LIT; notify the executor so
        # multi-stream operators can finalize a side (build completion)
        out_seq = task.out_seq
        for src in list(task.input_reqs):
            chans = task.input_reqs[src]
            for ch in list(chans):
                if self.store.scontains("DST", (src, ch), "done"):
                    last = self.store.tget("LIT", (src, ch), -1)
                    if chans[ch] > last:
                        del chans[ch]
            if not chans:
                del task.input_reqs[src]
                # executor work (a join's build side finalizes here): a
                # done.* span, so it is the executors' time in the query's
                # record and not the dispatch's own
                with tracing.span(
                        f"done.source.{type(executor).__name__}"), \
                        opstats.OPSTATS.current_op(qid, task.actor,
                                                   task.channel):
                    extra = executor.source_done(
                        info.source_streams[src], task.channel)
                # emit decisions never inspect device data (a live-row count is
                # a full host round trip); empty batches flow and are harmless
                emitted = extra is not None
                if emitted:
                    self._stamp_exec_wm(executor, extra, task.channel)
                    with tracing.span("push.exec"):
                        self._emit(info, task.channel, out_seq, extra)
                    self._metric(task.actor, task.channel, self._rows_of(extra), 0)
                    opstats.OPSTATS.exec_out(qid, task.actor, task.channel,
                                             self._rows_of(extra))
                    out_seq += 1
                self._tape(task.actor, task.channel,
                           ("srcdone", info.source_streams[src], emitted))
        task.out_seq = out_seq
        if not task.input_reqs:
            with tracing.span(f"done.{type(executor).__name__}"), \
                    opstats.OPSTATS.current_op(qid, task.actor, task.channel):
                out = executor.done(task.channel)
            # spill-tier executors (external sort, grace join) emit their
            # result as a lazy SEQUENCE of bounded batches — a generator keeps
            # only one merged batch on device at a time
            if out is None or isinstance(out, DeviceBatch):
                outs = [out]
            else:
                outs = out  # list or generator
            for o in outs:
                if o is not None:
                    self._stamp_exec_wm(executor, o, task.channel)
                    with tracing.span("push.exec"):
                        self._emit(info, task.channel, out_seq, o)
                    self._metric(task.actor, task.channel, self._rows_of(o), 0)
                    opstats.OPSTATS.exec_out(qid, task.actor, task.channel,
                                             self._rows_of(o))
                    out_seq += 1
            # all sink emissions must land before DST says done: a consumer
            # (collect, coordinator result read) may act on "done" immediately
            self._flush_emits()
            with self.store.transaction():
                self.store.tset("LIT", (task.actor, task.channel), out_seq - 1)
                self.store.sadd("DST", (task.actor, task.channel), "done")
            return True
        plan = self.cache.plan_get(
            task.actor,
            task.channel,
            task.input_reqs,
            self._actor_stages(),
            self._sorted_actors(),
            # a fused stage amortizes its whole member chain over one
            # dispatch — let it drain a wider slice of the ready queue than
            # the per-operator default (still deterministic: the cap is a
            # static executor attribute, so tape replay sees the same sets)
            max_batches=getattr(executor, "MAX_PIPELINE_BATCHES", None)
            or self.max_batches,
            channel_major=self._channel_major_actors(),
        )
        if plan is None:
            self.store.ntt_push(task.actor, task)
            return False
        src_actor, names = plan
        # consumer side of the critical-path data edges: which (channel,
        # seq) batches of src_actor this dispatch consumed
        _note(src=src_actor, **{"in": [[n[1], n[2]] for n in names]})
        batches = [self.cache.get(n) for n in names]
        stream_id = info.source_streams[src_actor]
        opstats.OPSTATS.exec_in(qid, task.actor, task.channel, batches)
        with tracing.span(f"exec.{type(executor).__name__}"), \
                opstats.OPSTATS.current_op(qid, task.actor, task.channel):
            out = executor.execute(batches, stream_id, task.channel)
        out_seq = task.out_seq
        emitted = out is not None
        if emitted:
            self._stamp_exec_wm(executor, out, task.channel)
            with tracing.span("push.exec"):
                self._emit(info, task.channel, out_seq, out)
            out_seq += 1
        self._metric(task.actor, task.channel, self._rows_of(out), 0)
        opstats.OPSTATS.exec_out(qid, task.actor, task.channel,
                                 self._rows_of(out))
        self._tape(task.actor, task.channel, ("exec", src_actor, tuple(names), emitted))
        consumed: Dict[int, Dict[int, int]] = {src_actor: {}}
        for (sa, sch, seq, *_rest) in names:
            consumed[sa][sch] = max(consumed[sa].get(sch, 0), seq + 1)
        with self.store.transaction():
            for sch, nxt in consumed[src_actor].items():
                self.store.tset("EWT", (src_actor, sch, task.actor, task.channel), nxt - 1)
        self.cache.gc(names)
        new_task = task.advance(consumed, out_seq)
        interval = self.g.exec_config.get("checkpoint_interval")
        if interval and self.g.ckpt_dir is not None and new_task.state_seq % interval == 0:
            self._checkpoint(executor, new_task)
        self.store.ntt_push(task.actor, new_task)
        return True

    # -- metrics --------------------------------------------------------------
    # typed per-channel accounting lives in obs/metrics.py (EngineMetrics);
    # the flush cadence and the ("metrics", worker_id) store contract are
    # unchanged from the inline dict this replaced
    _METRICS_FLUSH_EVERY = 64

    def _metrics_guard(self):
        """Per-ENGINE lock for the EngineMetrics read-modify-write (the
        query service dispatches one engine's tasks from several threads).
        Per-engine so concurrent queries never contend on each other's
        counters; the global lock only guards the lazy creation."""
        lock = getattr(self, "_metrics_lock", None)
        if lock is None:
            with _LAZY_INIT_LOCK:
                lock = getattr(self, "_metrics_lock", None)
                if lock is None:
                    lock = self._metrics_lock = threading.Lock()
        return lock

    def _metric(self, actor: int, channel: int, rows, nbytes: int) -> None:
        """rows: an int, or a device count scalar (resolved lazily at flush
        time, when its async host copy has long landed — emit paths must not
        block on a device round trip for a counter)."""
        with self._metrics_guard():
            m = getattr(self, "_metrics", None)
            if m is None:
                m = self._metrics = obs.EngineMetrics()
            m.task(actor, channel, rows, nbytes)
            dirty = m.dirty >= self._METRICS_FLUSH_EVERY
        if dirty:
            self._flush_metrics()

    def _rows_of(self, batch):
        """Host count if known, else the batch's async device count (for
        deferred metric resolution), else None."""
        if batch is None:
            return 0
        if batch.nrows is not None:
            return batch.nrows
        return batch.nrows_dev

    def _flush_metrics(self) -> None:
        m = getattr(self, "_metrics", None)
        if m:
            wid = getattr(self, "worker_id", "embedded")
            qid = getattr(self.g, "query_id", None)
            key = ("metrics", wid) if qid is None else ("metrics", qid, wid)
            with self._metrics_guard():
                snap = m.snapshot()
            self.store.set(key, snap)
            # same cadence for the operator-stats plane: queued nrows_dev
            # scalars (async copies long landed) fold into the ledger here
            opstats.OPSTATS.resolve_pending()

    def _shutdown_prefetch(self) -> None:
        """Cancel speculative reads and release the IO threads — without this
        every Engine leaks its pool, and interpreter exit can block on a read
        stuck in a wedged filesystem."""
        pool = getattr(self, "_prefetch_pool", None)
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
            self._prefetch_pool = None
            self._prefetch = None

    def _actor_stages(self) -> Dict[int, int]:
        """AST is write-once at graph build; workers cache it locally instead
        of a per-task RPC (distributed hot loop)."""
        return dict(self.store.titems("AST"))

    def _sorted_actors(self):
        return self.store.smembers("SAT")

    def _channel_major_actors(self):
        return self.store.smembers("CMT")

    # -- fault tolerance ------------------------------------------------------
    def _tape(self, actor: int, ch: int, event) -> None:
        """Record the exec channel's event history (the lineage 'tape'): which
        exact batch sets were consumed and which steps emitted.  Replaying the
        tape after a failure reproduces byte-identical output seqs, which is
        what lets already-consumed outputs stay valid downstream (the
        TapedExecutorTask discipline, pyquokka/task.py:139, fault-tolerance.md)."""
        if self.g.hbq is None:
            return
        self.store.tape_append(actor, ch, event)

    def _ckpt_store(self):
        """Checkpoints outlive their writer (reference: S3, core.py:678-685):
        exec_config["checkpoint_store"] may point anywhere fsspec can reach;
        default = the run's checkpoint dir (shared on one machine)."""
        store = getattr(self, "_ckpt_store_obj", None)
        if store is None:
            from quokka_tpu.runtime.ckptstore import CheckpointStore

            root = self.g.exec_config.get("checkpoint_store") or self.g.ckpt_dir
            # query-service graphs share one checkpoint root: snapshot names
            # carry the query namespace so neighbors never restore each other
            store = self._ckpt_store_obj = CheckpointStore(
                root, namespace=getattr(self.g, "query_id", None))
        return store

    def _checkpoint(self, executor, task: ExecutorTask) -> None:
        """Snapshot executor state + input frontier + tape position
        (core.py:678-685)."""
        if not getattr(executor, "SUPPORTS_CHECKPOINT", False):
            # no snapshot support: recovery rewinds to state 0 + full tape
            # replay; recording an LCT here would silently drop state
            return
        # flush barrier: every spill the tape references up to this point
        # must be durable before the checkpoint triple is recorded —
        # recovery that restores here may immediately replay from the HBQ
        self._flush_spills()
        state = executor.checkpoint()
        try:
            self._ckpt_store().save(
                task.actor, task.channel, task.state_seq, pickle.dumps(state)
            )
        except (CorruptArtifactError, OSError) as e:
            # a failed snapshot is a SKIPPED snapshot, never a dead query:
            # checkpointing only shortens recovery (older checkpoints and
            # the full tape remain valid recovery points), so a flaky
            # store/torn upload must not kill a healthy run.  LCT is not
            # recorded — recovery never points at the failed save.
            obs.REGISTRY.counter("recover.ckpt_save_skipped").inc()
            obs.RECORDER.record("recover.ckpt_save_skipped",
                                f"a{task.actor}c{task.channel}",
                                state=task.state_seq, error=repr(e)[:160])
            obs.diag(f"[ckpt] snapshot ({task.actor},{task.channel}) state "
                     f"{task.state_seq} skipped: {e!r}")
            return
        tape_len = self.store.tape_len(task.actor, task.channel)
        with self.store.transaction():
            self.store.tset(
                "LCT",
                (task.actor, task.channel),
                (task.state_seq, task.out_seq, tape_len),
            )
            # full checkpoint HISTORY, not just the latest: recovery may have
            # to rewind a producer PAST its latest checkpoint when a co-dead
            # consumer's tape needs outputs the latest checkpoint postdates
            # (the reference's rewind requests, coordinator.py:221-229)
            self.store.tappend(
                "LT", ("ckpts", task.actor, task.channel),
                (task.state_seq, task.out_seq, tape_len),
            )
            self.store.tset(
                "IRT",
                (task.actor, task.channel, task.state_seq),
                {a: dict(c) for a, c in task.input_reqs.items()},
            )
        # The tape is NOT trimmed at checkpoints: pre-checkpoint events must
        # stay replayable because a failure can lose both a producer and a
        # consumer, and regenerating the consumer's lost inputs may require
        # replaying the producer from an older state than its latest
        # checkpoint (no shared spill disk is assumed).  Tape entries are
        # small host tuples — the reference similarly keeps full lineage in
        # Redis for the run's lifetime.
        #
        # Standing queries additionally persist a resume manifest (source
        # segment log + watermark trail + this recovery point) so a FULL
        # process restart — not just an in-process kill — resumes from here
        # instead of offset zero (quokka_tpu/streaming/manifest.py).
        if getattr(self.g, "stream_manifest", None):
            from quokka_tpu.streaming import manifest as _smanifest

            _smanifest.update(self.g)
        # Durable BATCH queries persist the analogous batch resume manifest
        # at the same cadence (quokka_tpu/runtime/resume.py): the service
        # supervisor re-admits orphans from it after a process death.
        elif getattr(self.g, "resume_manifest", None):
            from quokka_tpu.runtime import resume as _bresume

            _bresume.update(self.g)

    def simulate_failure_and_recover(self, failed: List[Tuple[int, int]]) -> None:
        """Kill the given exec (actor, channel) workers — losing executor
        state, their queued tasks, and cached inputs destined to them — then
        run the recovery protocol (coordinator.py:219-552): restore from a
        checkpoint chosen by the rewind planner, rebuild the input frontier
        from IRT, and replay already-produced inputs from the HBQ spill."""
        assert self.g.hbq is not None, "fault tolerance is not enabled"
        # flush barrier: the rewind planner and the replay tasks it queues
        # consult HBQ listings — pending async spills must land first
        self._flush_spills()
        dead_exec = []
        for (a, ch) in failed:
            info = self.g.actors[a]
            assert info.kind == "exec", "simulated failures target exec workers"
            for name in list(self.cache.flights_info()):
                if name[3] == a and name[5] == ch:
                    self.cache.gc([name])
            dead_exec.append((a, ch))
        choices = plan_rewinds(self.store, dead_exec)
        for (a, ch) in failed:
            self._recover_channel(a, ch, choice=choices.get((a, ch)))

    def _recover_channel(self, a: int, ch: int, choice=None) -> None:
        """Rebuild one lost channel by QUEUEING recovery tasks into NTT (the
        reference pushes TapedInputTask/TapedExecutorTask/ReplayTask from the
        coordinator, pyquokka/coordinator.py:424-552): whichever worker owns
        the channel after reassignment pops and executes them through its
        normal task loop.  Shared by the embedded failure simulation and the
        distributed worker's channel adoption (runtime/worker.py).
        `choice` = (state_seq, out_seq, tape_pos) from the rewind planner;
        None restores the latest checkpoint."""
        info = self.g.actors[a]
        # replayed pushes must honor adaptations recorded before the loss
        self._adapt_refresh()
        self.store.tdel("DST", (a, ch))
        self.store.ntt_remove_channel(a, ch)
        if info.kind == "input":
            # inputs carry no state: re-derive the remaining tape from GIT.
            # Seqs below the streaming GC floor were committed AND consumed
            # past every recorded checkpoint frontier before manifest.gc
            # dropped their GIT/LT rows, so the rebuild starts at the floor.
            last = self.store.tget("LIT", (a, ch), -1)
            floor = self.store.tget("LT", ("gc_floor", a, ch), 0)
            done = self.store.smembers("GIT", (a, ch))
            remaining = [s for s in range(floor, last + 1) if s not in done]
            if remaining:
                self.store.ntt_push(a, TapedInputTask(a, ch, remaining))
            elif (getattr(info.reader, "UNBOUNDED", False)
                  and not self.store.tget("SST", a)):
                # a fully committed UNBOUNDED channel is idle, not done:
                # requeue an empty tape so the poll loop keeps tailing
                self.store.ntt_push(a, TapedInputTask(a, ch, []))
            else:
                self.store.sadd("DST", (a, ch), "done")
            return
        if choice is None:
            choice = self.store.tget("LCT", (a, ch)) or (0, 0, 0)
        state_seq, out_seq, tape_pos = choice
        tape_base = self.store.tget("LT", ("tape_base", a, ch), 0)
        if tape_pos < tape_base:
            # streaming GC trimmed the tape below this recovery point
            # (manifest.gc trims only below the covering checkpoint of the
            # retained floor, so a planner choice landing here means the
            # floor discipline was violated) — fail loudly rather than
            # replay a silently truncated tape as if it were complete
            raise RuntimeError(
                f"recovery of channel ({a}, {ch}) needs tape history from "
                f"position {tape_pos}, but the tape was trimmed to "
                f"{tape_base} (streaming GC floor violation)"
            )
        reqs = {
            s: dict(c)
            for s, c in self.store.tget("IRT", (a, ch, state_seq)).items()
        }
        n_exec_events = sum(
            1 for ev in self.store.tape_slice(a, ch, tape_pos) if ev[0] == "exec"
        )
        self.store.ntt_push(
            a,
            TapedExecutorTask(
                a, ch, state_seq, out_seq, state_seq + n_exec_events, reqs,
                tape_pos,
            ),
        )

    # -- HBQ resolution hooks -------------------------------------------------
    # The embedded engine owns the run's only HBQ; the distributed Worker
    # overrides these to aggregate its OWN spill dir with every live peer's
    # (served over the data plane) — the reference's ReplayTask-co-located-
    # with-an-HBQ-copy discipline (coordinator.py:424-552) with the transfer
    # direction inverted: the adopter pulls instead of the holder pushing.
    def _hbq_names_for_target(self, tgt_actor: int, tgt_ch: int):
        return self.g.hbq.names_for_target(tgt_actor, tgt_ch)

    def _hbq_fetch(self, name: Tuple):
        return self.g.hbq.get(name)

    def _recompute_object(self, name: Tuple):
        """Last-resort recovery of a lost object (no live HBQ holds it):
        when its producer is an INPUT actor, the read is pure per lineage —
        re-read the lineage and re-partition for exactly the lost consumer
        channel (the reference's 'new input requests', coordinator.py:274-334).
        Exec-produced objects are regenerated by the producer's own tape
        replay instead; returns None for those."""
        src_a, src_ch, seq, tgt_a, _pfn, tgt_ch = name
        info = self.g.actors.get(src_a)
        if info is None or info.kind != "input":
            return None
        lineage = self.store.tget("LT", (src_a, src_ch, seq))
        if lineage is None:
            return None
        batch = self._read_and_bridge(info, src_ch, lineage)
        if info.predicate is not None:
            # exactly the live input path: source predicate BEFORE push
            # (handle_input_task), else the recomputed object gains rows
            batch = info.predicate(batch)
        # seq-aware: an adapted edge (ADT) routes this historical sequence
        # exactly as the original push did
        self._adapt_refresh()
        parts = self._partition_fn(src_a, tgt_a)(batch, src_ch, seq)
        return parts.get(tgt_ch)

    def _resolve_lost_object(self, name: Tuple):
        """cache -> any live HBQ -> input re-read; None if irrecoverable
        right now (the producer's tape replay may still regenerate it).
        Watermarks re-attach from the SWM trail: batch attrs do not survive
        the arrow round trip, and replay determinism needs the exact
        original watermark sequence."""
        b = self.cache.get(name)
        if b is not None:
            return self._attach_stream_wm(name, b)
        table = self._hbq_fetch(name)
        if table is not None:
            return self._attach_stream_wm(name, bridge.arrow_to_device(table))
        return self._attach_stream_wm(name, self._recompute_object(name))

    def _hbq_contains(self, name: Tuple) -> bool:
        """Listing-level probe; the distributed Worker overrides this to also
        consult peer HBQ listings (no bytes move either way)."""
        return self.g.hbq is not None and self.g.hbq.contains(name)

    def _object_available(self, name: Tuple) -> bool:
        """Existence probe WITHOUT materializing bytes: local cache hit, an
        HBQ listing (local or a peer's), or an input-lineage recompute is
        possible.  handle_exectape_task pre-flights the whole tape with this
        so a rewind to (0,0,0) on a long-running channel doesn't hold the
        channel's entire consumed history in device memory at once."""
        if self.cache.get(name) is not None:
            return True
        if self._hbq_contains(name):
            return True
        src_a, src_ch, seq = name[0], name[1], name[2]
        info = self.g.actors.get(src_a)
        return (
            info is not None
            and info.kind == "input"
            and self.store.tget("LT", (src_a, src_ch, seq)) is not None
        )

    def handle_exectape_task(self, task: TapedExecutorTask) -> bool:
        """Run a queued tape replay: recreate the executor, restore the
        checkpoint named by task.state_seq, re-run the recorded event history,
        then requeue the channel as a live ExecutorTask plus a ReplayTask that
        refills its input cache from the HBQ spill.

        Tape inputs are pre-flighted with EXISTENCE PROBES before any event
        executes (a missing one — its producer's own adoption/replay may not
        have re-pushed it yet — requeues this task untouched), then resolved
        one event at a time inside _replay_tape so a rewind to (0,0,0) never
        holds the channel's full consumed history in memory simultaneously.
        A probe-then-vanish race (peer dies mid-replay) surfaces as
        LostObjectError and requeues the same way: replay emissions are
        seq-keyed and deterministic, so the retried replay overwrites its own
        partial output rather than duplicating it."""
        a, ch = task.actor, task.channel
        self._flush_spills()  # tape inputs probe the HBQ listing below
        self._adapt_refresh()  # replay emissions route per recorded ADT
        reqs = {s: dict(c) for s, c in task.input_reqs.items()}
        tape = self.store.tape_slice(a, ch, task.tape_pos)

        def _requeue_waiting(name):
            # a vanished input whose producer is ALIVE will never reappear
            # on its own (e.g. its only spill copy was quarantined as
            # corrupt): force the producer to rewind far enough to re-emit
            # it (no-op outside the embedded single-threaded loop).  A
            # rewind queued now counts as progress — recovery work exists.
            rewound = self._maybe_force_producer_rewind(name)
            # time-based, not attempt-based: the co-dead producer's own
            # replay (possibly from state 0 with a long tape) can
            # legitimately take minutes to regenerate this object.  The
            # bound is QK_REPLAY_DEADLINE: a genuinely irrecoverable loss
            # used to wedge the full 600s under load (the ROADMAP
            # test_distributed note) with no way to shorten the verdict
            deadline = getattr(task, "retry_deadline", None)
            if deadline is None:
                deadline = task.retry_deadline = (
                    time.time() + config.replay_retry_deadline_s())
            if os.environ.get("QUOKKA_DEBUG_REPLAY"):
                now = time.time()
                if now - getattr(task, "_dbg_at", 0) > 3.0:
                    task._dbg_at = now
                    obs.diag(f"[replay-wait] ({a},{ch}) waiting on {name} "
                             f"cache={self.cache.get(name) is not None} "
                             f"hbq={self._hbq_contains(name)}")
            if time.time() > deadline:
                raise RuntimeError(
                    f"tape input {name} for channel ({a},{ch}) is in "
                    "no live HBQ and its producer never regenerated it "
                    f"within QK_REPLAY_DEADLINE="
                    f"{config.replay_retry_deadline_s():g}s — "
                    "irrecoverable loss"
                )
            self.store.ntt_push(a, task)
            time.sleep(0.05)
            return rewound

        probed = set()
        for ev in tape:
            if ev[0] != "exec":
                continue
            for name in ev[2]:
                if name in probed:
                    continue
                if not self._object_available(name):
                    return _requeue_waiting(name)
                probed.add(name)
        self.execs[(a, ch)] = self._bind_executor(
            self.g.actors[a].executor_factory())
        try:
            blob = self._ckpt_store().load(a, ch, task.state_seq)
        except CorruptArtifactError:
            # corrupt checkpoint == LOST checkpoint (the store already
            # quarantined it): rewind this channel to an older checkpoint —
            # ultimately (0,0,0) + full tape replay — instead of crashing
            # or restoring from untrusted bytes.  True: the queued fallback
            # IS progress (the embedded loop's no-progress stall check
            # would otherwise fire when this was the only pending task)
            self._ckpt_fallback(task)
            return True
        if blob is not None:
            self.execs[(a, ch)].restore(pickle.loads(blob))
        elif task.state_seq > 0:
            raise FileNotFoundError(
                f"checkpoint for ({a},{ch}) state {task.state_seq} named by "
                "LCT is missing from the checkpoint store — cannot rebuild"
            )
        try:
            state_seq, out_seq = self._replay_tape(
                a, ch, tape, reqs, task.state_seq, task.out_seq
            )
        except LostObjectError as e:
            self.execs.pop((a, ch), None)  # discard the partial rebuild
            return _requeue_waiting(e.name)
        # replay-complete check: the tape must advance the state exactly to
        # where the coordinator said the channel was when it queued this task
        assert state_seq == task.last_state_seq, (
            f"tape replay of ({a},{ch}) reached state {state_seq}, "
            f"expected {task.last_state_seq} — lineage tape diverged"
        )
        if self.g.hbq is not None:
            hbq_names = self._hbq_names_for_target(a, ch)
            specs = {
                name
                for name in hbq_names
                if name[0] in reqs
                and name[1] in reqs[name[0]]
                and name[2] >= reqs[name[0]][name[1]]
            }
            # ... plus every input-produced object the producer already
            # COMMITTED (GIT) past the restored frontier, whether or not a
            # live HBQ lists it: a partition that lived only in the dead
            # worker's cache/private HBQ is in nobody's listing, and without
            # a spec nobody regenerates it — the consumer then waits forever
            # while the recovered input task skips the seq as already-done
            # (the deadlock this closes).  These names re-read from lineage
            # in handle_replay_task (_recompute_object — the reference's
            # 'new input requests', coordinator.py:274-334).  Bounded to
            # GIT'd seqs: uncommitted seqs arrive from the live/recovered
            # producer normally, and exec-produced inputs re-push via their
            # producer's own tape replay.
            for src_a, chans in reqs.items():
                src_info = self.g.actors.get(src_a)
                if src_info is None or src_info.kind != "input":
                    continue
                for sch, nxt in chans.items():
                    for s in self.store.smembers("GIT", (src_a, sch)):
                        if s >= nxt:
                            specs.add((src_a, sch, s, a, src_a, ch))
            if specs:
                self.store.ntt_push(a, ReplayTask(a, ch, sorted(specs)))
        self.store.ntt_push(a, ExecutorTask(a, ch, state_seq, out_seq, reqs))
        return True

    def _ckpt_fallback(self, task: TapedExecutorTask) -> None:
        """Requeue a tape replay whose checkpoint failed its integrity
        check, rebuilt at the deepest available OLDER checkpoint (the
        ``ckpts`` history recorded at checkpoint time; (0,0,0) is always
        available — state 0 + full tape replay needs no snapshot).  The
        target ``last_state_seq`` is unchanged, so the replay still proves
        it reached exactly the state the channel died at."""
        a, ch = task.actor, task.channel
        hist = ckpt_candidates(self.store, a, ch)
        choice = max((h for h in hist if h[0] < task.state_seq),
                     key=lambda h: h[0])
        obs.REGISTRY.counter("recover.ckpt_fallback").inc()
        obs.RECORDER.record("recover.ckpt_fallback", f"a{a}c{ch}",
                            bad_state=task.state_seq, to=repr(choice))
        state_seq, out_seq, tape_pos = choice
        reqs = {
            s: dict(c)
            for s, c in self.store.tget("IRT", (a, ch, state_seq)).items()
        }
        self.store.ntt_push(
            a,
            TapedExecutorTask(a, ch, state_seq, out_seq,
                              task.last_state_seq, reqs, tape_pos),
        )

    # Escalation for an unrecoverable-by-waiting tape/replay input: the
    # object is in no cache and no HBQ (e.g. its spill was quarantined as
    # corrupt), and its producer is a LIVE exec channel — nothing in the
    # basic chain will ever regenerate it, so the producer itself must
    # rewind to a checkpoint old enough to re-emit it (corruption is
    # treated as loss OF THE PRODUCER'S OUTPUT, the same judgment
    # plan_rewinds makes for co-dead producers).  Embedded-engine only:
    # its dispatch loop is single-threaded, so rewinding a live channel
    # cannot race an in-flight dispatch of that channel.  The distributed
    # worker and the multi-threaded query service keep the wait-with-
    # deadline behavior (loud failure, never silent corruption).
    _allow_forced_rewind = True

    def _maybe_force_producer_rewind(self, name) -> bool:
        """Returns True when a rewind was queued NOW — that is real
        scheduling progress (new recovery work exists), which keeps the
        embedded loop's no-progress stall check honest while the waiting
        consumer requeues itself."""
        if not self._allow_forced_rewind or getattr(self, "_svc_ready", False):
            return False
        src_a, src_ch, seq = name[0], name[1], name[2]
        info = self.g.actors.get(src_a)
        if info is None or info.kind != "exec":
            return False
        forced = getattr(self, "_forced_rewinds", None)
        if forced is None:
            forced = self._forced_rewinds = set()
        key = (src_a, src_ch, seq)
        if key in forced:
            return False
        forced.add(key)
        # a LATER rewind of the same channel replaces any queued earlier one
        # (_recover_channel drops the channel's queued tasks), so every
        # rewind must cover the MINIMUM seq ever lost from this channel —
        # rewinding only far enough for the newest loss would cancel the
        # pending replay that was going to regenerate an older one
        floors = getattr(self, "_rewind_floor", None)
        if floors is None:
            floors = self._rewind_floor = {}
        floor = min(seq, floors.get((src_a, src_ch), seq))
        floors[(src_a, src_ch)] = floor
        hist = ckpt_candidates(self.store, src_a, src_ch)
        # the checkpoint must PREDATE the lost output seq or the replay
        # never re-emits it (same covering rule as plan_rewinds)
        choice = max((h for h in hist if h[1] <= floor), key=lambda h: h[0])
        obs.REGISTRY.counter("recover.producer_rewind").inc()
        obs.RECORDER.record("recover.producer_rewind", f"a{src_a}c{src_ch}",
                            for_seq=seq, to=repr(choice))
        self._recover_channel(src_a, src_ch, choice=choice)
        return True

    def dispatch_task(self, task) -> bool:
        """Route a popped NTT task to its handler by task kind, recording
        the dispatch in the flight recorder: completed dispatches as
        duration events, could-not-progress requeues coalesced to one
        ``task.wait`` instant per (actor, channel) stall episode (the retry
        loop would otherwise flood the ring and evict the history a stall
        dump needs)."""
        rec = obs.RECORDER
        qid = getattr(self.g, "query_id", None)
        label = f"{task.name}:a{task.actor}c{task.channel}"
        if qid is not None:
            label = f"{qid}:{label}"
        _OBS_NOTE.d = {}
        # the root of this dispatch's spans (obs/spans.py): what closes
        # under it knows its query and its parent, and the self times
        # partition the dispatch for the query's record
        with tracing.dispatch(task.name, label, qid) as frame:
            try:
                with rec.activity("task:" + label):
                    ok = frame.ok = self._dispatch(task)
            finally:
                note = getattr(_OBS_NOTE, "d", None) or {}
                _OBS_NOTE.d = None
        if ok:
            dt = frame.dur
            self._observe_latency(dt)
            opstats.OPSTATS.dispatch_time(qid, task.actor, task.channel, dt)
        if not rec.enabled:
            return ok
        qargs = {"a": task.actor, "c": task.channel, "k": task.name}
        if qid is not None:
            qargs["q"] = qid
        idle = getattr(self, "_obs_idle", None)
        if idle is None:
            idle = self._obs_idle = set()
        key = (task.actor, task.channel, task.name)
        if ok:
            rec.record("task", label, dur=dt, self_s=frame.self_s, **qargs,
                       **note)
            idle.discard(key)
        elif key not in idle:
            idle.add(key)
            rec.record("task.wait", label, **qargs)
        return ok

    def _init_latency_hists(self, graph) -> None:
        """Latency histograms resolved ONCE, while the graph is alive: the
        observe path must never use a creating registry lookup, or a
        dispatch quantum completing after TaskGraph.cleanup would resurrect
        the GC'd per-query instrument as a permanent /metrics leak
        (observing into the orphaned object instead is harmless).  Shared
        with the distributed Worker, whose __init__ bypasses Engine's."""
        self._lat_hist = obs.REGISTRY.histogram("task.latency_s")
        qid = getattr(graph, "query_id", None)
        self._qlat_hist = (
            obs.REGISTRY.histogram(f"task.latency_s.{qid}")
            if qid is not None else None)
        # shuffle instruments, same once-resolved discipline (push runs on
        # the dispatch path; per-query twins are GC'd in TaskGraph.cleanup)
        self._shuffle_bytes = obs.REGISTRY.counter("shuffle.bytes")
        self._shuffle_bytes_q = (
            obs.REGISTRY.counter(f"shuffle.bytes.{qid}")
            if qid is not None else None)
        self._shuffle_syncs_q = (
            obs.REGISTRY.counter(f"shuffle.host_syncs.{qid}")
            if qid is not None else None)
        # compile-plane attribution: per-query twins of the compile.* event
        # counters (GC'd in TaskGraph.cleanup) plus the plan fingerprint the
        # query's program uses are recorded under (runtime/compileplane.py)
        self._compile_counters = (
            {ev: obs.REGISTRY.counter(f"compile.{ev}.{qid}")
             for ev in ("cache_hit", "miss", "prewarm_hit")}
            if qid is not None else None)
        self._plan_fp = getattr(graph, "plan_fp", None)
        # operator-statistics plane: topology registered once while the
        # graph is alive (covers the distributed Worker too, whose __init__
        # bypasses Engine's); recording for an unregistered query is a no-op
        opstats.OPSTATS.register_plan(graph)

    def _observe_latency(self, dt: float) -> None:
        """Dispatch latency into the typed histograms (resolved once in
        __init__): one process-wide family plus a per-query one (GC'd with
        the query in TaskGraph.cleanup) that service stats() reads p50/p95
        from."""
        self._lat_hist.observe(dt)
        if self._qlat_hist is not None:
            self._qlat_hist.observe(dt)

    def _dispatch(self, task) -> bool:
        from quokka_tpu.runtime import compileplane

        # every program this dispatch compiles/loads is attributed to this
        # query (per-query compile.* counters) and recorded under its plan
        # fingerprint for the next submit's pre-warm
        with compileplane.query_scope(self._compile_counters, self._plan_fp):
            if task.name == "input":
                return self.handle_input_task(task)
            if task.name == "exec":
                return self.handle_exec_task(task)
            if task.name == "exectape":
                return self.handle_exectape_task(task)
            return self.handle_replay_task(task)

    def handle_replay_task(self, task: ReplayTask) -> bool:
        """Re-push spilled post-partition objects to the (rebuilt) consumer's
        cache — the reference's ReplayTask (pyquokka/core.py:967-1025), the
        objects coming off this worker's own HBQ or a live peer's (or an
        input re-read when no copy survives).

        Unresolvable names (every surviving copy corrupt/quarantined, the
        producer's regeneration not landed yet) requeue with the remaining
        specs instead of being silently dropped — a dropped spec would
        starve the rebuilt consumer forever.  A live exec producer of such
        a name is force-rewound (embedded engine) so regeneration actually
        happens; after the deadline the loss is surfaced loudly."""
        self._flush_spills()  # _resolve_lost_object reads the HBQ below
        missing = []
        resolved = 0
        for name in task.replay_specs:
            b = self._resolve_lost_object(name)
            if b is not None:
                self._cache_put(name, b)
                resolved += 1
            else:
                missing.append(name)
        if not missing:
            return True
        rewound = False
        for name in missing:
            rewound |= self._maybe_force_producer_rewind(name)
        deadline = getattr(task, "retry_deadline", None)
        if deadline is None:
            deadline = task.retry_deadline = (
                time.time() + config.replay_retry_deadline_s())
        if time.time() > deadline:
            raise RuntimeError(
                f"replay objects {missing[:3]}{'...' if len(missing) > 3 else ''} "
                f"for channel ({task.actor},{task.channel}) survive in no "
                "cache or HBQ and were never regenerated within "
                f"QK_REPLAY_DEADLINE={config.replay_retry_deadline_s():g}s "
                "— irrecoverable loss"
            )
        task.replay_specs = missing
        self.store.ntt_push(task.actor, task)
        time.sleep(0.05)
        # resolved objects ARE progress (they may unblock the consumer this
        # pass); so is a freshly queued producer rewind — only a fully
        # fruitless pass reads as no-progress to the stall check
        return rewound or resolved > 0

    def _replay_tape(self, actor: int, ch: int, events, reqs,
                     state_seq: int, out_seq: int):
        """Re-run the recorded event history: identical inputs in identical
        order reproduce identical outputs at identical seqs (so downstream
        consumers — which may already hold some of them — stay consistent).
        Inputs resolve LAZILY, one event at a time — probed available by the
        caller, but never all materialized at once."""
        info = self.g.actors[actor]
        executor = self.execs[(actor, ch)]
        for ev in events:
            if ev[0] == "exec":
                _, src_actor, names, emitted = ev
                batches = []
                for name in names:
                    b = self._resolve_lost_object(name)
                    if b is None:
                        raise LostObjectError(name)
                    batches.append(b)
                out = executor.execute(batches, info.source_streams[src_actor], ch)
                re_emitted = out is not None
                assert re_emitted == emitted, "non-deterministic replay"
                if re_emitted:
                    self._stamp_exec_wm(executor, out, ch)
                    self._emit(info, ch, out_seq, out)
                    out_seq += 1
                for name in names:
                    sa, sch, seq = name[0], name[1], name[2]
                    reqs[sa][sch] = max(reqs[sa].get(sch, 0), seq + 1)
                state_seq += 1
            else:
                # exhausted sources stay in reqs here; the first live prune
                # re-drops them (executors guard repeated source_done calls)
                _, stream_id, emitted = ev
                extra = executor.source_done(stream_id, ch)
                re_emitted = extra is not None
                assert re_emitted == emitted, "non-deterministic replay"
                if re_emitted:
                    self._stamp_exec_wm(executor, extra, ch)
                    self._emit(info, ch, out_seq, extra)
                    out_seq += 1
        return state_seq, out_seq

    # at most this many sink batches may be in flight on the emitter thread
    # (bounds device memory held by un-converted DeviceBatches)
    _EMIT_INFLIGHT = 8

    def _emit(self, info: ActorInfo, channel: int, seq: int, out: DeviceBatch) -> None:
        if getattr(info, "blocking", False) or info.blocking_dataset is not None:
            # sink emission is the engine's big blocking host segment (a full
            # device->host sync per output batch): run it on a single emitter
            # thread so the task loop keeps dispatching device work — the
            # reference gets this overlap from concurrent Ray actors
            # (pyquokka/core.py:276-376).  One thread => FIFO order; appends
            # are seq-keyed so replay re-emissions stay idempotent.  The
            # emitter is FLUSHED before a channel is marked done (DST) so no
            # consumer can observe a partially-shipped result set.
            self._emit_submit(
                lambda: self._convert_and_append(info, channel, seq, out)
            )
        else:
            self.push(info.id, channel, seq, out)

    def _convert_and_append(self, info, channel, seq, out):
        with tracing.span("emit.result_d2h"):
            table = bridge.device_to_arrow(out, site="emit.result")
        self._result_append(info, channel, seq, table)

    def _emit_submit(self, fn) -> None:
        pool = getattr(self, "_emit_pool", None)
        if pool is None:
            with _LAZY_INIT_LOCK:
                pool = getattr(self, "_emit_pool", None)
                if pool is None:
                    from concurrent.futures import ThreadPoolExecutor

                    self._emit_futs = []
                    self._emit_lock = threading.Lock()
                    pool = self._emit_pool = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="quokka-emit"
                    )
        with self._emit_lock:
            self._emit_futs.append(pool.submit(self._offthread, fn))
        while True:
            with self._emit_lock:
                if len(self._emit_futs) <= self._EMIT_INFLIGHT:
                    break
                f = self._emit_futs.pop(0)
            # wait OUTSIDE the lock: conversion is a d2h sync
            tracing.device_wait("emit.backlog", f.result)

    def _flush_emits(self) -> None:
        futs = getattr(self, "_emit_futs", None)
        if futs:
            with self._emit_lock:
                futs, self._emit_futs = self._emit_futs, []
            for f in futs:
                # propagate the first conversion/append error
                tracing.device_wait("emit.flush", f.result)

    def _shutdown_emitter(self) -> None:
        pool = getattr(self, "_emit_pool", None)
        if pool is not None:
            self._emit_pool = None
            pool.shutdown(wait=True)

    def _result_append(self, info: ActorInfo, channel: int, seq: int, table) -> None:
        """Blocking-node output sink; the distributed worker overrides this to
        ship result tables to the coordinator.  seq-keyed so fault-tolerant
        replay overwrites, never duplicates."""
        info.blocking_dataset.append(channel, table, seq=seq)
        if getattr(self.g, "resume_manifest", None):
            # durable-batch sink floor (monotone: replay re-appends must not
            # rewind it) — the resume manifest records how far the
            # client-visible result had materialized
            cur = self.store.tget("RMT", ("sink", info.id, channel), 0)
            if seq + 1 > cur:
                self.store.tset("RMT", ("sink", info.id, channel), seq + 1)

    # -- coordinator loop (coordinator.py:106-165) ----------------------------
    # Stage discipline follows the reference exactly: INPUT tasks only run when
    # their actor's stage <= the current execution stage; EXEC tasks always run
    # (their input requirements + the input gating enforce ordering,
    # core.py:504 comment); the stage advances when no undone actor remains at
    # the current stage.
    def run(self, max_batches: Optional[int] = None, timeout: float = 3600.0) -> None:
        try:
            self._run(max_batches, timeout)
            self._flush_emits()
        finally:
            try:
                self._flush_metrics()
            except Exception:
                pass  # a dead store must not block thread shutdown below
            self._shutdown_prefetch()
            self._shutdown_emitter()
            self._shutdown_spill()
            self._export_trace()

    def _export_trace(self) -> None:
        """QK_TRACE_EVENTS=<path>: write this process's flight events as
        Chrome trace JSON at run end (embedded engine only — distributed
        runs export the MERGED multi-worker timeline from the coordinator,
        runtime/distributed.py)."""
        path = obs.trace_export_path()
        if path is None or getattr(self, "worker_id", None) is not None:
            return
        try:
            obs.write_chrome_trace(
                path, obs.merge_streams({"engine": obs.RECORDER.snapshot()}))
        except OSError as e:
            obs.diag(f"[flight-recorder] trace export to {path} failed: {e}")

    def _io_threads(self) -> int:
        n = sum(a.channels for a in self.g.actors.values() if a.kind == "input")
        return max(2, min(4, n))

    def _warm_prefetch(self, actors) -> None:
        """Kick off the first read of every stage-0 input channel before the
        task loop starts, so initial decode+h2d runs in parallel across
        channels instead of serially on first touch."""
        if getattr(self, "_warmed", False):
            return  # re-entrant run(): finished channels must not re-read
        self._warmed = True
        self._ensure_prefetch_pool()
        for info in actors:
            if info.kind != "input" or info.stage != 0:
                continue
            for ch in range(info.channels):
                key = (info.id, ch)
                if key in self._prefetch or self.store.scontains(
                    "DST", (info.id, ch), "done"
                ):
                    continue
                lineage = self.store.tget("LT", (info.id, ch, 0))
                if lineage is None:
                    continue
                self._prefetch[key] = (
                    0,
                    self._prefetch_pool.submit(
                        self._offthread, self._read_and_bridge, info, ch,
                        lineage),
                )

    def _run(self, max_batches: Optional[int], timeout: float) -> None:
        if max_batches is not None:
            self.max_batches = max_batches
        actors = sorted(self.g.actors.values(), key=lambda a: (a.stage, a.id))
        self._warm_prefetch(actors)
        stages = sorted({a.stage for a in actors})
        stage_idx = 0
        t0 = time.time()
        inject = self.g.exec_config.get("inject_failure")
        handled = 0
        # chaos plane (QK_CHAOS kill=N): lose seeded-random exec channels at
        # seeded-random task boundaries, on top of any scripted injection
        from quokka_tpu.chaos import CHAOS

        chaos_kills = []
        if CHAOS.enabled and self.g.hbq is not None:
            exec_channels = sorted(
                (a.id, ch) for a in actors if a.kind == "exec"
                for ch in range(a.channels))
            chaos_kills = list(CHAOS.plan_embedded_failures(exec_channels))
        while True:
            if time.time() - t0 > timeout:
                _, report, _ = obs.dump_flight(
                    f"embedded engine run exceeded {timeout:.0f}s timeout",
                    {"engine": obs.RECORDER.snapshot()})
                raise TimeoutError(
                    "engine run exceeded timeout; pending tasks: "
                    f"{self.store.ntt_total()}"
                    + (f"; flight report: {report}" if report else "")
                )
            current = stages[stage_idx]
            progress = False
            for info in actors:
                if info.kind == "input" and info.stage > current:
                    continue
                task = self.store.ntt_pop(info.id)
                if task is None:
                    continue
                ok = self.dispatch_task(task)
                progress |= ok
                if ok:
                    handled += 1
                    if inject is not None and handled >= inject["after_tasks"]:
                        self.simulate_failure_and_recover(inject["channels"])
                        inject = None
                        progress = True
                    while chaos_kills and handled >= chaos_kills[0][0]:
                        _, chans = chaos_kills.pop(0)
                        CHAOS.record_kill(f"embedded {chans}")
                        self.simulate_failure_and_recover(chans)
                        progress = True
            if self._all_done(actors):
                return
            # advance when nothing undone remains at the current stage
            while stage_idx < len(stages) - 1 and not self._stage_undone(
                actors, stages[stage_idx]
            ):
                stage_idx += 1
                progress = True
            if not progress:
                _, report, _ = obs.dump_flight(
                    "embedded engine stalled: no task progressed",
                    {"engine": obs.RECORDER.snapshot()})
                raise RuntimeError(
                    "engine stalled: no task progressed and the stage cannot "
                    f"advance (stage={stages[stage_idx]}, "
                    f"pending={self.store.ntt_total()})"
                    + (f"; flight report: {report}" if report else "")
                )

    # -- service stepping (query service, service/server.py) ------------------
    # The multi-query scheduler round-robins NTT pops ACROSS live query
    # namespaces; within one query, each call to service_step is one
    # fair-scheduling quantum: pop and dispatch AT MOST ONE task, honoring
    # the same stage discipline as run().  Task-granular quanta are what
    # keep a large query from starving a small one sharing the pool.

    def _service_prepare(self) -> None:
        if getattr(self, "_svc_ready", False):
            return
        with _LAZY_INIT_LOCK:
            if getattr(self, "_svc_ready", False):
                return
            self._svc_actors = sorted(
                self.g.actors.values(), key=lambda a: (a.stage, a.id))
            self._svc_stages = sorted({a.stage for a in self._svc_actors})
            self._svc_stage_idx = 0
            self._svc_cursor = 0
            # serializes the stage barrier: a racy `_svc_stage_idx += 1`
            # from two dispatch threads could advance PAST an unchecked
            # stage (skipping its _stage_undone barrier)
            self._svc_stage_lock = threading.Lock()
            self._warm_prefetch(self._svc_actors)
            self._svc_ready = True

    def service_step(self) -> str:
        """Returns 'done' (query complete), 'progress' (a task ran),
        'wait' (a task popped but could not progress and requeued itself),
        or 'idle' (nothing poppable at the current stage)."""
        with tracing.span("step.pick", q=getattr(self.g, "query_id", None),
                          ring=False) as pick:
            task = self._service_pick()
            if isinstance(task, str):
                # "done"/"idle": nothing was picked, so no pick to time;
                # the walk stays the step's own (svc.fruitless when idle)
                pick.cancel()
                return task
        ok = self.dispatch_task(task)
        return "progress" if ok else "wait"

    def _service_pick(self):
        """The next task of this query for one quantum, or "done"/"idle":
        the stage barrier, the completion check and the actor walk."""
        self._service_prepare()
        actors = self._svc_actors
        stages = self._svc_stages
        # stage barrier: advance when nothing undone remains at the current
        # stage.  Under the lock so each increment is preceded by its own
        # _stage_undone check — an unsynchronized += from two dispatch
        # threads could hop over an unchecked stage.
        with self._svc_stage_lock:
            while (self._svc_stage_idx < len(stages) - 1
                   and not self._stage_undone(actors,
                                              stages[self._svc_stage_idx])):
                self._svc_stage_idx += 1
        if self._all_done(actors):
            return "done"
        current = stages[self._svc_stage_idx]
        n = len(actors)
        start = self._svc_cursor
        for i in range(n):
            info = actors[(start + i) % n]
            if info.kind == "input" and info.stage > current:
                continue
            task = self.store.ntt_pop(info.id)
            if task is None:
                continue
            self._svc_cursor = (start + i + 1) % n
            return task
        return "idle"

    def service_finalize(self) -> None:
        """Run-end teardown for a service-driven engine: ship pending sink
        emissions, flush counters, release the IO/emit threads (the
        shared store and caches stay — they belong to the service)."""
        try:
            self._flush_emits()
        finally:
            try:
                self._flush_metrics()
            except Exception as e:  # torn-down store must not block teardown
                obs.diag(f"[service] final metrics flush failed: {e!r}")
            self._shutdown_prefetch()
            self._shutdown_emitter()
            self._shutdown_spill()

    def _stage_undone(self, actors, stage) -> bool:
        for info in actors:
            if info.stage != stage:
                continue
            for ch in range(info.channels):
                if not self.store.scontains("DST", (info.id, ch), "done"):
                    return True
        return False

    def _all_done(self, actors) -> bool:
        for info in actors:
            for ch in range(info.channels):
                if not self.store.scontains("DST", (info.id, ch), "done"):
                    return False
        return True
