"""QueryService: a persistent multi-query engine over one warm runtime.

One long-lived process hosts many concurrent queries:

- **shared, warm state** — one ControlStore (each query in its own
  namespace), the process-global device scan cache, and the process-global
  jit/XLA compile caches all outlive any single query, so the second query
  over the same files/kernel shapes starts hot;
- **a worker pool** — ``QK_SERVICE_WORKERS`` dispatch threads multiplex
  every running query.  Scheduling is round-robin ACROSS query namespaces
  at task granularity with a per-query in-flight cap
  (``QK_SERVICE_INFLIGHT``), so a heavy TPC-H Q5 cannot starve a
  concurrent Q1;
- **admission control** — a byte-budgeted gate (service/admission.py):
  queries whose estimated working set would overshoot
  ``QK_SERVICE_MEM_BUDGET`` wait in a bounded FIFO queue and fail with
  ``AdmissionTimeout`` if they never fit;
- **isolation** — per-query BatchCache, namespaced store tables, namespaced
  HBQ spill filenames and checkpoint names in ONE shared spill dir, and an
  explicit ``drop_namespace`` GC at query end.

Usage::

    svc = QueryService(pool_size=2)
    h1 = svc.submit(ctx.read_parquet(p).groupby("k").agg_sql("sum(v) as s"))
    h2 = svc.submit(other_stream)
    df1, df2 = h1.to_df(), h2.to_df()
    svc.shutdown()
"""

from __future__ import annotations

import os
import pickle
import shutil
import tempfile
import threading
import time
import weakref
from typing import Dict, List, Optional

from quokka_tpu import obs
from quokka_tpu.runtime.cache import BatchCache
from quokka_tpu.runtime.engine import TaskGraph, new_query_id
from quokka_tpu.runtime.tables import ControlStore
from quokka_tpu.service.admission import (
    AdmissionController,
    AdmissionTimeout,
    _env_float,
    _env_int,
    estimate_working_set,
)
from quokka_tpu.service.session import (
    DONE,
    FAILED,
    RUNNING,
    QueryHandle,
    QuerySession,
)


class ServiceShutdown(RuntimeError):
    """submit() after shutdown(), or a query torn down by shutdown()."""


class QueryStallTimeout(TimeoutError):
    """A running query made no progress within QK_SERVICE_QUERY_TIMEOUT."""


class QueryCancelled(RuntimeError):
    """The query was cancelled via QueryHandle.cancel(): dispatch stopped at
    the next task boundary, admission bytes released, namespace/spill/
    checkpoints/manifest GC'd.  Distinct from the stall timeout — this is a
    client decision, not a health judgment."""


class DeadlineExceeded(TimeoutError):
    """The query outlived its submit(..., deadline_s=...) budget and was
    cooperatively cancelled at the next task boundary.  Distinct from
    QueryStallTimeout (a PROGRESSING query past its deadline still dies;
    a stalled one dies even without a deadline)."""


class QueryService:
    """Persistent multi-query engine: ``submit(stream) -> QueryHandle``."""

    def __init__(self,
                 pool_size: Optional[int] = None,
                 exec_config: Optional[dict] = None,
                 *,
                 mem_budget: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 max_concurrent: Optional[int] = None,
                 admit_timeout: Optional[float] = None,
                 inflight_per_query: Optional[int] = None,
                 query_timeout: Optional[float] = None,
                 spill_dir: Optional[str] = None):
        from quokka_tpu import config as qconfig

        self.exec_config = dict(qconfig.DEFAULT_EXEC_CONFIG)
        if exec_config:
            self.exec_config.update(exec_config)
        self.pool_size = (
            _env_int("QK_SERVICE_WORKERS", 2) if pool_size is None
            else max(1, pool_size)
        )
        self.inflight_per_query = (
            _env_int("QK_SERVICE_INFLIGHT", 2)
            if inflight_per_query is None else max(1, inflight_per_query)
        )
        self.query_timeout = (
            _env_float("QK_SERVICE_QUERY_TIMEOUT", 600.0)
            if query_timeout is None else query_timeout
        )
        self.store = ControlStore()
        self.admission = AdmissionController(
            mem_budget=mem_budget, queue_depth=queue_depth,
            max_concurrent=max_concurrent, admit_timeout=admit_timeout)
        if spill_dir is not None:
            os.makedirs(spill_dir, exist_ok=True)
            self._spill_dir = spill_dir
            self._own_spill = False
        else:
            base = self.exec_config.get("hbq_path", "/tmp/quokka_tpu_spill/")
            os.makedirs(base, exist_ok=True)
            self._spill_dir = tempfile.mkdtemp(prefix="service-", dir=base)
            self._own_spill = True
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._sessions: Dict[str, QuerySession] = {}  # LIVE queries only
        # every session ever enqueued, weakly: attach(query_id) keeps
        # working after the service drops its strong reference at finish,
        # for exactly as long as any client handle keeps the session alive
        self._by_id = weakref.WeakValueDictionary()
        self._queued: Dict[str, QuerySession] = {}
        self._running: List[str] = []  # round-robin order
        self._rr = 0
        self._finished = 0
        self._shutdown = False
        self._threads = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"qksvc-{i}")
            for i in range(self.pool_size)
        ]
        for t in self._threads:
            t.start()
        # the active chaos spec (QK_CHAOS) is part of the service's
        # identity: a soak triaging a failed run needs to see, in the
        # flight timeline, which fault plan this service ran under
        from quokka_tpu.chaos import CHAOS

        obs.RECORDER.record("service.start", f"pool={self.pool_size}",
                            chaos=CHAOS.describe())
        # QK_METRICS_PORT: external scrapers watch this service live
        # (/metrics Prometheus text + /status JSON of stats())
        self.metrics_server = obs.export.start_from_env(service=self)
        # health plane: the refcounted history sampler records registry
        # snapshots every QK_HISTORY_INTERVAL_S and drives the alert engine
        # (/history + /health); released at shutdown
        obs.history.acquire_sampler()
        # QK_PREWARM=1: load every recorded plan's persisted executables in
        # the background at startup, so even the first-ever submit of a
        # known plan shape dispatches against warm programs
        if os.environ.get("QK_PREWARM", "") not in ("", "0"):
            from quokka_tpu.runtime import compileplane

            compileplane.prewarm_all(wait=False)

    def prewarm(self, streams=None, timeout: float = 120.0) -> int:
        """Ahead-of-time warm the compile plane before traffic arrives.

        ``streams``: DataStreams whose plans this service will soon run —
        each is lowered into a throwaway graph to derive its plan
        fingerprint, and that plan's persisted executables are loaded
        synchronously (bounded by ``timeout``).  ``streams=None`` replays
        EVERY plan the ledger has ever recorded and returns the number of
        plans that loaded >= 1 persisted executable; with ``streams`` it
        returns the number of streams whose plan warmup was dispatched (an
        already-resident plan needs none and contributes 0).  Never raises
        (warmup is an optimization layer)."""
        import contextlib

        from quokka_tpu.runtime import compileplane
        from quokka_tpu.runtime.tables import ControlStore

        if streams is None:
            return compileplane.prewarm_all(wait=True, timeout=timeout)
        n = 0
        for stream in streams:
            # the throwaway graph exists only to derive plan_fp: restore the
            # context's latest_graph (introspection must keep answering from
            # the last EXECUTED graph) and tear down its spill dirs
            prev = getattr(stream.ctx, "latest_graph", None)
            graph = None
            try:
                graph = TaskGraph(self.exec_config, store=ControlStore())
                stream.ctx.lower_into(stream.node_id, graph)
                # lowering already fired this plan's background replay
                # (_lower_plan); wait on THAT thread rather than spawning
                # a duplicate that would race it over the same .aot files
                t = getattr(graph, "prewarm_thread", None)
                if t is not None:
                    t.join(timeout)
                n += t is not None
            except Exception as e:  # noqa: BLE001 — warm less, never fail
                obs.diag(f"[service] prewarm of a stream failed: {e!r}")
            finally:
                stream.ctx.latest_graph = prev
                if graph is not None:
                    with contextlib.suppress(Exception):
                        graph.cleanup()
        return n

    # -- client surface ------------------------------------------------------
    def submit(self, stream, *, working_set_bytes: Optional[int] = None,
               exec_config: Optional[dict] = None,
               durable: Optional[bool] = None,
               resume_from: Optional[str] = None,
               deadline_s: Optional[float] = None) -> QueryHandle:
        """Lower a DataStream's plan into this service's shared runtime and
        queue it for admission.  Returns immediately with a QueryHandle;
        raises AdmissionQueueFull when the wait queue is at capacity.

        ``durable=True`` (default from ``QK_DURABLE_BATCH``; requires
        ``fault_tolerance``) makes the query survive a full service process
        death: the engine rewrites a batch resume manifest (plan payload +
        fingerprint, per-channel checkpoint frontiers, sink floor) at every
        checkpoint cadence, and a restarted service re-admits it via
        ``recover_orphans()`` — or explicitly via
        ``submit(stream, resume_from=<manifest>)``, which verifies the
        resubmitted plan's structural fingerprint against the manifest and
        fails loudly (``ManifestMismatch``) on drift.

        ``deadline_s`` is a per-query wall-clock budget measured from
        submit: a query still unfinished past it is cooperatively cancelled
        at the next task boundary and fails with ``DeadlineExceeded``
        (default from ``QK_QUERY_DEADLINE_S``; distinct from the global
        stall timeout, which only fires on NO progress)."""
        # the inside view of the caller's submit time: one ``submit`` span
        # whose children are the phases (their self times are the query
        # record's ``entry.*`` keys, obs/querylog.py)
        with obs.spans.span("submit") as top:
            handle = self._submit(
                top, stream, working_set_bytes=working_set_bytes,
                exec_config=exec_config, durable=durable,
                resume_from=resume_from, deadline_s=deadline_s)
        obs.querylog.stamp(handle.query_id, "submit_out", top.t0 + top.dur)
        return handle

    def _submit(self, top, stream, *, working_set_bytes, exec_config,
                durable, resume_from, deadline_s) -> QueryHandle:
        with self._lock:
            if self._shutdown:
                raise ServiceShutdown("QueryService is shut down")
        ctx = stream.ctx
        cfg = self._merged_config(ctx, exec_config)
        if deadline_s is None:
            env_deadline = _env_float("QK_QUERY_DEADLINE_S", 0.0)
            deadline_s = env_deadline if env_deadline > 0 else None
        if resume_from is not None:
            from quokka_tpu.runtime import resume as bresume

            if not cfg.get("fault_tolerance"):
                raise ValueError(
                    "resume_from needs fault_tolerance=True: the resumed "
                    "query restores executor checkpoints and replays "
                    "spilled batches, neither of which exists without it")
            m = bresume.load(resume_from)
            return self._resume_orphan(m, resume_from, stream=stream,
                                       exec_config=exec_config,
                                       deadline_s=deadline_s)
        if durable is None:
            durable = bool(_env_int("QK_DURABLE_BATCH", 0))
        if durable and not cfg.get("fault_tolerance"):
            raise ValueError(
                "durable=True needs fault_tolerance=True: the resume "
                "manifest records checkpoint frontiers and replays spilled "
                "batches, neither of which exists without it")
        qid = top.q = new_query_id()
        obs.querylog.open(qid)
        obs.querylog.stamp(qid, "submit_in", top.t0)
        graph = TaskGraph(cfg, store=self.store,
                          cache=BatchCache(owner=qid), query_id=qid,
                          spill_dir=self._spill_dir)
        try:
            with obs.spans.span("submit.prepare_plan"):
                sub, sink_id = ctx._prepare_plan(stream.node_id)
            blob = None
            if durable:
                # capture the PREPARED (pre-lowering) plan: recovery
                # re-lowers it in a fresh context, and the structural
                # fingerprint check proves the re-lowering is the same plan
                try:
                    blob = pickle.dumps({
                        "sub": sub, "sink_id": sink_id,
                        "exec_channels": ctx.exec_channels,
                        "exec_config": cfg,
                    })
                except Exception as e:
                    raise ValueError(
                        "durable=True needs a picklable plan (no lambdas/"
                        f"closures in map/filter payloads): {e!r}") from e
            with obs.spans.span("submit.lower_plan"):
                sink_actor = ctx._lower_plan(sub, sink_id, graph)
            with obs.spans.span("submit.estimate"):
                est = (int(working_set_bytes)
                       if working_set_bytes is not None
                       else estimate_working_set(graph))
            if durable:
                from quokka_tpu.runtime import resume as bresume

                graph.resume_manifest = bresume.default_path(graph)
                graph.resume_plan_blob = blob
                graph.resume_est_bytes = est
            with obs.spans.span("submit.enqueue"):
                session = QuerySession(qid, graph, sink_actor, est,
                                       self.inflight_per_query)
                session.durable = durable
                if deadline_s is not None:
                    session.deadline_at = (session.submitted_at
                                           + float(deadline_s))
                self._enqueue_session(session)
                if durable:
                    # initial manifest at submit: a crash before the first
                    # checkpoint still re-admits (as a fresh run — no
                    # frontier to resume, but no silently vanished query)
                    bresume.update(graph)
        except BaseException:
            obs.querylog.discard(qid)
            graph.cleanup()
            raise
        with obs.spans.span("submit.enqueue"):
            # admit synchronously when it fits: the caller's next submit
            # must see this query CHARGED against the budget, not still in
            # the queue
            self._admit_pending()
        obs.RECORDER.record("service.submit", qid, q=qid, est_bytes=est,
                            durable=durable)
        return session.handle

    def _enqueue_session(self, session: QuerySession) -> None:
        """Charge admission and queue a freshly built session — the one
        locked shutdown-recheck/offer/queue/notify block both submit paths
        share (a raced shutdown() must never strand an offered session)."""
        with self._lock:
            if self._shutdown:
                raise ServiceShutdown("QueryService is shut down")
            self.admission.offer(session.query_id, session.est_bytes)
            session._service = self
            self._sessions[session.query_id] = session
            self._by_id[session.query_id] = session
            self._queued[session.query_id] = session
            self._wake.notify_all()

    def _merged_config(self, ctx, exec_config: Optional[dict]) -> dict:
        """Service config overlaid with the context's NON-default keys (every
        QuokkaContext carries the full default dict, so a blind update()
        would silently revert the service-level exec_config to defaults on
        every submit), then any per-submit overrides."""
        from quokka_tpu import config as qconfig

        cfg = dict(self.exec_config)
        defaults = qconfig.DEFAULT_EXEC_CONFIG
        for k, v in ctx.exec_config.items():
            if k not in defaults or defaults[k] != v:
                cfg[k] = v
        if exec_config:
            cfg.update(exec_config)
        return cfg

    def submit_continuous(self, stream, *,
                          resume_from: Optional[str] = None,
                          delivered_floor: Optional[int] = None,
                          manifest_path: Optional[str] = None,
                          working_set_bytes: Optional[int] = None,
                          exec_config: Optional[dict] = None):
        """Run ``stream`` as a STANDING query over its unbounded sources
        (quokka_tpu/streaming/): batches keep flowing as the tailed inputs
        grow, windowed/asof operators emit finalized panes incrementally as
        the event-time watermark advances, and the returned
        ``StreamingHandle`` delivers them via ``poll_deltas()`` until
        ``stop()`` drains the stream (final state bit-exact with the
        equivalent one-shot batch run).

        With ``fault_tolerance`` on, incremental checkpoints (operator
        state + source offsets + watermark snapshot) flow through the normal
        checksummed atomic checkpoint path and additionally persist a resume
        manifest; ``resume_from=<manifest>`` resubmits the SAME plan after a
        full service restart and continues from the last checkpointed pane
        boundary — only post-frontier segments replay, never the whole
        stream.  A client that durably captured N delta tables before the
        crash passes ``delivered_floor=N`` so the resume point never
        postdates its capture frontier (closing the output-commit gap —
        every uncaptured pane re-emits, deduped by pane identity).
        Restart survival requires a stable ``spill_dir`` (and/or
        ``checkpoint_store``); standing queries share admission and fair
        scheduling with batch queries but are exempt from the query-stall
        timeout (idle is healthy).  Under an active ``QK_CHAOS`` kill spec,
        seeded kills of the streaming operators are injected and recovered
        through the tape-replay protocol, exactly-once."""
        from quokka_tpu.chaos import CHAOS
        from quokka_tpu.streaming import manifest as smanifest
        from quokka_tpu.streaming.handle import StreamingHandle

        with self._lock:
            if self._shutdown:
                raise ServiceShutdown("QueryService is shut down")
        ctx = stream.ctx
        cfg = self._merged_config(ctx, exec_config)
        if resume_from and not cfg.get("fault_tolerance"):
            raise ValueError(
                "resume_from needs fault_tolerance=True: the resumed "
                "stream restores executor checkpoints and replays spilled "
                "segments, neither of which exists without it")
        resume = smanifest.load(resume_from) if resume_from else None
        qid = resume["query_id"] if resume else new_query_id()
        with self._lock:
            if qid in self._sessions:
                # a duplicate resume of a LIVE stream would run two engines
                # against one store/spill/checkpoint namespace — interleaved
                # seq assignments and conflicting pane deltas, silently
                raise ValueError(
                    f"stream {qid} is already running in this service — "
                    "stop it before resuming its manifest again")
        graph = TaskGraph(cfg, store=self.store,
                          cache=BatchCache(owner=qid), query_id=qid,
                          spill_dir=self._spill_dir)
        resume_info = None
        try:
            sink_actor = ctx.lower_into(stream.node_id, graph)
            if not any(getattr(info.reader, "UNBOUNDED", False)
                       for info in graph.actors.values()
                       if info.kind == "input"):
                raise ValueError(
                    "submit_continuous needs at least one UNBOUNDED source "
                    "(a streaming.TailingCsvReader / TailingParquetDirReader"
                    "); use submit() for finite plans")
            if cfg.get("fault_tolerance"):
                graph.stream_manifest = (
                    manifest_path or smanifest.default_path(graph))
            if resume is not None:
                resume_info = smanifest.apply_resume(
                    graph, resume, delivered_floor=delivered_floor)
            est = (int(working_set_bytes) if working_set_bytes is not None
                   else estimate_working_set(graph))
            session = QuerySession(qid, graph, sink_actor, est,
                                   self.inflight_per_query)
            session.streaming = True
            # seeded chaos: standing queries take REPEATED kills of their
            # checkpointable streaming operators over the stream's lifetime
            if CHAOS.enabled and cfg.get("fault_tolerance"):
                chans = sorted(
                    (a, ch) for (a, ch), e in session.engine.execs.items()
                    if getattr(e, "SUPPORTS_CHECKPOINT", False))
                plan = CHAOS.plan_stream_kills(chans)
                if plan:
                    session.inject_plan = [
                        {"after_tasks": after, "channels": channels}
                        for after, channels in plan]
                    if session.inject is None:
                        session.inject = session.inject_plan.pop(0)
            self._enqueue_session(session)
        except BaseException:
            # an aborted submit never ran: durable resume state (if any)
            # must survive for the next attempt
            obs.querylog.discard(qid)
            graph.cleanup(preserve_durable=resume_from is not None)
            raise
        self._admit_pending()
        obs.RECORDER.record("service.submit_continuous", qid, q=qid,
                            est_bytes=est, resumed=resume is not None)
        return StreamingHandle(session, resume_info=resume_info)

    # -- supervisor: durable-batch orphan recovery ---------------------------
    def recover_orphans(self, manifest_dir: Optional[str] = None
                        ) -> List[QueryHandle]:
        """Scan the manifest directory for orphaned durable batch queries (a
        previous service incarnation died with them in flight) and re-admit
        each through NORMAL admission — FIFO behind anything already queued,
        no barging — resuming from its last durable frontier.  Unreadable or
        foreign manifests are quarantined (``.corrupt``, counted on
        ``resume.quarantined``), never allowed to wedge the healthy orphans
        behind them.  Returns one QueryHandle per re-admitted query; call it
        right after constructing the restarted service (same ``spill_dir``)."""
        from quokka_tpu.runtime import resume as bresume

        if manifest_dir is None:
            manifest_dir = os.path.join(self._spill_dir, "ckpt")
        handles: List[QueryHandle] = []
        for path in bresume.scan(manifest_dir):
            m = bresume.load_or_quarantine(path)
            if m is None:
                continue
            with self._lock:
                if m["query_id"] in self._sessions:
                    continue  # live in THIS incarnation: not an orphan
            try:
                handles.append(self._resume_orphan(m, path))
            except bresume.ManifestMismatch as e:
                # foreign fingerprint / missing plan payload: same janitor
                # treatment as an unreadable manifest
                bresume.quarantine_manifest(path, repr(e))
        obs.REGISTRY.counter("resume.orphans").inc(len(handles))
        return handles

    def _resume_orphan(self, m: Dict, path: str, *, stream=None,
                       exec_config: Optional[dict] = None,
                       deadline_s: Optional[float] = None) -> QueryHandle:
        """Re-admit one manifest: re-lower its plan (from the manifest's own
        pickled plan payload, or from ``stream`` when the client resubmits
        explicitly), verify the structural fingerprint, apply the restart
        surgery, and enqueue through normal admission."""
        from quokka_tpu.runtime import resume as bresume

        qid = m["query_id"]
        with self._lock:
            if qid in self._sessions:
                # mirror of the streaming guard: a duplicate resume of a
                # LIVE query would run two engines against one store/spill/
                # checkpoint namespace — interleaved seq assignments and
                # conflicting results, silently
                raise ValueError(
                    f"query {qid} is already running in this service — "
                    "it cannot be resumed from its manifest again")
        blob = m.get("plan_blob")
        if stream is not None:
            ctx = stream.ctx
            cfg = self._merged_config(ctx, exec_config)
            sub, sink_id = ctx._prepare_plan(stream.node_id)
        else:
            if not blob:
                raise bresume.ManifestMismatch(
                    f"manifest {path} carries no plan payload — it cannot "
                    "be resumed without the original stream")
            from quokka_tpu.context import QuokkaContext

            payload = pickle.loads(blob)
            ctx = QuokkaContext()
            ctx.exec_channels = payload.get("exec_channels",
                                            ctx.exec_channels)
            sub, sink_id = payload["sub"], payload["sink_id"]
            cfg = dict(payload.get("exec_config") or self.exec_config)
        graph = TaskGraph(cfg, store=self.store,
                          cache=BatchCache(owner=qid), query_id=qid,
                          spill_dir=self._spill_dir)
        try:
            sink_actor = ctx._lower_plan(sub, sink_id, graph)
            graph.resume_manifest = path
            graph.resume_plan_blob = blob
            info = bresume.apply_resume(graph, m)
            est = int(m.get("est_bytes") or estimate_working_set(graph))
            graph.resume_est_bytes = est
            session = QuerySession(qid, graph, sink_actor, est,
                                   self.inflight_per_query)
            session.durable = True
            session.resume_info = info
            if deadline_s is not None:
                session.deadline_at = (session.submitted_at
                                       + float(deadline_s))
            self._enqueue_session(session)
        except BaseException:
            # an aborted resume never ran: the durable recovery trio must
            # survive for the next attempt
            obs.querylog.discard(qid)
            graph.cleanup(preserve_durable=True)
            raise
        self._admit_pending()
        obs.RECORDER.record(
            "service.resume", qid, q=qid, est_bytes=est,
            execs=len(info["execs"]), replay_specs=info["replay_specs"],
            corrupt_spills=info["corrupt_spills"])
        return session.handle

    def attach(self, query_id: str,
               cursor: Optional[Dict[int, int]] = None) -> QueryHandle:
        """A fresh handle for a query by id — including one re-admitted by
        ``recover_orphans()`` or already finished (for as long as any handle
        keeps its session alive).  ``cursor`` ({channel: last seq the client
        durably captured}) seeds the handle's delivery cursor so its first
        ``poll_batches()`` drains exactly the undelivered tail — a resumed
        sink rebuilds the full seq-keyed result set, so replayed batches
        below the cursor never re-surface and nothing above it is skipped."""
        with self._lock:
            session = self._sessions.get(query_id)
        if session is None:
            session = self._by_id.get(query_id)
        if session is None:
            raise KeyError(
                f"query {query_id!r} is unknown to this service (never "
                "submitted here, or finished with every handle released)")
        handle = QueryHandle(session)
        if cursor:
            handle._cursor.update(cursor)
        return handle

    # -- cancellation + deadlines --------------------------------------------
    def _cancel_ping(self, session: QuerySession) -> None:
        """QueryHandle.cancel() entry: a QUEUED query cancels synchronously
        (it holds no slot to drain); a RUNNING one is flagged and the worker
        loop honors it at the next task boundary."""
        obs.REGISTRY.counter("cancel.requested").inc()
        with self._lock:
            queued = self._queued.pop(session.query_id, None) is not None
            if queued:
                self.admission.cancel(session.query_id)
            self._wake.notify_all()
        if queued:
            self._finish(session, QueryCancelled(
                f"query {session.query_id} cancelled while queued"))

    def _reap_deadlines(self) -> None:
        """Fail QUEUED sessions whose deadline expired before admission
        (RUNNING ones are checked at every slot grant)."""
        now = time.time()
        expired: List[QuerySession] = []
        with self._lock:
            for qid, s in list(self._queued.items()):
                if s.deadline_at is not None and now > s.deadline_at:
                    self._queued.pop(qid, None)
                    self.admission.cancel(qid)
                    expired.append(s)
        for s in expired:
            obs.REGISTRY.counter("cancel.deadline").inc()
            self._finish(s, DeadlineExceeded(
                f"query {s.query_id} exceeded its deadline while queued "
                f"({now - s.submitted_at:.1f}s since submit)"))

    def stats(self) -> Dict:
        from quokka_tpu.runtime import scancache

        now = time.time()
        # non-creating lookup: a scrape racing a query's teardown must not
        # resurrect the just-GC'd per-query histogram (it would leak one
        # empty labeled family per finished query, forever)
        hists = obs.REGISTRY.histograms()
        counters = obs.REGISTRY.snapshot()
        with self._lock:
            sessions = {}
            for qid, s in self._sessions.items():
                h = hists.get(f"task.latency_s.{qid}")
                lat = h.stats() if h is not None else \
                    obs.Histogram.empty_stats()
                sessions[qid] = {
                    "status": s.status, "est_bytes": s.est_bytes,
                    "inflight": s.inflight, "handled": s.handled,
                    # queue-wait so far (live) or final; task-latency
                    # quantiles from the per-query histogram
                    "queue_wait_s": round(
                        ((s.started_at or now) - s.submitted_at), 6),
                    "task_p50_s": lat["p50"],
                    "task_p95_s": lat["p95"],
                    "tasks": lat["count"],
                    # memory plane columns (obs/memplane.py): snapshot
                    # lookups, never creating — the per-query gauges GC
                    # with the namespace and must stay gone
                    "mem_live_bytes": counters.get(
                        f"mem.live_bytes.{qid}", 0),
                    "mem_peak_bytes": counters.get(
                        f"mem.peak_bytes.{qid}", 0),
                    "mem_spill_bytes": counters.get(
                        f"mem.spill_resident_bytes.{qid}", 0),
                    # EXPLAIN ANALYZE plane: the session's hottest operator
                    # (non-creating ledger lookup; None before first stats)
                    "top_operator": obs.OPSTATS.top_operator(qid),
                }
                if s.durable:
                    # durable-batch columns: manifest cadence (the RMT
                    # journal length), resume provenance, cancel/deadline
                    # state — the /status surface for the supervisor plane
                    sessions[qid].update({
                        "durable": True,
                        "manifest_writes": len(
                            s.graph.store.tget("RMT", ("hist",)) or []),
                        "resumed": s.resume_info is not None,
                        "cancel_requested": s.cancel_requested,
                        "deadline_in_s": (
                            round(s.deadline_at - now, 3)
                            if s.deadline_at is not None else None),
                    })
                if not s.streaming:
                    # health plane: completion estimate + ETA (a standing
                    # query has no completion fraction — its row carries
                    # the watermark/pane figures instead)
                    prog = (dict(s.progress_snap)
                            if s.progress_snap is not None
                            else obs.progress.TRACKER.snapshot(qid))
                    sessions[qid].update({
                        "progress": prog["fraction"] if prog else None,
                        "eta_s": prog["eta_s"] if prog else None,
                        "progress_basis": prog["basis"] if prog else None,
                    })
                if s.streaming:
                    # standing-query row: source watermarks + pane/late
                    # counters (snapshot lookups — a scrape must never
                    # resurrect a GC'd per-query instrument)
                    wms = {}
                    for info in s.graph.actors.values():
                        if info.kind != "input" or not getattr(
                                info.reader, "UNBOUNDED", False):
                            continue
                        for ch in range(info.channels):
                            wms[f"{info.id}.{ch}"] = s.graph.store.tget(
                                "SWMC", (info.id, ch))
                    sessions[qid].update({
                        "streaming": True,
                        "watermarks": wms,
                        "watermark_lag_s": counters.get(
                            f"stream.watermark_lag_s.{qid}", 0.0),
                        "panes": counters.get(f"stream.panes.{qid}", 0),
                        "late_dropped": counters.get(
                            f"stream.late_dropped.{qid}", 0),
                    })
        return {
            "pool_size": self.pool_size,
            "workers_alive": sum(t.is_alive() for t in self._threads),
            "admission": self.admission.stats(),
            "sessions": sessions,  # live only; finished sessions are GC'd
            "finished": self._finished,
            # records kept by the process-wide query log (obs/querylog.py):
            # one per finished query, read with obs.querylog.records()
            "recent_queries": obs.querylog.size(),
            "scan_cache": scancache.GLOBAL.stats(),
            "queue_wait": obs.REGISTRY.histogram(
                "admission.queue_wait_s").stats(),
        }

    def shutdown(self, timeout: float = 30.0) -> None:
        """Stop the pool; unfinished queries fail with ServiceShutdown."""
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            self._wake.notify_all()
        for t in self._threads:
            t.join(timeout)
        for s in list(self._sessions.values()):
            if not s.finished:
                self.admission.cancel(s.query_id)
                s.finish(ServiceShutdown(
                    f"service shut down with query {s.query_id} unfinished"))
                self.admission.release(s.query_id)
        if self._own_spill:
            shutil.rmtree(self._spill_dir, ignore_errors=True)
        if self.metrics_server is not None:
            self.metrics_server.close()
        obs.history.release_sampler()
        obs.RECORDER.record("service.stop", "")

    close = shutdown

    def __enter__(self) -> "QueryService":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- scheduler -----------------------------------------------------------
    def _admit_pending(self) -> None:
        admitted, timed_out = self.admission.poll()
        if not admitted and not timed_out:
            return
        to_fail: List = []
        with self._lock:
            now = time.time()
            for qid in admitted:
                s = self._queued.pop(qid, None)
                if s is None:
                    continue
                s.status = RUNNING
                s.started_at = now
                s.last_progress = now
                self._running.append(qid)
                obs.REGISTRY.histogram("admission.queue_wait_s").observe(
                    now - s.submitted_at)
                obs.RECORDER.record("service.admit", qid, q=qid)
                obs.querylog.stamp(qid, "admitted")
            for qid, waited in timed_out:
                s = self._queued.pop(qid, None)
                if s is not None:
                    to_fail.append((s, waited))
        for s, waited in to_fail:
            obs.RECORDER.record("service.admit_timeout", s.query_id,
                                q=s.query_id)
            s.finish(AdmissionTimeout(
                f"query {s.query_id} (est {s.est_bytes >> 20} MiB) waited "
                f"{waited:.1f}s for admission under the "
                f"QK_SERVICE_MEM_BUDGET byte budget"))
            with self._lock:
                self._sessions.pop(s.query_id, None)
                self._finished += 1

    def _next_slot(self) -> Optional[QuerySession]:
        """Round-robin pick of a running session with a free in-flight slot;
        takes the slot (caller MUST release via _release_slot)."""
        with self._lock:
            n = len(self._running)
            for i in range(n):
                idx = (self._rr + i) % n
                s = self._sessions.get(self._running[idx])
                if (s is None or s.status != RUNNING or s.want_exclusive
                        or s.inflight >= s.inflight_cap):
                    continue
                s.inflight += 1
                self._rr = (idx + 1) % max(1, n)
                return s
        return None

    def _release_slot(self, session: QuerySession) -> None:
        with self._lock:
            session.inflight -= 1

    def _worker_loop(self) -> None:
        """One pool thread: quanta until shutdown.  Each turn is one
        ``svc.quantum`` span (a profiler annotation, never a ring event: one
        per turn would double the ring's traffic), so in a trace a worker
        is always inside a named span; what the turn spent outside its
        children (``_admit_pending``, ``_reap_deadlines``, ``_next_slot``,
        ``_release_slot`` and their lock) is the loop's own cost,
        ``service.loop_s``."""
        fruitless: Optional[int] = 0  # consecutive non-progress quanta
        while fruitless is not None:
            with obs.spans.span("svc.quantum", ring=False) as turn:
                fruitless = self._quantum(fruitless)
            obs.REGISTRY.counter("service.loop_s").inc(turn.self_s)

    def _quantum(self, fruitless: int) -> Optional[int]:
        """One turn of a pool thread; returns the thread's count of
        consecutive fruitless quanta, or None at shutdown."""
        with self._lock:
            if self._shutdown:
                return None
            n_running = len(self._running)
        self._admit_pending()
        self._reap_deadlines()
        session = self._next_slot()
        if session is None:
            # parked: no query to charge, so a process-wide counter
            with obs.spans.span("svc.park", ring=False) as park, self._wake:
                if not self._shutdown:
                    self._wake.wait(0.005)
            obs.REGISTRY.counter("service.park_s").inc(park.dur)
            return fruitless
        # cooperative cancellation/deadline: honored at the task
        # boundary, before dispatching another quantum for this query
        if session.cancel_requested or (
                session.deadline_at is not None
                and time.time() > session.deadline_at):
            self._release_slot(session)
            if session.cancel_requested:
                self._finish(session, QueryCancelled(
                    f"query {session.query_id} cancelled"))
            else:
                obs.REGISTRY.counter("cancel.deadline").inc()
                self._finish(session, DeadlineExceeded(
                    f"query {session.query_id} exceeded its deadline "
                    f"({time.time() - session.submitted_at:.1f}s since "
                    "submit)"))
            return fruitless
        err: Optional[BaseException] = None
        outcome = None
        try:
            # a step that progressed is its children (step.pick, the task);
            # one that returned wait/idle is the query waiting on its own
            # pipeline: svc.fruitless, a duration and a count in its record
            # (no ring event: a worker spinning on a blocked query would
            # evict the ring's history, as task.wait's coalescing says)
            with obs.spans.span("svc.step", q=session.query_id,
                                ring=False) as step:
                outcome = session.engine.service_step()
                if outcome in ("wait", "idle"):
                    step.rename("svc.fruitless")
        except BaseException as e:  # noqa: BLE001 — fail THIS query only
            err = e
        finally:
            self._release_slot(session)
        if err is not None:
            self._finish(session, err)
            return fruitless
        if outcome == "done":
            self._finish(session, None)
            return 0
        if outcome == "progress":
            session.last_progress = time.time()
            due = False
            with self._lock:
                session.handled += 1
                inj = session.inject
                due = (inj is not None
                       and session.handled >= inj["after_tasks"])
            if due:
                self._maybe_inject(session)
            return 0
        # "wait" / "idle": the query is blocked on its own pipeline.
        # Standing queries are exempt from the stall timeout — one
        # waiting for data is healthy, and keeps its slot
        # indefinitely (watermark-lag / /status surface staleness);
        # they share the batch queries' backoff below
        if (not session.streaming and
                time.time() - session.last_progress
                > self.query_timeout):
            self._finish(session, QueryStallTimeout(
                f"query {session.query_id} made no progress for "
                f"{self.query_timeout:.0f}s "
                f"(pending tasks: {session.graph.store.ntt_total()})"))
            return fruitless
        # back off only once every running query got a fruitless
        # quantum from this thread — a single blocked query must
        # neither hot-spin the pool nor throttle its neighbors
        fruitless += 1
        if fruitless >= max(2, 2 * n_running):
            fruitless = 0
            # charged to the session the last fruitless quantum was for
            with obs.spans.span("svc.backoff", q=session.query_id,
                                ring=False):
                time.sleep(0.002)
        return fruitless

    def _maybe_inject(self, session: QuerySession) -> None:
        """Run the query's configured fault injection (the
        test_fault_tolerance.py ``inject_failure`` discipline) with the
        session held EXCLUSIVELY — recovery rewrites executor state and
        queues, which must not race a concurrent dispatch of the same
        query.  Other queries keep running throughout."""
        with self._lock:
            inj = session.inject
            if inj is None or session.want_exclusive:
                return
            session.want_exclusive = True  # scheduler stops granting slots
        deadline = time.time() + 30.0
        while True:
            with self._lock:
                if session.inflight == 0:
                    session.inflight = 1
                    break
                if time.time() > deadline:
                    session.want_exclusive = False
                    return  # retry after the next progress quantum
            time.sleep(0.001)
        err = None
        try:
            obs.RECORDER.record("service.inject", session.query_id,
                                q=session.query_id,
                                channels=repr(inj["channels"]))
            session.engine.simulate_failure_and_recover(inj["channels"])
            # standing queries re-arm from the seeded stream-kill plan:
            # kills keep landing over the stream's lifetime, each recovered
            # through the tape-replay protocol
            session.inject = (session.inject_plan.pop(0)
                              if session.inject_plan else None)
        except BaseException as e:  # noqa: BLE001
            err = e
        finally:
            with self._lock:
                session.inflight -= 1
                session.want_exclusive = False
        if err is not None:
            self._finish(session, err)

    def _finish(self, session: QuerySession,
                err: Optional[BaseException]) -> None:
        qid = session.query_id
        # stop granting slots, then wait for in-flight quanta to drain so
        # teardown never races a live dispatch.  The drain window is the
        # query-stall timeout: a quantum still running past it is the same
        # wedged-dispatch judgment the stall detector makes — log loudly
        # and tear down anyway rather than leak the session forever.
        with self._lock:
            if session.status in (DONE, FAILED):
                return
            session.want_exclusive = True
        deadline = time.time() + self.query_timeout
        with obs.spans.span("svc.drain", q=qid):
            while time.time() < deadline:
                with self._lock:
                    if session.inflight == 0:
                        break
                time.sleep(0.001)
            else:
                obs.diag(f"[service] tearing down {qid} with "
                         f"{session.inflight} dispatch quantum(s) still "
                         f"live after {self.query_timeout:.0f}s drain")
        first = session.finish(err)
        with self._lock:
            if qid in self._running:
                self._running.remove(qid)
            # drop the service-side reference: a persistent service would
            # otherwise retain every finished query's Engine/graph/results
            # forever (the client's QueryHandle keeps the session alive for
            # exactly as long as the client cares)
            self._sessions.pop(qid, None)
            self._finished += 1
            self._wake.notify_all()
        if first:
            self.admission.release(qid)
            kind = "service.fail" if err is not None else "service.done"
            obs.RECORDER.record(kind, qid, q=qid,
                                **({"error": repr(err)} if err else {}))
