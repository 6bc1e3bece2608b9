"""Per-query session state + the client-facing QueryHandle.

A session is one query's life inside the service: its namespaced TaskGraph,
its Engine (executors + partition fns + per-query BatchCache), scheduling
state (in-flight count, round-robin bookkeeping, injection hooks), and the
completion plumbing the handle waits on.  The handle is the only object
clients hold; it stays valid after the service GCs the query's namespace
(results, metrics and scan-cache attribution are snapshotted at finish).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

# status values a session moves through (strictly forward)
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"


class QuerySession:
    """Internal per-query record.  The service's scheduler lock guards the
    scheduling fields (inflight, want_exclusive); the session's own lock
    guards the one-shot finish transition."""

    def __init__(self, query_id: str, graph, sink_actor: int, est_bytes: int,
                 inflight_cap: int):
        from quokka_tpu.runtime.engine import Engine

        from quokka_tpu.obs import querylog

        # the query's record starts here (idempotent: submit() opened it
        # before planning, the resume and standing paths open it now)
        querylog.open(query_id)
        self.query_id = query_id
        self.graph = graph
        self.sink_actor = sink_actor
        self.est_bytes = est_bytes
        self.engine = Engine(graph)
        self.status = QUEUED
        self.error: Optional[BaseException] = None
        self.handle = QueryHandle(self)
        self._done = threading.Event()
        self._finish_lock = threading.Lock()
        # scheduling state (guarded by the SERVICE lock, not this session's)
        self.inflight = 0
        self.inflight_cap = max(1, inflight_cap)
        self.want_exclusive = False
        self.handled = 0  # successfully dispatched tasks (injection trigger)
        self.submitted_at = time.time()
        self.started_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # the service's stall detector measures QK_SERVICE_QUERY_TIMEOUT
        # against this (server._worker_loop)
        self.last_progress = time.time()
        # task-latency quantiles snapshotted at finish (the per-query
        # histogram GCs with the namespace; the handle keeps answering)
        self.latency_stats: Optional[Dict] = None
        # fault-injection hook (the test_fault_tolerance.py discipline):
        # {"after_tasks": n, "channels": [(actor, ch), ...]} — consumed once
        self.inject = dict(graph.exec_config.get("inject_failure") or {}) or None
        # standing queries re-arm injection from this queue after each kill
        # (the chaos plane's seeded stream-kill plan) — cumulative
        # after_tasks thresholds, consumed in order
        self.inject_plan: list = []
        # submit_continuous sets True: exempt from the query-stall timeout
        # (an idle standing query is healthy), torn down with its durable
        # recovery state preserved, surfaced as a standing row in /status
        self.streaming = False
        # submit(durable=True) / recover_orphans set True: the engine
        # rewrites a batch resume manifest at each checkpoint, and a
        # service-shutdown teardown preserves the durable recovery trio
        self.durable = False
        # cooperative cancellation + per-query deadline: the worker loop
        # honors both at the next task boundary (server._worker_loop);
        # deadline_at is an absolute time.time() cutoff
        self.cancel_requested = False
        self.deadline_at: Optional[float] = None
        # the resume report from runtime/resume.apply_resume, when this
        # session was re-admitted from an orphaned manifest
        self.resume_info: Optional[Dict] = None
        # backref set by QueryService._enqueue_session (cancel plumbing)
        self._service = None
        # snapshotted at finish, before the namespace GC
        self.scan_stats: Optional[Dict] = None
        # memory-plane footprint ({live, peak, spill_resident} bytes),
        # snapshotted at finish before the ledger drops the query
        self.mem_stats: Optional[Dict] = None
        # operator-statistics snapshot (obs/opstats.py), taken at finish
        # before on_query_gc drops the per-query ledger state
        self.opstats: Optional[Dict] = None
        # final progress snapshot (obs/progress.py), stamped fraction=1.0
        # at finish before the tracker drops the query
        self.progress_snap: Optional[Dict] = None
        # the plan's output columns (QueryService.submit): an answer with no
        # rows is an empty frame of them, as DataStream.collect gives
        self.schema: Optional[List[str]] = None

    # -- finish (exactly once) ----------------------------------------------
    def finish(self, error: Optional[BaseException] = None) -> bool:
        """Transition to DONE/FAILED; returns False if already finished.
        Tears the query down: flush emitters/metrics, snapshot per-query
        stats, then GC the namespace (store tables, spill, checkpoints)."""
        with self._finish_lock:
            if self.status in (DONE, FAILED):
                return False
            self.status = FAILED if error is not None else DONE
            self.error = error
        from quokka_tpu import obs
        from quokka_tpu.obs import spans

        obs.querylog.stamp(self.query_id, "finalize_in")
        try:
            # svc.finalize: entry to just before _done.set() releases the
            # client's to_df — what a request's turnover costs the pool
            with spans.span("svc.finalize", q=self.query_id):
                error = self._teardown(error)
        finally:
            self.finished_at = time.time()
            try:
                # the query's one record (obs/querylog.py), from the
                # snapshots just taken; plain values only
                obs.querylog.close(
                    self.query_id, self.status,
                    plan_fp=getattr(self.graph, "plan_fp", None),
                    opstats=self.opstats, scan_stats=self.scan_stats,
                    pool_size=getattr(self._service, "pool_size", 0))
            finally:
                self._done.set()
        return True

    def _teardown(self, error: Optional[BaseException]
                  ) -> Optional[BaseException]:
        """finish()'s work: flush, snapshot, GC.  Returns the query's error
        (the flush's own, where it failed and the query had none)."""
        from quokka_tpu import obs
        from quokka_tpu.obs import spans

        with spans.span("finalize.flush"):
            try:
                self.engine.service_finalize()
            except Exception as e:  # noqa: BLE001 — keep first error
                if error is None:
                    self.status = FAILED
                    self.error = error = e
        with spans.span("finalize.snapshots"):
            from quokka_tpu.runtime import scancache

            stats = scancache.GLOBAL.stats()["by_query"].get(self.query_id)
            self.scan_stats = dict(stats) if stats else {"hits": 0,
                                                         "misses": 0}
            h = obs.REGISTRY.histograms().get(
                f"task.latency_s.{self.query_id}")
            self.latency_stats = (h.stats() if h is not None
                                  else obs.Histogram.empty_stats())
            from quokka_tpu.obs import memplane

            self.mem_stats = memplane.LEDGER.query_footprint(self.query_id)
            from quokka_tpu.obs import opstats

            self.opstats = opstats.OPSTATS.snapshot(self.query_id)
            from quokka_tpu.obs import progress as progress_mod

            # a clean finish pins the bar at 1.0; a failed query keeps its
            # last honest estimate — it did NOT complete
            self.progress_snap = progress_mod.TRACKER.on_query_gc(
                self.query_id, finished=error is None)
        with spans.span("finalize.cleanup"):
            try:
                # a standing query that FAILED (or was shut down mid-stream)
                # keeps its durable recovery trio — checkpoints, HBQ spill,
                # resume manifest — so a restarted replica resumes it; a
                # cleanly stopped stream is complete and GCs everything.
                # A DURABLE BATCH query keeps its trio only on service
                # shutdown (the restart/recover_orphans path); success,
                # cancel, deadline and plain failure all GC fully —
                # manifests never accumulate from completed queries
                preserve = self.streaming and error is not None
                if not preserve and self.durable and error is not None:
                    from quokka_tpu.service.server import ServiceShutdown

                    preserve = isinstance(error, ServiceShutdown)
                self.graph.cleanup(preserve_durable=preserve)
            except Exception as e:  # noqa: BLE001 — teardown must not kill
                # the pool thread running it
                obs.diag(f"[service] cleanup of {self.query_id} failed: "
                         f"{e!r}")
        return error

    @property
    def finished(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)


class QueryHandle:
    """What ``QueryService.submit`` returns: completion waiting, the
    (incrementally filling) ResultDataset, per-query metrics and scan-cache
    attribution.  Safe to use from any thread."""

    def __init__(self, session: QuerySession):
        self._s = session
        # per-handle delivery cursor ({channel: last seen seq}) for
        # poll_batches(): a re-attached client seeds it with its own capture
        # frontier and drains exactly the undelivered tail
        self._cursor: Dict[int, int] = {}

    @property
    def query_id(self) -> str:
        return self._s.query_id

    @property
    def status(self) -> str:
        return self._s.status

    @property
    def done(self) -> bool:
        return self._s.finished

    @property
    def error(self) -> Optional[BaseException]:
        return self._s.error

    @property
    def dataset(self):
        """The LIVE ResultDataset — partial while the query streams, the
        full result once ``done``."""
        return self._s.graph.result(self._s.sink_actor)

    @property
    def resume_info(self) -> Optional[Dict]:
        """The resume report ({execs, inputs, replay_specs, ...}) when this
        query was re-admitted from an orphaned manifest; None otherwise."""
        return self._s.resume_info

    @property
    def manifest_path(self) -> Optional[str]:
        """The durable resume-manifest path for a ``durable=True`` query
        (None otherwise) — what ``QueryService.recover_orphans`` scans for
        after a crash."""
        return getattr(self._s.graph, "resume_manifest", None)

    def poll_batches(self):
        """Drain result batches this handle has not seen yet: a list of
        ``(channel, seq, table)`` strictly after the handle's cursor, which
        advances past everything returned.  Seq-keyed, so a resumed query's
        replayed batches never surface twice through one handle."""
        ds = self.dataset
        if ds is None:
            return []
        items = ds.items_since(self._cursor)
        for ch, s, _t in items:
            self._cursor[ch] = s
        return items

    def cancel(self, wait: bool = True,
               timeout: Optional[float] = 60.0) -> "QueryHandle":
        """Cooperatively cancel this query: dispatch stops at the next task
        boundary, admission bytes release, and the namespace/spill/
        checkpoints/manifest GC.  The handle then reports a
        ``QueryCancelled`` error.  Idempotent; a no-op once finished."""
        s = self._s
        s.cancel_requested = True
        svc = s._service
        if svc is not None:
            svc._cancel_ping(s)
        if wait:
            s.wait(timeout)
        return self

    @staticmethod
    def attach(service, query_id: str,
               cursor: Optional[Dict[int, int]] = None) -> "QueryHandle":
        """Re-attach to a query by id (``QueryService.attach``) — a fresh
        handle whose delivery cursor starts at ``cursor`` ({channel: last
        seq the client durably captured}), so the first ``poll_batches``
        returns exactly the undelivered tail."""
        return service.attach(query_id, cursor=cursor)

    def wait(self, timeout: Optional[float] = None) -> "QueryHandle":
        if not self._s.wait(timeout):
            raise TimeoutError(
                f"query {self.query_id} did not finish within {timeout}s "
                f"(status={self.status})"
            )
        return self

    def result(self, timeout: Optional[float] = None):
        """Block until the query finishes and return its ResultDataset;
        re-raises the query's error if it failed."""
        self.wait(timeout)
        if self._s.error is not None:
            raise self._s.error
        return self.dataset

    def to_arrow(self, timeout: Optional[float] = None):
        return self._materialize(self.result(timeout).to_arrow)

    def to_df(self, timeout: Optional[float] = None):
        df = self._materialize(self.result(timeout).to_df)
        if df is None and self._s.schema is not None:
            import pandas as pd

            return pd.DataFrame(columns=self._s.schema)
        return df

    def _materialize(self, convert):
        """What the caller's thread still does once the query is done (the
        result's Arrow tables to one table or frame): a ring event and a
        trace annotation; the query's record is closed by then."""
        from quokka_tpu.obs import spans

        with spans.span("handle.materialize", q=self.query_id):
            return convert()

    def metrics(self) -> Dict:
        """Per-(actor, channel) progress counters (TaskGraph.metrics shape)
        — answered from the finish-time snapshot after teardown."""
        return self._s.graph.metrics()

    def scan_cache_stats(self) -> Optional[Dict]:
        """This query's shared-scan-cache attribution ({hits, misses}) —
        live while running, snapshotted at finish."""
        if self._s.scan_stats is not None:
            return dict(self._s.scan_stats)
        from quokka_tpu.runtime import scancache

        return scancache.GLOBAL.stats()["by_query"].get(self.query_id)

    def latency_stats(self) -> Optional[Dict]:
        """Per-query task-latency quantiles ({count, sum, p50, p95, p99})
        — live from the typed histogram while running, snapshotted at
        finish (the histogram itself GCs with the query's namespace)."""
        if self._s.latency_stats is not None:
            return dict(self._s.latency_stats)
        from quokka_tpu import obs

        h = obs.REGISTRY.histograms().get(
            f"task.latency_s.{self.query_id}")
        return h.stats() if h is not None else obs.Histogram.empty_stats()

    def memory_stats(self) -> Dict:
        """This query's memory-ledger footprint ({live_bytes, peak_bytes,
        spill_resident_bytes}) — live while running, snapshotted at finish
        (the ledger drops the query's accounting with its namespace)."""
        if self._s.mem_stats is not None:
            return dict(self._s.mem_stats)
        from quokka_tpu.obs import memplane

        return memplane.LEDGER.query_footprint(self.query_id)

    def progress(self) -> Optional[Dict]:
        """Live completion estimate ({fraction, eta_s, basis, ...},
        obs/progress.py): monotone 0→1 fraction blending scanned source
        bytes against the plan's profiled (or size-hinted) totals with
        per-operator row completion, plus an EWMA-throughput ETA.  The
        finish-time snapshot (fraction pinned 1.0 on success) after."""
        if self._s.progress_snap is not None:
            return dict(self._s.progress_snap)
        from quokka_tpu.obs import progress as progress_mod

        return progress_mod.TRACKER.snapshot(self.query_id)

    def explain(self, as_dict: bool = False):
        """EXPLAIN ANALYZE: the plan DAG annotated with measured actuals —
        per-operator rows/selectivity/time share, the per-exchange-edge skew
        report, top hot operators.  Live over the operator-stats ledger
        while the query runs; the finish-time snapshot after.  ``as_dict``
        returns the raw snapshot instead of the rendered text."""
        from quokka_tpu.obs import explain as explain_mod, opstats

        snap = (dict(self._s.opstats) if self._s.opstats is not None
                else opstats.OPSTATS.snapshot(self.query_id))
        if as_dict:
            return snap
        return explain_mod.render(snap)

    def timings(self) -> Dict[str, Optional[float]]:
        s = self._s
        return {
            "submitted_at": s.submitted_at,
            "started_at": s.started_at,
            "finished_at": s.finished_at,
            "queue_s": (s.started_at - s.submitted_at)
            if s.started_at else None,
            "run_s": (s.finished_at - s.started_at)
            if s.started_at and s.finished_at else None,
        }
