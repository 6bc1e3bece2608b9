"""The service's query log: one flat record per finished query.

What an operator of a served engine reads after the fact, and what the
benchmark's per-layer metrics read after a window: where each request's
host time went, by layer, from inside the program.  While a query is live
its spans (obs/spans.py) add their self times to an accumulator here; at
``QuerySession.finish`` the accumulator, the finish-time snapshots and the
stamps become one dict of plain numbers and strings (so that it pins no
``Engine``, graph or device array) in a bounded process-wide deque that
outlives the session, the service and the ring's wrap-around.

Clocks: every stamp and every duration is ``time.perf_counter()`` (the
clock of a caller timing ``submit()`` -> ``to_df()`` in the same process,
and of a profiler trace's anchor); ``wall_done`` alone is ``time.time()``.

Record keys (README "Observability" documents each):

- ``q``, ``plan_fp``, ``status``;
- stamps: ``submit_in``, ``submit_out``, ``admitted``, ``first_task``,
  ``last_task``, ``finalize_in``, ``done``, ``wall_done``;
- self seconds by layer.  The dispatches' partition: ``runtime.dispatch_self``
  + ``executors.exec_self`` + ``runtime.push`` + ``io.read`` + ``emit.d2h`` +
  ``compile.acquire`` + ``other`` = ``task_s``, the summed duration of the
  dispatches that progressed.  Beside it: ``runtime.pick``,
  ``service.sched_wait``, ``service.finalize`` and its ``finalize.*`` parts,
  ``entry.submit`` and its ``entry.*`` parts, and the ``offthread.*`` sums
  of helper threads (overlapping the workers; outside every partition);
- the wait for the device, beside the partition and inside its seconds
  (``spans.device_read``: every ``sync.<site>`` span of the query's threads):
  ``syncs`` (blocking reads), ``sync.wait`` (their seconds, all),
  ``sync.in_dispatch`` (the part inside dispatches that progressed, so at
  most ``task_s``), ``sync.offthread`` (the part on helper threads),
  ``d2h_bytes`` (what the reads brought from the device, the result's
  tables included), ``h2d_bytes`` (what ``bridge.to_device`` put, on
  whichever thread) and ``sync_sites``: at most 8 ``[site, count,
  seconds]``, the largest seconds first;
- counts: ``tasks``, ``requeues``, ``backoffs``,
  ``compile_hits``,
  ``compile_misses``, ``compiled``, ``rows_in``, ``padded_in``,
  ``rows_unknown``, ``agg_merges_compiled``, ``agg_merges_general`` (merges
  and final tails of the aggregators by the path they took:
  ops/aggtail.py), ``asof_flushes``, ``asof_probe_rows``,
  ``asof_probe_padded``, ``asof_quote_padded`` (the streaming asof join's
  chunk probes: how many, the trades they held, the slots they filled, and
  the padded quote rows each searched, summed: executors/ts_execs.py),
  ``asof_match_sort``, ``asof_match_search`` (``asof_join`` calls the device
  merge answered, and the device binary search: ops/asof.py),
  ``join_probe_direct``, ``join_probe_search`` (padded probe slots the
  sort branch of ``hash_join_pk`` answered from a direct-address table and
  by binary search: ops/join.py), ``join_probe_general`` (padded probe slots
  through the many-to-many join), ``join_builds`` (builds the join executors
  finalised, one a join and channel: executors/sql_execs.py),
  ``str_pred_dict_rows`` (dictionary entries a string predicate walked on
  the host: ops/expr_compile.py), ``groupby_sort_slots``,
  ``groupby_groups_out`` (the general group-by: padded slots its partial
  aggregates and merges sorted, and the groups they emitted: ops/fuse.py,
  ops/aggtail.py, ops/kernels.py), ``scan_hits``, ``scan_misses``;
- ``pool_size`` and ``park_s_total``, ``loop_s_total``: the service's
  worker threads, and their cumulative counters when the query finished.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

MAXLEN = 4096
COMPILED_MAX = 32  # programs named per record; the counts go on
SYNC_SITES_MAX = 8  # read sites named per record; the sums hold them all

STAMPS = ("submit_in", "submit_out", "admitted", "first_task", "last_task",
          "finalize_in", "done", "wall_done")
# the in-dispatch partition: these sum to task_s
DISPATCH_LAYERS = ("runtime.dispatch_self", "executors.exec_self",
                   "runtime.push", "io.read", "emit.d2h", "compile.acquire",
                   "other")
OFFTHREAD = ("offthread.reader.execute", "offthread.bridge.to_device",
             "offthread.emit.result_d2h", "offthread.spill.hbq",
             "offthread.other")
# span name -> (record key, which of the span's two sums it adds)
_OUTSIDE = {
    "submit": ("entry.submit", "dur"),
    "submit.prepare_plan": ("entry.prepare_plan", "self"),
    "submit.lower_plan": ("entry.lower_plan", "self"),
    "submit.estimate": ("entry.estimate", "self"),
    "submit.enqueue": ("entry.enqueue", "self"),
    "step.pick": ("runtime.pick", "self"),
    "svc.step": ("runtime.pick", "self"),  # a progressing step's own cost
    "svc.fruitless": ("service.sched_wait", "self"),
    "task.requeue": ("service.sched_wait", "dur"),
    "svc.backoff": ("service.sched_wait", "dur"),
    "svc.drain": ("service.sched_wait", "dur"),
    "svc.finalize": ("service.finalize", "dur"),
    "finalize.flush": ("finalize.flush", "self"),
    "finalize.snapshots": ("finalize.snapshots", "self"),
    "finalize.cleanup": ("finalize.cleanup", "self"),
}
_COUNTED = {"svc.fruitless": "requeues", "svc.backoff": "backoffs"}
SECONDS = tuple(dict.fromkeys(
    [key for key, _ in _OUTSIDE.values()] + list(DISPATCH_LAYERS)
    + ["sync.wait", "sync.in_dispatch", "sync.offthread"] + list(OFFTHREAD)
    + ["task_s"]))
COUNTS = ("tasks", "requeues", "backoffs", "syncs", "d2h_bytes", "h2d_bytes",
          "compile_hits", "compile_misses", "rows_in", "padded_in", "rows_unknown",
          "agg_merges_compiled", "agg_merges_general", "asof_flushes",
          "asof_probe_rows", "asof_probe_padded", "asof_quote_padded",
          "asof_match_sort", "asof_match_search",
          "join_probe_direct", "join_probe_search", "join_probe_general",
          "join_builds", "str_pred_dict_rows", "groupby_sort_slots",
          "groupby_groups_out", "scan_hits", "scan_misses")
KEYS = (("q", "plan_fp", "status") + STAMPS + SECONDS + COUNTS
        + ("compiled", "sync_sites", "pool_size", "park_s_total",
           "loop_s_total"))

# summed over the operators of the finish-time opstats snapshot
_FROM_OPSTATS = ("rows_in", "padded_in", "rows_unknown",
                 "agg_merges_compiled", "agg_merges_general", "asof_flushes",
                 "asof_probe_rows", "asof_probe_padded", "asof_quote_padded",
                 "asof_match_sort", "asof_match_search",
                 "join_probe_direct", "join_probe_search",
                 "join_probe_general", "join_builds", "str_pred_dict_rows",
                 "groupby_sort_slots", "groupby_groups_out")

_lock = threading.Lock()
_open: Dict[str, dict] = {}   # live queries' accumulators
_log: deque = deque(maxlen=MAXLEN)


def open(q: str) -> None:  # noqa: A001 — the log's verb
    """Start accumulating for a query (idempotent)."""
    with _lock:
        if q not in _open:
            acc = dict.fromkeys(SECONDS, 0.0)
            acc.update(dict.fromkeys(COUNTS, 0))
            acc.update(dict.fromkeys(STAMPS))
            acc["compiled"] = []
            acc["sync_sites"] = {}  # site -> [count, seconds]; a list at close
            _open[q] = acc


def discard(q: str) -> None:
    """A query that never ran (its submit raised) leaves no record."""
    with _lock:
        _open.pop(q, None)


def stamp(q: str, key: str, t: Optional[float] = None) -> None:
    """Set one of STAMPS (``time.perf_counter()`` unless given)."""
    t = time.perf_counter() if t is None else t
    with _lock:
        acc = _open.get(q)
        if acc is not None:
            acc[key] = t


def add(q: str, name: str, dur: float, self_s: float) -> None:
    """A span with a query id closed outside any dispatch.  A no-op for a
    query with no open accumulator (an embedded run; a span that closes
    after the record did)."""
    if name in _OUTSIDE:
        key, which = _OUTSIDE[name]
    elif name.startswith("offthread."):
        key = name if name in OFFTHREAD else "offthread.other"
        which = "dur"
    else:
        return
    with _lock:
        acc = _open.get(q)
        if acc is None:
            return
        acc[key] += dur if which == "dur" else self_s
        count = _COUNTED.get(name)
        if count is not None:
            acc[count] += 1


def task(q: str, t0: float, dur: float, parts: Dict[str, float]) -> None:
    """One dispatch that progressed: its start, its duration and, in
    ``parts``, its self times by layer (DISPATCH_LAYERS: they sum to
    ``dur``)."""
    with _lock:
        acc = _open.get(q)
        if acc is None:
            return
        acc["tasks"] += 1
        acc["task_s"] += dur
        if acc["first_task"] is None:
            acc["first_task"] = t0
        acc["last_task"] = max(acc["last_task"] or 0.0, t0 + dur)
        for key, v in parts.items():
            acc[key] += v


def sync(q: str, reads, where: Optional[str]) -> None:
    """Device reads of the query's threads (``spans.device_read``), each a
    ``(site, seconds, bytes)``.  ``where`` is the SECONDS key their seconds
    also count under: ``sync.in_dispatch`` (a dispatch that progressed),
    ``sync.offthread`` (a helper thread) or None (``submit``,
    ``svc.finalize``, a dispatch that could not progress)."""
    with _lock:
        acc = _open.get(q)
        if acc is None:
            return
        kept = acc["sync_sites"]
        for site, seconds, nbytes in reads:
            acc["syncs"] += 1
            acc["sync.wait"] += seconds
            acc["d2h_bytes"] += nbytes
            if where is not None:
                acc[where] += seconds
            ent = kept.setdefault(site, [0, 0.0])
            ent[0] += 1
            ent[1] += seconds


def h2d(q: str, nbytes: int) -> None:
    """Bytes a ``bridge.to_device`` span of the query put on the device."""
    with _lock:
        acc = _open.get(q)
        if acc is not None:
            acc["h2d_bytes"] += nbytes


def compiled(q: Optional[str], kind: str, key_hash: str, seconds: float,
             real: bool, hit: str) -> None:
    """A compile-plane miss (``hit`` "miss") or persisted load ("cache_hit")
    that a dispatch of this query paid for."""
    if q is None:
        return
    with _lock:
        acc = _open.get(q)
        if acc is None:
            return
        acc["compile_hits" if hit == "cache_hit" else "compile_misses"] += 1
        if len(acc["compiled"]) < COMPILED_MAX:
            acc["compiled"].append(
                [kind, key_hash, round(seconds, 6), bool(real)])


def close(q: str, status: str, plan_fp: Optional[str] = None,
          opstats: Optional[dict] = None,
          scan_stats: Optional[dict] = None,
          pool_size: int = 0) -> None:
    """Turn the query's accumulator into its record, stamped ``done`` now.
    ``opstats``/``scan_stats``: the session's finish-time snapshots (the
    counts they hold are already resolved; nothing is read from the
    device here).  A no-op where the query had no accumulator."""
    from quokka_tpu.obs.metrics import REGISTRY

    ops = (opstats or {}).get("operators") or ()
    park_s = float(REGISTRY.counter("service.park_s").value)
    loop_s = float(REGISTRY.counter("service.loop_s").value)
    with _lock:
        acc = _open.pop(q, None)
        if acc is None:
            return
        acc.update(
            q=q, plan_fp=plan_fp, status=status,
            **{k: sum(int(o.get(k, 0)) for o in ops) for k in _FROM_OPSTATS},
            scan_hits=int((scan_stats or {}).get("hits", 0)),
            scan_misses=int((scan_stats or {}).get("misses", 0)),
            pool_size=int(pool_size), park_s_total=park_s,
            loop_s_total=loop_s,
            done=time.perf_counter(), wall_done=time.time())
        top = sorted(acc["sync_sites"].items(), key=lambda kv: -kv[1][1])
        acc["sync_sites"] = [[site, n, round(seconds, 6)]
                             for site, (n, seconds) in top[:SYNC_SITES_MAX]]
        _log.append({k: acc[k] for k in KEYS})


def records(since: Optional[float] = None) -> List[dict]:
    """Copies of the kept records, oldest first; with ``since``, those whose
    ``done`` (``time.perf_counter()``) is later."""
    with _lock:
        kept = list(_log)
    return [dict(r, compiled=[list(c) for c in r["compiled"]],
                 sync_sites=[list(c) for c in r["sync_sites"]])
            for r in kept if since is None or r["done"] > since]


def size() -> int:
    return len(_log)


def reset() -> None:
    """Tests only: forget every record and accumulator."""
    with _lock:
        _log.clear()
        _open.clear()
