"""Per-process flight recorder: a lock-light ring buffer of timestamped
events.

Every runtime component records what it just did — task begin/end, batch
push/pull, compiles, cache hits/misses, lock waits, heartbeats, state
transitions — into a bounded ring.  Workers ship incremental snapshots to
the coordinator through the control store; the coordinator's merger
(obs/merge.py) assembles the per-worker streams into one timeline.  When a
run wedges, the last-N events per process ARE the diagnosis: the ring is
what the stall detector and the QK_SANITIZE watchdog dump.

Lock-light by construction: a slot index comes from ``itertools.count``
(atomic under CPython — implemented in C, no bytecode boundary inside
``next``) and the event lands with a single list-item store.  No lock is
taken on the record path; snapshots tolerate a torn read by sorting on the
embedded sequence number and dropping slots mid-overwrite.

Event wire format (what ships to the coordinator): a plain tuple

    (seq, ts, kind, name, dur_s, thread, args_or_None)

with ``ts = time.time()`` at event END (wall clock, so streams from
different processes merge on one axis) and ``dur_s`` the event's duration
(0.0 for instants).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

Event = Tuple[int, float, str, str, float, str, Optional[dict]]

_DEFAULT_CAPACITY = 4096
_OFF_VALUES = ("0", "false", "no", "off")

# the high-rate event kinds a bare QK_TRACE_SAMPLE=N applies to: these are
# per-task / per-batch / per-store-op and can evict the rare stall/chaos/
# strategy events a post-mortem actually needs from the ring
_DEFAULT_SAMPLED_KINDS = ("task", "task.wait", "cache.hit", "mem.track",
                          "rpc", "push.batch", "pull.batch")


def _sample_from_env() -> Dict[str, int]:
    """``QK_TRACE_SAMPLE``: per-event-type sampling — keep 1 in N of each
    listed kind.  ``QK_TRACE_SAMPLE=8`` samples the default high-rate set
    at 1/8; ``QK_TRACE_SAMPLE=task=8,rpc=4`` names kinds explicitly.
    Unlisted kinds always record (rare events must never be sampled)."""
    spec = os.environ.get("QK_TRACE_SAMPLE", "").strip()
    if not spec or spec in ("0", "1"):
        return {}
    rates: Dict[str, int] = {}
    if spec.isdigit():
        n = int(spec)
        return {k: n for k in _DEFAULT_SAMPLED_KINDS} if n > 1 else {}
    for part in spec.split(","):
        kind, _, n = part.strip().partition("=")
        try:
            rate = int(n)
        except ValueError:
            continue
        if kind and rate > 1:
            rates[kind] = rate
    return rates


def recorder_enabled() -> bool:
    """The recorder is ON unless QK_TRACE_EVENTS explicitly disables it —
    it must be live BEFORE anyone knows the run is going to wedge."""
    return os.environ.get(
        "QK_TRACE_EVENTS", "").strip().lower() not in _OFF_VALUES


def trace_export_path() -> Optional[str]:
    """Chrome-trace export destination, or None when only the in-memory
    ring is wanted.  ``QK_TRACE_EVENTS=1`` -> ``quokka_trace.json`` in the
    cwd; any other non-off value is taken as the path itself."""
    v = os.environ.get("QK_TRACE_EVENTS", "").strip()
    if not v or v.lower() in _OFF_VALUES:
        return None
    if v.lower() in ("1", "true", "yes", "on"):
        return "quokka_trace.json"
    return v


class FlightRecorder:
    """Bounded event ring + a per-thread "current activity" marker.

    The activity marker exists for the in-process dump path (watchdog,
    faulthandler): a blocked call never produces its completion event, so
    the marker is the only record of WHAT is blocked."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY,
                 enabled: Optional[bool] = None,
                 sample: Optional[Dict[str, int]] = None):
        self.capacity = max(16, int(capacity))
        self.enabled = recorder_enabled() if enabled is None else enabled
        self._buf: List[Optional[Event]] = [None] * self.capacity
        self._seq = itertools.count()
        # highest sequence number issued so far; a plain store racing other
        # recorders only ever reads slightly stale, which a drop COUNTER
        # tolerates (it exists to say "the ring wrapped, the tail is gone",
        # not to account bytes)
        self._last = -1
        # per-kind eviction accounting: lock-light dict increments (a rare
        # racing undercount is within the drop counter's stated tolerance)
        self._dropped_by: Dict[str, int] = {}
        # per-kind sampling: kind -> keep 1 in N; per-kind admission
        # counters via itertools.count (atomic under CPython) so the
        # decision is deterministic, not random
        self._sample = dict(sample if sample is not None
                            else _sample_from_env())
        self._sample_seq: Dict[str, itertools.count] = {
            k: itertools.count() for k in self._sample}
        self._sampled_by: Dict[str, int] = {}
        # thread name -> (activity, start_ts): the per-thread marker the
        # stall dumps read when a blocked call never completes
        self._current: Dict[str, Tuple[str, float]] = {}

    # -- hot path -----------------------------------------------------------
    def record(self, kind: str, name: str = "", /, dur: float = 0.0,
               **args) -> int:
        # kind and name are positional-only: an event's args may hold a
        # ``kind`` or ``name`` of their own (compile.acquire's program kind)
        if not self.enabled:
            return -1
        rate = self._sample.get(kind)
        if rate is not None and next(self._sample_seq[kind]) % rate:
            # sampled down, not dropped: the rare kinds this protects from
            # ring eviction are never listed in the sample map
            self._sampled_by[kind] = self._sampled_by.get(kind, 0) + 1
            return -1
        i = next(self._seq)
        slot = i % self.capacity
        old = self._buf[slot]
        if old is not None:
            # the ring wrapped: the evicted event's KIND is what a
            # post-mortem lost — account per type, not just a total
            k = old[2]
            self._dropped_by[k] = self._dropped_by.get(k, 0) + 1
        self._buf[slot] = (
            i, time.time(), kind, name, float(dur),
            threading.current_thread().name, args or None,
        )
        if i > self._last:
            self._last = i
        return i

    @property
    def dropped(self) -> Dict[str, int]:
        """Per-event-type counts of events silently overwritten since the
        last reset: once the ring wraps, every record evicts the oldest
        event.  A nonzero type means merged timelines / critical-path
        profiles are missing that kind's earliest tail — sample the
        high-rate kinds down (QK_TRACE_SAMPLE) or raise QK_TRACE_BUFFER."""
        return dict(self._dropped_by)

    @property
    def dropped_total(self) -> int:
        """Total evicted events (the scalar the drop gauges export)."""
        return sum(self._dropped_by.values())

    @property
    def sampled(self) -> Dict[str, int]:
        """Per-kind counts of events QK_TRACE_SAMPLE elided (never entered
        the ring; distinct from ``dropped``, which is ring eviction)."""
        return dict(self._sampled_by)

    class _Activity:
        __slots__ = ("rec", "name", "prev")

        def __init__(self, rec: "FlightRecorder", name: str):
            self.rec = rec
            self.name = name
            self.prev = None

        def __enter__(self):
            if self.rec.enabled:
                # markers nest (a task dispatch performs many RPCs): save
                # the outer marker so an inner completion restores it —
                # clearing instead would blind the watchdog to the task a
                # thread wedges in AFTER its last completed RPC
                key = threading.current_thread().name
                self.prev = self.rec._current.get(key)
                self.rec._current[key] = (self.name, time.time())
            return self

        def __exit__(self, *exc):
            if self.rec.enabled:
                key = threading.current_thread().name
                if self.prev is not None:
                    self.rec._current[key] = self.prev
                else:
                    self.rec._current.pop(key, None)
            return False

    def activity(self, name: str) -> "_Activity":
        """``with RECORDER.activity("rpc:get"):`` — marks the thread's
        current (possibly about-to-block) operation for stall dumps;
        nested markers restore the enclosing one on exit."""
        return FlightRecorder._Activity(self, name)

    # -- snapshots ----------------------------------------------------------
    def snapshot(self, since: int = -1,
                 last_n: Optional[int] = None) -> List[Event]:
        """Events with seq > ``since`` in sequence order.  Tolerates
        concurrent writers: a slot overwritten mid-scan just yields its
        newer event (or is dropped if it moved below ``since``)."""
        evs = [e for e in list(self._buf) if e is not None and e[0] > since]
        evs.sort(key=lambda e: e[0])
        if last_n is not None and len(evs) > last_n:
            evs = evs[-last_n:]
        return evs

    def current(self) -> Dict[str, Tuple[str, float]]:
        """thread name -> (activity, seconds_in_it)."""
        now = time.time()
        return {t: (name, now - t0)
                for t, (name, t0) in list(self._current.items())}

    def dump_text(self, stream, last_n: int = 40) -> None:
        """Human-readable tail + per-thread current activity (what the
        QK_SANITIZE watchdog appends under its stack dump)."""
        cur = self.current()
        if cur:
            stream.write("[flight-recorder] current activity per thread:\n")
            for t, (name, age) in sorted(cur.items()):
                stream.write(f"  {t}: {name} (for {age:.2f}s)\n")
        if self.dropped_total:
            by_kind = ", ".join(f"{k}={n}" for k, n in
                                sorted(self.dropped.items()) if n)
            stream.write(f"[flight-recorder] WARNING: ring dropped "
                         f"{self.dropped_total} event(s) ({by_kind}; "
                         f"capacity {self.capacity}; raise QK_TRACE_BUFFER "
                         f"or sample with QK_TRACE_SAMPLE)\n")
        evs = self.snapshot(last_n=last_n)
        stream.write(f"[flight-recorder] last {len(evs)} event(s):\n")
        for (_seq, ts, kind, name, dur, thread, args) in evs:
            extra = f" {args}" if args else ""
            stream.write(
                f"  {ts:.6f} [{thread}] {kind}:{name}"
                + (f" dur={dur * 1e3:.2f}ms" if dur else "") + extra + "\n")

    def reset(self) -> None:
        self._buf = [None] * self.capacity
        self._seq = itertools.count()
        self._last = -1
        self._current.clear()
        self._dropped_by.clear()
        self._sampled_by.clear()
        self._sample_seq = {k: itertools.count() for k in self._sample}


def _capacity_from_env() -> int:
    try:
        return int(os.environ.get("QK_TRACE_BUFFER", _DEFAULT_CAPACITY))
    except ValueError:
        return _DEFAULT_CAPACITY


RECORDER = FlightRecorder(capacity=_capacity_from_env())
