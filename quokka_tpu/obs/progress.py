"""Per-query progress/ETA estimation: the health plane's forward-looking eye.

Every instrument to date looks backward (what the query did); nothing says
how far along a RUNNING query is.  This module blends two host-side signals
already in the opstats ledger into a monotone completion fraction plus an
EWMA-throughput ETA:

- **scanned-source progress** — bytes (and rows) the scan operators have
  produced so far, against the plan-fingerprint cardinality profile's
  persisted ``source_bytes`` total (PR 14).  A warm plan therefore knows its
  denominator from MEASUREMENT; a cold plan (no profile) falls back to the
  readers' ``size_hint()`` bytes, the same degraded prior admission uses.
- **per-operator completion** — each exec operator's observed ``rows_out``
  against the profile's persisted per-operator max rows, averaged across
  profiled operators (warm plans only: a cold plan has no per-op prior).

The blend is clamped monotone per query (an out-of-order opstats report or
a profile denominator that proves too small can never move the bar
backward) and capped below 1.0 until the query actually finishes — the
estimator never claims completion it cannot know.

ZERO device syncs: the estimator consumes only the ledger's host-side
integer figures (``OpStats.progress_view``); deferred device-count scalars
stay on the pending list untouched.  explain-smoke's ``host_syncs == 0``
gate covers the whole collection path.

Surfaces: ``QueryHandle.progress()``, the per-session ``progress``/``eta_s``
columns in ``QueryService.stats()`` (hence ``/status``), the
``progress.fraction.<qid>`` / ``progress.eta_s.<qid>`` gauges on
``/metrics`` (GC'd with the query), and —
pane-frontier based — ``StreamingHandle.progress()``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

# EWMA smoothing for the fraction-per-second throughput estimate: heavy
# enough that one slow poll doesn't whipsaw the ETA, light enough to track
# a genuine rate change within a few samples.
_EWMA_ALPHA = 0.3
# a live query never reports complete: the last percent belongs to the
# finish transition (sink flush, teardown), which only finish() observes
_LIVE_CAP = 0.99
# rates below this (fraction/s) produce no ETA: the query is effectively
# stalled and an ETA in the thousands of hours is noise, not information
_MIN_RATE = 1e-6


class ProgressTracker:
    """Process-wide per-query progress state.  All figures flow one way:
    ``snapshot(qid)`` reads the opstats ledger, folds in the cached
    cardinality-profile prior, and updates the monotone fraction + EWMA
    rate under this tracker's own lock (never the registry lock)."""

    def __init__(self):
        self._lock = threading.Lock()
        # qid -> {fraction, rate, last_t, profile, profile_loaded, gauges}
        self._q: Dict[str, dict] = {}
        # most recently finished query's final snapshot (readable after a
        # one-shot run's cleanup — the opstats _last idiom)
        self._last: Optional[dict] = None

    # -- estimation ----------------------------------------------------------
    def snapshot(self, qid: Optional[str],
                 now: Optional[float] = None) -> Optional[dict]:
        """The query's current progress estimate:

        ``{fraction, eta_s, basis, elapsed_s, rate_per_s, source_bytes_done,
        source_bytes_total, op_completion, profiled_ops}``

        ``fraction`` is monotone per query and < 1.0 while live.  ``basis``
        is ``"cardprofile"`` (measured denominators), ``"size_hint"`` (cold
        plan), or ``"none"`` (no denominator at all — fraction stays 0).
        None for an id the ledger does not know (after GC: the stashed
        final snapshot if it matches)."""
        if qid is None:
            return None
        from quokka_tpu.obs import opstats

        view = opstats.OPSTATS.progress_view(qid)
        if view is None:
            with self._lock:
                last = self._last
            return last if last and last.get("query_id") == qid else None
        now = time.time() if now is None else now
        profile = self._profile_for(qid, view.get("plan_fp"))
        raw, basis, detail = _estimate(view, profile)
        with self._lock:
            st = self._q.setdefault(qid, {
                "fraction": 0.0, "rate": None, "last_t": None,
            })
            frac = min(max(st["fraction"], raw), _LIVE_CAP)
            if st["last_t"] is not None:
                dt = now - st["last_t"]
                if dt > 0:
                    inst = max(0.0, (frac - st["fraction"]) / dt)
                    st["rate"] = (inst if st["rate"] is None else
                                  _EWMA_ALPHA * inst
                                  + (1.0 - _EWMA_ALPHA) * st["rate"])
            st["fraction"] = frac
            st["last_t"] = now
            rate = st["rate"]
        eta = ((1.0 - frac) / rate
               if rate is not None and rate > _MIN_RATE else None)
        snap = {
            "query_id": qid,
            "fraction": round(frac, 6),
            "eta_s": round(eta, 3) if eta is not None else None,
            "basis": basis,
            "elapsed_s": round(max(0.0, now - view["t0"]), 6),
            "rate_per_s": round(rate, 9) if rate is not None else None,
            **detail,
        }
        self._export_gauges(qid, snap)
        return snap

    def _profile_for(self, qid: str, plan_fp: Optional[str]
                     ) -> Optional[dict]:
        """The plan's persisted cardinality entry, loaded from disk ONCE per
        query and cached (a per-poll profile read would put file I/O on
        every /status scrape)."""
        with self._lock:
            st = self._q.get(qid)
            if st is not None and st.get("profile_loaded"):
                return st.get("profile")
        from quokka_tpu.obs import opstats

        profile = None
        with contextlib.suppress(Exception):
            profile = opstats._plan_entry(plan_fp)
        with self._lock:
            st = self._q.setdefault(qid, {
                "fraction": 0.0, "rate": None, "last_t": None,
            })
            st["profile"] = profile
            st["profile_loaded"] = True
        return profile

    def _export_gauges(self, qid: str, snap: dict) -> None:
        from quokka_tpu import obs

        names = (f"progress.fraction.{qid}", f"progress.eta_s.{qid}")
        with self._lock:
            st = self._q.get(qid)
            if st is None:
                return  # GC'd between estimate and export: do not resurrect
            st["gauges"] = names
        obs.REGISTRY.gauge(names[0]).set(snap["fraction"])
        obs.REGISTRY.gauge(names[1]).set(
            snap["eta_s"] if snap["eta_s"] is not None else -1.0)

    # -- lifecycle -----------------------------------------------------------
    def on_query_gc(self, qid: Optional[str],
                    finished: bool = True) -> Optional[dict]:
        """``TaskGraph.cleanup`` hook (the opstats/memplane discipline):
        stamp the final snapshot — fraction 1.0 for a finished query — stash
        it for post-GC readers, drop per-query state + gauge twins."""
        if qid is None:
            return None
        snap = self.snapshot(qid)
        with self._lock:
            st = self._q.pop(qid, None)
            # idempotent: a second GC (session.finish already ran; the
            # engine's cleanup hook fires later) must not restamp the
            # stashed final snapshot — a failed query keeps its honest
            # fraction even though this call defaults finished=True
            already_final = (st is None and self._last is not None
                             and self._last.get("query_id") == qid)
            if already_final:
                return dict(self._last)
            if snap is not None and snap.get("query_id") == qid:
                snap = dict(snap)
                if finished:
                    snap["fraction"] = 1.0
                    snap["eta_s"] = 0.0
                self._last = snap
            gauges = (st or {}).get("gauges") or ()
        if gauges:
            from quokka_tpu import obs

            obs.REGISTRY.remove(*gauges)
        return snap

    def last_finished(self) -> Optional[dict]:
        """The most recently GC'd query's final progress snapshot."""
        with self._lock:
            return self._last

    def reset(self) -> None:
        """Tests only."""
        with self._lock:
            self._q.clear()
            self._last = None


def _estimate(view: dict, profile: Optional[dict]):
    """(raw_fraction, basis, detail) from one ledger view + optional
    cardinality-profile prior.  Pure function of host-side ints — the
    known-answer tests drive it directly."""
    scanned = int(view.get("scanned_bytes", 0) or 0)
    detail: Dict[str, object] = {
        "source_bytes_done": scanned,
        "source_bytes_total": 0,
        "op_completion": None,
        "profiled_ops": 0,
    }
    prof_bytes = 0
    if isinstance(profile, dict):
        with contextlib.suppress(TypeError, ValueError):
            prof_bytes = int(profile.get("source_bytes", 0) or 0)
    if prof_bytes > 0:
        detail["source_bytes_total"] = prof_bytes
        scan_frac = min(1.0, scanned / prof_bytes)
        # per-operator completion against the profiled per-op max rows
        rows_prior = profile.get("rows")
        fracs = []
        if isinstance(rows_prior, dict):
            for key, rows_out in (view.get("op_rows_out") or {}).items():
                with contextlib.suppress(TypeError, ValueError):
                    want = int(rows_prior.get(key, 0) or 0)
                    if want > 0:
                        fracs.append(min(1.0, int(rows_out) / want))
        if fracs:
            op_frac = sum(fracs) / len(fracs)
            detail["op_completion"] = round(op_frac, 6)
            detail["profiled_ops"] = len(fracs)
            return 0.5 * scan_frac + 0.5 * op_frac, "cardprofile", detail
        return scan_frac, "cardprofile", detail
    hint = int(view.get("size_hint_bytes", 0) or 0)
    if hint > 0:
        detail["source_bytes_total"] = hint
        return min(1.0, scanned / hint), "size_hint", detail
    return 0.0, "none", detail


def refresh_live() -> None:
    """Snapshot every query the opstats ledger knows, refreshing the
    ``progress.fraction.*`` gauges — the history sampler calls this each
    tick so the no-progress alert rule sees fractions even when no client
    is polling /status or a handle."""
    from quokka_tpu.obs import opstats

    for qid in opstats.OPSTATS.live_queries():
        with contextlib.suppress(Exception):
            TRACKER.snapshot(qid)


TRACKER = ProgressTracker()
