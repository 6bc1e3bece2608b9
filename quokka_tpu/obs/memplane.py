"""Memory observability plane: the device/host-buffer ledger.

The obs plane could attribute every second of a query's wall time (critpath)
but not a single byte of its memory.  This module closes that gap with a
process-wide ledger: every tracked allocation — reader batches in the device
scan cache, shuffle partitions in the BatchCache, a join's finalized build
side, HBQ spill residency, checkpoint snapshots, persisted AOT executables —
registers a ``(query_id, site, nbytes, device)`` entry on create and retires
it on free/spill/GC.  From the ledger the plane serves:

- **gauges**: ``mem.live_bytes`` / ``mem.peak_bytes`` /
  ``mem.spill_resident_bytes`` aggregates, per-query twins (GC'd in
  ``TaskGraph.cleanup`` like every per-query family) and a per-site-class
  residency family ``mem.site_bytes.<site>``;
- **reconciliation**: the device-class ledger total checked against
  ``jax.live_arrays()`` within a tolerance (``QK_MEM_RECONCILE``), so drift
  between what we think is resident and what the runtime actually holds is
  measurable, not folklore;
- **leak flagging**: any entry still live after its query's namespace drop
  becomes a named ``MemLeakError`` report with the allocation-site flight
  events attached (strict mode ``QK_MEM_STRICT=1`` raises it);
- **OOM forensics**: on an allocation failure (``alloc_guard``) or a
  ``QK_MEM_BUDGET`` breach, a forensics bundle lands in ``QK_DUMP_DIR`` —
  top-K holders by site, per-query footprints, the recent ledger tail and
  the merged flight timeline — the memory analogue of the stall dump;
- **measured admission**: each finished query persists its measured
  ``peak_bytes`` keyed by plan fingerprint (the strategy-profile atomic
  pattern, one file per backend fingerprint under
  ``<cache>/memprofile/``), and ``service/admission.py`` prefers that
  figure over reader ``size_hint()`` guesses on the next submit of the
  same plan shape.

Tracking happens at the choke points the runtime already owns (cache put/gc,
HBQ put/gc/wipe, scan-cache put/evict, checkpoint save/wipe, AOT persist) —
not by wrapping every ``jnp`` call; lint rule QK018 keeps new device
allocations from growing outside those ledgered paths.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

# site classes: where in the runtime a tracked allocation lives
SITE_READER = "reader"          # device scan cache (post-bridge batches)
SITE_SHUFFLE = "shuffle"        # BatchCache partitions awaiting consumers
SITE_BUILD = "build"            # a join's finalized build side
SITE_SPILL = "spill"            # HBQ spill files (host disk)
SITE_CKPT = "checkpoint"        # executor-state snapshots
SITE_EXEC = "executable"        # persisted AOT executables

DEVICE = "device"
HOST = "host"

_PROFILE_VERSION = 1
_TAIL_LEN = 256
_TOP_K = 20


def budget_bytes() -> int:
    """``QK_MEM_BUDGET``: soft byte budget for tracked live memory; 0/unset
    disables the breach check (the bundle, not an allocator limit)."""
    try:
        return int(os.environ.get("QK_MEM_BUDGET", 0))
    except ValueError:
        return 0


def reconcile_tolerance() -> float:
    """``QK_MEM_RECONCILE``: allowed relative drift between the ledger's
    device-class total and what jax reports live (default 10%)."""
    try:
        return float(os.environ.get("QK_MEM_RECONCILE", 0.10))
    except ValueError:
        return 0.10


def strict_mode() -> bool:
    """``QK_MEM_STRICT=1``: a leak report raises instead of diagnosing."""
    return os.environ.get("QK_MEM_STRICT", "").strip().lower() in (
        "1", "true", "yes", "on")


class MemLeakError(RuntimeError):
    """Ledger entries survived their query's namespace drop.  ``leaks`` is
    a list of {token, site, nbytes, device, events} dicts — ``events`` are
    the allocation-site flight-recorder events, so the report names WHERE
    each leaked buffer came from, not just that one exists."""

    def __init__(self, query_id: str, leaks: List[dict]):
        self.query_id = query_id
        self.leaks = list(leaks)
        total = sum(leak["nbytes"] for leak in self.leaks)
        sites = sorted({leak["site"] for leak in self.leaks})
        super().__init__(
            f"query {query_id}: {len(self.leaks)} ledger entr"
            f"{'y' if len(self.leaks) == 1 else 'ies'} still live after "
            f"namespace GC ({total} bytes; sites: {', '.join(sites)})")


def _tok_id(token) -> str:
    """Compact per-process id stamped into flight events so a leak report
    can find the exact allocation event for each surviving entry."""
    return format(hash(token) & 0xFFFFFFFF, "08x")


class MemLedger:
    """Thread-safe allocation ledger.  Entries are keyed by an arbitrary
    hashable token (the tracking site picks one that identifies the buffer:
    a cache name 6-tuple, a spill filename, a checkpoint path).  ``track``
    of an existing token replaces it (BatchCache dedup semantics)."""

    def __init__(self, tail: int = _TAIL_LEN):
        self._lock = threading.Lock()
        # token -> (query_id, site, nbytes, device)
        self._entries: Dict[object, Tuple[Optional[str], str, int, str]] = {}
        self._live = 0
        self._peak = 0
        self._device_live = 0
        self._spill = 0
        self._site: Dict[str, int] = {}
        self._live_q: Dict[str, int] = {}
        self._peak_q: Dict[str, int] = {}
        self._spill_q: Dict[str, int] = {}
        self._spill_peak_q: Dict[str, int] = {}
        self._tail: deque = deque(maxlen=tail)
        self._breached = False
        # reconciliation baselines: jax holds buffers the ledger never
        # claims to track (jit constants, RNG state), so both sides compare
        # as DELTAS from the moment set_baseline() was called
        self._jax_baseline = 0
        self._ledger_baseline = 0

    # -- accounting core (callers hold self._lock) ---------------------------
    def _apply(self, ent, sign: int) -> None:
        query, site, nbytes, device = ent
        delta = sign * nbytes
        self._live += delta
        if device == DEVICE:
            self._device_live += delta
        if site == SITE_SPILL:
            self._spill += delta
        self._site[site] = self._site.get(site, 0) + delta
        if query is not None and query in self._live_q:
            self._live_q[query] += delta
            if site == SITE_SPILL:
                self._spill_q[query] = self._spill_q.get(query, 0) + delta
        if sign > 0:
            if self._live > self._peak:
                self._peak = self._live
            if query is not None:
                q_live = self._live_q.get(query, 0)
                if q_live > self._peak_q.get(query, 0):
                    self._peak_q[query] = q_live
                q_spill = self._spill_q.get(query, 0)
                if q_spill > self._spill_peak_q.get(query, 0):
                    self._spill_peak_q[query] = q_spill

    def _gauge_pairs(self, query: Optional[str],
                     site: Optional[str]) -> List[Tuple[str, float]]:
        pairs = [("mem.live_bytes", self._live),
                 ("mem.peak_bytes", self._peak),
                 ("mem.spill_resident_bytes", self._spill)]
        if site is not None:
            pairs.append((f"mem.site_bytes.{site}", self._site.get(site, 0)))
        # per-query twins only while the query's accounting is live:
        # a straggler retire after drop_query must never resurrect a GC'd
        # instrument as a permanent /metrics family
        if query is not None and query in self._live_q:
            pairs += [
                (f"mem.live_bytes.{query}", self._live_q[query]),
                (f"mem.peak_bytes.{query}", self._peak_q.get(query, 0)),
                (f"mem.spill_resident_bytes.{query}",
                 self._spill_q.get(query, 0)),
            ]
        return pairs

    @staticmethod
    def _set_gauges(pairs: List[Tuple[str, float]]) -> None:
        from quokka_tpu import obs

        for name, value in pairs:
            obs.REGISTRY.gauge(name).set(value)

    # -- track / retire ------------------------------------------------------
    def track(self, token, site: str, nbytes, *,
              query: Optional[str] = None, device: str = DEVICE) -> None:
        nbytes = max(0, int(nbytes))
        breach = False
        with self._lock:
            old = self._entries.pop(token, None)
            if old is not None:
                self._apply(old, -1)
            if query is not None and query not in self._live_q:
                self._live_q[query] = 0
            ent = (query, site, nbytes, device)
            self._entries[token] = ent
            self._apply(ent, +1)
            self._tail.append((time.time(), "track", site, query, nbytes))
            budget = budget_bytes()
            if budget > 0:
                if self._live > budget and not self._breached:
                    self._breached = True  # latch: one bundle per episode
                    breach = True
                elif self._live <= budget:
                    self._breached = False
            pairs = self._gauge_pairs(query, site)
        self._set_gauges(pairs)
        from quokka_tpu import obs

        obs.RECORDER.record("mem.track", site, nbytes=nbytes,
                            tok=_tok_id(token),
                            **({"q": query} if query else {}))
        if breach:
            obs.REGISTRY.counter("mem.budget_breach").inc()
            obs.diag(f"[memplane] live tracked memory {self._live} exceeds "
                     f"QK_MEM_BUDGET={budget_bytes()} (site {site!r}"
                     + (f", query {query}" if query else "") + ")")
            oom_bundle(f"QK_MEM_BUDGET breach at site {site!r}", ledger=self)

    def retire(self, token) -> None:
        with self._lock:
            ent = self._entries.pop(token, None)
            if ent is None:
                return
            self._apply(ent, -1)
            query, site, nbytes, _device = ent
            self._tail.append((time.time(), "retire", site, query, nbytes))
            pairs = self._gauge_pairs(query, site)
        self._set_gauges(pairs)

    def retire_prefix(self, prefix: Tuple) -> None:
        """Retire every tuple-keyed entry whose token starts with ``prefix``
        (bulk GC: an HBQ wipe, a checkpoint namespace drop)."""
        plen = len(prefix)
        pairs: List[Tuple[str, float]] = []
        with self._lock:
            toks = [t for t in self._entries
                    if isinstance(t, tuple) and t[:plen] == prefix]
            queries, sites = set(), set()
            for tok in toks:
                ent = self._entries.pop(tok)
                self._apply(ent, -1)
                query, site, nbytes, _device = ent
                queries.add(query)
                sites.add(site)
                self._tail.append(
                    (time.time(), "retire", site, query, nbytes))
            if toks:
                pairs = self._gauge_pairs(None, None)
                for site in sites:
                    pairs.append((f"mem.site_bytes.{site}",
                                  self._site.get(site, 0)))
                for query in queries:
                    if query is not None and query in self._live_q:
                        pairs += self._gauge_pairs(query, None)[3:]
        if pairs:
            self._set_gauges(pairs)

    # -- readers -------------------------------------------------------------
    def live_bytes(self, query: Optional[str] = None) -> int:
        with self._lock:
            return self._live if query is None \
                else self._live_q.get(query, 0)

    def peak_bytes(self, query: Optional[str] = None) -> int:
        with self._lock:
            return self._peak if query is None \
                else self._peak_q.get(query, 0)

    def spill_bytes(self, query: Optional[str] = None) -> int:
        with self._lock:
            return self._spill if query is None \
                else self._spill_q.get(query, 0)

    def device_live_bytes(self) -> int:
        with self._lock:
            return self._device_live

    def site_totals(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._site)

    def entry_count(self, query: Optional[str] = None) -> int:
        with self._lock:
            if query is None:
                return len(self._entries)
            return sum(1 for ent in self._entries.values()
                       if ent[0] == query)

    def query_footprint(self, query: str) -> Dict[str, int]:
        """{live_bytes, peak_bytes, spill_resident_bytes} for one query —
        what the session snapshots at finish (the per-query gauges GC with
        the namespace; the handle keeps answering)."""
        with self._lock:
            return {
                "live_bytes": self._live_q.get(query, 0),
                "peak_bytes": self._peak_q.get(query, 0),
                "spill_resident_bytes": self._spill_q.get(query, 0),
            }

    def reset_peak(self) -> None:
        """Re-arm the aggregate high-water mark at the current live total
        (a caller brackets each measured query with this)."""
        with self._lock:
            self._peak = self._live
            pairs = self._gauge_pairs(None, None)
        self._set_gauges(pairs)

    def snapshot(self, top_k: int = _TOP_K) -> Dict:
        """Everything the OOM bundle wants, in one locked read."""
        with self._lock:
            holders = sorted(self._entries.items(),
                             key=lambda kv: -kv[1][2])[:top_k]
            queries = set(self._live_q) | set(self._peak_q)
            return {
                "live_bytes": self._live,
                "peak_bytes": self._peak,
                "device_live_bytes": self._device_live,
                "spill_resident_bytes": self._spill,
                "entries": len(self._entries),
                "site_bytes": dict(self._site),
                "query_footprints": {
                    q: {"live_bytes": self._live_q.get(q, 0),
                        "peak_bytes": self._peak_q.get(q, 0),
                        "spill_resident_bytes": self._spill_q.get(q, 0)}
                    for q in sorted(queries)},
                "top_holders": [
                    {"token": repr(tok), "query": ent[0], "site": ent[1],
                     "nbytes": ent[2], "device": ent[3]}
                    for tok, ent in holders],
                "ledger_tail": [
                    {"ts": ts, "op": op, "site": site, "query": q,
                     "nbytes": nb}
                    for ts, op, site, q, nb in self._tail],
            }

    # -- reconciliation ------------------------------------------------------
    def set_baseline(self) -> None:
        """Mark the current moment as reconciliation zero: jax buffers that
        predate it (jit constants, caches, RNG state) are outside the
        ledger's claim and must not count as drift."""
        with self._lock:
            self._jax_baseline = _jax_live_bytes()
            self._ledger_baseline = self._device_live

    def reconcile(self, tolerance: Optional[float] = None) -> Dict:
        """Compare the ledger's device-class growth since ``set_baseline``
        against what ``jax.live_arrays()`` actually reports.  Returns
        {available, ledger_bytes, jax_bytes, drift_frac, within,
        tolerance}."""
        tol = reconcile_tolerance() if tolerance is None else float(tolerance)
        jax_now = _jax_live_bytes()
        if jax_now < 0:
            return {"available": False, "within": True, "tolerance": tol,
                    "ledger_bytes": 0, "jax_bytes": 0, "drift_frac": 0.0}
        with self._lock:
            ledger_delta = self._device_live - self._ledger_baseline
            jax_delta = jax_now - self._jax_baseline
        denom = max(ledger_delta, jax_delta, 1)
        drift = abs(jax_delta - ledger_delta) / denom
        return {
            "available": True,
            "ledger_bytes": ledger_delta,
            "jax_bytes": jax_delta,
            "drift_frac": round(drift, 6),
            "tolerance": tol,
            "within": drift <= tol,
        }

    # -- leak detection + query GC -------------------------------------------
    def check_leaks(self, query_id: str, *,
                    strict: Optional[bool] = None) -> Optional[MemLeakError]:
        """Collect (and retire) every entry still charged to ``query_id``.
        Returns the MemLeakError report (None when clean); raises it when
        strict (param, else ``QK_MEM_STRICT``)."""
        if query_id is None:
            return None
        with self._lock:
            leaked = [(tok, ent) for tok, ent in self._entries.items()
                      if ent[0] == query_id]
            sites = set()
            for tok, ent in leaked:
                del self._entries[tok]
                self._apply(ent, -1)
                sites.add(ent[1])
                self._tail.append(
                    (time.time(), "leak", ent[1], query_id, ent[2]))
            pairs = self._gauge_pairs(query_id, None) if leaked else []
            for site in sites:
                pairs.append((f"mem.site_bytes.{site}",
                              self._site.get(site, 0)))
        if not leaked:
            return None
        self._set_gauges(pairs)
        from quokka_tpu import obs

        # attach each leaked entry's allocation-site flight events: the
        # report should say where the buffer CAME from, not just its size
        by_tok: Dict[str, List] = {}
        for ev in obs.RECORDER.snapshot():
            if ev[2] == "mem.track" and ev[6]:
                by_tok.setdefault(ev[6].get("tok", ""), []).append(
                    {"ts": ev[1], "site": ev[3], "thread": ev[5],
                     "args": ev[6]})
        leaks = [{"token": repr(tok), "site": ent[1], "nbytes": ent[2],
                  "device": ent[3], "events": by_tok.get(_tok_id(tok), [])}
                 for tok, ent in leaked]
        err = MemLeakError(query_id, leaks)
        obs.REGISTRY.counter("mem.leaked").inc(len(leaked))
        obs.RECORDER.record("mem.leak", query_id, n=len(leaked),
                            nbytes=sum(leak["nbytes"] for leak in leaks))
        obs.diag(f"[memplane] {err}")
        if strict if strict is not None else strict_mode():
            raise err
        return err

    def drop_query(self, query_id: str) -> None:
        """Forget a finished query's per-query accounting (the engine
        removes the per-query gauge instruments right after)."""
        with self._lock:
            self._live_q.pop(query_id, None)
            self._peak_q.pop(query_id, None)
            self._spill_q.pop(query_id, None)
            self._spill_peak_q.pop(query_id, None)

    def on_query_gc(self, query_id: str,
                    plan_fp: Optional[str] = None) -> Optional[MemLeakError]:
        """The ``TaskGraph.cleanup`` hook: persist the measured footprint
        under the plan fingerprint, flag leaks, drop per-query state."""
        if query_id is None:
            return None
        with self._lock:
            peak = self._peak_q.get(query_id, 0)
            spill_peak = self._spill_peak_q.get(query_id, 0)
        if plan_fp and peak > 0:
            record_footprint(plan_fp, peak, spill_peak)
        try:
            return self.check_leaks(query_id)
        finally:
            self.drop_query(query_id)

    def reset(self) -> None:
        """Tests only: forget everything and zero the aggregate gauges."""
        with self._lock:
            self._entries.clear()
            self._live = self._peak = self._device_live = self._spill = 0
            self._site.clear()
            self._live_q.clear()
            self._peak_q.clear()
            self._spill_q.clear()
            self._spill_peak_q.clear()
            self._tail.clear()
            self._breached = False
            self._jax_baseline = self._ledger_baseline = 0
            pairs = self._gauge_pairs(None, None)
        self._set_gauges(pairs)


def _jax_live_bytes() -> int:
    """Total bytes of live jax arrays, or -1 when jax is unavailable."""
    try:
        import jax

        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in jax.live_arrays())
    except Exception:  # noqa: BLE001 — reconciliation is diagnostics
        return -1


LEDGER = MemLedger()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_BUNDLE_SEQ = itertools.count()


def oom_bundle(reason: str, directory: Optional[str] = None,
               ledger: Optional[MemLedger] = None,
               top_k: int = _TOP_K) -> str:
    """Write the memory forensics bundle into ``QK_DUMP_DIR``: top-K holders
    by site, per-query footprints, the recent ledger tail and the merged
    flight timeline (+ a Chrome trace beside it).  Returns the bundle path;
    never raises — a failed dump must not mask the OOM it describes."""
    try:
        from quokka_tpu import obs
        from quokka_tpu.obs import merge

        ledger = LEDGER if ledger is None else ledger
        d = directory or merge.dump_dir()
        os.makedirs(d, exist_ok=True)
        # per-process sequence: two bundles in the same second (breach
        # followed immediately by the allocator error) must not collide
        stamp = f"{os.getpid()}-{int(time.time())}-{next(_BUNDLE_SEQ)}"
        path = os.path.join(d, f"mem-{stamp}.oom.json")
        trace_path = os.path.join(d, f"mem-{stamp}.trace.json")
        events = obs.RECORDER.snapshot()
        with contextlib.suppress(Exception):
            merge.write_chrome_trace(
                trace_path, merge.merge_streams({"local": events}))
        bundle = {
            "reason": reason,
            "ts": time.time(),
            "pid": os.getpid(),
            "budget_bytes": budget_bytes(),
            **ledger.snapshot(top_k=top_k),
            "flight_timeline": [
                {"ts": ev[1], "kind": ev[2], "name": ev[3],
                 "dur_s": ev[4], "thread": ev[5], "args": ev[6]}
                for ev in events[-200:]],
            "chrome_trace": trace_path,
        }
        # operator-statistics snapshots: which operator's rows/bytes were
        # in flight when memory ran out (a blown join build reads straight
        # off its rows_in here)
        with contextlib.suppress(Exception):
            from quokka_tpu.obs import opstats as _opstats

            snaps = [s for s in (_opstats.OPSTATS.snapshot(q)
                                 for q in _opstats.OPSTATS.live_queries())
                     if s]
            if not snaps:
                last = _opstats.OPSTATS.last_finished()
                snaps = [last] if last else []
            bundle["opstats"] = snaps
        with open(path, "w", encoding="utf-8") as f:
            json.dump(bundle, f, indent=2, default=repr)
        obs.REGISTRY.counter("mem.oom_bundles").inc()
        obs.diag(f"[memplane] OOM forensics bundle: {path} ({reason})")
        return path
    except Exception as e:  # noqa: BLE001 — diagnostics must not mask OOM
        with contextlib.suppress(OSError, ValueError):
            sys.stderr.write(f"[memplane] oom bundle failed: {e!r}\n")
        return ""


@contextlib.contextmanager
def alloc_guard(site: str):
    """Wrap a device-allocating region: an allocator out-of-memory error
    writes the forensics bundle before re-raising, so the post-mortem has
    the ledger state from the exact failing moment."""
    try:
        yield
    except Exception as e:
        msg = str(e)
        if ("RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()
                or isinstance(e, MemoryError)):
            oom_bundle(f"allocation failure at site {site!r}: {msg[:200]}")
        raise


# ---------------------------------------------------------------------------
# Measured footprints (admission's input): strategy-profile persistence
# ---------------------------------------------------------------------------


def _profile_dir() -> Optional[str]:
    """``QK_MEMPROFILE_DIR`` overrides (empty disables, the QK_STRATEGY_DIR
    idiom); default lives beside the strategy profiles under the cache
    root."""
    env = os.environ.get("QK_MEMPROFILE_DIR")
    if env is not None:
        return env or None
    from quokka_tpu import config

    return os.path.join(config.CACHE_ROOT, "memprofile")


def _profile_path() -> Optional[str]:
    d = _profile_dir()
    if d is None:
        return None
    from quokka_tpu.runtime import compileplane

    return os.path.join(d, compileplane.backend_fingerprint() + ".json")


def _load_profile(path: str) -> Optional[dict]:
    """The profile dict, or None when absent/corrupt/foreign.  A profile
    measured on a different backend topology is rejected wholesale — its
    footprints describe different device placement."""
    try:
        with open(path, encoding="utf-8") as f:
            prof = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(prof, dict) or prof.get("version") != _PROFILE_VERSION:
        return None
    from quokka_tpu.runtime import compileplane

    if prof.get("fingerprint") != compileplane.backend_fingerprint():
        return None
    return prof if isinstance(prof.get("plans"), dict) else None


def record_footprint(plan_fp: str, peak_bytes: int,
                     spill_bytes: int = 0) -> None:
    """Persist a finished query's measured peak under its plan fingerprint
    (atomic tmp + replace, max-merged across runs so a lightly-loaded run
    never shrinks the admission charge below an observed peak).  Best
    effort: never raises."""
    if not plan_fp or peak_bytes <= 0:
        return
    path = _profile_path()
    if path is None:
        return
    try:
        from quokka_tpu.runtime import compileplane

        prof = _load_profile(path) or {
            "version": _PROFILE_VERSION,
            "fingerprint": compileplane.backend_fingerprint(),
            "plans": {},
        }
        ent = prof["plans"].get(plan_fp)
        ent = ent if isinstance(ent, dict) else {}
        prof["plans"][plan_fp] = {
            "peak_bytes": max(int(peak_bytes),
                              int(ent.get("peak_bytes", 0) or 0)),
            "spill_bytes": max(int(spill_bytes),
                               int(ent.get("spill_bytes", 0) or 0)),
            "runs": int(ent.get("runs", 0) or 0) + 1,
        }
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # a tmp name per THREAD: two pool threads persisting at once
        # shared one, and the second os.replace found it gone
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(prof, f)
        os.replace(tmp, path)
    except (OSError, TypeError, ValueError) as e:
        from quokka_tpu import obs

        obs.diag(f"[memplane] footprint persist for {plan_fp} failed: {e!r}")


def measured_footprint(plan_fp: Optional[str]) -> Optional[int]:
    """The measured peak bytes for a plan fingerprint, or None (no profile,
    foreign backend fingerprint, unknown plan) — admission falls back to
    ``size_hint()`` estimation then."""
    if not plan_fp:
        return None
    path = _profile_path()
    if path is None:
        return None
    prof = _load_profile(path)
    if prof is None:
        return None
    ent = prof["plans"].get(plan_fp)
    if not isinstance(ent, dict):
        return None
    try:
        peak = int(ent.get("peak_bytes", 0))
    except (TypeError, ValueError):
        return None
    return peak if peak > 0 else None
