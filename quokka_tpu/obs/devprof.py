"""Device-time & roofline efficiency plane.

The obs stack can say where host wall-time went (``obs/critpath.py``), what
memory was held (``obs/memplane.py``) and how many rows moved
(``obs/opstats.py``) — but not whether the device was *busy* or *efficient*.
This module closes that gap with three pieces:

1. **Per-program static cost ledger.**  At AOT compile time
   ``runtime/compileplane.acquire`` hands the freshly compiled executable to
   :func:`record_cost`, which extracts XLA's static cost figures
   (``compiled.cost_analysis()``: flops, bytes accessed, output bytes) and
   persists them in a ``<artifact>.cost.json`` sidecar next to the AOT
   executable, keyed by the same program signature.  A cache hit replays the
   sidecar via :func:`load_cost` — no recompile, no re-analysis.

2. **Calibrated peaks.**  :func:`calibrate` micro-benchmarks peak achievable
   FLOP/s (MXU-shaped matmul) and memory bandwidth (streaming elementwise
   add) once per backend fingerprint — the exact ``ops/strategy.py``
   pattern — and persists ``{peak_flops_s, peak_bw_bytes_s}`` under
   ``<cache>/devprof/<fingerprint>.json``.  A profile written by a foreign
   fingerprint (different host, jax version, device kind/count) is rejected
   wholesale, like every other persisted profile in the tree.

3. **Runtime attribution, ZERO new host syncs.**  Every program dispatch
   funnels through :func:`on_dispatch`, which charges the program's *static*
   flops/bytes to the thread-local current operator that ``obs/opstats.py``
   already maintains.  Joining those charges against opstats' measured wall
   seconds per operator yields achieved-FLOP/s, achieved bandwidth,
   arithmetic intensity and roofline-efficiency %% — attached to the opstats
   snapshot (:func:`attach`), rendered by ``explain()`` / ``bench.py
   --measure`` / ``/status``, and exported as ``quokka_devprof_*``
   Prometheus families.  No figure here ever reads a device value.

At query GC :func:`on_query_finished` persists the observed per-source scan
seconds and the query's achieved bandwidth into the same profile, which is
what lets ``planner/cost.py`` convert rows×bytes estimates into *predicted
device seconds* (``CostModel.estimate_seconds``: measured program seconds >
roofline prediction > hint) — ROADMAP item 2's feedback loop reasoning in
seconds instead of abstract bytes.

Env knobs (README "Device profiling & roofline"):

- ``QK_DEVPROF``: unset/1 -> profiling on; ``0`` -> everything off.
- ``QK_EFF_FLOOR``: roofline-efficiency fraction below which explain()
  flags an operator (default 0.05).
- ``QK_DEVPROF_DIR``: profile directory; empty string disables
  persistence; unset -> ``<cache>/devprof``.
- ``QK_DEVPROF_CALIBRATE``: ``0`` -> ``ensure_calibrated`` will not run
  the micro-benchmarks (loads an existing profile only).
"""

from __future__ import annotations

import json
import math
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from quokka_tpu import config

_PROFILE_VERSION = 1
_COST_VERSION = 1

# process-wide state: static program costs, per-(query, actor) attribution,
# per-program dispatch tallies, and the calibrated-peaks profile
_lock = threading.Lock()
_costs: Dict[Any, Dict[str, float]] = {}
_attr: Dict[Tuple[str, int], List[float]] = {}
_prog_disp: Dict[Any, int] = {}
_qgauges: Dict[str, List[str]] = {}
_peaks: Optional[Dict[str, Any]] = None
_calib_state = "unloaded"


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------


def enabled() -> bool:
    """``QK_DEVPROF``: unset/1 -> on; ``0`` -> the whole plane off."""
    return os.environ.get("QK_DEVPROF", "1") != "0"


def eff_floor() -> float:
    """``QK_EFF_FLOOR``: roofline-efficiency fraction below which an
    operator is flagged in explain() (default 0.05)."""
    try:
        return float(os.environ.get("QK_EFF_FLOOR", 0.05))
    except ValueError:
        return 0.05


def _dir() -> Optional[str]:
    """Profile directory; QK_DEVPROF_DIR='' disables persistence (the
    tests' default via conftest), unset falls back to <cache>/devprof."""
    d = os.environ.get("QK_DEVPROF_DIR")
    if d is not None:
        return d or None
    return os.path.join(config.CACHE_ROOT, "devprof")


def _fingerprint() -> str:
    from quokka_tpu.runtime import compileplane

    return compileplane.backend_fingerprint()


def _profile_path() -> Optional[str]:
    d = _dir()
    return os.path.join(d, f"{_fingerprint()}.json") if d else None


# ---------------------------------------------------------------------------
# Calibration profile: load / validate / persist (strategy.py discipline)
# ---------------------------------------------------------------------------


def _valid_profile(data: Any) -> bool:
    if not isinstance(data, dict):
        return False
    if data.get("version") != _PROFILE_VERSION:
        return False
    if data.get("fingerprint") != _fingerprint():
        return False
    for k in ("peak_flops_s", "peak_bw_bytes_s"):
        v = data.get(k)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            return False
    if not isinstance(data.get("sources", {}), dict):
        return False
    return True


def _load_profile(path: Optional[str]) -> Optional[Dict[str, Any]]:
    """Wholesale rejection: a corrupt, versioned-away or foreign-fingerprint
    profile is ignored entirely (never partially trusted)."""
    if not path:
        return None
    try:
        with open(path) as f:
            data = json.load(f)
        if not _valid_profile(data):
            raise ValueError("invalid devprof profile")
        return data
    except (OSError, ValueError):
        return None


def _persist_profile(data: Dict[str, Any]) -> None:
    path = _profile_path()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # a tmp name per THREAD: two pool threads persisting at once
        # shared one, and the second os.replace found it gone
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except OSError as e:
        from quokka_tpu import obs

        obs.diag(f"devprof: profile persist failed: {e}")


def _install(prof: Optional[Dict[str, Any]]) -> None:
    """Adopt a profile in-process and mirror the peaks onto gauges."""
    global _peaks, _calib_state
    with _lock:
        if prof is not None:
            _peaks = prof
        _calib_state = "loaded"
    if prof is not None:
        from quokka_tpu import obs

        obs.REGISTRY.gauge("devprof.peak_flops").set(prof["peak_flops_s"])
        obs.REGISTRY.gauge("devprof.peak_bw_bytes").set(
            prof["peak_bw_bytes_s"])


def _ensure_loaded() -> None:
    with _lock:
        if _calib_state == "loaded":
            return
        path = _profile_path()
    # file I/O strictly outside the lock (QK025)
    _install(_load_profile(path))


def peaks() -> Optional[Dict[str, Any]]:
    """The installed calibration profile, lazily loaded from disk; None
    until calibrate() has run for this backend fingerprint."""
    _ensure_loaded()
    with _lock:
        return _peaks


def planning_bw() -> Optional[float]:
    """Bandwidth figure the planner's seconds conversion uses: the observed
    achieved bandwidth once real queries have run, else the calibrated
    peak.  None when uncalibrated (the cost model then stays on its hint
    rung)."""
    p = peaks()
    if p is None:
        return None
    v = p.get("observed_bw_bytes_s")
    if isinstance(v, (int, float)) and math.isfinite(v) and v > 0:
        return float(v)
    return float(p["peak_bw_bytes_s"])


def measured_source_seconds(sig: str) -> Optional[Tuple[float, float]]:
    """(seconds, bytes) recorded for a source signature by a previous run
    of the same scan, or None — the cost model's ``seconds(measured)``
    rung."""
    p = peaks()
    if p is None:
        return None
    row = p.get("sources", {}).get(sig)
    if not isinstance(row, dict):
        return None
    s, b = row.get("seconds"), row.get("bytes")
    if (isinstance(s, (int, float)) and math.isfinite(s) and s > 0
            and isinstance(b, (int, float)) and b >= 0):
        return float(s), float(b)
    return None


def _time_best(fn, reps: int = 3) -> float:
    import time

    fn()  # warm: compile + first dispatch
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate(force: bool = False) -> Dict[str, Any]:
    """Micro-benchmark peak FLOP/s and memory bandwidth for this backend
    fingerprint, install the profile in-process and persist it.  Idempotent
    per fingerprint unless forced."""
    if not force:
        existing = peaks()
        if existing is not None:
            return existing
    import jax
    import jax.numpy as jnp

    timings: Dict[str, float] = {}
    # peak FLOP/s: square matmul (2*n^3 flops) — the MXU-shaped workload
    n = 256
    a = jnp.ones((n, n), dtype=jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    t_mm = _time_best(lambda: mm(a, a).block_until_ready())
    timings["matmul_s"] = t_mm
    peak_flops = (2.0 * n ** 3) / max(t_mm, 1e-9)
    # peak bandwidth: streaming elementwise add (read 2 arrays, write 1)
    m = 1 << 21
    v = jnp.ones((m,), dtype=jnp.float32)
    add = jax.jit(lambda x, y: x + y)
    t_add = _time_best(lambda: add(v, v).block_until_ready())
    timings["stream_s"] = t_add
    peak_bw = (3.0 * 4.0 * m) / max(t_add, 1e-9)

    prof: Dict[str, Any] = {
        "version": _PROFILE_VERSION,
        "fingerprint": _fingerprint(),
        "peak_flops_s": peak_flops,
        "peak_bw_bytes_s": peak_bw,
        "timings_s": timings,
        "sources": {},
    }
    # carry observations forward across re-calibration
    prev = peaks()
    if prev is not None:
        prof["sources"] = dict(prev.get("sources", {}))
        if "observed_bw_bytes_s" in prev:
            prof["observed_bw_bytes_s"] = prev["observed_bw_bytes_s"]
    _install(prof)
    _persist_profile(prof)
    return prof


def ensure_calibrated() -> Dict[str, Any]:
    """Load-or-calibrate once: the bench/smoke entry point.  Honors
    ``QK_DEVPROF_CALIBRATE=0`` (load an existing profile only — the skip
    that keeps unit tests deterministic)."""
    p = peaks()
    if p is not None:
        return p
    if (not enabled()
            or os.environ.get("QK_DEVPROF_CALIBRATE", "1") == "0"):
        return {}
    return calibrate()


def reset() -> None:
    """Forget everything in-process (tests): costs, attribution, profile."""
    global _peaks, _calib_state
    with _lock:
        _costs.clear()
        _attr.clear()
        _prog_disp.clear()
        _qgauges.clear()
        _peaks = None
        _calib_state = "unloaded"


# ---------------------------------------------------------------------------
# Per-program static costs
# ---------------------------------------------------------------------------


def extract_cost(compiled) -> Optional[Dict[str, float]]:
    """Static cost figures from a compiled executable's
    ``cost_analysis()``.  jax returns a list of per-program dicts whose
    keys are XLA metric names (``'flops'``, ``'bytes accessed'``,
    ``"bytes accessedout{}"`` for output bytes); absent/negative entries
    read as 0."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None

    def _num(key: str) -> float:
        v = ca.get(key)
        if isinstance(v, (int, float)) and math.isfinite(float(v)) and v > 0:
            return float(v)
        return 0.0

    return {
        "flops": _num("flops"),
        "bytes": _num("bytes accessed"),
        "out_bytes": _num("bytes accessedout{}"),
    }


def _cost_sidecar(path: str) -> str:
    return path + ".cost.json"


def record_cost(key, compiled, path: Optional[str] = None) -> None:
    """Compile-time hook: ledger the executable's static costs under its
    program signature and persist the sidecar next to the AOT artifact."""
    if not enabled():
        return
    cost = extract_cost(compiled)
    if cost is None:
        return
    with _lock:
        _costs[key] = cost
    from quokka_tpu import obs

    obs.REGISTRY.counter("devprof.programs_costed").inc()
    if path:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            # a tmp name per THREAD: both pool threads compile the same
            # program at times, and the second os.replace found it gone
            tmp = (f"{_cost_sidecar(path)}.tmp.{os.getpid()}"
                   f".{threading.get_ident()}")
            with open(tmp, "w") as f:
                json.dump({"version": _COST_VERSION, **cost}, f)
            os.replace(tmp, _cost_sidecar(path))
        except OSError as e:
            obs.diag(f"devprof: cost sidecar persist failed: {e}")


def load_cost(key, path: str) -> bool:
    """AOT-cache-hit hook: replay the persisted cost sidecar (no recompile,
    no re-analysis).  Missing/corrupt sidecars (artifacts predating this
    plane) simply leave the program uncosted."""
    if not enabled():
        return False
    with _lock:
        if key in _costs:
            return True
    try:
        with open(_cost_sidecar(path)) as f:
            data = json.load(f)
        if (not isinstance(data, dict)
                or data.get("version") != _COST_VERSION):
            raise ValueError("invalid cost sidecar")
        cost = {k: float(data[k])
                for k in ("flops", "bytes", "out_bytes")}
        if any(not math.isfinite(v) or v < 0 for v in cost.values()):
            raise ValueError("invalid cost figures")
    except (OSError, ValueError, KeyError, TypeError):
        return False
    with _lock:
        _costs[key] = cost
    from quokka_tpu import obs

    obs.REGISTRY.counter("devprof.programs_costed").inc()
    return True


def program_cost(key) -> Optional[Dict[str, float]]:
    with _lock:
        c = _costs.get(key)
        return dict(c) if c else None


def costs_snapshot() -> List[Dict[str, Any]]:
    """Every costed program: signature hash, static figures, arithmetic
    intensity, lifetime dispatch count (for /status and the smoke)."""
    from quokka_tpu.runtime import compileplane

    with _lock:
        items = [(k, dict(c), _prog_disp.get(k, 0))
                 for k, c in _costs.items()]
    out = []
    for key, cost, disp in items:
        out.append({
            "sig": compileplane.key_hash(key),
            "flops": cost["flops"],
            "bytes": cost["bytes"],
            "out_bytes": cost["out_bytes"],
            "intensity": (cost["flops"] / cost["bytes"]
                          if cost["bytes"] > 0 else None),
            "dispatches": disp,
        })
    out.sort(key=lambda r: (-r["flops"], r["sig"]))
    return out


# ---------------------------------------------------------------------------
# Runtime attribution (the dispatch hot path)
# ---------------------------------------------------------------------------


def on_dispatch(key) -> None:
    """Charge one program dispatch's static flops/bytes to the current
    operator (opstats' thread-local marker).  Dict lookups + float adds
    under a short lock — never a device read."""
    if not enabled():
        return
    cost = _costs.get(key)  # GIL-atomic read; missing -> uncosted program
    if cost is None:
        return
    from quokka_tpu.obs import opstats

    cur = getattr(opstats._CUR, "key", None)
    with _lock:
        _prog_disp[key] = _prog_disp.get(key, 0) + 1
        if cur is not None:
            slot = _attr.get((cur[0], cur[1]))
            if slot is None:
                slot = _attr[(cur[0], cur[1])] = [0.0, 0.0, 0.0, 0]
            slot[0] += cost["flops"]
            slot[1] += cost["bytes"]
            slot[2] += cost["out_bytes"]
            slot[3] += 1


# ---------------------------------------------------------------------------
# Roofline math
# ---------------------------------------------------------------------------


def roofline(flops: float, nbytes: float, seconds: Optional[float],
             peak_flops: Optional[float], peak_bw: Optional[float]
             ) -> Dict[str, Optional[float]]:
    """Achieved rates + roofline efficiency for one (cost, seconds) pair.

    Efficiency = achieved / attainable, where attainable =
    ``min(peak_flops, intensity * peak_bw)`` — the classic roofline: a
    memory-bound program (low intensity) is judged against the bandwidth
    ceiling, a compute-bound one against the FLOP ceiling.  A program with
    no flops at all (pure data movement) is judged purely on bandwidth.
    None when nothing is attributable or peaks are uncalibrated."""
    intensity = flops / nbytes if nbytes > 0 else None
    if seconds is None or seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return {"intensity": intensity, "achieved_flops_s": None,
                "achieved_bw_s": None, "efficiency": None}
    af = flops / seconds if flops > 0 else 0.0
    ab = nbytes / seconds if nbytes > 0 else 0.0
    eff: Optional[float] = None
    if peak_flops and peak_bw:
        if flops > 0:
            attainable = peak_flops
            if intensity is not None:
                attainable = min(peak_flops, intensity * peak_bw)
            eff = af / attainable if attainable > 0 else None
        else:
            eff = ab / peak_bw
    return {"intensity": intensity,
            "achieved_flops_s": af if flops > 0 else None,
            "achieved_bw_s": ab if nbytes > 0 else None,
            "efficiency": eff}


# ---------------------------------------------------------------------------
# Snapshot attachment + query lifecycle
# ---------------------------------------------------------------------------


def attach(qid: str, snap: Dict[str, Any]) -> None:
    """Join the query's per-operator attribution against opstats' measured
    wall seconds and attach the ``efficiency`` section to the snapshot
    (explain/bench/status all read it from there).  Also mirrors each
    operator's roofline efficiency onto a per-query gauge for /metrics."""
    if not enabled():
        return
    prof = peaks()
    pf = prof.get("peak_flops_s") if prof else None
    pb = prof.get("peak_bw_bytes_s") if prof else None
    with _lock:
        acc = {k[1]: list(v) for k, v in _attr.items() if k[0] == qid}
    rows: List[Dict[str, Any]] = []
    gnames: List[str] = []
    for op in snap.get("operators", []):
        slot = acc.get(op.get("actor"))
        if slot is None:
            continue
        flops, nbytes, out_b, disp = slot
        rl = roofline(flops, nbytes, op.get("time_s"), pf, pb)
        row = {
            "actor": op.get("actor"),
            "op": op.get("op"),
            "time_s": op.get("time_s"),
            "flops": flops,
            "bytes": nbytes,
            "out_bytes": out_b,
            "program_dispatches": disp,
            **rl,
        }
        row["flagged"] = (rl["efficiency"] is not None
                          and rl["efficiency"] < eff_floor())
        rows.append(row)
        if rl["efficiency"] is not None:
            from quokka_tpu import obs

            name = f"devprof.eff.{qid}.a{op.get('actor')}"
            obs.REGISTRY.gauge(name).set(rl["efficiency"])
            gnames.append(name)
    rows.sort(key=lambda r: -(r["time_s"] or 0.0))
    snap["efficiency"] = {
        "peaks": ({"fingerprint": prof["fingerprint"],
                   "peak_flops_s": pf, "peak_bw_bytes_s": pb}
                  if prof else None),
        "operators": rows,
    }
    if gnames:
        with _lock:
            _qgauges[qid] = sorted(set(_qgauges.get(qid, []) + gnames))


def on_query_finished(qid: str, plan_fp: Optional[str],
                      snap: Dict[str, Any]) -> None:
    """Query-GC hook (rides ``opstats.on_query_gc``): drop the per-query
    attribution + gauges and persist the run's observations — per-source
    scan seconds (the seconds(measured) rung) and the achieved bandwidth
    (the seconds(roofline) conversion factor) — into the calibration
    profile.  Never raises; persistence is best-effort."""
    with _lock:
        acc = {k[1]: list(v) for k, v in _attr.items() if k[0] == qid}
        for k in [k for k in _attr if k[0] == qid]:
            del _attr[k]
        gnames = _qgauges.pop(qid, [])
    if gnames:
        from quokka_tpu import obs

        obs.REGISTRY.remove(*gnames)
    if not enabled():
        return
    prof = peaks()
    if prof is None or not _dir():
        return
    # observations from the final snapshot: input operators carry the
    # source signature their measured cardinalities persist under — the
    # same key cost.source_signature computes at plan time
    sources: Dict[str, Dict[str, float]] = {}
    tot_bytes = tot_s = 0.0
    for op in snap.get("operators", []):
        t = op.get("time_s")
        if isinstance(t, (int, float)) and t > 0:
            slot = acc.get(op.get("actor"))
            if slot is not None:
                tot_bytes += slot[1]
                tot_s += t
            sig = op.get("src_sig")
            if sig and op.get("kind") == "input":
                b = op.get("bytes_in") or 0
                sources[str(sig)] = {"seconds": float(t), "bytes": float(b)}
    if not sources and tot_s <= 0:
        return
    path = _profile_path()
    cur = _load_profile(path) or prof
    merged = dict(cur)
    merged_sources = dict(cur.get("sources", {}))
    for sig, row in sources.items():
        prev = merged_sources.get(sig)
        runs = (prev.get("runs", 0) if isinstance(prev, dict) else 0) + 1
        merged_sources[sig] = {**row, "runs": runs}
    merged["sources"] = merged_sources
    if tot_s > 0 and tot_bytes > 0:
        obs_bw = tot_bytes / tot_s
        prev_bw = merged.get("observed_bw_bytes_s")
        if isinstance(prev_bw, (int, float)) and prev_bw > 0:
            obs_bw = 0.5 * prev_bw + 0.5 * obs_bw
        merged["observed_bw_bytes_s"] = obs_bw
    _install(merged)
    _persist_profile(merged)


def summary() -> Dict[str, Any]:
    """Compact process-level digest for /status."""
    prof = peaks()
    with _lock:
        ncost = len(_costs)
        ndisp = sum(_prog_disp.values())
    return {
        "enabled": enabled(),
        "calibrated": prof is not None,
        "fingerprint": prof["fingerprint"] if prof else None,
        "peak_flops_s": prof["peak_flops_s"] if prof else None,
        "peak_bw_bytes_s": prof["peak_bw_bytes_s"] if prof else None,
        "observed_bw_bytes_s": (prof or {}).get("observed_bw_bytes_s"),
        "programs_costed": ncost,
        "program_dispatches": ndisp,
    }
