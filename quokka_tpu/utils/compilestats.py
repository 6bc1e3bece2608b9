"""Process-wide XLA compile counters, fed by jax.monitoring events.

The engine's static-shape discipline means a query shape should compile its
kernel set once and then reuse it forever — across batches within a run,
across runs within a process (jit caches), and across processes (the
persistent compilation cache, config.py).  These counters make reuse
observable: `snapshot()["backend_compiles"]` staying flat across repeated
runs IS the proof, and ``benchmarks/run.py`` reports the deltas around its
warm-up passes and its window.

Counter meanings:
- backend_compiles / backend_compile_seconds: compile_or_get_cached calls —
  NOTE this event fires on persistent-cache HITS too (jax wraps the whole
  lookup-or-compile in one duration event), so real compilations are
  `real_compiles = backend_compiles - cache_hits` (snapshot derives it).
- cache_hits: persistent-cache loads that avoided a real backend compile.
- traces: jaxprs traced (cheap, happens once per in-process signature).
"""

from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_stats = {
    "backend_compiles": 0,
    "backend_compile_seconds": 0.0,
    "cache_hits": 0,
    "traces": 0,
}
_registered = False
# jax records the cache-hit event synchronously in the compiling thread, so
# a per-thread count tells a caller whether ITS compile was a cache load
_tls = threading.local()


def _on_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _tls.cache_hits = thread_cache_hits() + 1
        with _lock:
            _stats["cache_hits"] += 1


def thread_cache_hits() -> int:
    """Persistent-cache hits seen by the calling thread: unchanged across a
    ``lowered.compile()`` means that compile was a real one."""
    return getattr(_tls, "cache_hits", 0)


def thread_real_compiles() -> int:
    """Backend compiles the calling thread paid for itself (persistent-cache
    loads taken off): a difference across a call says whether that call
    really compiled, and how many programs."""
    return getattr(_tls, "backend_compiles", 0) - thread_cache_hits()


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    with _lock:
        if event == "/jax/core/compile/backend_compile_duration":
            _stats["backend_compiles"] += 1
            _stats["backend_compile_seconds"] += duration_secs
            _tls.backend_compiles = getattr(_tls, "backend_compiles", 0) + 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            _stats["traces"] += 1
        else:
            return
    # compile activity in the flight recorder: merged timelines show which
    # worker paid a compile (or a persistent-cache load), when, and for
    # which query's task where one is open on this thread.  Programs of the
    # compile plane are named by its own compile.acquire span; this event
    # is what names the rest (eager jnp calls, plain jit fallbacks)
    try:
        from quokka_tpu.obs import recorder, spans

        recorder.RECORDER.record(
            "compile",
            "backend_compile" if event.endswith("backend_compile_duration")
            else "trace",
            dur=duration_secs, **spans.current_task())
    except Exception:
        return  # monitoring must never break the compile path


def ensure_registered() -> None:
    global _registered
    if _registered:
        return
    _registered = True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> Dict:
    ensure_registered()
    with _lock:
        out = dict(_stats)
    out["backend_compile_seconds"] = round(out["backend_compile_seconds"], 3)
    out["real_compiles"] = max(0, out["backend_compiles"] - out["cache_hits"])
    return out
