"""Structured tracing: named spans that know their request and their parent.

The span API that used to live in utils/tracing.py (which now re-exports
this module).  Four consumers share one ``span(...)`` call site:

- the aggregate summary (``QUOKKA_TRACE=1`` or ``set_enabled(True)``):
  name -> (count, total seconds), read through ``stats()`` — the
  replacement for the reference's print_if_profile timestamp prints
  (pyquokka/core.py:20-30);
- the flight recorder: every span lands as a duration event in the ring
  (obs/recorder.py), with ``q`` (the query id) and ``p`` (the enclosing
  span's name; ``task`` directly under a dispatch, ``offthread`` on a
  helper thread) in its args, so merged timelines show where time went per
  worker and per request;
- the query log (obs/querylog.py): a span's **self time** (its duration
  minus what the spans nested in it covered) is summed by layer into the
  one record each finished query leaves;
- the profiler's timeline: while a ``jax.profiler`` trace is running, each
  span is also a ``TraceAnnotation("qk.<name>")`` on the thread that ran
  it, so the device's timeline and the engine's meet on the trace's clock.

``device_read(site, value)`` is the one place a thread of the program blocks
until the device has produced something: a ``sync.<site>`` span around an
explicit ``jax.device_get``, seen by all four consumers like any span, and
summed by the query log into the request's wait for the device (``syncs``,
``sync.wait``, ``d2h_bytes``, ``sync_sites``) beside the partition, which it
leaves as it was: a read's seconds stay the enclosing span's own.

Nesting is tracked per thread: a span opened inside another is its child,
inherits its query id, and adds its duration to the parent's "covered by
children" sum when it closes.  ``dispatch(...)`` opens the root of one task
dispatch; the self times of everything closed under it partition the
dispatch's duration exactly.  Durations are on ``time.perf_counter()``.
"""

from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation as _Annotation

from quokka_tpu.obs import querylog as _querylog
from quokka_tpu.obs import recorder as _recorder

_enabled = os.environ.get("QUOKKA_TRACE", "0") not in ("0", "", "false")

_lock = threading.Lock()
_stats = defaultdict(lambda: [0, 0.0])  # name -> [count, total_seconds]

# the innermost open span of each thread
_TLS = threading.local()
_tracing = _Annotation.is_enabled  # an atomic read while no trace runs
_now = time.perf_counter

# in-dispatch span name -> the query record's layer key (obs/querylog.py)
_LAYER_PREFIXES = (
    (("exec.", "done.", "asof.", "groupby.", "join."),
     "executors.exec_self"),
    (("push.",), "runtime.push"),
    (("reader.execute", "bridge.to_device", "prefetch.wait"), "io.read"),
    (("emit.",), "emit.d2h"),
    (("compile.acquire",), "compile.acquire"),
)
_LAYER_OF: Dict[str, str] = {}
# the span whose ``bytes`` are what crossed host -> device (runtime/engine.py)
_H2D = "bridge.to_device"


def _layer(name: str) -> str:
    layer = _LAYER_OF.get(name)
    if layer is None:
        layer = next((key for prefixes, key in _LAYER_PREFIXES
                      if name.startswith(prefixes)), "other")
        _LAYER_OF[name] = layer
    return layer


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Turn aggregate collection on programmatically (a test's lever:
    ``stats()`` is populated even without QUOKKA_TRACE=1)."""
    global _enabled
    _enabled = bool(on)


class Span:
    """One timed region: ``with span("exec.Join"): ...``.  After the block,
    ``dur`` and ``self_s`` hold its duration and self time, ``t0`` its start
    (all ``time.perf_counter()``).

    ``ring=False`` keeps it out of the flight recorder and the aggregate
    summary (a per-turn span would evict the ring's history); it is still a
    parent for what nests in it and an annotation in a running trace.
    ``args`` (a dict, settable inside the block) ride the ring event.
    ``cancel()`` inside the block makes the region vanish: no event, and
    its time stays the parent's own."""

    __slots__ = ("name", "q", "ring", "args", "parent", "root", "covered",
                 "t0", "dur", "self_s", "keep", "_ann")

    # a transparent span (a device read) leaves its seconds the parent's own
    transparent = False

    def __init__(self, name: str, q: Optional[str] = None,
                 ring: bool = True):
        self.name = name
        self.q = q
        self.ring = ring
        self.args: Optional[dict] = None
        self.covered = 0.0
        self.dur = self.self_s = 0.0
        self.keep = True

    def __enter__(self) -> "Span":
        parent = self.parent = getattr(_TLS, "top", None)
        if parent is not None:
            self.root = parent.root
            if self.q is None:
                self.q = parent.q
        else:
            self.root = None
        _TLS.top = self
        if _tracing():
            self._ann = _Annotation("qk." + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> bool:
        dt = self.dur = _now() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        parent = self.parent
        _TLS.top = parent
        if self.keep:
            if parent is not None:
                parent.covered += self.covered if self.transparent else dt
            self.self_s = dt - self.covered
            self._account(dt, parent)
        return False

    def cancel(self) -> None:
        self.keep = False

    def rename(self, name: str) -> None:
        """Name the region by how it ended (``svc.step`` -> ``svc.fruitless``);
        a running trace keeps the name it was opened under."""
        self.name = name

    def _account(self, dt: float, parent: Optional["Span"]) -> None:
        root = self.root
        if root is not None:
            # inside a dispatch: its thread's own dict, no lock
            layer = _layer(self.name)
            root.parts[layer] = root.parts.get(layer, 0.0) + self.self_s
            if self.name == _H2D and self.args:
                root.h2d += self.args.get("bytes", 0)
        elif self.q is not None:
            _querylog.add(self.q, _off_name(self.name, parent), dt,
                          self.self_s)
            if self.name == _H2D and self.args:
                _querylog.h2d(self.q, self.args.get("bytes", 0))
        self._emit(dt, parent)

    def _emit(self, dt: float, parent: Optional["Span"]) -> None:
        if not self.ring:
            return
        if _enabled:
            with _lock:
                s = _stats[self.name]
                s[0] += 1
                s[1] += dt
        rec = _recorder.RECORDER
        if rec.enabled:
            args = dict(self.args) if self.args else {}
            if self.q is not None:
                args["q"] = self.q
            if parent is not None:
                args["p"] = "task" if parent is self.root else parent.name
            rec.record("span", self.name, dur=dt, **args)


def _off_name(name: str, parent: Optional[Span]) -> str:
    if parent is not None and parent.name == "offthread":
        return "offthread." + name
    return name


span = Span


def add_bytes(n: int) -> None:
    """Add ``n`` to the ``bytes`` of the innermost span open on this thread
    (``pack.pack_put``: what it put on the wire; under ``bridge.to_device``
    they are the record's ``h2d_bytes``)."""
    top = getattr(_TLS, "top", None)
    if top is not None:
        if top.args is None:
            top.args = {"bytes": n}
        else:
            top.args["bytes"] = top.args.get("bytes", 0) + n


class _Sync(Span):
    """``sync.<site>``: one blocking read of the device (``device_read``) or
    wait for another thread's (``device_wait``).  Transparent unless it keeps
    a layer of its own (``sync.count_valid``: ``other``)."""

    __slots__ = ("transparent",)

    def __init__(self, site: str, own_layer: bool):
        Span.__init__(self, "sync." + site)
        self.transparent = not own_layer

    def _account(self, dt: float, parent: Optional[Span]) -> None:
        root = self.root
        site = self.name[5:]
        nbytes = self.args["bytes"] if self.args else 0
        if root is not None:
            if not self.transparent:
                root.parts["other"] = root.parts.get("other", 0.0) + self.self_s
            root.syncs.append((site, dt, nbytes))
        elif self.q is not None:
            p = parent
            while p is not None and p.name != "offthread":
                p = p.parent
            _querylog.sync(self.q, [(site, dt, nbytes)],
                           "sync.offthread" if p is not None else None)
        self._emit(dt, parent)


def _device_bytes(value: Any, host: Any) -> int:
    if isinstance(value, jax.Array):
        return host.nbytes
    if isinstance(value, (np.ndarray, np.generic, int, float, bool)):
        return 0
    return sum(h.nbytes for v, h in zip(jax.tree_util.tree_leaves(value),
                                        jax.tree_util.tree_leaves(host))
               if isinstance(v, jax.Array))


def device_read(site: str, value: Any, own_layer: bool = False) -> Any:
    """Bring ``value`` (an array, a scalar or a pytree of them) to the host:
    THE place a thread blocks until the device has produced something.  Opens
    ``sync.<site>`` around an explicit ``jax.device_get`` (allowed under any
    ``jax.transfer_guard``, so a process that disallows transfers finds the
    reads that go beside this), sets the span's ``bytes`` to what came from
    the device, and returns numpy values shaped like ``value``: the caller
    converts with ``int()`` / ``bool()`` as it did.  ``site`` is one stable
    name a call site, dots by layer (``join.build_stats``)."""
    with _Sync(site, own_layer) as sp:
        with jax.transfer_guard_device_to_host("allow"):
            host = jax.device_get(value)
        sp.args = {"bytes": _device_bytes(value, host)}
    return host


def device_wait(site: str, wait: Callable[[], Any]) -> Any:
    """``wait()`` under ``sync.<site>``, for a thread that parks until another
    thread's device read is done (a future's ``result``): a read of 0 bytes."""
    with _Sync(site, False) as sp:
        sp.args = {"bytes": 0}
        return wait()


class Dispatch(Span):
    """The root of one task dispatch (``Engine.dispatch_task``).  Spans
    closed under it sum their self times by layer into ``parts``, the device
    reads among them go into ``syncs`` (site, seconds, bytes) and what
    ``bridge.to_device`` put into ``h2d``; the
    caller sets ``ok`` before the block ends, and a dispatch that
    progressed hands ``parts`` (its own self time under
    ``runtime.dispatch_self``) to the query's record.  One that could not
    progress is the query waiting on its own pipeline: its whole duration
    goes to the record's scheduling wait.  ``label`` names the task for
    whatever compiles under it.  The caller writes the ``task`` ring event
    itself (it carries the dispatch's causal note)."""

    __slots__ = ("label", "parts", "ok", "syncs", "h2d")

    def __init__(self, kind: str, label: str, q: Optional[str]):
        Span.__init__(self, "task:" + kind, q, ring=False)
        self.label = label
        self.parts: Dict[str, float] = {}
        self.ok = False
        self.syncs: list = []
        self.h2d = 0

    def __enter__(self) -> "Dispatch":
        Span.__enter__(self)
        self.root = self
        return self

    def _account(self, dt: float, parent: Optional[Span]) -> None:
        if self.q is None:
            return
        if self.ok:
            self.parts["runtime.dispatch_self"] = self.self_s
            _querylog.task(self.q, self.t0, dt, self.parts)
        else:
            _querylog.add(self.q, "task.requeue", dt, dt)
        if self.syncs:
            _querylog.sync(self.q, self.syncs,
                           "sync.in_dispatch" if self.ok else None)
        if self.h2d:
            _querylog.h2d(self.q, self.h2d)


dispatch = Dispatch


class _Offthread(Span):
    __slots__ = ()

    def _account(self, dt: float, parent: Optional[Span]) -> None:
        pass  # a carrier of q for what closes inside it, not a region


def offthread(q: Optional[str]) -> Span:
    """``with offthread(qid): ...`` on a helper thread (prefetch pool,
    emitter, spill writer): spans closed inside carry ``q`` and
    ``p="offthread"``, and the record sums them under ``offthread.<name>``,
    outside the partition of the dispatches."""
    return _Offthread("offthread", q, ring=False)


def current_task() -> Dict[str, str]:
    """{"q": query id, "task": dispatch label} of what the calling thread
    is inside, with the keys that are known: what an event recorded from
    under a dispatch says about who asked."""
    top = getattr(_TLS, "top", None)
    if top is None:
        return {}
    out = {}
    if top.q is not None:
        out["q"] = top.q
    if top.root is not None:
        out["task"] = top.root.label
    return out


def stats() -> Dict[str, Dict[str, float]]:
    """Structured snapshot: name -> {count, total_s}."""
    with _lock:
        return {name: {"count": n, "total_s": round(total, 6)}
                for name, (n, total) in _stats.items()}


def summary() -> str:
    with _lock:
        rows = sorted(_stats.items(), key=lambda kv: -kv[1][1])
    lines = [f"{'span':<28}{'count':>8}{'total_s':>10}{'avg_ms':>10}"]
    for name, (n, total) in rows:
        lines.append(f"{name:<28}{n:>8}{total:>10.3f}{total / max(n,1) * 1e3:>10.2f}")
    return "\n".join(lines)


def reset():
    with _lock:
        _stats.clear()
