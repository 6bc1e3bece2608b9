"""Observability layer: flight recorder, spans, metrics, timeline merger.

One import surface for every runtime component:

- ``recorder`` / ``RECORDER``: per-process lock-light ring buffer of
  timestamped events (task begin/end, batch push/pull, compile, cache
  hit/miss, heartbeats, state transitions).  Always on by default — it is
  the forensic record the stall detector dumps when a run wedges — and
  cheap enough to leave on (a tuple store per event, no locks on the hot
  path).  ``QK_TRACE_EVENTS=0`` disables it outright.
- ``spans``: the span API (``QUOKKA_TRACE=1`` aggregate summary, the role
  utils/tracing.py used to play) — spans know their query and their parent,
  land in the flight recorder as duration events with ``q``/``p``, and are
  ``qk.<name>`` annotations in a running ``jax.profiler`` trace.
- ``querylog``: one flat record per finished query (stamps, self seconds by
  layer, counts), kept in a bounded process-wide deque: the service's query
  log, and what the benchmark's per-layer metrics read.
- ``metrics``: typed counters/gauges plus the engine's per-channel task
  accounting (folded out of runtime/engine.py).
- ``merge``: coordinator-side merger — assembles per-worker event streams
  into one ordered timeline, exports Chrome trace-event JSON (loadable in
  Perfetto: ui.perfetto.dev -> Open trace file) and renders human-readable
  stall reports naming the stuck worker and its in-flight task.

Clocks: a ring event's ``ts`` is ``time.time()`` at the event's END (so
streams from different processes merge on one axis) and its ``dur`` a
``time.perf_counter()`` difference; a query record's stamps and seconds are
all ``time.perf_counter()`` (``wall_done`` alone is ``time.time()``); a
``qk.*`` annotation is on the profiler trace's own clock.

Env vars (the full table is in README "Observability"):

- ``QK_TRACE_EVENTS``: unset/1 -> recorder on; ``0`` -> recorder off; a
  path (or ``1`` for ``quokka_trace.json``) -> ALSO export the merged
  Chrome trace at run end.
- ``QK_DUMP_DIR``: where stall dumps land (default
  ``<tmp>/quokka_tpu_dumps``).
- ``QUOKKA_TRACE=1``: aggregate span totals into ``spans.stats()``.
- ``QK_COORD_TIMEOUT``: coordinator run timeout seconds (default 600).
- ``QK_CHAOS``: seeded fault-injection spec (quokka_tpu/chaos).  Every
  injected fault lands here as a ``chaos.*`` event, every checksum
  rejection as ``integrity.corrupt``, and every recovery escalation as a
  ``recover.*`` event — a chaos soak is triaged from the same merged
  timeline as a production stall.
"""

from __future__ import annotations

import contextlib
import sys

from quokka_tpu.obs import (
    alerts,
    critpath,
    explain,
    export,
    history,
    memplane,
    merge,
    metrics,
    opstats,
    progress,
    querylog,
    recorder,
    spans,
)
from quokka_tpu.obs.opstats import OPSTATS
from quokka_tpu.obs.merge import (
    dump_flight,
    merge_streams,
    stall_report,
    to_chrome_trace,
    write_chrome_trace,
)
from quokka_tpu.obs.metrics import (
    REGISTRY,
    Counter,
    EngineMetrics,
    Gauge,
    Histogram,
)
from quokka_tpu.obs.recorder import (
    RECORDER,
    FlightRecorder,
    recorder_enabled,
    trace_export_path,
)
from quokka_tpu.obs.spans import span, summary

_RPC_SLOW_S = 0.005


def diag(msg: str) -> None:
    """The sanctioned diagnostic logger for library code (lint rule QK007
    bans bare ``print`` outside CLI entry points): one line to stderr,
    flushed, plus a ``diag`` event in the flight recorder so the message
    shows up in merged timelines next to what the process was doing."""
    line = msg.rstrip("\n")
    RECORDER.record("diag", line[:200])
    # a closed stderr (daemonized worker) must not kill the caller
    with contextlib.suppress(OSError, ValueError):
        sys.stderr.write(line + "\n")
        sys.stderr.flush()


def rpc_event(method: str, dur: float) -> None:
    """Account one client-side RPC: always a counter + latency-histogram
    observation, an event only when it was slow (every store op would
    otherwise flood the ring and evict the task-level events a stall dump
    needs)."""
    REGISTRY.counter(f"rpc.{method}").inc()
    REGISTRY.histogram("rpc.latency_s").observe(dur)
    if dur > _RPC_SLOW_S:
        RECORDER.record("rpc", method, dur=dur)
