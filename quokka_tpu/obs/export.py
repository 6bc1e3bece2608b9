"""Prometheus text exposition + the /metrics //status HTTP sidecar.

Renders the typed Registry (obs/metrics.py) in Prometheus text format
(version 0.0.4) and serves it from a stdlib-only background HTTP server so
an external scraper can watch a soak or a long-lived QueryService run from
outside the process:

    GET /metrics   Prometheus text: counters, gauges, histograms
    GET /status    JSON: live QueryService.stats() (when a service is
                   attached), process info, recorder drop counter.
                   ``?format=json`` is an explicit alias (the machine
                   contract a router scrapes); ``?format=text`` renders a
                   human-readable summary instead
    GET /history   JSON: the bounded metrics-history ring (obs/history.py)
                   with derived per-counter rates
    GET /health    JSON: the alert engine's ok/degraded/critical verdict
                   plus the firing rules (obs/alerts.py)

``QK_METRICS_PORT`` opts in: QueryService starts a sidecar on that port at
construction and stops it at shutdown (port ``0`` binds an ephemeral port,
readable from ``server.port`` — what tests use).  No third-party
dependency: the container has no prometheus_client, and the text format is
ten lines of escaping rules.

Naming: dotted instrument names sanitize to ``quokka_<name>`` metric
families.  Per-query/per-site instrument families (``task.latency_s.<qid>``,
``cache.plan_hit.<qid>``, ``rpc.<method>``, ``chaos.<site>``) render as ONE
family with a label instead of one family per query — the cardinality lives
in label values, where Prometheus expects it.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from quokka_tpu.obs import recorder as _recorder
from quokka_tpu.obs.metrics import REGISTRY, Histogram, Registry

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# (kind, dotted-prefix, family, label_key).  A name matches when it is the
# right instrument kind and extends the prefix with a NON-EMPTY suffix; the
# suffix becomes the label value, so per-query/per-site instruments render
# as ONE family with a label instead of unbounded family names.
# INVARIANT: when the runtime also keeps an unlabeled AGGREGATE instrument
# of a labeled family (observing every event into both), the aggregate
# needs its own _EXACT_FAMILIES name below — sharing the labeled family
# would double-count under sum()-style PromQL.
_LABEL_FAMILIES: Tuple[Tuple[str, str, str, str], ...] = (
    ("histogram", "task.latency_s.", "quokka_task_latency_seconds", "query"),
    ("counter", "cache.plan_hit.", "quokka_cache_plan_hit", "query"),
    ("counter", "cache.plan_miss.", "quokka_cache_plan_miss", "query"),
    ("counter", "chaos.", "quokka_chaos_injected", "site"),
    ("counter", "rpc.", "quokka_rpc_calls", "method"),
    # compile plane (runtime/compileplane.py): per-query twins of the
    # cache-hit/miss/prewarm-hit event counters
    ("counter", "compile.cache_hit.", "quokka_compile_cache_hit", "query"),
    ("counter", "compile.miss.", "quokka_compile_miss", "query"),
    ("counter", "compile.prewarm_hit.", "quokka_compile_prewarm_hit",
     "query"),
    # streaming plane (quokka_tpu/streaming/): standing-query pane/late
    # counters + watermark-staleness gauge, per-query twins GC'd with the
    # namespace exactly like the shuffle/compile families
    ("counter", "stream.panes.", "quokka_stream_panes", "query"),
    ("counter", "stream.late_dropped.", "quokka_stream_late_dropped",
     "query"),
    ("gauge", "stream.watermark_lag_s.", "quokka_stream_watermark_lag_seconds",
     "query"),
    # memory plane (obs/memplane.py): per-query footprint gauges GC'd with
    # the namespace, plus per-site-class residency
    ("gauge", "mem.live_bytes.", "quokka_mem_live_bytes", "query"),
    ("gauge", "mem.peak_bytes.", "quokka_mem_peak_bytes", "query"),
    ("gauge", "mem.spill_resident_bytes.", "quokka_mem_spill_resident_bytes",
     "query"),
    ("gauge", "mem.site_bytes.", "quokka_mem_site_bytes", "site"),
    # EXPLAIN ANALYZE plane (obs/opstats.py): per-query operator-row
    # gauges and per-exchange-edge skew ratios ("<qid>.a<src>-a<tgt>"),
    # created at snapshot time and GC'd in opstats.on_query_gc
    ("gauge", "opstats.rows_in.", "quokka_opstats_rows_in", "query"),
    ("gauge", "opstats.rows_out.", "quokka_opstats_rows_out", "query"),
    ("gauge", "shuffle.skew.", "quokka_shuffle_skew_ratio", "edge"),
    # per-query twins of the shuffle byte/sync counters (engine.py GCs the
    # instruments with the namespace; the label keeps the family bounded)
    ("counter", "shuffle.bytes.", "quokka_shuffle_bytes_by_query", "query"),
    ("counter", "shuffle.host_syncs.", "quokka_shuffle_host_syncs_by_query",
     "query"),
    # health plane (obs/progress.py + obs/alerts.py): per-query progress
    # gauges GC'd with the query, per-rule alert-fired counters
    ("gauge", "progress.fraction.", "quokka_progress_fraction", "query"),
    ("gauge", "progress.eta_s.", "quokka_progress_eta_seconds", "query"),
    ("counter", "alert.", "quokka_alerts_fired", "rule"),
)

# Aggregate instruments that ALSO exist as a labeled per-query family: the
# engine observes every dispatch into both 'task.latency_s' and
# 'task.latency_s.<qid>' (same for cache.plan_hit/miss).  The aggregate
# must NOT share the labeled family's name, or sum()-style PromQL over the
# family double-counts every observation.
_EXACT_FAMILIES: Dict[Tuple[str, str], str] = {
    ("histogram", "task.latency_s"): "quokka_task_latency_all_seconds",
    ("counter", "cache.plan_hit"): "quokka_cache_plan_hit_all",
    ("counter", "cache.plan_miss"): "quokka_cache_plan_miss_all",
    ("counter", "compile.cache_hit"): "quokka_compile_cache_hit_all",
    ("counter", "compile.miss"): "quokka_compile_miss_all",
    ("counter", "compile.prewarm_hit"): "quokka_compile_prewarm_hit_all",
    ("counter", "stream.panes"): "quokka_stream_panes_all",
    ("counter", "stream.late_dropped"): "quokka_stream_late_dropped_all",
    ("gauge", "stream.watermark_lag_s"):
        "quokka_stream_watermark_lag_all_seconds",
    ("gauge", "mem.live_bytes"): "quokka_mem_live_bytes_all",
    ("gauge", "mem.peak_bytes"): "quokka_mem_peak_bytes_all",
    ("gauge", "mem.spill_resident_bytes"):
        "quokka_mem_spill_resident_bytes_all",
    # worst skew ratio observed process-wide (per-edge twins carry the
    # labeled family above)
    ("gauge", "shuffle.skew"): "quokka_shuffle_skew_ratio_max",
    ("counter", "opstats.size_hint_drift_bytes"):
        "quokka_opstats_size_hint_drift_bytes",
}


def _sanitize(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch in "_:" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def escape_label_value(v: str) -> str:
    """Prometheus label-value escaping: backslash, double quote, newline."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _family(name: str, kind: str) -> Tuple[str, Optional[str]]:
    """(family_name, label_or_None) for one instrument name."""
    exact = _EXACT_FAMILIES.get((kind, name))
    if exact is not None:
        return exact, None
    # strategy.<op>.<choice> (ops/strategy.note_used): two label dimensions,
    # so the kernel-strategy matrix reads as ONE family —
    # quokka_kernel_strategy_used_total{op="asof",choice="searchsorted"}
    if kind == "counter" and name.startswith("strategy."):
        rest = name[len("strategy."):]
        op, _, choice_ = rest.partition(".")
        if op and choice_:
            return ("quokka_kernel_strategy_used",
                    f'op="{escape_label_value(op)}",'
                    f'choice="{escape_label_value(choice_)}"')
    for want_kind, prefix, fam, key in _LABEL_FAMILIES:
        if (kind == want_kind and name.startswith(prefix)
                and len(name) > len(prefix)):
            val = name[len(prefix):]
            return fam, f'{key}="{escape_label_value(val)}"'
    if kind == "histogram" and name.endswith("_s"):
        # seconds-suffix convention: task.latency_s -> ..._latency_seconds
        return "quokka_" + _sanitize(name[:-2]) + "_seconds", None
    return "quokka_" + _sanitize(name), None


def _fmt(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def render(registry: Registry = None,
           extra_gauges: Optional[Dict[str, float]] = None) -> str:
    """The /metrics payload.  ``extra_gauges`` lets callers append
    process-level facts (recorder drops, uptime) without registering
    instruments."""
    registry = REGISTRY if registry is None else registry
    lines: List[str] = []
    typed: Dict[str, str] = {}   # family -> TYPE already emitted

    def emit(family: str, kind: str, label: Optional[str], value,
             suffix: str = "", extra_label: str = "") -> None:
        if typed.get(family) != kind:
            lines.append(f"# TYPE {family} {kind}")
            typed[family] = kind
        labels = ",".join(x for x in (label, extra_label) if x)
        body = "{" + labels + "}" if labels else ""
        lines.append(f"{family}{suffix}{body} {_fmt(value)}")

    with registry._lock:
        counters = {n: c.value for n, c in registry._counters.items()}
        gauges = {n: g.value for n, g in registry._gauges.items()}
        histograms = dict(registry._histograms)
    for name in sorted(counters):
        fam, label = _family(name, "counter")
        emit(fam + "_total", "counter", label, counters[name])
    for name in sorted(gauges):
        fam, label = _family(name, "gauge")
        emit(fam, "gauge", label, gauges[name])
    for name in sorted(histograms):
        h: Histogram = histograms[name]
        fam, label = _family(name, "histogram")
        # one atomic snapshot: bucket{+Inf} == _count must hold per scrape
        cum, h_sum, h_count = h.snapshot()
        for bound, acc in cum:
            emit(fam, "histogram", label, acc, suffix="_bucket",
                 extra_label=f'le="{_fmt(bound)}"')
        emit(fam, "histogram", label, h_sum, suffix="_sum")
        emit(fam, "histogram", label, h_count, suffix="_count")
    for name in sorted(extra_gauges or {}):
        emit("quokka_" + _sanitize(name), "gauge", None, extra_gauges[name])
    return "\n".join(lines) + "\n"


class MetricsServer:
    """Background stdlib HTTP sidecar serving /metrics and /status.

    ``service`` (a QueryService) is optional; without one, /status reports
    process-level info only.  ``port=0`` binds an ephemeral port (read it
    back from ``self.port``)."""

    def __init__(self, port: Optional[int] = None, host: str = "127.0.0.1",
                 service=None, registry: Registry = None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        if port is None:
            port = int(os.environ.get("QK_METRICS_PORT", "0"))
        self.service = service
        self.registry = REGISTRY if registry is None else registry
        self._started = time.time()
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # scrapes are not diagnostics
                pass

            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                params = dict(
                    kv.partition("=")[::2] for kv in query.split("&") if kv)
                try:
                    if path == "/metrics":
                        self._send(200, outer.metrics_text().encode(),
                                   CONTENT_TYPE)
                    elif path == "/status":
                        # JSON is (and stays) the default; ?format=json is
                        # the explicit machine-contract spelling, text the
                        # human one
                        if params.get("format") == "text":
                            self._send(200, outer.status_text().encode(),
                                       "text/plain; charset=utf-8")
                        else:
                            self._send(200,
                                       json.dumps(outer.status(),
                                                  default=repr).encode(),
                                       "application/json")
                    elif path == "/history":
                        from quokka_tpu.obs import history

                        self._send(200,
                                   json.dumps(history.RING.payload(),
                                              default=repr).encode(),
                                   "application/json")
                    elif path == "/health":
                        from quokka_tpu.obs import alerts

                        self._send(200,
                                   json.dumps(alerts.ENGINE.health(),
                                              default=repr).encode(),
                                   "application/json")
                    else:
                        self._send(404, b"not found: try /metrics, "
                                        b"/status, /history or /health\n",
                                   "text/plain")
                except Exception as e:  # noqa: BLE001 — a scrape must not
                    # take the serving thread down with it; if even the
                    # 500 cannot be sent the scraper already hung up
                    with contextlib.suppress(OSError):
                        self._send(500, repr(e).encode(), "text/plain")

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name=f"qk-metrics-{self.port}")
        self._thread.start()

    # -- payloads -----------------------------------------------------------
    def metrics_text(self) -> str:
        return render(self.registry, extra_gauges={
            "obs_dropped_events": _recorder.RECORDER.dropped_total,
            "uptime_seconds": round(time.time() - self._started, 3),
        })

    def status(self) -> Dict:
        snap = self.registry.snapshot()
        out = {
            "pid": os.getpid(),
            "time": time.time(),
            "uptime_s": round(time.time() - self._started, 3),
            "obs": {
                "recorder_enabled": _recorder.RECORDER.enabled,
                "dropped_events": _recorder.RECORDER.dropped_total,
                "dropped_by_type": _recorder.RECORDER.dropped,
                "sampled_by_type": _recorder.RECORDER.sampled,
                "ring_capacity": _recorder.RECORDER.capacity,
            },
            # the counters an operator triages incidents from
            "integrity_corrupt": snap.get("integrity.corrupt", 0),
            "chaos": {k.split(".", 1)[1]: v for k, v in snap.items()
                      if k.startswith("chaos.")},
        }
        svc = self.service
        if svc is not None:
            try:
                out["service"] = svc.stats()
            except Exception as e:  # noqa: BLE001 — a torn-down service
                out["service"] = {"error": repr(e)}  # must not 500 /status
        return out

    def status_text(self) -> str:
        """Human-readable /status?format=text render of the same dict the
        JSON twin serves — a terminal-width summary, not a new contract."""
        st = self.status()
        from quokka_tpu.obs import alerts

        health = alerts.ENGINE.health()
        lines = [
            f"quokka pid={st['pid']} uptime={st['uptime_s']:.1f}s "
            f"health={health['status']}",
        ]
        for f in health["firing"]:
            lines.append(f"  ALERT [{f['severity']}] {f['rule']}: "
                         f"{f['message']}")
        svc = st.get("service")
        if isinstance(svc, dict) and "error" not in svc:
            lines.append(
                f"service: pool={svc.get('pool_size')} "
                f"alive={svc.get('workers_alive')} "
                f"finished={svc.get('finished')}")
            for qid, row in sorted(svc.get("sessions", {}).items()):
                frac = row.get("progress")
                eta = row.get("eta_s")
                prog = (f" {frac:.0%}" if isinstance(frac, float) else "")
                prog += (f" eta={eta:.1f}s" if isinstance(eta, float)
                         else "")
                lines.append(f"  {qid} [{row.get('status')}]{prog}")
        if st.get("integrity_corrupt"):
            lines.append(f"integrity.corrupt={st['integrity_corrupt']}")
        return "\n".join(lines) + "\n"

    def url(self, path: str = "/metrics") -> str:
        return f"http://{self.host}:{self.port}{path}"

    def close(self) -> None:
        # double-close / already-dead socket is a no-op, not an error
        with contextlib.suppress(OSError):
            self._httpd.shutdown()
            self._httpd.server_close()

    stop = close


def start_from_env(service=None) -> Optional[MetricsServer]:
    """Start a sidecar when ``QK_METRICS_PORT`` is set (any value,
    including ``0`` for an ephemeral port); None when unset."""
    port = os.environ.get("QK_METRICS_PORT")
    if port is None or port.strip() == "":
        return None
    try:
        return MetricsServer(port=int(port), service=service)
    except (OSError, ValueError) as e:
        from quokka_tpu import obs

        obs.diag(f"[metrics] sidecar on QK_METRICS_PORT={port!r} failed "
                 f"to start: {e!r}")
        return None
