"""From a ``jax.profiler`` trace (``.xplane.pb``) to the device's busy time,
its time per XLA module and its longest idle gaps, all inside the traced
span: the profiler records from ``start_trace`` to ``stop_trace``, the span
is what the benchmark's clock measured between them, and an operation
outside it (or the part of one that hangs over an edge) counts nowhere.

``load`` keeps what the reduction needs in a small plain form (so a trimmed
recorded trace is a JSON test fixture); ``reduce`` is arithmetic on that.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
ANCHOR = "bench.anchor"  # opened right after start_trace: ties the clocks


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(xplane_path: str) -> dict:
    """{"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}: every line of each TPU plane,
    and the benchmark's own annotations from the host's threads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            out["device"][plane.name] = {
                line.name: [[e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX))
    return out


def _anchor_ns(planes: dict):
    starts = [s for name, s, _ in planes["host"] if name == ANCHOR]
    return starts[0] if starts else None


def span_ns(planes: dict, seconds: float):
    """The traced span on the trace's clock: from the anchor annotation
    (opened when the benchmark read its clock for the span's start) for
    ``seconds``.  None where the trace holds no anchor."""
    anchor = _anchor_ns(planes)
    return None if anchor is None else (anchor, anchor + int(seconds * 1e9))


def with_requests(planes: dict, log, anchor_s: float) -> dict:
    """The same planes with the host's part rebuilt from the completion log
    (``time.perf_counter`` seconds; ``anchor_s`` is when the anchor opened):
    one ``bench.submit`` and one ``bench.to_df`` span per request, whole, on
    the trace's clock.  (Annotations in the clients' loop would not do: the
    profiler drops one that opened before the trace did, which a long
    request's ``to_df`` always has.)"""
    anchor = _anchor_ns(planes)
    if anchor is None:
        return planes

    def to_ns(t):
        return anchor + int((t - anchor_s) * 1e9)

    host = [[ANCHOR, anchor, 0]]
    for r in log:
        if r.t_submitted:
            host.append(["bench.submit", to_ns(r.t_submit),
                         int((r.t_submitted - r.t_submit) * 1e9)])
            host.append(["bench.to_df", to_ns(r.t_submitted),
                         int((r.t_end - r.t_submitted) * 1e9)])
    return dict(planes, host=host)


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _module_name(event_name: str) -> str:
    # "jit__compact_idx(1234567890)" -> "jit__compact_idx"
    return re.sub(r"\(\d+\)$", "", event_name)


def _clip(events, span):
    """[name, start, end] of each event's part inside ``span``."""
    lo, hi = span if span else (float("-inf"), float("inf"))
    return [[name, max(s, lo), min(s + d, hi)] for name, s, d in events
            if min(s + d, hi) > max(s, lo)]


def reduce(planes: dict, span=None, top: int = 10):
    """None where the span holds no device operation; else
    {"busy_s": mean over the chips of the union of device-op intervals,
     "device_ops": [[module, seconds], ...] (top ``top``, summed over chips),
     "idle_gaps": [[what the host's annotations say, seconds], ...] (the
     ``top`` longest gaps between device ops on the first chip)}.
    ``span`` (start, end on the trace's clock, from ``span_ns``) cuts every
    event to it and makes its edges the ends of the first and last gap;
    without it the whole file counts."""
    busy, per_module, first_busy = [], {}, None
    for name in sorted(planes["device"]):
        lines = planes["device"][name]
        ops = _clip(lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [],
                    span)
        if not ops:
            continue
        merged = union([s, e] for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        modules = _clip(lines.get(MODULES_LINE) or [], span) or ops
        for ev, s, e in modules:
            key = _module_name(ev)
            per_module[key] = per_module.get(key, 0) + e - s
    if not busy or sum(busy) == 0:
        return None
    if span:
        first_busy = [[span[0], span[0]]] + first_busy + [[span[1], span[1]]]
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(first_busy, first_busy[1:])
                   if b[0] > a[1]), reverse=True)[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            per_module.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(planes["host"], s, e), length / 1e9]
                      for length, s, e in gaps],
    }


def _label(host_events, start, end) -> str:
    """Which of the benchmark's annotations were open during [start, end):
    ``to_df*3+submit*1``, or ``none``.  Spans inside the program are a later
    (tracing) PR's."""
    counts = {}
    for name, s, d in host_events:
        if name != ANCHOR and s < end and s + d > start:
            key = name[len(ANNOTATION_PREFIX):]
            counts[key] = counts.get(key, 0) + 1
    return "+".join(f"{k}*{v}" for k, v in sorted(counts.items())) or "none"
