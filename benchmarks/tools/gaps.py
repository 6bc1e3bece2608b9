#!/usr/bin/env python3
"""What the host was doing while the device sat idle, over a whole traced
span and not its ten longest gaps.

    python3 benchmarks/tools/gaps.py <trace dir> [--seconds S] [--json OUT]

Reads a ``jax.profiler`` trace of the program (the benchmark's, kept with
``BENCH_KEEP_TRACE=<dir>``, or any other).  While a trace runs, every span
of the program is a ``qk.<name>`` annotation on the thread that ran it
(quokka_tpu/obs/spans.py), on the trace's own clock beside the device's
ops.  Printed:

- the device's idle seconds by **activity**: during each gap between
  device ops, the innermost ``qk.*`` annotation open on each host thread.
  A parked pool thread (``svc.park``) counts only where nothing else is
  open anywhere ("every worker parked"); otherwise the instant is split
  evenly between the threads that are inside something.  ``task:exec``
  and the like are a dispatch's own time, outside every span nested in it;
  ``svc.loop`` is a pool thread's turn outside its children; ``none``:
  no annotation open on any thread;
- the gaps by length, with the activity that holds most of each class;
- program executions per XLA module and per finished query (the span's
  ``qk.svc.finalize`` annotations count the queries).

The span is the whole trace, or from the benchmark's anchor annotation for
``--seconds`` where given.  A trace with no TPU plane (one made on the CPU)
gives the threads' own seconds by activity and no idle table.
"""

import argparse
import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace  # noqa: E402

PREFIX = "qk."
PARK = "svc.park"
RENAMED = {"svc.quantum": "svc.loop"}  # a turn's self time is the loop's
GAP_CLASSES_MS = (0.1, 1.0, 3.0, 4.5, 6.5, 20.0)


def load(xplane_path: str) -> dict:
    """{"ops": [[start, end]], "modules": [[name, start, end]] of the first
    TPU plane, "threads": [[[name, start, end], ...] per host line that holds
    qk.* annotations], "anchor": start_ns or None}."""
    from jax.profiler import ProfileData

    out = {"ops": [], "modules": [], "threads": [], "anchor": None}
    data = ProfileData.from_file(xplane_path)
    device_done = False
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            if device_done:
                continue  # the first chip, as harness/trace.py's gaps
            device_done = True
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    out["ops"] = [[int(e.start_ns),
                                   int(e.start_ns + e.duration_ns)]
                                  for e in line.events]
                elif line.name == trace.MODULES_LINE:
                    out["modules"] = [
                        [trace._module_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        mine.append([e.name[len(PREFIX):], int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)])
                    elif e.name == trace.ANCHOR and out["anchor"] is None:
                        out["anchor"] = int(e.start_ns)
                if mine:
                    out["threads"].append(mine)
    return out


def innermost(events):
    """One thread's nested [name, start, end] annotations as disjoint
    [start, end, name] pieces, each named by the innermost annotation open
    in it, in time order."""
    pieces = []

    def emit(start, end, name):
        if end > start:
            pieces.append([start, end, RENAMED.get(name, name)])

    stack = []  # [name, end, cursor]: cursor = where its own time resumes
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            emit(top[2], top[1], top[0])
        if stack:
            top = stack[-1]
            end = min(end, top[1])  # a child never outlives its parent
            emit(top[2], start, top[0])
            top[2] = max(top[2], end)
        stack.append([name, end, start])
    while stack:
        top = stack.pop()
        emit(top[2], top[1], top[0])
    pieces.sort()
    return pieces


def gaps_of(ops, span):
    """The idle [start, end) intervals of the span, and the busy ns."""
    lo, hi = span
    busy = trace.union([max(s, lo), min(e, hi)] for s, e in ops
                       if min(e, hi) > max(s, lo))
    edges = [[lo, lo]] + busy + [[hi, hi]]
    gaps = [[a[1], b[0]] for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    return gaps, sum(e - s for s, e in busy)


def attribute(gaps, threads):
    """{activity: idle ns} over all gaps, and per gap its (length, the
    activity holding most of it)."""
    starts = [[p[0] for p in pieces] for pieces in threads]
    total, per_gap = {}, []
    for lo, hi in gaps:
        cuts, live = {lo, hi}, []
        for pieces, st in zip(threads, starts):
            i = max(0, bisect.bisect_right(st, lo) - 1)
            while i < len(pieces) and pieces[i][0] < hi:
                s, e, name = pieces[i]
                if e > lo:
                    live.append((max(s, lo), min(e, hi), name))
                    cuts.update((max(s, lo), min(e, hi)))
                i += 1
        cuts = sorted(cuts)
        mine = {}
        for a, b in zip(cuts, cuts[1:]):
            names = [n for s, e, n in live if s <= a and e >= b]
            active = [n for n in names if n != PARK]
            if active:
                for n in active:
                    mine[n] = mine.get(n, 0.0) + (b - a) / len(active)
            else:
                key = "every worker parked" if names else "none"
                mine[key] = mine.get(key, 0.0) + (b - a)
        for k, v in mine.items():
            total[k] = total.get(k, 0.0) + v
        per_gap.append((hi - lo, max(mine, key=mine.get) if mine else "none"))
    return total, per_gap


def reduce(planes: dict, seconds=None) -> dict:
    every = ([t for s, e in planes["ops"] for t in (s, e)]
             + [t for th in planes["threads"] for _, s, e in th
                for t in (s, e)])
    if not every:
        return {"span_s": 0.0, "threads": 0}
    lo = planes["anchor"] if planes["anchor"] is not None else min(every)
    hi = lo + int(seconds * 1e9) if seconds else max(every)
    threads = [innermost(th) for th in planes["threads"]]
    out = {"span_s": (hi - lo) / 1e9, "threads": len(threads)}
    own = {}
    for pieces in threads:
        for s, e, name in pieces:
            part = min(e, hi) - max(s, lo)
            if part > 0:
                own[name] = own.get(name, 0.0) + part / 1e9
    out["thread_seconds"] = dict(sorted(own.items(), key=lambda kv: -kv[1]))
    finished = sum(1 for th in planes["threads"] for name, s, e in th
                   if name == "svc.finalize" and lo <= e <= hi)
    out["queries_finished"] = finished
    if not planes["ops"]:
        return out
    gaps, busy = gaps_of(planes["ops"], (lo, hi))
    total, per_gap = attribute(gaps, threads)
    idle = sum(e - s for s, e in gaps)
    out.update(busy_s=busy / 1e9, idle_s=idle / 1e9, gaps=len(gaps))
    out["idle_by_activity"] = [
        [k, v / 1e9, v / idle if idle else 0.0]
        for k, v in sorted(total.items(), key=lambda kv: -kv[1])]
    classes = []
    bounds = [0.0] + [ms * 1e6 for ms in GAP_CLASSES_MS] + [float("inf")]
    for a, b in zip(bounds, bounds[1:]):
        inside = [(n, who) for n, who in per_gap if a <= n < b]
        if not inside:
            continue
        by = {}
        for n, who in inside:
            by[who] = by.get(who, 0.0) + n
        top = max(by, key=by.get)
        classes.append({"from_ms": a / 1e6, "to_ms": b / 1e6,
                        "gaps": len(inside),
                        "seconds": sum(n for n, _ in inside) / 1e9,
                        "mostly": top,
                        "mostly_share": by[top] / sum(by.values())})
    out["gap_classes"] = classes
    launches = {}
    for name, s, e in planes["modules"]:
        if s >= lo and s < hi:
            launches[name] = launches.get(name, 0) + 1
    per = max(finished, 1)
    out["launches"] = [[k, n, n / per] for k, n in
                       sorted(launches.items(), key=lambda kv: -kv[1])]
    out["launches_per_query"] = sum(launches.values()) / per
    return out


def render(r: dict) -> str:
    lines = [f"span {r['span_s']:.3f} s, {r['threads']} host thread(s) with "
             f"qk.* annotations, {r.get('queries_finished', 0)} "
             f"query(ies) finished in it"]
    if "idle_s" in r:
        lines.append(f"device busy {r['busy_s']:.3f} s, idle {r['idle_s']:.3f}"
                     f" s in {r['gaps']} gaps")
        lines.append(f"{'idle by host activity':<36}{'seconds':>10}"
                     f"{'share':>8}")
        for name, s, share in r["idle_by_activity"]:
            lines.append(f"  {name:<34}{s:>10.4f}{100 * share:>7.1f}%")
        lines.append("gaps by length:")
        for c in r["gap_classes"]:
            hi = "inf" if c["to_ms"] == float("inf") else f"{c['to_ms']:g}"
            lines.append(
                f"  {c['from_ms']:g}-{hi} ms: {c['gaps']} gaps, "
                f"{c['seconds']:.4f} s, mostly {c['mostly']} "
                f"({100 * c['mostly_share']:.0f}%)")
        lines.append(f"program executions per finished query: "
                     f"{r['launches_per_query']:.1f}")
        for name, n, per in r["launches"][:15]:
            lines.append(f"  {name:<40}{n:>8}{per:>9.1f}")
    else:
        lines.append("no TPU plane in this trace: host threads only")
    lines.append("host threads' own seconds by activity (all threads):")
    for name, s in list(r.get("thread_seconds", {}).items())[:25]:
        lines.append(f"  {name:<34}{s:>10.4f}")
    return "\n".join(lines)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--seconds", type=float, default=None,
                    help="the span's length from the anchor annotation")
    ap.add_argument("--json", default=None, help="also write the numbers")
    args = ap.parse_args(argv)
    r = reduce(load(trace.find_xplane(args.trace_dir)), args.seconds)
    print(render(r))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(r, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
