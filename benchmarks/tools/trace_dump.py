#!/usr/bin/env python3
"""The builder's look at one trace by hand: every plane and line of an
``.xplane.pb`` with its event count and some names, and the reduction's
plain form trimmed to a test fixture.

    python3 benchmarks/tools/trace_dump.py <trace_dir> <out_dir> [max_events]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace  # noqa: E402


def main(trace_dir, out_dir, max_events=4000):
    from jax.profiler import ProfileData

    path = trace.find_xplane(trace_dir)
    os.makedirs(out_dir, exist_ok=True)
    lines = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            lines.append(f"  line {line.name!r}: {len(events)} events; "
                         f"top by time {top}")
    planes = trace.load(path)
    lines.append(f"reduce: {json.dumps(trace.reduce(planes))}")
    with open(os.path.join(out_dir, "trace_summary.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    # a fixture: one span of the trace, the same for every line, as long as
    # max_events device ops allow, opening at the second annotation
    starts = sorted(s for _, s, _ in planes["host"])
    t0 = starts[min(1, len(starts) - 1)] if starts else 0
    ops = sorted(s for ls in planes["device"].values()
                 for _, s, _ in ls.get(trace.OPS_LINE, []) if s >= t0)
    t1 = ops[min(int(max_events), len(ops) - 1)] if ops else t0

    def cut(events):
        return [e for e in events if e[1] < t1 and e[1] + e[2] > t0]

    small = {"device": {p: {ln: cut(ev) for ln, ev in ls.items()}
                        for p, ls in planes["device"].items()},
             "host": cut(planes["host"]), "span_ns": [t0, t1]}
    with open(os.path.join(out_dir, "trace_small.json"), "w") as f:
        json.dump(small, f)


if __name__ == "__main__":
    main(*sys.argv[1:])
