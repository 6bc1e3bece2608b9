#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's tables from ``--seed`` and draws the run's parameter sets
from it, starts a ``QueryService``, warms the cell's own traffic until a
whole pass compiles nothing, drives the closed loop for ``--seconds``, then
holds every answer of the window to the plain reference for its parameters.  The last line of stdout is the result object; the numbers
compared, each beside its limit, are the last lines of stderr.

``--rehearse`` (never given by the driver) lets the same run go wherever jax
lands, at the small size the configuration's file gives for it: the CPU
rehearsal and the tests.  Its result names the platform jax reported.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))  # the checkout: quokka_tpu

from harness import check, loadgen, peaks, spec, tables, trace  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
TRACE_DIR = os.path.join(CACHE, "trace")
WARMUP_MAX_PASSES = 8  # a pass: every (query, parameters) of the run once
TRACE_AFTER_S = 1.0  # the traced span opens this long into the window ...
TRACE_SPAN_S = 5.0   # ... and lasts this long, in every cell


def say(*a):
    print("bench:", *a, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="no TPU required, the configuration's small size")
    return ap.parse_args(argv)


def device_or_exit(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        say(f"FAIL: this cell needs {chips} TPU chip(s); jax reports "
            f"{len(devs)} x {devs[0].platform}")
        sys.exit(3)
    return devs


class Run:
    """What the metric readers read: the window's completion log, the
    compile counts around it, the reduced trace (traced runs) and the
    cell's least bytes and peaks."""

    def __init__(self):
        self.log = []
        self.setup_s = None
        self.compiles_before = self.compiles_after = None
        self.trace = None          # trace.reduce(...) of the traced span
        self.trace_span = None     # (t0, t1) of it on time.perf_counter
        self.least_bytes = {}      # query -> bytes
        self.parameter_sets = {}   # query -> the parameter sets of this run
        self.peaks = None


def serve(cell, args, paths, run: Run, devs):
    """Set-up after the data, the window, and the device's memory peak."""
    from quokka_tpu import QuokkaContext
    from quokka_tpu.service import QueryService
    from quokka_tpu.utils import compilestats

    svc_conf = cell.config["service"]
    spill = os.path.join(CACHE, "spill")
    exec_config = {"hbq_path": spill + os.sep}
    svc = QueryService(pool_size=svc_conf["pool_size"],
                       exec_config=exec_config, spill_dir=spill)

    def stream(query, params):
        ctx = QuokkaContext(io_channels=svc_conf["io_channels"],
                            exec_channels=svc_conf["exec_channels"],
                            exec_config=exec_config)
        return cell.queries[query].build(ctx, paths, params)

    def submit(query, params):
        return svc.submit(stream(query, params))

    traffic = cell.traffic
    clients, timeout_s = traffic["clients"], traffic["request_timeout_s"]
    sets = run.parameter_sets = loadgen.plan(traffic, args.seed)
    say(f"parameter sets of this run: {json.dumps(sets)}")
    try:
        svc.prewarm([stream(q, sets[q][0]) for q in cell.queries])
        passes, converged = 0, False
        while passes < WARMUP_MAX_PASSES and not converged:
            before = compilestats.snapshot()
            warm = loadgen.run_closed(
                submit, clients,
                lambda c: loadgen.warm_up_pass(sets, c, clients), timeout_s)
            after = compilestats.snapshot()
            passes += 1
            loads = after["backend_compiles"] - before["backend_compiles"]
            say(f"warm-up pass {passes}: {len(warm)} requests, "
                f"{after['real_compiles'] - before['real_compiles']} real "
                f"compiles, {loads} programs compiled or loaded, "
                f"{sum(r.t_done is None for r in warm)} unanswered")
            converged = loads == 0
        say(f"warm-up: {passes} passes, "
            f"{'converged' if converged else 'NOT converged (cap reached)'}")

        tracer = None
        if args.trace:
            tracer = threading.Thread(
                target=trace_span, args=(run, args.seconds), name="tracer")
        run.compiles_before = compilestats.snapshot()
        run.setup_s = time.perf_counter() - T_PROCESS
        say(f"window opens after {run.setup_s:.1f} s of set-up")
        if tracer:
            tracer.start()
        run.log = loadgen.run_closed(
            submit, clients,
            lambda c: loadgen.schedule(traffic["mix"], sets, args.seed, c,
                                       clients),
            timeout_s, seconds=args.seconds)
        run.compiles_after = compilestats.snapshot()
        if tracer:
            tracer.join()
            run.trace = read_trace(run)
        stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        in_window = {k: run.compiles_after[k] - run.compiles_before[k]
                     for k in ("real_compiles", "backend_compiles")}
        say(f"window: {len(run.log)} requests, {in_window['real_compiles']} "
            f"real compiles, {in_window['backend_compiles']} programs "
            f"compiled or loaded inside it; device memory peak {peak} of "
            f"{stats[0].get('bytes_limit')} bytes")
        return peak
    finally:
        svc.shutdown()
        shutil.rmtree(spill, ignore_errors=True)


def trace_span(run: Run, seconds: float) -> None:
    """The tracer's thread: trace TRACE_SPAN_S of the window (less where
    the window is shorter).  The trace is read once the window has closed,
    so that reading it does not slow the requests it shows."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    time.sleep(min(TRACE_AFTER_S, seconds / 4))
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace.ANCHOR):
        t0 = time.perf_counter()  # the trace's clock and ours, tied here
    time.sleep(min(TRACE_SPAN_S, seconds / 2))
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    run.trace_span = (t0, t1)


def read_trace(run: Run):
    """Reduce the trace and delete its files."""
    try:
        planes = trace.load(trace.find_xplane(TRACE_DIR))
        t0, t1 = run.trace_span
        return trace.reduce(trace.with_requests(planes, run.log, t0),
                            trace.span_ns(planes, t1 - t0))
    finally:
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:  # the builder's look at a trace by hand (tools/)
            shutil.copytree(TRACE_DIR, keep, dirs_exist_ok=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def judge_answers(cell, paths, run: Run):
    """Reference after the window: every answer compared with the reference
    for its own parameters, each number beside its limit.  Returns
    (correct, compared)."""
    compared, correct = {}, True
    for name, query in cell.queries.items():
        mine = [r for r in run.log if r.query == name]
        answered = [r for r in mine if r.answer is not None]
        limits = dict(query.LIMITS, unanswered=0)
        numbers = {"unanswered": len(mine) - len(answered)}
        by_params = {}
        for r in answered:
            by_params.setdefault(json.dumps(r.params, sort_keys=True),
                                 []).append(r)
        t0 = time.perf_counter()
        for same in by_params.values():
            found, per_answer = check.compare(
                [r.answer for r in same],
                query.reference(paths, same[0].params),
                query.SORT_KEYS, query.EXACT)
            check.merge(numbers, found)
            for r, (wrong, err) in zip(same, per_answer):
                r.ok = wrong <= limits["wrong_cells"] and (
                    err <= limits["sum_rel_err"])
        say(f"reference for {name}: {time.perf_counter() - t0:.1f} s, "
            f"{len(answered)} answers to {len(by_params)} parameter sets "
            f"compared; by column: "
            f"{ {k: v for k, v in numbers.items() if k.startswith('rel_err.')} }")
        correct = correct and all(k in numbers for k in limits) and (
            check.judge(numbers, limits))
        for key, limit in limits.items():
            value = numbers.get(key, float("inf"))  # rows missing: inf
            compared[f"{name}.{key}"] = {
                "value": value if math.isfinite(value) else 1e300,
                "limit": limit}
    return correct and bool(run.log), compared


def main(argv) -> int:
    args = parse_args(argv)
    cell = spec.Cell(args.workload)
    os.makedirs(CACHE, exist_ok=True)
    os.environ.setdefault("QUOKKA_TPU_SPILL_DIR", os.path.join(CACHE, "spill"))
    devs = device_or_exit(cell.chips, args.rehearse)
    run = Run()
    if devs[0].platform == "tpu":
        run.peaks = peaks.peaks(devs[0].device_kind)

    t0 = time.perf_counter()
    paths = tables.for_cell(cell, args.seed, args.rehearse)
    say(f"tables ready in {time.perf_counter() - t0:.1f} s: "
        f"{ {t: tables.row_count(paths, t) for t in paths} }")
    run.least_bytes = {name: q.least_bytes(paths)
                       for name, q in cell.queries.items()}

    peak = serve(cell, args, paths, run, devs)
    correct, compared = judge_answers(cell, paths, run)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry, reader in cell.metrics(group):
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(run.log),
              "failed": sum(not r.ok for r in run.log), "metrics": metrics,
              "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace_span[1] - run.trace_span[0]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["window"] = {
        "requests": len(run.log), "answered_right": sum(r.ok for r in run.log),
        "parameter_sets": sum(len(v) for v in run.parameter_sets.values())}
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = compared
    errors = sorted({r.error for r in run.log if r.error})
    for e in errors[:5]:
        say(f"request error: {e}")
    for key, c in compared.items():
        say(f"compared {key} = {c['value']} (limit {c['limit']})")
    say(f"correct = {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
