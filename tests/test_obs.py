"""Observability layer: flight recorder ring, timeline merger, Chrome
trace export, stall analysis, typed metrics — plus the acceptance e2e: a
deliberately wedged two-worker run produces a merged Chrome-trace JSON and
a stall report naming the stuck worker and its in-flight task, within
seconds of the wedge instead of the historical bare 600 s timeout."""

import io
import json
import os
import subprocess
import sys
import time

from quokka_tpu import obs
from quokka_tpu.obs.recorder import FlightRecorder

# -- ring buffer -------------------------------------------------------------


def test_ring_overflow_keeps_newest_events():
    rec = FlightRecorder(capacity=16, enabled=True)
    for i in range(40):
        rec.record("k", f"e{i}")
    evs = rec.snapshot()
    assert len(evs) == 16
    assert [e[0] for e in evs] == list(range(24, 40))  # newest 16, in order
    assert evs[-1][3] == "e39"


def test_ring_snapshot_since_and_last_n():
    rec = FlightRecorder(capacity=64, enabled=True)
    for i in range(10):
        rec.record("k", f"e{i}")
    assert [e[3] for e in rec.snapshot(since=6)] == ["e7", "e8", "e9"]
    assert [e[3] for e in rec.snapshot(last_n=2)] == ["e8", "e9"]


def test_ring_disabled_records_nothing():
    rec = FlightRecorder(capacity=16, enabled=False)
    assert rec.record("k", "x") == -1
    assert rec.snapshot() == []


def test_current_activity_marker():
    rec = FlightRecorder(capacity=16, enabled=True)
    with rec.activity("rpc:get"):
        cur = rec.current()
        assert any(name == "rpc:get" for name, _age in cur.values())
    assert rec.current() == {}


def test_nested_activity_restores_outer_marker():
    # a dispatch marker must survive the RPCs it performs: wedging AFTER
    # the last completed RPC still shows the task in watchdog/stall dumps
    rec = FlightRecorder(capacity=16, enabled=True)
    with rec.activity("task:exec:a2c0"):
        with rec.activity("rpc:ntt_pop"):
            assert [n for n, _ in rec.current().values()] == ["rpc:ntt_pop"]
        assert [n for n, _ in rec.current().values()] == ["task:exec:a2c0"]
    assert rec.current() == {}


def test_dump_text_renders_tail_and_activity():
    rec = FlightRecorder(capacity=16, enabled=True)
    rec.record("task", "exec:a1c0", dur=0.01)
    out = io.StringIO()
    with rec.activity("rpc:ntt_pop"):
        rec.dump_text(out)
    text = out.getvalue()
    assert "exec:a1c0" in text and "rpc:ntt_pop" in text


# -- merger + chrome export --------------------------------------------------


def _ev(seq, ts, kind="k", name="n", dur=0.0, thread="t0", args=None):
    return (seq, ts, kind, name, dur, thread, args)


def test_merged_timeline_is_monotonic_across_workers():
    streams = {
        "worker-0": [_ev(0, 10.0), _ev(1, 12.0), _ev(2, 14.0)],
        "worker-1": [_ev(0, 11.0), _ev(1, 13.0)],
        "coordinator": [_ev(5, 9.5), _ev(6, 13.5)],
    }
    merged = obs.merge_streams(streams)
    assert len(merged) == 7
    ts = [d["ts"] for d in merged]
    assert ts == sorted(ts)  # one wall-clock axis, never decreasing
    # per-stream order survives the merge
    w0 = [d["seq"] for d in merged if d["pid"] == "worker-0"]
    assert w0 == sorted(w0)


def test_chrome_trace_export_shape():
    merged = obs.merge_streams({
        "worker-0": [_ev(0, 100.0, "span", "exec.Agg", dur=0.25),
                     _ev(1, 100.5, "hb", "worker-0")],
    })
    trace = obs.to_chrome_trace(merged)
    evs = trace["traceEvents"]
    assert len(evs) == 2
    span = next(e for e in evs if e["ph"] == "X")
    inst = next(e for e in evs if e["ph"] == "i")
    assert span["dur"] == 0.25 * 1e6 and span["ts"] == 0.0  # rebased start
    assert span["pid"] == "worker-0" and span["cat"] == "span"
    assert inst["name"] == "worker-0"
    json.dumps(trace)  # must be serializable as-is


def test_write_chrome_trace_roundtrip(tmp_path):
    p = str(tmp_path / "t.trace.json")
    obs.write_chrome_trace(p, obs.merge_streams(
        {"w": [_ev(0, 1.0, dur=0.1)]}))
    with open(p) as f:
        data = json.load(f)
    assert data["traceEvents"][0]["ph"] == "X"


# -- stall analysis ----------------------------------------------------------


def test_find_stuck_names_silent_worker_and_inflight_task():
    now = 1000.0
    heartbeats = {0: now - 9.0, 1: now - 0.1}
    inflight = {0: (2, 0, "exec", now - 9.2), 1: (1, 1, "input", now - 0.2)}
    stuck = obs.merge.find_stuck(heartbeats, inflight, now=now)
    assert [w for w, _, _ in stuck] == [0]
    head = obs.merge.stuck_headline(stuck)
    assert "stuck worker 0" in head
    assert "exec" in head and "actor 2" in head and "channel 0" in head


def test_stuck_headline_distinguishes_missing_heartbeat_data():
    # embedded dumps have no per-worker heartbeats: the verdict must not
    # claim "all heartbeats fresh" about data it never had
    assert "fresh" in obs.merge.stuck_headline([], have_heartbeats=True)
    head = obs.merge.stuck_headline([], have_heartbeats=False)
    assert "no per-worker heartbeat data" in head


def test_stall_report_contains_verdict_workers_and_events():
    now = 1000.0
    merged = obs.merge_streams(
        {"worker-0": [_ev(0, now - 10.0, "task", "exec:a2c0", dur=0.5)]})
    report = obs.stall_report(
        "unit-test stall", merged,
        heartbeats={0: now - 9.0, 1: now - 0.1},
        states={1: {"phase": "idle"}},
        inflight={0: (2, 0, "exec", now - 9.2)},
        ntt_depth={(2,): 3}, now=now)
    assert "reason: unit-test stall" in report
    assert "stuck worker 0" in report and "WEDGED" in report
    assert "worker 1" in report and "exec:a2c0" in report


def test_dump_flight_writes_trace_and_report(tmp_path):
    now = time.time()
    trace, report, head = obs.dump_flight(
        "unit dump", {"worker-0": [_ev(0, now, "task", "exec:a1c0", 0.1)]},
        heartbeats={0: now - 30.0}, inflight={0: (1, 0, "exec", now - 31.0)},
        directory=str(tmp_path), echo=False)
    assert os.path.exists(trace) and os.path.exists(report)
    assert "stuck worker 0" in head
    with open(trace) as f:
        assert json.load(f)["traceEvents"]
    with open(report) as f:
        text = f.read()
    assert "stuck worker 0" in text and "perfetto" in text


# -- spans feed both the summary and the recorder ----------------------------


def test_span_lands_in_summary_and_recorder(monkeypatch):
    from quokka_tpu.obs import spans

    spans.set_enabled(True)
    spans.reset()
    before = obs.RECORDER.snapshot()
    last = before[-1][0] if before else -1
    with spans.span("unit.work") as outer:
        with spans.span("unit.inner") as inner:
            pass
    st = spans.stats()
    assert st["unit.work"]["count"] == 1
    assert st["unit.inner"]["count"] == 1
    # a span's self time is its duration less what its children covered
    assert abs(outer.self_s - (outer.dur - inner.dur)) < 1e-9
    assert "unit.work" in spans.summary()
    if obs.RECORDER.enabled:
        evs = {e[3]: e for e in obs.RECORDER.snapshot(since=last)
               if e[2] == "span"}
        assert "unit.work" in evs and "unit.inner" in evs
        assert evs["unit.inner"][6] == {"p": "unit.work"}
    spans.reset()
    spans.set_enabled(os.environ.get("QUOKKA_TRACE", "0")
                      not in ("0", "", "false"))


def test_tracing_shim_reexports_obs_spans():
    from quokka_tpu.obs import spans
    from quokka_tpu.utils import tracing

    assert tracing.span is spans.span and tracing.summary is spans.summary


# -- typed metrics -----------------------------------------------------------


def test_registry_counters_and_gauges():
    from quokka_tpu.obs.metrics import Registry

    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    assert reg.snapshot() == {"c": 5, "g": 2.5}
    reg.reset()
    assert reg.snapshot() == {}


def test_engine_metrics_snapshot_shape_matches_store_contract():
    m = obs.EngineMetrics()
    assert not m
    m.task(1, 0, 10, 256)
    m.task(1, 0, 5, 128)
    m.task(2, 1, None, 0)
    assert m and m.dirty == 3
    snap = m.snapshot()
    assert snap[(1, 0)] == {"tasks": 2, "rows": 15, "bytes": 384}
    assert snap[(2, 1)] == {"tasks": 1, "rows": 0, "bytes": 0}
    assert "real_compiles" in snap["__compile__"]
    assert m.dirty == 0


def test_engine_metrics_deferred_device_rows_resolve_at_flush():
    class FakeDeviceScalar:
        def __int__(self):
            return 7

    m = obs.EngineMetrics()
    m.task(0, 0, FakeDeviceScalar(), 0)
    assert m.snapshot()[(0, 0)]["rows"] == 7


# -- coordinator store bookkeeping -------------------------------------------


def test_heartbeat_state_and_inflight_pop_records():
    from quokka_tpu.runtime.state import WorkerState
    from quokka_tpu.runtime.store_service import CoordinatorStore
    from quokka_tpu.runtime.task import ExecutorTask

    cs = CoordinatorStore()
    st = WorkerState(worker_id=0, phase="run", task=("exec", 2, 0),
                     last_progress=123.0, queue_hint=4, events_seq=99)
    cs.heartbeat(0, st)
    cs.heartbeat(1)  # bare heartbeat still works (startup barrier path)
    assert cs.worker_states[0].task == ("exec", 2, 0)
    assert 1 in cs.heartbeats and 1 not in cs.worker_states
    cs.ntt_push(2, ExecutorTask(2, 0, 0, 0, {}))
    task = cs.ntt_pop(2, [0], 0)
    assert task is not None
    actor, ch, kind, t, args = cs.inflight[0]
    assert (actor, ch, kind) == (2, 0, "exec")
    assert "state_seq=0" in args and "out_seq=0" in args
    cs.flight_append(0, [_ev(0, 1.0), _ev(1, 2.0)])
    assert len(cs.flight_streams()["worker-0"]) == 2


def test_resolve_timeout_env_and_explicit(monkeypatch):
    from quokka_tpu.runtime.distributed import (
        DEFAULT_RUN_TIMEOUT,
        _resolve_timeout,
    )

    monkeypatch.delenv("QK_COORD_TIMEOUT", raising=False)
    assert _resolve_timeout(None) == DEFAULT_RUN_TIMEOUT
    assert _resolve_timeout(42.0) == 42.0
    monkeypatch.setenv("QK_COORD_TIMEOUT", "7")
    assert _resolve_timeout(None) == 7.0
    assert _resolve_timeout(300.0) == 300.0  # explicit beats env
    monkeypatch.setenv("QK_COORD_TIMEOUT", "junk")
    assert _resolve_timeout(None) == DEFAULT_RUN_TIMEOUT


# -- acceptance e2e: wedged two-worker run -> flight dump --------------------


def test_wedged_run_dumps_merged_trace_and_stall_report(tmp_path):
    """Reuses the deliberately-deadlocked two-worker fixture WITHOUT the
    sanitizer: the coordinator's QK_COORD_TIMEOUT fires in seconds, and the
    stall detector must leave behind (a) a merged Chrome-trace JSON and
    (b) a stall report naming the stuck worker and its in-flight task."""
    script = os.path.join(os.path.dirname(__file__),
                          "sanitize_deadlock_case.py")
    env = {k: v for k, v in os.environ.items() if k != "QK_SANITIZE"}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "QK_COORD_TIMEOUT": "25",
        "QK_DUMP_DIR": str(tmp_path),
    })
    t0 = time.time()
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=240, env=env)
    elapsed = time.time() - t0
    out = r.stdout + r.stderr
    assert r.returncode != 0, out
    assert "UNEXPECTED-COMPLETION" not in out, out
    assert elapsed < 180, f"took {elapsed:.0f}s — stall detector never fired"
    assert "exceeded timeout" in out, out
    traces = [f for f in os.listdir(tmp_path) if f.endswith(".trace.json")]
    reports = [f for f in os.listdir(tmp_path) if f.endswith(".report.txt")]
    assert traces and reports, (os.listdir(tmp_path), out)
    with open(os.path.join(tmp_path, traces[0])) as f:
        trace = json.load(f)
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert any(p.startswith("worker-") for p in pids), pids
    with open(os.path.join(tmp_path, reports[0])) as f:
        report = f.read()
    # the verdict names the stuck worker and its in-flight exec task
    assert "stuck worker" in report, report
    assert "in-flight exec task" in report, report
    assert "WEDGED" in report, report
    # ... and the raised error carries the same verdict + the report path
    assert "stuck worker" in out, out
