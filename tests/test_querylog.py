"""The query log and the spans that feed it: one flat record per finished
query (obs/querylog.py), self times that partition the dispatches, ring
events that know their query and their parent, named compile events, host
annotations in a profiler trace, the pinned shape of
``explain(as_dict=True)``, and the benchmark's six per-layer metrics that
read the records."""

import collections
import gc
import json
import os
import subprocess
import sys
import weakref

import jax
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext, obs
from quokka_tpu.obs import querylog
from quokka_tpu.runtime import compileplane, scancache
from quokka_tpu.service import QueryService

import tpch_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RECORD_KEYS = {
    "q", "plan_fp", "status",
    "submit_in", "submit_out", "admitted", "first_task", "last_task",
    "finalize_in", "done", "wall_done",
    "entry.submit", "entry.prepare_plan", "entry.lower_plan",
    "entry.estimate", "entry.enqueue",
    "runtime.pick", "service.sched_wait", "service.finalize",
    "finalize.flush", "finalize.snapshots", "finalize.cleanup",
    "runtime.dispatch_self", "executors.exec_self", "runtime.push",
    "io.read", "emit.d2h", "compile.acquire", "other",
    "sync.wait", "sync.in_dispatch", "sync.offthread",
    "offthread.reader.execute", "offthread.bridge.to_device",
    "offthread.emit.result_d2h", "offthread.spill.hbq", "offthread.other",
    "task_s", "tasks", "requeues", "backoffs", "syncs", "d2h_bytes", "h2d_bytes",
    "sync_sites", "compile_hits",
    "compile_misses", "rows_in", "padded_in", "rows_unknown",
    "agg_merges_compiled", "agg_merges_general", "asof_flushes",
    "asof_probe_rows", "asof_probe_padded", "asof_quote_padded",
    "asof_match_sort", "asof_match_search",
    "join_probe_direct", "join_probe_search", "join_payload_slots",
    "join_probe_general", "join_builds", "str_pred_dict_rows", "groupby_sort_slots",
    "groupby_groups_out", "agg_merge_slots", "compact_sort_slots",
    "compact_nonzero_slots", "distinct_rows_in", "distinct_rows_out",
    "distinct_sort_slots", "scan_hits", "scan_misses", "compiled", "pool_size", "park_s_total", "loop_s_total",
}
IN_DISPATCH = ("runtime.dispatch_self", "executors.exec_self",
               "runtime.push", "io.read", "emit.d2h", "compile.acquire",
               "other")
NEW_METRICS = ("worker_park_pct", "finalize_ms", "requeues_per_query",
               "dispatch_self_ms", "exec_host_ms", "pad_waste_pct")


@pytest.fixture(autouse=True)
def fresh():
    scancache.clear()
    querylog.reset()
    yield
    scancache.clear()


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    root = tmp_path_factory.mktemp("querylog_tpch")
    tables = tpch_data.generate(sf=0.003, seed=11)
    out = {}
    for name in ("lineitem", "orders"):
        out[name] = str(root / f"{name}.parquet")
        pq.write_table(tables[name], out[name], row_group_size=4096)
    return out


def agg_stream(ctx, paths, bound="0.05"):
    return (ctx.read_parquet(paths["lineitem"],
                             columns=["l_returnflag", "l_quantity",
                                      "l_discount"])
            .filter_sql(f"l_discount < {bound}")
            .groupby("l_returnflag")
            .agg_sql("sum(l_quantity) as qty, count(*) as n"))


def join_stream(ctx, paths):
    li = ctx.read_parquet(paths["lineitem"],
                          columns=["l_orderkey", "l_quantity"])
    od = ctx.read_parquet(paths["orders"],
                          columns=["o_orderkey", "o_orderpriority"])
    return (li.join(od, left_on="l_orderkey", right_on="o_orderkey")
            .groupby("o_orderpriority")
            .agg_sql("sum(l_quantity) as qty, count(*) as n"))


def ring_since(seq):
    return obs.RECORDER.snapshot(since=seq)


def last_seq():
    evs = obs.RECORDER.snapshot()
    return evs[-1][0] if evs else -1


def test_one_flat_record_per_finished_query_and_nothing_pinned(paths):
    with QueryService(pool_size=2) as svc:
        ctx = QuokkaContext()
        handles = [svc.submit(agg_stream(ctx, paths)) for _ in range(3)]
        for h in handles:
            assert len(h.to_df(timeout=300)) > 0
        assert svc.stats()["recent_queries"] == 3
        recs = querylog.records()
        assert sorted(r["q"] for r in recs) == sorted(
            h.query_id for h in handles)
        for r in recs:
            assert set(r) == RECORD_KEYS == set(querylog.KEYS)
            assert r["status"] == "done" and r["pool_size"] == 2
            for key, value in r.items():
                if key in ("compiled", "sync_sites"):
                    width = 4 if key == "compiled" else 3
                    assert all(isinstance(c, list) and len(c) == width
                               and not any(isinstance(x, (list, dict))
                                           for x in c) for c in value)
                else:
                    assert isinstance(value, (int, float, str)), (key, value)
            assert (r["submit_in"] <= r["submit_out"]
                    and r["submit_in"] <= r["admitted"] <= r["first_task"]
                    <= r["last_task"] <= r["finalize_in"] <= r["done"])
            assert r["tasks"] > 0 and r["rows_in"] > 0
            assert r["padded_in"] >= r["rows_in"]
        json.dumps(recs)  # plain values all the way down
        # a record pins nothing of its query: the engine goes, it stays
        engine = weakref.ref(handles[0]._s.engine)
        graph = weakref.ref(handles[0]._s.graph)
        first = handles[0].query_id
        del handles, h
        gc.collect()
        assert engine() is None and graph() is None
        assert first in {r["q"] for r in querylog.records()}
        # copies: a caller cannot edit the log
        querylog.records()[0]["compiled"].append("x")
        assert "x" not in querylog.records()[0]["compiled"]


def test_a_served_q1_folds_its_aggregates_in_compiled_programs(paths):
    """Q1's partials are a few rows each: every merge of both aggregators
    and the final tail take the compiled path (ops/aggtail.py), none the
    op-by-op one; the record says so."""
    q1 = (QuokkaContext().read_parquet(
              paths["lineitem"],
              columns=["l_returnflag", "l_linestatus", "l_quantity",
                       "l_extendedprice", "l_discount", "l_shipdate"])
          .filter_sql("l_shipdate <= date '1998-09-02'")
          .groupby(["l_returnflag", "l_linestatus"],
                   orderby=["l_returnflag", "l_linestatus"])
          .agg_sql("sum(l_quantity) as sum_qty, "
                   "sum(l_extendedprice * (1 - l_discount)) as sum_disc, "
                   "avg(l_discount) as avg_disc, count(*) as n"))
    with QueryService(pool_size=2) as svc:
        df = svc.submit(q1).to_df(timeout=300)
    assert list(df["l_returnflag"] + df["l_linestatus"]) == sorted(
        df["l_returnflag"] + df["l_linestatus"]) and df["n"].sum() > 0
    (rec,) = querylog.records()
    # two partial channels, two final channels: four merges and two tails
    assert rec["agg_merges_compiled"] >= 3 and rec["agg_merges_general"] == 0


def test_self_times_partition_the_task_time(paths):
    seq = last_seq()
    with QueryService(pool_size=2) as svc:
        ctx = QuokkaContext()
        handles = [svc.submit(s) for s in (agg_stream(ctx, paths),
                                           join_stream(ctx, paths),
                                           agg_stream(ctx, paths, "0.03"))]
        for h in handles:
            h.to_df(timeout=300)
    ring_tasks = collections.defaultdict(float)
    for ev in ring_since(seq):
        if ev[2] == "task":
            ring_tasks[ev[6]["q"]] += ev[4]
            assert 0.0 <= ev[6]["self_s"] <= ev[4] + 1e-9
    recs = querylog.records()
    assert len(recs) == 3
    for r in recs:
        parts = sum(r[k] for k in IN_DISPATCH)
        assert parts == pytest.approx(r["task_s"], rel=0.01)
        # the record's task time is the ring's task events' (the ring holds
        # them all here: three small queries)
        assert r["task_s"] == pytest.approx(ring_tasks[r["q"]], rel=0.01)
        assert all(r[k] >= 0.0 for k in IN_DISPATCH)
        # the wait for the device lies inside the partition's seconds
        assert 0.0 <= r["sync.in_dispatch"] <= r["task_s"] + 1e-9
        assert r["sync.in_dispatch"] + r["sync.offthread"] <= (
            r["sync.wait"] + 1e-9)
        assert (r["syncs"] > 0) == (r["sync.wait"] > 0)
        assert r["syncs"] >= sum(n for _, n, _ in r["sync_sites"]) > 0
        assert r["executors.exec_self"] > 0 and r["runtime.dispatch_self"] > 0
        assert 0 < r["service.finalize"] <= r["done"] - r["last_task"]
        assert (r["finalize.flush"] + r["finalize.snapshots"]
                + r["finalize.cleanup"]) <= r["service.finalize"]
        assert (r["entry.prepare_plan"] + r["entry.lower_plan"]
                + r["entry.estimate"] + r["entry.enqueue"]
                ) <= r["entry.submit"] * 1.001
        assert r["entry.submit"] == pytest.approx(
            r["submit_out"] - r["submit_in"], rel=0.01)


def test_ring_spans_inside_a_dispatch_know_their_query_and_parent(paths):
    seq = last_seq()
    with QueryService(pool_size=2) as svc:
        h = svc.submit(join_stream(QuokkaContext(), paths))
        h.to_df(timeout=300)
        qid = h.query_id
    events = ring_since(seq)
    assert all(len(ev) == 7 for ev in events)  # the wire tuple is unchanged
    spans = [ev for ev in events if ev[2] == "span"]
    in_dispatch = [ev for ev in spans
                   if ev[3].startswith(("exec.", "done.", "push."))]
    assert in_dispatch
    for ev in in_dispatch:
        assert ev[6]["q"] == qid and ev[6]["p"]
    assert {"task"} <= {ev[6]["p"] for ev in in_dispatch}
    # a helper thread's span carries the engine's query id too
    emitted = [ev for ev in spans if ev[3] == "emit.result_d2h"]
    assert emitted and all(ev[6] == {"q": qid, "p": "offthread"}
                           for ev in emitted)
    # the service's own spans name their query; the per-turn ones stay out
    names = {ev[3] for ev in spans}
    assert {"svc.finalize", "finalize.flush", "finalize.snapshots",
            "finalize.cleanup", "svc.drain", "submit", "submit.lower_plan",
            "handle.materialize"} <= names
    assert not names & {"svc.quantum", "svc.park", "svc.step", "step.pick",
                        "svc.fruitless", "svc.backoff"}
    for ev in spans:
        if ev[3].startswith(("svc.", "finalize.", "submit")):
            assert ev[6]["q"] == qid


def test_the_log_is_bounded_and_survives_shutdown(paths, monkeypatch):
    assert querylog.MAXLEN == 4096 and querylog._log.maxlen == 4096
    monkeypatch.setattr(querylog, "_log", collections.deque(maxlen=3))
    svc = QueryService(pool_size=2)
    ctx = QuokkaContext()
    ids = []
    for _ in range(5):
        h = svc.submit(agg_stream(ctx, paths))
        h.to_df(timeout=300)
        ids.append(h.query_id)
    svc.shutdown()
    assert [r["q"] for r in querylog.records()] == ids[-3:]
    cut = querylog.records()[0]["done"]
    assert [r["q"] for r in querylog.records(since=cut)] == ids[-2:]
    assert querylog._open == {}  # no accumulator outlives its query


def test_a_compile_plane_miss_names_its_program_and_who_asked(paths,
                                                              monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    # no persisted executable to load, so a new program is a miss; and no
    # XLA cache to answer the compile, so that miss really compiles
    monkeypatch.setattr(compileplane, "_entry_path",
                        lambda key, create=False: None)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    seq = last_seq()
    try:
        with QueryService(pool_size=2) as svc:
            # a filter constant no other test compiles: a new predicate
            h = svc.submit(agg_stream(QuokkaContext(), paths, "0.0271828"))
            h.to_df(timeout=300)
            qid = h.query_id
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    acquired = [ev for ev in ring_since(seq)
                if ev[2] == "span" and ev[3] == "compile.acquire"
                and ev[6].get("q") == qid]
    assert acquired
    for ev in acquired:
        args = ev[6]
        assert args["hit"] == "miss" and isinstance(args["real"], bool)
        assert args["kind"] and isinstance(args["kind"], str)
        assert len(args["key_hash"]) == 24 and len(args["sig"]) <= 200
        # who asked: the enclosing span always, the task where a dispatch
        # asked (planning and the helper threads compile too)
        assert args["p"]
        assert args.get("task", qid + ":").startswith(qid + ":")
    assert any("task" in ev[6] for ev in acquired)
    (rec,) = [r for r in querylog.records() if r["q"] == qid]
    assert rec["compile_misses"] == len(acquired) and rec["compile_hits"] == 0
    assert rec["compile.acquire"] > 0
    named = {(c[0], c[1]) for c in rec["compiled"]}
    assert named == {(ev[6]["kind"], ev[6]["key_hash"]) for ev in acquired}
    assert any(c[3] for c in rec["compiled"])  # something really compiled


def test_host_spans_are_annotations_in_a_profiler_trace(paths, tmp_path):
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "trace")
    with QueryService(pool_size=2) as svc:
        ctx = QuokkaContext()
        svc.submit(agg_stream(ctx, paths)).to_df(timeout=300)  # warm
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            svc.submit(agg_stream(ctx, paths)).to_df(timeout=300)
        finally:
            jax.profiler.stop_trace()
    found = []
    for dirpath, _dirs, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    assert found
    names = set()
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("qk."))
    assert any(n.startswith("qk.task:") for n in names)
    assert any(n.startswith("qk.exec.") for n in names)
    assert {"qk.svc.quantum", "qk.step.pick", "qk.svc.finalize",
            "qk.submit"} <= names


def test_explain_as_dict_has_a_pinned_shape(paths):
    with QueryService(pool_size=2) as svc:
        h = svc.submit(join_stream(QuokkaContext(), paths))
        h.to_df(timeout=300)
        snap = h.explain(as_dict=True)
    assert set(snap) >= {
        "query_id", "plan_fp", "wall_s", "time_s", "size_hint_bytes",
        "skew_threshold", "operators", "edges", "top_operators",
        "rows_unknown", "planner"}
    assert set(snap) <= {
        "query_id", "plan_fp", "wall_s", "time_s", "size_hint_bytes",
        "skew_threshold", "operators", "edges", "top_operators",
        "rows_unknown", "planner", "efficiency"}
    always = {"actor", "op", "kind", "channels", "targets", "stage",
              "rows_in", "rows_out", "bytes_in", "bytes_out", "batches_in",
              "batches_out", "dispatches", "padded_in", "rows_unknown",
              "time_s", "time_share"}
    sometimes = {"selectivity", "pad_waste", "size_hint_bytes", "src_sig"}
    assert snap["operators"]
    for op in snap["operators"]:
        assert always <= set(op)
        # what is left are the executors' own notes: rows (join_build_rows
        # ...), the aggregators' merges by path and the slots they sorted
        # (ops/aggtail.py), the general group-by's sorted slots and emitted
        # groups, the joins' builds and probe slots, and the compactions'
        # mask slots by how their indices were found (ops/kernels.py)
        assert all(k.endswith("_rows") or k.startswith(("agg_merge",
                                                        "groupby_", "join_",
                                                        "compact_"))
                   for k in set(op) - always - sometimes), sorted(op)
        assert op["padded_in"] >= op["rows_in"] >= 0
    for edge in snap["edges"]:
        assert set(edge) == {"edge", "src", "tgt", "channels", "rows_total",
                             "rows_max", "rows_mean", "skew_ratio", "skewed",
                             "channel_rows"}
    (rec,) = [r for r in querylog.records() if r["q"] == snap["query_id"]]
    assert rec["rows_in"] == sum(o["rows_in"] for o in snap["operators"])
    assert rec["padded_in"] == sum(o["padded_in"] for o in snap["operators"])
    assert rec["agg_merges_compiled"] == sum(
        o.get("agg_merges_compiled", 0) for o in snap["operators"]) > 0


def test_a_rehearsed_traced_run_reports_the_six_metrics(tmp_path):
    """The benchmark's q1 cell at its rehearsal size, in a process of its
    own (x64 off, as on the chip), keeping its trace; then the gap tool on
    that trace."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_KEEP_TRACE=str(tmp_path / "kept"),
               QK_KERNEL_STRATEGY=("groupby=sort,join_build=sort,"
                                   "asof=sort"))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "tpch_sf1.q1_s2", "--seed", str(2**31 + 91),
         "--seconds", "2", "--rehearse", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    for name in NEW_METRICS:
        value = result["metrics"][name]["value"]
        assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert result["metrics"]["pad_waste_pct"]["value"] < 100
    assert result["metrics"]["worker_park_pct"]["value"] <= 100
    gaps = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools", "gaps.py"),
         str(tmp_path / "kept"), "--json", str(tmp_path / "gaps.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert gaps.returncode == 0, gaps.stderr[-2000:]
    with open(tmp_path / "gaps.json", encoding="utf-8") as f:
        reduced = json.load(f)
    assert reduced["queries_finished"] > 0
    assert reduced["thread_seconds"].get("svc.park", 0.0) >= 0
    assert any(k.startswith("task:") for k in reduced["thread_seconds"])
