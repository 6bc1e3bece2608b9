"""Every blocking device read through one span (ISSUE 36): ``spans.device_read``
returns what the conversion it replaced returned and reports its bytes; a
``sync.<site>`` span leaves the dispatches' partition where it was and adds
the request's wait for the device beside it (``syncs``, ``sync.wait``,
``sync.in_dispatch``, ``sync.offthread``, ``d2h_bytes``, ``h2d_bytes``,
``sync_sites``); the ring event says which executor read; served asof, join
and group-by requests leave records that hold the invariants."""

import json
import importlib.util
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext, obs
from quokka_tpu.obs import querylog
from quokka_tpu.obs import spans
from quokka_tpu.ops import pack
from quokka_tpu.runtime import scancache
from quokka_tpu.service import QueryService

import tpch_data

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TPU_PICKS = "asof=sort,groupby=sort,join_build=sort"
WAIT = 0.02  # a wait long enough to tell from a span's own cost


@pytest.fixture(autouse=True)
def fresh():
    scancache.clear()
    querylog.reset()
    yield
    scancache.clear()


def last_seq():
    evs = obs.RECORDER.snapshot()
    return evs[-1][0] if evs else -1


def sync_events(seq):
    return [ev for ev in obs.RECORDER.snapshot(since=seq)
            if ev[2] == "span" and ev[3].startswith("sync.")]


def record_of(q, body):
    """The record a query leaves whose threads ran ``body()``."""
    querylog.open(q)
    body()
    querylog.close(q, "done")
    (rec,) = [r for r in querylog.records() if r["q"] == q]
    return rec


def park():
    return spans.device_wait("test.park", lambda: time.sleep(WAIT))


# -- the funnel's value and bytes ---------------------------------------------

VALUES = {
    "array": lambda: jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
    "scalar": lambda: jnp.sum(jnp.arange(5, dtype=jnp.int32)),
    "pytree": lambda: (jnp.ones(8, jnp.float32), None,
                       {"n": jnp.int32(7), "host": np.arange(3)}, 5),
}


@pytest.mark.parametrize("shape", list(VALUES))
def test_funnel_returns_what_the_conversion_returned(shape):
    value = VALUES[shape]()
    seq = last_seq()
    host = spans.device_read("test." + shape, value)
    want = jax.tree_util.tree_map(np.asarray, value)
    flat_host, tree_host = jax.tree_util.tree_flatten(host)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree_host == tree_want
    for got, exp in zip(flat_host, flat_want):
        assert np.array_equal(got, exp) and np.asarray(got).dtype == exp.dtype
        assert not isinstance(got, jax.Array)
    if shape == "scalar":
        assert int(host) == int(value) == 10
    # bytes: what came from the device, not what was on the host already
    device_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(value)
                       if isinstance(leaf, jax.Array))
    (ev,) = sync_events(seq)
    assert ev[3] == "sync.test." + shape and ev[6]["bytes"] == device_bytes > 0


def test_funnel_reads_under_a_guard_that_disallows_transfers():
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        assert int(spans.device_read("test.guarded", jnp.int32(3))) == 3


def test_host_arrays_pass_get_packed_without_a_read():
    seq = last_seq()
    arrays = [np.arange(4), np.ones(3)]
    assert pack.get_packed(arrays)[0] is arrays[0]
    assert not sync_events(seq)
    got = pack.get_packed([jnp.arange(4), np.ones(3)], site="test.packed")
    assert [type(a) for a in got] == [np.ndarray, np.ndarray]
    (ev,) = sync_events(seq)
    assert ev[3] == "sync.test.packed" and ev[6]["bytes"] == got[0].nbytes


# -- the record: the partition stays, the split comes beside it ---------------


def test_read_under_an_executor_stays_the_executors_seconds():
    def body():
        with spans.dispatch("exec", "a1c0", "q-exec") as d:
            with spans.span("exec.FakeExecutor") as ex:
                park()
            d.ok = True
        body.exec_dur, body.task_dur = ex.dur, d.dur

    rec = record_of("q-exec", body)
    assert rec["task_s"] == pytest.approx(body.task_dur)
    # an unspanned read left its seconds in exec.X's self time: so does this
    assert rec["executors.exec_self"] == pytest.approx(body.exec_dur)
    assert rec["executors.exec_self"] >= WAIT
    assert sum(rec[k] for k in querylog.DISPATCH_LAYERS) == pytest.approx(
        rec["task_s"])
    assert rec["syncs"] == 1 and rec["other"] == 0.0
    assert WAIT <= rec["sync.wait"] == rec["sync.in_dispatch"] <= rec["task_s"]
    assert rec["sync.offthread"] == 0.0 and rec["d2h_bytes"] == 0
    assert rec["sync_sites"] == [["test.park", 1, round(rec["sync.wait"], 6)]]


def test_count_valid_keeps_its_own_layer():
    def body():
        with spans.dispatch("exec", "a1c0", "q-cv") as d:
            with spans.span("exec.FakeExecutor") as ex:
                n = spans.device_read("count_valid", jnp.int32(41),
                                      own_layer=True)
                time.sleep(WAIT)
            d.ok = True
        assert int(n) == 41
        body.exec_self = ex.self_s

    rec = record_of("q-cv", body)
    assert rec["other"] > 0.0 and rec["other"] == rec["sync.wait"]
    assert rec["executors.exec_self"] == pytest.approx(body.exec_self)
    assert sum(rec[k] for k in querylog.DISPATCH_LAYERS) == pytest.approx(
        rec["task_s"])
    assert rec["syncs"] == 1 and rec["d2h_bytes"] == 4


def test_read_in_a_dispatch_that_could_not_progress_is_waited_not_tasked():
    def body():
        with spans.dispatch("exec", "a1c0", "q-requeue"):
            park()  # ok stays False

    rec = record_of("q-requeue", body)
    assert rec["tasks"] == 0 and rec["task_s"] == 0.0
    assert rec["service.sched_wait"] >= WAIT
    assert rec["syncs"] == 1 and rec["sync.wait"] >= WAIT
    assert rec["sync.in_dispatch"] == 0.0 == rec["sync.offthread"]


def test_read_under_finalize_is_waited_outside_the_dispatches():
    def body():
        with spans.span("svc.finalize", q="q-fin") as fin:
            with spans.span("finalize.snapshots") as snap:
                park()
        body.fin, body.snap = fin.dur, snap.self_s

    rec = record_of("q-fin", body)
    assert rec["service.finalize"] == pytest.approx(body.fin)
    assert rec["finalize.snapshots"] == pytest.approx(body.snap)
    assert body.snap >= WAIT
    assert rec["syncs"] == 1 and rec["sync.wait"] >= WAIT
    assert rec["sync.in_dispatch"] == 0.0 == rec["sync.offthread"]


def test_read_on_a_helper_thread_is_offthread():
    def helper():
        with spans.offthread("q-off"):
            with spans.span("emit.result_d2h"):
                spans.device_read("emit.result", jnp.zeros(16, jnp.float32))
                park()

    def body():
        t = threading.Thread(target=helper)
        t.start()
        t.join()

    rec = record_of("q-off", body)
    assert rec["syncs"] == 2 and rec["d2h_bytes"] == 64
    assert WAIT <= rec["sync.wait"] == rec["sync.offthread"]
    assert rec["sync.in_dispatch"] == 0.0 and rec["task_s"] == 0.0
    assert rec["offthread.emit.result_d2h"] >= rec["sync.offthread"]


def test_ring_event_names_the_query_the_reader_and_the_bytes():
    seq = last_seq()
    querylog.open("q-ring")
    with spans.dispatch("exec", "a1c0", "q-ring") as d:
        spans.device_read("test.top", jnp.int32(1))
        with spans.span("exec.FakeExecutor"):
            with spans.span("join.build"):
                spans.device_read("join.build_stats",
                                  (jnp.int32(1), jnp.float32(2.0)))
        d.ok = True
    querylog.discard("q-ring")
    top, build = sync_events(seq)
    assert top[3] == "sync.test.top" and top[6] == {
        "bytes": 4, "q": "q-ring", "p": "task"}
    assert build[3] == "sync.join.build_stats" and build[6] == {
        "bytes": 8, "q": "q-ring", "p": "join.build"}
    assert build[4] > 0.0


def test_sync_sites_are_capped_and_ordered():
    def body():
        # eleven sites, two reads each, site i blocked 2 x (i + 1) ms: given
        # to the record as the spans give them (a sleep's length would follow
        # the load of the machine)
        for i in range(11):
            querylog.sync("q-sites", [(f"test.site{i}", 0.001 * (i + 1), 4)],
                          "sync.in_dispatch")
            querylog.sync("q-sites", [(f"test.site{i}", 0.001 * (i + 1), 4)],
                          None)

    rec = record_of("q-sites", body)
    sites = rec["sync_sites"]
    assert len(sites) == querylog.SYNC_SITES_MAX == 8
    assert [s[0] for s in sites] == [f"test.site{i}" for i in range(10, 2, -1)]
    assert all(n == 2 for _, n, _ in sites)
    assert [s[2] for s in sites] == [round(0.002 * (i + 1), 6)
                                     for i in range(10, 2, -1)]
    # the sums hold the sites the list dropped
    assert rec["syncs"] == 22 and rec["d2h_bytes"] == 88
    assert rec["sync.wait"] == pytest.approx(0.132)
    assert rec["sync.in_dispatch"] == pytest.approx(0.066)


def test_h2d_bytes_are_what_bridge_to_device_put():
    def helper():
        with spans.offthread("q-h2d"):
            with spans.span("bridge.to_device"):
                pack.pack_put([np.arange(1000, dtype=np.float64)])

    def body():
        with spans.dispatch("input", "a0c0", "q-h2d") as d:
            with spans.span("bridge.to_device"):
                pack.pack_put([np.arange(100, dtype=np.int32)])
                pack.pack_put([np.zeros(50, dtype=np.float32)])
            with spans.span("exec.FakeExecutor"):
                pack.pack_put([np.zeros(7, dtype=np.float32)])  # not a scan
            d.ok = True
        t = threading.Thread(target=helper)
        t.start()
        t.join()

    rec = record_of("q-h2d", body)
    assert rec["h2d_bytes"] == 400 + 200 + 8000
    assert rec["d2h_bytes"] == 0 and rec["syncs"] == 0


# -- served requests ----------------------------------------------------------


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    root = tmp_path_factory.mktemp("device_reads")
    out = {}
    # a size whose build no other test file makes: a program persisted by
    # another run's process can fail the one that loads it (ROADMAP Design 1a)
    tpch = tpch_data.generate(sf=0.03, seed=36)
    for name in ("lineitem", "orders"):
        out[name] = str(root / f"{name}.parquet")
        pq.write_table(tpch[name], out[name], row_group_size=32768)
    with open(os.path.join(BENCH, "configs", "ticks_1d.json")) as f:
        conf = json.load(f)
    ticks = _load(os.path.join(BENCH, "datagen", "ticks.py"),
                  "bench_datagen_ticks_pr36").generate(
        36, **conf["datagen"]["rehearsal_args"])
    for name, table in ticks.items():
        out[name] = str(root / f"{name}.parquet")
        pq.write_table(table, out[name], row_group_size=8192)
    return out


def asof_stream(ctx, paths):
    t = ctx.read_sorted_parquet(paths["trades"], sorted_by="time")
    q = ctx.read_sorted_parquet(paths["quotes"], sorted_by="time")
    return (t.join_asof(q, on="time", by="symbol")
            .with_columns_sql("bid * size as notional")
            .groupby("symbol")
            .agg_sql("sum(notional) as total, count(*) as n"))


def join_stream(ctx, paths):
    li = ctx.read_parquet(paths["lineitem"],
                          columns=["l_orderkey", "l_quantity"])
    od = ctx.read_parquet(paths["orders"],
                          columns=["o_orderkey", "o_orderpriority"])
    return (li.join(od, left_on="l_orderkey", right_on="o_orderkey")
            .groupby("o_orderpriority")
            .agg_sql("sum(l_quantity) as qty, count(*) as n"))


def groupby_stream(ctx, paths):
    return (ctx.read_parquet(paths["lineitem"],
                             columns=["l_orderkey", "l_quantity",
                                      "l_discount"])
            .groupby("l_orderkey")
            .agg_sql("sum(l_quantity) as qty, sum(l_discount) as disc"))


# shape -> (the plan, sites it must read at, how many reads two runs of the
# same request may differ by: a count, or a share of the larger).  A function of the plan and
# the tables but for the sites that follow arrival (PERF.md section 7): two
# channels that reach a shared build before its statistics are cached each
# read them (join.build_stats), and in the asof join a flush attempt
# (asof.ready), a dispatch's deferred row count (metrics.rows) and
# count_valid follow how the parts arrived.
SERVED = {"asof": (asof_stream, {"asof.ready", "asof.watermark"}, 0.25),
          "join": (join_stream, {"join.build_stats", "count_valid"}, 2),
          "groupby": (groupby_stream, {"opstats.snapshot"}, 0)}


@pytest.mark.parametrize("shape", list(SERVED))
def test_served_request_leaves_the_split_beside_the_partition(
        shape, tables, monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY", TPU_PICKS)
    build, must_read, swing = SERVED[shape]
    frames = []
    with QueryService(pool_size=2) as svc:
        for _ in range(3):  # one after the other: the same request thrice
            frames.append(svc.submit(build(QuokkaContext(), tables))
                          .to_df(timeout=600))
    assert len(frames[0]) > 0 and all(len(f) == len(frames[0])
                                      for f in frames)
    recs = querylog.records()
    assert len(recs) == 3
    for r in recs:
        assert r["status"] == "done" and r["syncs"] > 0
        assert 0.0 <= r["sync.in_dispatch"] <= r["task_s"] + 1e-9
        assert (r["sync.in_dispatch"] + r["sync.offthread"]
                <= r["sync.wait"] + 1e-9)
        assert sum(r[k] for k in querylog.DISPATCH_LAYERS) == pytest.approx(
            r["task_s"], rel=0.01)
        sites = {s[0] for s in r["sync_sites"]}
        assert must_read <= sites, sites
        # the answer came through the emitter's read, padded columns and mask
        assert "emit.result" in sites or r["syncs"] > querylog.SYNC_SITES_MAX
        assert r["d2h_bytes"] > 0
        assert r["sync_sites"] == sorted(r["sync_sites"],
                                         key=lambda s: -s[2])
    # the reads are a function of the plan and the tables: the first request
    # reads the tables (h2d) and the later ones find them in the scan cache
    most = max(recs[1]["syncs"], recs[2]["syncs"])
    assert abs(recs[1]["syncs"] - recs[2]["syncs"]) <= (
        swing if isinstance(swing, int) else swing * most)
    assert recs[0]["h2d_bytes"] > 0
    assert recs[1]["h2d_bytes"] == recs[2]["h2d_bytes"] == 0
