"""Query service: persistent multi-query engine — concurrent execution on
one shared worker pool + control store, byte-budgeted admission control,
fair scheduling, warm shared caches, and cross-query failure recovery.

Acceptance (ISSUE 3): two concurrent TPC-H queries on one shared pool match
serial results; the admission gate queues a query past the byte budget and
releases it when one finishes; a worker kill during 2-way concurrency
recovers both queries without cross-query replay leakage.
"""

import os
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.dataset.readers import InputArrowDataset
from quokka_tpu.runtime import scancache
from quokka_tpu.runtime.tables import ControlStore
from quokka_tpu.service import (
    AdmissionQueueFull,
    AdmissionTimeout,
    QueryService,
)

import tpch_data


@pytest.fixture(autouse=True)
def fresh_scan_cache():
    scancache.clear()
    yield
    scancache.clear()


@pytest.fixture(scope="module")
def tpch_paths(tmp_path_factory):
    root = tmp_path_factory.mktemp("svc_tpch")
    tables = tpch_data.generate(sf=0.003, seed=7)
    paths = {}
    for name in ("lineitem", "orders", "customer"):
        p = str(root / f"{name}.parquet")
        pq.write_table(tables[name], p, row_group_size=4096)
        paths[name] = p
    return paths


def q1_stream(ctx, paths):
    return (
        ctx.read_parquet(
            paths["lineitem"],
            columns=["l_returnflag", "l_linestatus", "l_quantity",
                     "l_extendedprice", "l_discount"],
        )
        .groupby(["l_returnflag", "l_linestatus"])
        .agg_sql(
            "sum(l_quantity) as sum_qty, "
            "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
            "count(*) as n"
        )
    )


def q3_stream(ctx, paths):
    lineitem = ctx.read_parquet(
        paths["lineitem"],
        columns=["l_orderkey", "l_extendedprice", "l_discount"])
    orders = ctx.read_parquet(
        paths["orders"], columns=["o_orderkey", "o_custkey"])
    customer = ctx.read_parquet(
        paths["customer"], columns=["c_custkey", "c_mktsegment"])
    from quokka_tpu.expression import col

    return (
        lineitem.join(orders, left_on="l_orderkey", right_on="o_orderkey")
        .join(customer.filter(col("c_mktsegment") == "BUILDING"),
              left_on="o_custkey", right_on="c_custkey")
        .groupby("l_orderkey")
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue, "
                 "count(*) as n")
    )


def _sorted(df, by):
    return df.sort_values(by).reset_index(drop=True)


def _no_namespace_rows(store: ControlStore, query_id: str) -> bool:
    for t in store.tables.values():
        if isinstance(t, set):
            if any(isinstance(m, tuple) and len(m) == 2 and m[0] == query_id
                   for m in t):
                return False
        elif any(isinstance(k, tuple) and len(k) == 2 and k[0] == query_id
                 for k in t):
            return False
    return all(not (isinstance(k, tuple) and query_id in k)
               for k in store.kv)


class TestConcurrentExecution:
    def test_two_concurrent_tpch_queries_match_serial(self, tpch_paths):
        serial_q1 = _sorted(q1_stream(QuokkaContext(), tpch_paths).collect(),
                            ["l_returnflag", "l_linestatus"])
        serial_q3 = _sorted(q3_stream(QuokkaContext(), tpch_paths).collect(),
                            ["l_orderkey"])
        with QueryService(pool_size=2) as svc:
            h1 = svc.submit(q1_stream(QuokkaContext(), tpch_paths))
            h3 = svc.submit(q3_stream(QuokkaContext(), tpch_paths))
            got1 = _sorted(h1.to_df(timeout=300),
                           ["l_returnflag", "l_linestatus"])
            got3 = _sorted(h3.to_df(timeout=300), ["l_orderkey"])
            pd.testing.assert_frame_equal(got1, serial_q1, rtol=1e-9,
                                          check_dtype=False)
            pd.testing.assert_frame_equal(got3, serial_q3, rtol=1e-9,
                                          check_dtype=False)
            # exact-count columns are byte-identical regardless of interleave
            assert got1["n"].tolist() == serial_q1["n"].tolist()
            assert got3["n"].tolist() == serial_q3["n"].tolist()
            # finished queries' namespaces are GC'd from the shared store
            assert _no_namespace_rows(svc.store, h1.query_id)
            assert _no_namespace_rows(svc.store, h3.query_id)

    def test_many_queries_share_one_pool(self, tpch_paths):
        serial = _sorted(q1_stream(QuokkaContext(), tpch_paths).collect(),
                         ["l_returnflag", "l_linestatus"])
        with QueryService(pool_size=2) as svc:
            handles = [svc.submit(q1_stream(QuokkaContext(), tpch_paths))
                       for _ in range(4)]
            for h in handles:
                got = _sorted(h.to_df(timeout=300),
                              ["l_returnflag", "l_linestatus"])
                pd.testing.assert_frame_equal(got, serial, rtol=1e-9,
                                              check_dtype=False)
            # per-query flight-recorder/metrics tagging: every query reports
            # its own progress counters under its own namespace
            rows = [sum(v["rows"] for k, v in h.metrics().items()
                        if isinstance(k, tuple)) for h in handles]
            assert len({r for r in rows if r > 0}) <= 1 and rows[0] > 0

    def test_stats_expose_latency_quantiles(self, tpch_paths):
        """ISSUE 5 satellite: stats()/handles carry per-query p50/p95 task
        latency + queue wait from the typed histograms (not just
        status/bytes), and the snapshot survives the namespace GC."""
        with QueryService(pool_size=2) as svc:
            h = svc.submit(q1_stream(QuokkaContext(), tpch_paths))
            h.wait(300)
            lat = h.latency_stats()
            assert lat["count"] > 0
            assert lat["p50"] > 0 and lat["p95"] >= lat["p50"]
            st = svc.stats()
            assert st["workers_alive"] == 2
            assert st["queue_wait"]["count"] >= 1  # admission wait observed
        # the per-query histogram is GC'd with the query's namespace...
        from quokka_tpu import obs

        assert f"task.latency_s.{h.query_id}" not in obs.REGISTRY.histograms()
        # ...but the handle still answers from its finish-time snapshot
        assert h.latency_stats()["count"] == lat["count"]

    def test_scan_cache_warm_across_queries(self, tpch_paths):
        with QueryService(pool_size=2) as svc:
            h1 = svc.submit(q1_stream(QuokkaContext(), tpch_paths))
            h1.wait(300)
            h2 = svc.submit(q1_stream(QuokkaContext(), tpch_paths))
            h2.wait(300)
            s1, s2 = h1.scan_cache_stats(), h2.scan_cache_stats()
            assert s1["misses"] > 0  # cold: first scan pays decode + h2d
            assert s2["hits"] > 0 and s2["misses"] == 0, (s1, s2)


class _SlowArrowDataset(InputArrowDataset):
    """Arrow reader with a per-lineage delay — deterministic 'long-running
    query' for admission-gate tests."""

    def __init__(self, table, batch_rows=512, delay_s=0.05):
        super().__init__(table, batch_rows=batch_rows)
        self.delay_s = delay_s

    def execute(self, channel, lineage):
        time.sleep(self.delay_s)
        return super().execute(channel, lineage)


def _slow_query(ctx, table, delay_s=0.05):
    return (
        ctx.read_dataset(_SlowArrowDataset(table, delay_s=delay_s))
        .groupby("k").agg_sql("sum(v) as sv, count(*) as n")
    )


def _small_table(n=8192, seed=0):
    r = np.random.default_rng(seed)
    return pa.table({"k": r.integers(0, 16, n).astype(np.int64),
                     "v": r.integers(0, 1000, n).astype(np.int64)})


class TestAdmissionControl:
    def test_gate_queues_third_query_and_releases(self):
        table = _small_table()
        want = (table.to_pandas().groupby("k")
                .agg(sv=("v", "sum"), n=("v", "count")).reset_index())
        mb = 1 << 20
        with QueryService(pool_size=2, mem_budget=100 * mb,
                          admit_timeout=120) as svc:
            hs = [svc.submit(_slow_query(QuokkaContext(), table),
                             working_set_bytes=40 * mb) for _ in range(3)]
            # two fit under the budget (80 MiB); the third must QUEUE
            deadline = time.time() + 30
            while time.time() < deadline:
                st = svc.stats()["admission"]
                if len(st["admitted"]) == 2 and len(st["waiting"]) == 1:
                    break
                time.sleep(0.01)
            st = svc.stats()["admission"]
            assert len(st["admitted"]) == 2 and len(st["waiting"]) == 1, st
            assert st["waiting"][0][0] == hs[2].query_id
            assert hs[2].status == "queued"
            # a finishing query returns budget and releases the waiter
            for h in hs:
                got = _sorted(h.to_df(timeout=300), ["k"])
                pd.testing.assert_frame_equal(got, want, check_dtype=False)
            assert svc.stats()["admission"]["used_bytes"] == 0

    def test_admission_timeout_is_named(self):
        table = _small_table()
        mb = 1 << 20
        with QueryService(pool_size=1, mem_budget=50 * mb,
                          admit_timeout=0.3) as svc:
            h1 = svc.submit(_slow_query(QuokkaContext(), table,
                                        delay_s=0.15),
                            working_set_bytes=40 * mb)
            h2 = svc.submit(_slow_query(QuokkaContext(), table),
                            working_set_bytes=40 * mb)
            with pytest.raises(AdmissionTimeout):
                h2.result(timeout=60)
            assert h1.to_df(timeout=300) is not None

    def test_bounded_queue_rejects_at_submit(self):
        table = _small_table()
        mb = 1 << 20
        with QueryService(pool_size=1, mem_budget=50 * mb, queue_depth=1,
                          admit_timeout=60) as svc:
            h1 = svc.submit(_slow_query(QuokkaContext(), table,
                                        delay_s=0.1),
                            working_set_bytes=40 * mb)
            h2 = svc.submit(_slow_query(QuokkaContext(), table),
                            working_set_bytes=40 * mb)  # waits (1 queued)
            with pytest.raises(AdmissionQueueFull):
                svc.submit(_slow_query(QuokkaContext(), table),
                           working_set_bytes=40 * mb)
            assert h1.to_df(timeout=300) is not None
            assert h2.to_df(timeout=300) is not None


class TestFaultRecovery:
    def test_worker_kill_recovers_both_queries(self, tmp_path):
        """Fault injection (the test_fault_tolerance.py hooks) fires inside
        BOTH queries while they share the pool; each recovers from its own
        namespaced checkpoint + spill WITHOUT replaying the neighbor's
        objects — byte-identical counts and matching sums prove no
        cross-query replay leakage."""
        r = np.random.default_rng(3)
        table = pa.table({
            "k": r.integers(0, 50, 20_000).astype(np.int64),
            "v": r.normal(size=20_000),
        })

        def q(ctx):
            return (ctx.read_dataset(InputArrowDataset(table,
                                                       batch_rows=1024))
                    .groupby("k").agg_sql("sum(v) as sv, count(*) as n"))

        serial = _sorted(q(QuokkaContext()).collect(), ["k"])
        cfg = dict(fault_tolerance=True, hbq_path=str(tmp_path),
                   checkpoint_interval=3,
                   inject_failure={"after_tasks": 12, "channels": [(1, 0)]})
        with QueryService(pool_size=2) as svc:
            ctxs = [QuokkaContext(), QuokkaContext()]
            for c in ctxs:
                for k, v in cfg.items():
                    c.set_config(k, v)
            handles = [svc.submit(q(c)) for c in ctxs]
            for h in handles:
                got = _sorted(h.to_df(timeout=300), ["k"])
                pd.testing.assert_frame_equal(got, serial, rtol=1e-9,
                                              check_dtype=False)
                assert got["n"].tolist() == serial["n"].tolist()
            # both injections actually fired, and both namespaces are GC'd
            # (spill files included — no leaked cross-query replay source)
            for h in handles:
                assert _no_namespace_rows(svc.store, h.query_id)
            leftover = [f for f in os.listdir(svc._spill_dir)
                        if f.startswith("hbq-")]
            assert not leftover, leftover


class TestExecConfigMerge:
    def test_service_level_config_survives_default_context(self):
        """A plain QuokkaContext carries the FULL default exec_config; its
        defaults must not silently revert service-level overrides."""
        t = _small_table(1024)
        with QueryService(pool_size=1,
                          exec_config={"max_pipeline": 9}) as svc:
            ctx = QuokkaContext()  # all defaults
            ctx.set_config("max_pipeline_batches", 11)  # explicit non-default
            h = svc.submit(ctx.from_arrow(t).groupby("k")
                           .agg_sql("sum(v) as sv"))
            cfg = h._s.graph.exec_config
            assert cfg["max_pipeline"] == 9       # service override kept
            assert cfg["max_pipeline_batches"] == 11  # ctx non-default wins
            assert h.to_df(timeout=300) is not None


class TestNamespacedStore:
    def test_two_namespaces_do_not_collide(self):
        root = ControlStore()
        a, b = root.namespace("qa"), root.namespace("qb")
        a.tset("LIT", (0, 0), 5)
        b.tset("LIT", (0, 0), 9)
        a.sadd("DST", (0, 0), "done")
        a.sadd("SAT", 3)
        b.sadd("SAT", 4)
        a.tape_append(0, 0, ("exec", 1, [], True))
        assert a.tget("LIT", (0, 0)) == 5
        assert b.tget("LIT", (0, 0)) == 9
        assert a.scontains("DST", (0, 0), "done")
        assert not b.scontains("DST", (0, 0), "done")
        assert a.smembers("SAT") == {3} and b.smembers("SAT") == {4}
        assert a.tape_len(0, 0) == 1 and b.tape_len(0, 0) == 0
        from quokka_tpu.runtime.task import ExecutorTask

        a.ntt_push(2, ExecutorTask(2, 0, 0, 0, {}))
        assert a.ntt_total() == 1 and b.ntt_total() == 0
        dropped = root.drop_namespace("qa")
        assert dropped > 0
        assert a.tget("LIT", (0, 0)) is None
        assert b.tget("LIT", (0, 0)) == 9  # the neighbor is untouched
        assert b.smembers("SAT") == {4}

    def test_one_shot_path_drops_its_namespace(self):
        ctx = QuokkaContext()
        t = _small_table(1024)
        df = ctx.from_arrow(t).groupby("k").agg_sql("sum(v) as sv").collect()
        assert len(df) > 0
        g = ctx.latest_graph
        assert g.query_id is not None
        assert _no_namespace_rows(g.root_store, g.query_id)
        assert g.metrics(), "metrics must survive the namespace GC"


def test_two_way_runs_both_queries_concurrently(tpch_paths):
    """A two-worker service overlaps two submitted queries.  (Whether the
    overlap beats running them back to back is a speed: the benchmark's
    two-client cells measure it on the chip; as a pass/fail host-clock race
    it failed on any loaded box.)"""
    with QueryService(pool_size=2) as svc:
        h1 = svc.submit(q1_stream(QuokkaContext(), tpch_paths))
        h2 = svc.submit(q3_stream(QuokkaContext(), tpch_paths))
        assert len(h1.to_df(timeout=300)) > 0
        assert len(h2.to_df(timeout=300)) > 0
        t1, t2 = h1.timings(), h2.timings()
    # both were running at once: each started before the other finished
    assert max(t1["started_at"], t2["started_at"]) < min(
        t1["finished_at"], t2["finished_at"]), (t1, t2)
