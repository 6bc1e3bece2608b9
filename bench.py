"""Benchmark: TPC-H Q1 / Q3 / Q5 through the full engine on the local chip.

Prints one JSON line per query plus a FINAL summary line (the line of
record — the driver parses the last JSON line):

  {"metric": "tpch_q135_speedup_geomean_per_chip", "value": N, "unit": "x",
   "vs_baseline": N, "detail": {...}}

Baseline derivation (BASELINE.md): the reference's captured TPC-H run
(`blocking-runtime`, SF100 on 4 workers, 3 repeats each) shows

  Q1 ~= 9.56 s   (blocking-runtime:27,53,79)
  Q3 ~= 14.58 s  (blocking-runtime:113,147,181 — the l_orderkey/o_orderdate/
                  o_shippriority/revenue result block confirms the query)
  Q5 ~= 22.08 s  (blocking-runtime:220,259,298 — nation/revenue block)

Normalised to per-worker-per-SF seconds (work scales linearly with SF):
baseline_seconds(q, sf) = t_ref * 4 workers / 100 SF * sf.  A query's
speedup = baseline_seconds / our_seconds on ONE chip; vs_baseline >= 1.0
means one chip matches one reference worker's per-SF efficiency.  For Q1
this is arithmetically identical to the GB/s-scanned-per-chip metric of
earlier rounds (0.654 GB/s/worker), which is still emitted for continuity.

All device work runs in a SUPERVISED CHILD process with a hard timeout (this
parent never imports jax, so the chip belongs to one process at a time):
probe -> measure; on failure/timeout the child is killed and the measurement
retries once.  A run that finds no accelerator fails: there is no CPU
fallback, and every line names the platform it ran on.
"""

import json
import math
import os
import subprocess
import sys
import time

BASELINE_GBPS_PER_WORKER = 0.654
# blocking-runtime per-query averages (seconds, SF100, 4 workers)
REF_SECONDS_SF100_4W = {"q1": 9.559, "q3": 14.579, "q5": 22.081}
# asof join + sum: 1.3B quotes x 250M trades in ~35 s on 4 workers
# (BASELINE.md / blog/orderedstreams.md:51) => rows/s per worker
REF_ASOF_ROWS_PER_S_PER_WORKER = (1.3e9 + 2.5e8) / 35.0 / 4.0

# Plan-invariant verification (analysis/planck.py QK021-QK024) is default-ON
# for the bench: every optimizer pass of every benched plan is checked, and
# the per-query cost is reported as detail.plan_verify (plan-time only —
# never on the push path; acceptance is <= 5 ms per plan).
os.environ.setdefault("QK_PLAN_VERIFY", "1")

SF = float(os.environ.get("QUOKKA_BENCH_SF", "1.0"))
CACHE = os.environ.get("QUOKKA_BENCH_CACHE", "/tmp/quokka_tpu_bench")
# generous: a cold compile of the full kernel set is minutes; a healthy
# steady-state run is seconds
MEASURE_TIMEOUT = int(os.environ.get("QUOKKA_BENCH_TIMEOUT", "2400"))

BENCH_TABLES = ["lineitem", "orders", "customer", "supplier", "nation", "region"]

# tick-backtest scale (rows), ~the reference's 5.2:1 quote:trade ratio
ASOF_QUOTES = int(6_000_000 * SF)
ASOF_TRADES = int(1_150_000 * SF)
ASOF_SYMBOLS = 100


def ensure_data():
    """Generate-and-cache every table Q1/Q3/Q5 touch plus the tick-backtest
    trades/quotes; returns {name: path}."""
    os.makedirs(CACHE, exist_ok=True)
    paths = {
        t: os.path.join(CACHE, f"{t}_sf{SF}.parquet") for t in BENCH_TABLES
    }
    if not all(os.path.exists(p) for p in paths.values()):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tests"))
        import tpch_data

        tables = tpch_data.generate(sf=SF, seed=42)
        import pyarrow.parquet as pq

        for t, p in paths.items():
            if not os.path.exists(p):
                pq.write_table(tables[t], p, row_group_size=1 << 20)
    for t, n_rows, cols in (
        ("trades", ASOF_TRADES, "t"),
        ("quotes", ASOF_QUOTES, "q"),
    ):
        p = os.path.join(CACHE, f"{t}_sf{SF}.parquet")
        paths[t] = p
        if not os.path.exists(p):
            import numpy as np
            import pyarrow as pa
            import pyarrow.parquet as pq

            r = np.random.default_rng(7 if cols == "t" else 8)
            span = 86_400_000  # one trading day in ms
            times = np.sort(r.integers(0, span, n_rows)).astype(np.int64)
            syms = np.array([f"S{i:03d}" for i in range(ASOF_SYMBOLS)])
            table = {"time": times,
                     "symbol": syms[r.integers(0, ASOF_SYMBOLS, n_rows)]}
            if cols == "t":
                table["size"] = r.integers(1, 500, n_rows).astype(np.int64)
            else:
                table["bid"] = r.uniform(10, 500, n_rows).round(3)
            pq.write_table(pa.table(table), p, row_group_size=1 << 20)
    return paths


Q1_COLS = [
    "l_returnflag",
    "l_linestatus",
    "l_quantity",
    "l_extendedprice",
    "l_discount",
    "l_tax",
    "l_shipdate",
]

Q1_AGGS = (
    "sum(l_quantity) as sum_qty, "
    "sum(l_extendedprice) as sum_base_price, "
    "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
    "avg(l_quantity) as avg_qty, "
    "avg(l_extendedprice) as avg_price, "
    "avg(l_discount) as avg_disc, "
    "count(*) as count_order"
)


def _ctx():
    from quokka_tpu import QuokkaContext

    return QuokkaContext(io_channels=3, exec_channels=2)


def build_q1(paths, ctx=None):
    ctx = ctx or _ctx()
    return (
        ctx.read_parquet(paths["lineitem"], columns=Q1_COLS)
        .filter_sql("l_shipdate <= date '1998-12-01' - interval '90' day")
        .groupby(["l_returnflag", "l_linestatus"])
        .agg_sql(Q1_AGGS)
    )


def run_q1(paths):
    q = build_q1(paths)
    t0 = time.time()
    df = q.collect()
    dt = time.time() - t0
    assert len(df) == 6, df
    return dt


def build_q3(paths, ctx=None):
    from quokka_tpu.expression import col

    ctx = ctx or _ctx()
    lineitem = ctx.read_parquet(
        paths["lineitem"],
        columns=["l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"],
    )
    orders = ctx.read_parquet(
        paths["orders"],
        columns=["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    )
    customer = ctx.read_parquet(
        paths["customer"], columns=["c_custkey", "c_mktsegment"]
    )
    return (
        lineitem.filter_sql("l_shipdate > date '1995-03-15'")
        .join(
            orders.filter_sql("o_orderdate < date '1995-03-15'"),
            left_on="l_orderkey",
            right_on="o_orderkey",
        )
        .join(
            customer.filter(col("c_mktsegment") == "BUILDING"),
            left_on="o_custkey",
            right_on="c_custkey",
        )
        .groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue")
        .top_k(["revenue"], 10, [True])
    )


def run_q3(paths):
    q = build_q3(paths)
    t0 = time.time()
    df = q.collect()
    dt = time.time() - t0
    assert 0 < len(df) <= 10, df
    return dt


def build_q5(paths, ctx=None):
    from quokka_tpu.expression import col

    ctx = ctx or _ctx()
    lineitem = ctx.read_parquet(
        paths["lineitem"],
        columns=["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
    )
    orders = ctx.read_parquet(
        paths["orders"], columns=["o_orderkey", "o_custkey", "o_orderdate"]
    )
    customer = ctx.read_parquet(
        paths["customer"], columns=["c_custkey", "c_nationkey"]
    )
    supplier = ctx.read_parquet(
        paths["supplier"], columns=["s_suppkey", "s_nationkey"]
    )
    nation = ctx.read_parquet(
        paths["nation"], columns=["n_nationkey", "n_name", "n_regionkey"]
    )
    region = ctx.read_parquet(paths["region"], columns=["r_regionkey", "r_name"])
    return (
        lineitem.join(
            orders.filter_sql(
                "o_orderdate >= date '1994-01-01' and o_orderdate < date '1995-01-01'"
            ),
            left_on="l_orderkey",
            right_on="o_orderkey",
        )
        .join(customer, left_on="o_custkey", right_on="c_custkey")
        .join(
            supplier,
            left_on=["l_suppkey", "c_nationkey"],
            right_on=["s_suppkey", "s_nationkey"],
        )
        .join(nation, left_on="c_nationkey", right_on="n_nationkey")
        .join(
            region.filter(col("r_name") == "ASIA"),
            left_on="n_regionkey",
            right_on="r_regionkey",
        )
        .groupby("n_name")
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue")
    )


def run_q5(paths):
    q = build_q5(paths)
    t0 = time.time()
    df = q.collect()
    dt = time.time() - t0
    assert 0 < len(df) <= 5, df
    return dt


def build_asof(paths, ctx=None):
    """Tick backtest core: asof-join trades<-quotes by symbol + grouped sum
    (BASELINE.json config 4; the reference's apps/time-series headline —
    blog/orderedstreams.md:51)."""
    ctx = ctx or _ctx()
    t = ctx.read_sorted_parquet(paths["trades"], sorted_by="time")
    q = ctx.read_sorted_parquet(paths["quotes"], sorted_by="time")
    return (
        t.join_asof(q, on="time", by="symbol")
        .with_columns_sql("bid * size as notional")
        .groupby("symbol")
        .agg_sql("sum(notional) as total, count(*) as n")
    )


def run_asof(paths):
    qry = build_asof(paths)
    t0 = time.time()
    df = qry.collect()
    dt = time.time() - t0
    assert 0 < len(df) <= ASOF_SYMBOLS, df
    return dt


QUERIES = {"q1": run_q1, "q3": run_q3, "q5": run_q5}
BUILDERS = {"q1": build_q1, "q3": build_q3, "q5": build_q5}


# -- skewjoin: adaptive-vs-static on a zipfian-keyed build side -------------
# One fat key holds SKEWJOIN_FAT of the build rows, so static hash
# partitioning lands ~90% of the build on ONE channel — past the grace-join
# spill cliff (SPILL_JOIN_BUILD_ROWS, lowered for the bench so SF doesn't
# matter) that channel builds on disk.  The adaptive run's skew trigger
# (planner/adapt.py) salts the fat partition across all channels, keeping
# every build under the cliff and in memory.  The metric is the wall-clock
# ratio static/adaptive; `--check` requires >= SKEWJOIN_MIN_SPEEDUP.
SKEWJOIN_BUILD_ROWS = int(300_000 * max(SF, 0.1))
SKEWJOIN_KEYS = 1_000
SKEWJOIN_FAT = 0.9
SKEWJOIN_SPILL_ROWS = int(SKEWJOIN_BUILD_ROWS * 2 / 3)
# small row groups: the skew trigger can only fire on a batch boundary, so
# finer batches mean an earlier re-partition (less pre-trigger residue on
# the fat channel) and a static run that pays the spill tier per batch
SKEWJOIN_ROW_GROUP = 1 << 13
# grace-join fanout for the scenario: the spill tier sized for a genuinely
# memory-tight box (64 partitions of ~4.5k rows each at SF 1), not the
# roomy default — this is what the adaptive run gets to skip entirely
SKEWJOIN_SPILL_FANOUT = 64


def _skewjoin_paths():
    """Seeded zipfian-ish skew pair, cached beside the TPC-H parquet."""
    probe_p = os.path.join(CACHE, f"skewprobe_sf{SF}.parquet")
    build_p = os.path.join(
        CACHE, f"skewbuild_sf{SF}_rg{SKEWJOIN_ROW_GROUP}.parquet")
    if not (os.path.exists(probe_p) and os.path.exists(build_p)):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        r = np.random.default_rng(20260807)
        keys = r.integers(1, SKEWJOIN_KEYS,
                          SKEWJOIN_BUILD_ROWS).astype(np.int64)
        keys[r.random(SKEWJOIN_BUILD_ROWS) < SKEWJOIN_FAT] = 0
        pq.write_table(pa.table({
            "k": keys,
            "v": r.integers(0, 1000, SKEWJOIN_BUILD_ROWS).astype(np.int64),
        }), build_p, row_group_size=SKEWJOIN_ROW_GROUP)
        pq.write_table(pa.table({
            "pk": np.arange(SKEWJOIN_KEYS, dtype=np.int64),
            "g": np.arange(SKEWJOIN_KEYS, dtype=np.int64) % 50,
        }), probe_p)
    return {"probe": probe_p, "build": build_p}


def build_skewjoin(paths, ctx=None):
    ctx = ctx or _ctx()
    probe = ctx.read_parquet(paths["probe"])
    build = ctx.read_parquet(paths["build"])  # right side = build = skewed
    return (probe.join(build, left_on="pk", right_on="k")
            .groupby("g").agg_sql("sum(v) as sv, count(*) as n"))


def run_skewjoin(paths):
    qry = build_skewjoin(paths)
    t0 = time.time()
    df = qry.collect()
    dt = time.time() - t0
    assert 0 < len(df) <= 50, df
    return dt


def _quantile(xs, q):
    xs = sorted(xs)
    if not xs:
        return None
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def measure_service(paths, smoke=False):
    """``bench.py --service``: submit the TPC-H queries concurrently through
    a persistent QueryService (2- and 4-way) and report aggregate throughput
    plus per-query p50/p95 latency next to the serial numbers.

    N-way = N concurrent client streams, each submitting q1, q3, q5 (the
    TPC-H throughput-test shape); every stream's queries run on ONE shared
    worker pool with warm scan/compile caches.  The line of record compares
    the N-way wall clock against the same N passes run serially back-to-back
    on the equally-warm one-shot path."""
    from quokka_tpu.service import QueryService

    ways_list = [2] if smoke else [2, 4]
    qnames = list(BUILDERS)
    # warm pass (compiles every query shape + fills the scan cache), then
    # the timed serial pass the concurrent walls compare against
    for name in qnames:
        QUERIES[name](paths)
    serial_seconds = {name: QUERIES[name](paths) for name in qnames}
    serial_pass_s = sum(serial_seconds.values())
    lines = []
    speedups = []
    for ways in ways_list:
        # queued submissions legitimately wait ~a full round of query
        # runtime behind max_concurrent: give admission the same patience
        # as the measurement itself, or slow hosts die on AdmissionTimeout
        svc = QueryService(pool_size=ways, max_concurrent=ways,
                           inflight_per_query=2,
                           admit_timeout=float(MEASURE_TIMEOUT),
                           query_timeout=float(MEASURE_TIMEOUT))
        try:
            t0 = time.time()
            handles = []
            for _stream in range(ways):
                for name in qnames:
                    stream = BUILDERS[name](paths)
                    handles.append((name, svc.submit(stream)))
            per_query = {}
            for name, h in handles:
                ds = h.result(timeout=MEASURE_TIMEOUT)
                if smoke and ds.to_arrow() is None:
                    raise RuntimeError(
                        f"service smoke: {name} returned an empty result")
                t = h.timings()
                t["latency"] = h.latency_stats()  # per-task p50/p95
                per_query.setdefault(name, []).append(t)
            wall = time.time() - t0
        finally:
            svc.shutdown()
        n_queries = ways * len(qnames)
        serial_wall = ways * serial_pass_s
        speedup = serial_wall / wall if wall > 0 else 0.0
        speedups.append(speedup)
        lat_detail = {}
        for name, ts in per_query.items():
            runs = [t["run_s"] for t in ts if t["run_s"] is not None]
            totals = [
                t["finished_at"] - t["submitted_at"] for t in ts
                if t["finished_at"] is not None
            ]
            task_p50 = [t["latency"]["p50"] for t in ts
                        if t.get("latency") and t["latency"]["p50"]]
            task_p95 = [t["latency"]["p95"] for t in ts
                        if t.get("latency") and t["latency"]["p95"]]
            lat_detail[name] = {
                "serial_s": round(serial_seconds[name], 4),
                "run_p50_s": round(_quantile(runs, 0.5), 4),
                "run_p95_s": round(_quantile(runs, 0.95), 4),
                "total_p50_s": round(_quantile(totals, 0.5), 4),
                "total_p95_s": round(_quantile(totals, 0.95), 4),
                # per-TASK dispatch-latency quantiles from the typed
                # per-query histograms (QueryService.stats() shape)
                "task_p50_s": round(_quantile(task_p50, 0.5), 6)
                if task_p50 else None,
                "task_p95_s": round(_quantile(task_p95, 0.5), 6)
                if task_p95 else None,
            }
            sys.stderr.write(
                f"bench --service [{ways}-way] {name}: "
                f"task p50={lat_detail[name]['task_p50_s']}s "
                f"p95={lat_detail[name]['task_p95_s']}s over "
                f"{sum(t['latency']['count'] for t in ts if t.get('latency'))}"
                " dispatches\n")
        lines.append({
            "metric": f"service_{ways}way_aggregate_speedup",
            "value": round(speedup, 4),
            "unit": "x",
            "vs_baseline": round(speedup, 4),
            "detail": {
                "sf": SF,
                "ways": ways,
                "cpus": os.cpu_count(),  # 1-core hosts cannot beat serial
                "queries": n_queries,
                "wall_s": round(wall, 4),
                "serial_back_to_back_s": round(serial_wall, 4),
                "aggregate_qps": round(n_queries / wall, 4),
                "serial_qps": round(n_queries / serial_wall, 4),
                "per_query": lat_detail,
            },
        })
    # mixed-load line: the same 2-way workload with one CANCELLED and one
    # DEADLINE-EXCEEDED query in the mix.  Both casualties carry oversized
    # working-set declarations so they wait QUEUED behind the running
    # normals — the cancel and the deadline land deterministically at the
    # admission queue, never racing a finish — and first-class cancellation
    # must cost the surviving queries nothing: the line of record is the
    # mixed-run aggregate qps over the plain 2-way run's.
    from quokka_tpu.service import DeadlineExceeded, QueryCancelled

    ways = ways_list[0]
    # the byte budget is what pins the casualties: 1 PiB declarations can
    # never admit under 4 GiB, no matter how fast the normals drain
    svc = QueryService(pool_size=ways, max_concurrent=ways,
                       inflight_per_query=2, mem_budget=4 << 30,
                       admit_timeout=float(MEASURE_TIMEOUT),
                       query_timeout=float(MEASURE_TIMEOUT))
    try:
        t0 = time.time()
        handles = []
        for _stream in range(ways):
            for name in qnames:
                handles.append((name, svc.submit(BUILDERS[name](paths))))
        victim = svc.submit(BUILDERS[qnames[0]](paths),
                            working_set_bytes=1 << 50)
        # the deadline must expire while the normals still hold the pool
        # (the queued-reaper path) — generous values race a warm cache's
        # fast drain, after which an oversized query may legally run alone
        expired = svc.submit(BUILDERS[qnames[0]](paths),
                             working_set_bytes=1 << 50, deadline_s=0.02)
        victim.cancel(wait=False)
        for name, h in handles:
            h.result(timeout=MEASURE_TIMEOUT)
        wall = time.time() - t0
        try:
            victim.result(timeout=60)
            raise RuntimeError("bench --service mixed load: the cancelled "
                               "query returned a result")
        except QueryCancelled:
            pass
        try:
            expired.result(timeout=60)
            raise RuntimeError("bench --service mixed load: the deadline "
                               "query returned a result")
        except DeadlineExceeded:
            pass
        leaked = svc.admission.stats()["used_bytes"]
        if leaked:
            raise RuntimeError(
                f"bench --service mixed load: {leaked} admission bytes "
                "still held after cancel/deadline/finish")
    finally:
        svc.shutdown()
    n_queries = ways * len(qnames)
    mixed_qps = n_queries / wall if wall > 0 else 0.0
    plain_qps = lines[0]["detail"]["aggregate_qps"]
    lines.append({
        "metric": "service_mixed_load_throughput_ratio",
        "value": round(mixed_qps / plain_qps if plain_qps else 0.0, 4),
        "unit": "x",
        "vs_baseline": round(mixed_qps / plain_qps if plain_qps else 0.0, 4),
        "detail": {
            "sf": SF,
            "ways": ways,
            "queries": n_queries,
            "wall_s": round(wall, 4),
            "mixed_qps": round(mixed_qps, 4),
            "plain_qps": plain_qps,
            "cancelled": 1,
            "deadline_exceeded": 1,
            "admission_bytes_leaked": 0,
        },
    })
    for ln in lines:
        print(json.dumps(ln))
    geomean = math.exp(sum(math.log(max(s, 1e-9)) for s in speedups)
                       / len(speedups))
    print(json.dumps({
        "metric": "service_aggregate_speedup_geomean",
        "value": round(geomean, 4),
        "unit": "x",
        "vs_baseline": round(geomean, 4),
        "detail": {"sf": SF, "ways": ways_list,
                   "serial_seconds": {k: round(v, 4)
                                      for k, v in serial_seconds.items()}},
    }))
    sys.stdout.flush()
    return geomean

# span-name prefix -> breakdown bucket (obs/spans.py names).  push./spill.
# are TRANSFER (partition push bookkeeping + HBQ spill d2h/write), matching
# the critical-path profiler's attribution (obs/critpath.py) so the two
# reports agree on where exchange time goes.
_BUCKET_PREFIXES = (
    (("reader.", "prefetch"), "read_s"),
    (("bridge.", "emit.", "push.", "spill.", "count_valid"), "transfer_s"),
    (("exec.", "done.", "source."), "compute_s"),
)


def _span_breakdown(span_stats):
    """Collapse a spans.stats() snapshot into read/transfer/compute buckets
    (compile time is taken from compilestats deltas, not spans)."""
    buckets = {"read_s": 0.0, "transfer_s": 0.0, "compute_s": 0.0,
               "other_s": 0.0}
    for name, st in span_stats.items():
        for prefixes, bucket in _BUCKET_PREFIXES:
            if name.startswith(prefixes):
                buckets[bucket] += st["total_s"]
                break
        else:
            buckets["other_s"] += st["total_s"]
    return {k: round(v, 4) for k, v in buckets.items()}


def bench_out_dir() -> str:
    """Per-run bench artifacts (bench_obs.json, timed multichip JSON) land
    under ONE gitignored output dir instead of littering the repo root —
    override the dir with QUOKKA_BENCH_OUT."""
    d = os.environ.get("QUOKKA_BENCH_OUT", "bench_out")
    os.makedirs(d, exist_ok=True)
    return d


def _operators_detail():
    """EXPLAIN ANALYZE actuals of the most recently finished query — the
    opstats ledger stashes its final snapshot at query GC, so reading it
    right after a timed run attributes to that run.  None when the ledger
    saw nothing (itself a regression on join/asof queries: `--check`)."""
    try:
        from quokka_tpu.obs import explain as obs_explain
        from quokka_tpu.obs import opstats as obs_opstats

        return obs_explain.operators_detail(
            obs_opstats.OPSTATS.last_finished())
    except Exception as e:  # noqa: BLE001 — stats must not kill the bench
        sys.stderr.write(f"bench: operators detail unavailable: {e!r}\n")
        return None


def _efficiency_detail():
    """Device-efficiency digest of the most recently finished query
    (obs/devprof.py figures attached to the opstats snapshot at query GC):
    calibrated peaks + per-operator achieved FLOP/s, bandwidth and
    roofline %%.  None when the plane saw nothing."""
    try:
        from quokka_tpu.obs import explain as obs_explain
        from quokka_tpu.obs import opstats as obs_opstats

        return obs_explain.efficiency_detail(
            obs_opstats.OPSTATS.last_finished())
    except Exception as e:  # noqa: BLE001 — stats must not kill the bench
        sys.stderr.write(f"bench: efficiency detail unavailable: {e!r}\n")
        return None


def _progress_detail():
    """Final progress snapshot of the most recently finished query (the
    health plane stashes it at query GC, same discipline as the opstats
    detail): fraction/basis/elapsed prove the estimator tracked the run.
    None when the tracker saw nothing."""
    try:
        from quokka_tpu.obs import progress as obs_progress

        snap = obs_progress.TRACKER.last_finished()
        if not snap:
            return None
        return {k: snap.get(k) for k in
                ("fraction", "basis", "elapsed_s", "source_bytes_done",
                 "source_bytes_total", "profiled_ops")}
    except Exception as e:  # noqa: BLE001 — stats must not kill the bench
        sys.stderr.write(f"bench: progress detail unavailable: {e!r}\n")
        return None


def _fused_stages(operators):
    """How many whole-stage-fused operators actually dispatched in the last
    timed run (detail.operators rows whose op is a FusedStage,
    ops/stagefuse.py).  The join queries must report >= 1: `--check` treats
    a fresh join line without the field — or with zero fused stages while
    fusion is on by default — as the fusion win silently evaporating."""
    if not operators:
        return 0
    return sum(1 for o in operators.get("operators") or ()
               if str(o.get("op", "")).startswith("FusedStage")
               and o.get("dispatches", 0) > 0)


def _write_obs_summary(obs_per_query):
    """Per-query span/counter breakdown JSON next to the timing output
    (BENCH_*.json gains compile-vs-compute-vs-transfer visibility)."""
    from quokka_tpu import obs

    path = os.environ.get("QUOKKA_BENCH_OBS") or os.path.join(
        bench_out_dir(), "bench_obs.json")
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"per_query": obs_per_query,
                       "counters": obs.REGISTRY.snapshot()}, f, indent=2)
        sys.stderr.write(f"bench: per-query span/counter summary: {path}\n")
    except OSError as e:
        sys.stderr.write(f"bench: could not write obs summary {path}: {e}\n")


# `--check` floor for the skewjoin line: the adaptive run must beat the
# statically-skewed run by at least this factor (the tentpole's headline)
SKEWJOIN_MIN_SPEEDUP = 2.0


def measure_skewjoin(platform):
    """The skewjoin_adaptive_speedup line: the same zipfian join timed with
    runtime adaptation on (default) vs off (``QK_ADAPT=0``).

    Both variants plan COLD with cardprofile persistence OFF
    (QK_CARDPROFILE_DIR=""), so every run's plan is identical except for
    the adaptation mark — otherwise the first run's measured figures would
    shrink the tiny-output join to one channel and erase the very skew the
    trigger exists to fix.  The grace-join spill cliff is lowered to
    SKEWJOIN_SPILL_ROWS so the static run's fat channel builds on disk
    while the adapted run's salted channels all stay in memory.  One
    warmup run per variant pays the compiles; the value is best-of-2
    static seconds over best-of-2 adaptive seconds."""
    from quokka_tpu import config as qk_config

    env_overrides = {
        "QK_CARDPROFILE_DIR": "",
        # the skewed side must go through a hash EXCHANGE for the runtime
        # trigger to have an edge to re-partition: pin broadcast off
        "QK_BROADCAST_BYTES": "1",
        "QK_SKEW_RATIO": "1.5",
        "QK_ADAPT_MIN_ROWS": "20000",
    }
    saved_env = {k: os.environ.get(k) for k in (*env_overrides, "QK_ADAPT")}
    os.environ.update(env_overrides)
    saved_spill = qk_config.SPILL_JOIN_BUILD_ROWS
    saved_fanout = qk_config.SPILL_JOIN_FANOUT
    qk_config.SPILL_JOIN_BUILD_ROWS = SKEWJOIN_SPILL_ROWS
    qk_config.SPILL_JOIN_FANOUT = SKEWJOIN_SPILL_FANOUT
    try:
        paths = _skewjoin_paths()
        os.environ["QK_ADAPT"] = "0"
        run_skewjoin(paths)  # compile warm-up (static plan)
        static = sorted(run_skewjoin(paths) for _ in range(2))
        os.environ.pop("QK_ADAPT", None)
        run_skewjoin(paths)  # warm-up (adaptive: same kernels + salt/replicate)
        adaptive = sorted(run_skewjoin(paths) for _ in range(2))
        ops_detail = _operators_detail()
        planner = (ops_detail or {}).get("planner") or []
        adapted = any(d.get("kind") == "adapt_runtime" for d in planner)
        speedup = static[0] / adaptive[0]
        sys.stderr.write(
            f"bench: skewjoin static {static[0]:.3f}s adaptive "
            f"{adaptive[0]:.3f}s ({speedup:.2f}x, adapted={adapted})\n")
        return {
            "metric": "skewjoin_adaptive_speedup",
            "value": round(speedup, 4),
            "unit": "x",
            # normalized so 1.0 == exactly the required 2x floor
            "vs_baseline": round(speedup / SKEWJOIN_MIN_SPEEDUP, 4),
            "detail": {
                "sf": SF, "platform": platform,
                "build_rows": SKEWJOIN_BUILD_ROWS,
                "fat_fraction": SKEWJOIN_FAT,
                "spill_join_rows": SKEWJOIN_SPILL_ROWS,
                "spill_join_fanout": SKEWJOIN_SPILL_FANOUT,
                "seconds_static": [round(x, 4) for x in static],
                "seconds_adaptive": [round(x, 4) for x in adaptive],
                # proof the adaptive run actually re-partitioned mid-query
                # (`--check` fails a fresh line where the trigger slept)
                "adapted": adapted,
                "operators": ops_detail,
            },
        }
    finally:
        qk_config.SPILL_JOIN_BUILD_ROWS = saved_spill
        qk_config.SPILL_JOIN_FANOUT = saved_fanout
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def measure(paths):
    """The full measurement (runs inside the supervised child).  Emits one
    JSON line per query + the final summary line on fd 1 and exits 0."""
    import jax

    platform = jax.default_backend()
    nbytes = os.path.getsize(paths["lineitem"])
    per_query = {}
    from quokka_tpu.obs import spans as obs_spans
    from quokka_tpu.ops import strategy as kstrategy
    from quokka_tpu.utils import compilestats

    # the kernel-strategy matrix decides which kernels the bench times:
    # calibrate once per backend (persisted under the compile plane's
    # fingerprint) BEFORE the per-query compile snapshots, so the
    # calibration microbench's compiles never count as query warmup.
    # Every benched line then records the strategies that actually RAN
    # (detail.strategy), which `bench.py --check` validates against the
    # bench platform — the permanent fix for measuring a path the target
    # backend never runs (VERDICT r5 #2).
    kstrategy.ensure_calibrated()
    # device-profile peaks (obs/devprof.py): calibrate alongside the kernel
    # strategy matrix — same fingerprint discipline, same pre-query timing
    # so the microbench compiles never count as query warmup.  Each benched
    # line then carries detail.efficiency (achieved vs roofline).
    from quokka_tpu.obs import devprof as qk_devprof

    qk_devprof.ensure_calibrated()
    strategy_meta = {"choices": kstrategy.choices(),
                     "sources": kstrategy.sources()}
    sys.stderr.write(f"bench: kernel strategies {strategy_meta['choices']} "
                     f"(sources {strategy_meta['sources']})\n")

    # span aggregation ON regardless of QUOKKA_TRACE: the per-query
    # breakdown JSON is part of the bench contract; QUOKKA_TRACE=1 only
    # decides whether the human-readable summary prints too (read through
    # spans.enabled() — the one owner of the env truthiness rule)
    trace_print = obs_spans.enabled()
    obs_spans.set_enabled(True)
    obs_per_query = {}
    from quokka_tpu import obs as qk_obs

    def _shuffle_snap():
        snap = qk_obs.REGISTRY.snapshot()
        return {k: snap.get(k, 0) for k in
                ("shuffle.bytes", "shuffle.host_syncs", "shuffle.spill_bytes")}

    from quokka_tpu.analysis import planck as qk_planck
    from quokka_tpu.obs import memplane

    for qname, fn in QUERIES.items():
        ref = REF_SECONDS_SF100_4W[qname] * 4.0 / 100.0 * SF
        obs_spans.reset()
        kstrategy.reset_used()
        c0 = compilestats.snapshot()
        sh0 = _shuffle_snap()
        pv0 = dict(qk_planck.VERIFY_STATS)
        # memory plane: peak resets to current live before the query, so
        # detail.memory reports THIS query's high-water mark, not the
        # session's
        memplane.LEDGER.reset_peak()
        warm = fn(paths)  # compiles the kernel set for this query shape
        extra = {}
        if qname == "q1":
            # cold = compile warm but scan (buffer-pool) cache empty: pays
            # parquet decode + host encode + h2d transfer every batch.
            # (Runs before the compile snapshot so any shape first seen on
            # the cold path counts as warmup, not as timed-run churn.)
            from quokka_tpu.runtime import scancache

            scancache.clear()
            cold = fn(paths)
            extra = {
                "q1_seconds_cold_scan": round(cold, 4),
                "cold_scan_gbps": round(nbytes / cold / 1e9, 4),
                "cold_vs_baseline": round(
                    nbytes / cold / 1e9 / BASELINE_GBPS_PER_WORKER, 4
                ),
            }
        c1 = compilestats.snapshot()
        # two span windows so the buckets reconcile with their neighbors:
        # "warmup" pairs with warmup_seconds/compile_seconds_warmup,
        # "timed_runs" sums over the 3 runs whose best is `seconds`
        spans_warmup = obs_spans.stats()
        if trace_print:
            sys.stderr.write(f"[spans] {qname} warmup\n"
                             + obs_spans.summary() + "\n")
        obs_spans.reset()
        # critical-path profile of the LAST timed run: the DAG rebuilt from
        # the flight recorder, wall time attributed into compile/scan/
        # transfer/compute/queue/stall buckets (obs/critpath.py)
        from quokka_tpu.obs import critpath as obs_critpath

        sh1 = _shuffle_snap()
        times = [fn(paths) for _ in range(2)]
        with obs_critpath.profile() as _prof:
            times.append(fn(paths))
        crit = None
        if _prof.result is not None:
            crit = _prof.result.to_json()
            crit["measured_wall_s"] = round(times[-1], 4)
            # the full segment list lives in bench_obs.json; the stdout
            # line of record carries the bucket attribution only
            crit_line = {k: v for k, v in crit.items() if k != "path"}
            if trace_print:
                sys.stderr.write(_prof.result.render() + "\n")
        else:
            crit_line = None
        times = sorted(times)
        c2 = compilestats.snapshot()
        sh2 = _shuffle_snap()
        # shuffle volume of the 3 timed runs (counter deltas): bytes through
        # fan-out>1 exchanges, blocking host readbacks on the partition
        # path, and spilled bytes (0 without fault tolerance)
        shuffle_detail = {
            "warmup": {k.split(".", 1)[1]: int(sh1[k] - sh0[k]) for k in sh0},
            "per_timed_run": {k.split(".", 1)[1]: int((sh2[k] - sh1[k]) / 3)
                              for k in sh0},
        }
        t = times[0]
        speedup = ref / t
        spans_timed = obs_spans.stats()
        breakdown = {
            "warmup": {
                **_span_breakdown(spans_warmup),
                "compile_s": round(c1["backend_compile_seconds"]
                                   - c0["backend_compile_seconds"], 3),
            },
            "timed_runs": {
                **_span_breakdown(spans_timed),
                "runs": 3,
                "compile_s": round(c2["backend_compile_seconds"]
                                   - c1["backend_compile_seconds"], 3),
            },
        }
        obs_per_query[qname] = {"spans_warmup": spans_warmup,
                                "spans_timed": spans_timed,
                                "breakdown": breakdown,
                                "critpath": crit}
        if trace_print:
            sys.stderr.write(f"[spans] {qname} timed runs (3)\n"
                             + obs_spans.summary() + "\n")
        ops_detail = _operators_detail()
        pv_plans = qk_planck.VERIFY_STATS["plans"] - pv0["plans"]
        pv_ms = qk_planck.VERIFY_STATS["ms_total"] - pv0["ms_total"]
        per_query[qname] = {
            "seconds": round(t, 4),
            "seconds_all": [round(x, 4) for x in times],
            "warmup_seconds": round(warm, 4),
            "ref_seconds_scaled": round(ref, 4),
            "speedup_vs_ref_per_chip": round(speedup, 4),
            # kernel-reuse proof: warmup pays the real compiles and/or
            # persistent-cache loads, the timed runs must not add any
            "real_compiles_warmup": c1["real_compiles"] - c0["real_compiles"],
            "real_compiles_timed_runs": c2["real_compiles"] - c1["real_compiles"],
            "compile_seconds_warmup": round(
                c1["backend_compile_seconds"] - c0["backend_compile_seconds"], 3
            ),
            "cache_hits_warmup": c1["cache_hits"] - c0["cache_hits"],
            "breakdown": breakdown,
            "shuffle": shuffle_detail,
            # memory-ledger footprint across warmup + timed runs: device
            # high-water mark and spill-bytes delta (obs/memplane.py);
            # `--check` gates peak_bytes growth like warmup_seconds
            "memory": {
                "peak_bytes": int(memplane.LEDGER.peak_bytes()),
                "spill_bytes": int(sh2["shuffle.spill_bytes"]
                                   - sh0["shuffle.spill_bytes"]),
            },
            # the kernel family each strategy-dispatched operator actually
            # executed during this query (ops/strategy.note_used)
            "strategy": kstrategy.used_snapshot(),
            "critpath": crit_line,
            # EXPLAIN ANALYZE actuals of the last timed run (obs/opstats.py
            # snapshot stashed at query GC): per-operator rows/selectivity/
            # time share + the per-exchange-edge skew report.  `--check`
            # treats a missing block on join/asof queries as a regression.
            "operators": ops_detail,
            # proof the whole-stage-fused plan is what was measured: count
            # of FusedStage operators that dispatched (`--check` gates the
            # join lines on this being >= 1)
            "fused_stages": _fused_stages(ops_detail),
            # device-efficiency digest of the last timed run
            # (obs/devprof.py): peaks + per-operator roofline %.  `--check`
            # treats a missing block on join/asof lines as a regression.
            "efficiency": _efficiency_detail(),
            # health plane: the progress estimator's final snapshot for the
            # last timed run (obs/progress.py, stashed at query GC)
            "progress": _progress_detail(),
            # plan-invariant verifier cost (QK021-QK024, plan-time only):
            # per-plan average must stay <= 5 ms
            "plan_verify": {
                "plans": pv_plans,
                "ms_total": round(pv_ms, 3),
                "ms_per_plan": round(pv_ms / pv_plans, 3) if pv_plans else 0.0,
            },
            **extra,
        }
        # QK_SANITIZE=1: the recompile sentinel fails the run outright when
        # the timed runs compiled anything — a warmed query shape must reuse
        # its executables (analysis/sanitize.py)
        from quokka_tpu.analysis import sanitize

        sanitize.check_no_recompiles(c1, c2, context=f"{qname} timed runs")
        if qname == "q1":
            gbps = nbytes / t / 1e9
            print(json.dumps({
                "metric": "tpch_q1_scan_gbps_per_chip",
                "value": round(gbps, 4),
                "unit": "GB/s",
                "vs_baseline": round(gbps / BASELINE_GBPS_PER_WORKER, 4),
                "detail": {"sf": SF, "parquet_bytes": nbytes,
                           "platform": platform, **per_query[qname]},
            }))
        else:
            print(json.dumps({
                "metric": f"tpch_{qname}_speedup_vs_ref_per_chip",
                "value": round(speedup, 4),
                "unit": "x",
                "vs_baseline": round(speedup, 4),
                "detail": {"sf": SF, "platform": platform,
                           **per_query[qname]},
            }))
        sys.stdout.flush()
    # tick backtest: rows/s per chip vs the reference's per-worker rate.
    # The section carries its OWN alarm so an asof compile overrun/wedge
    # skips this one line instead of blowing the child's overall timeout
    # and discarding the already-printed TPC-H lines of record.
    import signal

    def _asof_alarm(sig, frm):
        raise TimeoutError("asof benchmark section timed out")

    old_handler = signal.signal(signal.SIGALRM, _asof_alarm)
    signal.alarm(int(os.environ.get("QUOKKA_BENCH_ASOF_TIMEOUT", "600")))
    try:
        obs_spans.reset()
        kstrategy.reset_used()
        run_asof(paths)  # compile warm-up
        asof_times = sorted(run_asof(paths) for _ in range(3))
        asof_rows = ASOF_TRADES + ASOF_QUOTES
        asof_rps = asof_rows / asof_times[0]
        asof_speedup = asof_rps / REF_ASOF_ROWS_PER_S_PER_WORKER
        asof_ops = _operators_detail()
        print(json.dumps({
            "metric": "tick_asof_rows_per_s_per_chip",
            "value": round(asof_rps),
            "unit": "rows/s",
            "vs_baseline": round(asof_speedup, 4),
            "detail": {
                "sf": SF, "platform": platform,
                "trades": ASOF_TRADES, "quotes": ASOF_QUOTES,
                "seconds_all": [round(x, 4) for x in asof_times],
                "ref_rows_per_s_per_worker": round(REF_ASOF_ROWS_PER_S_PER_WORKER),
                "strategy": kstrategy.used_snapshot(),
                "operators": asof_ops,
                "fused_stages": _fused_stages(asof_ops),
                "efficiency": _efficiency_detail(),
            },
        }))
        sys.stdout.flush()
        asof_spans = obs_spans.stats()
        obs_per_query["asof"] = {
            "spans": asof_spans,
            # one window here: warmup + 3 timed runs (the asof line reports
            # seconds_all, not a single best-run pairing)
            "breakdown": {**_span_breakdown(asof_spans), "runs": 4},
        }
        if trace_print:
            sys.stderr.write("[spans] asof (warmup + 3 timed runs)\n"
                             + obs_spans.summary() + "\n")
    except Exception as e:  # noqa: BLE001 — the TPC-H lines must survive
        sys.stderr.write(f"bench: asof section skipped: {e}\n")
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
    # skewjoin: adaptive-vs-static under zipfian build skew.  Own alarm so
    # a wedge here skips one line, not the already-printed TPC-H lines.
    def _skew_alarm(sig, frm):
        raise TimeoutError("skewjoin benchmark section timed out")

    old_handler = signal.signal(signal.SIGALRM, _skew_alarm)
    signal.alarm(int(os.environ.get("QUOKKA_BENCH_SKEW_TIMEOUT", "600")))
    try:
        print(json.dumps(measure_skewjoin(platform)))
        sys.stdout.flush()
    except Exception as e:  # noqa: BLE001 — the TPC-H lines must survive
        sys.stderr.write(f"bench: skewjoin section skipped: {e}\n")
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
    _write_obs_summary(obs_per_query)
    geomean = math.exp(
        sum(math.log(v["speedup_vs_ref_per_chip"]) for v in per_query.values())
        / len(per_query)
    )
    print(json.dumps({
        "metric": "tpch_q135_speedup_geomean_per_chip",
        "value": round(geomean, 4),
        "unit": "x",
        "vs_baseline": round(geomean, 4),
        "detail": {
            "sf": SF,
            "queries": per_query,
            "ref_seconds_sf100_4workers": REF_SECONDS_SF100_4W,
            "platform": platform,
            "tpu_fallback_to_cpu": platform == "cpu",
            "strategy_matrix": strategy_meta,
        },
    }))
    # roofline-efficiency geomean across every attributed operator of the
    # benched queries (obs/devprof.py): the one number `--trend` tracks for
    # "is the engine getting more or less out of the device per round"
    effs = [r["efficiency"] for q in per_query.values()
            for r in ((q.get("efficiency") or {}).get("operators") or ())
            if r.get("efficiency")]
    if effs:
        eff_geo = math.exp(sum(math.log(e) for e in effs) / len(effs))
        print(json.dumps({
            "metric": "devprof_efficiency_geomean",
            "value": round(eff_geo, 6),
            "unit": "frac",
            "vs_baseline": round(eff_geo, 6),
            "detail": {
                "operators": len(effs),
                "platform": platform,
                "peaks": next((q["efficiency"]["peaks"]
                               for q in per_query.values()
                               if q.get("efficiency")), None),
            },
        }))


def probe_tpu(attempts: int = 2, timeout: int = 150, backoff: int = 20) -> bool:
    """Check for an accelerator from a SUBPROCESS (this parent stays off jax:
    one process owns the chip, and the probe has exited before the measuring
    child starts).  Bounded retries with backoff; False means no accelerator
    answered."""
    probe = (
        "import jax, jax.numpy as jnp;"
        "d = jax.devices();"
        "(jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready();"
        "print('ok', d[0].platform)"
    )
    for i in range(attempts):
        try:
            r = subprocess.run(
                [sys.executable, "-c", probe],
                timeout=timeout, capture_output=True, text=True,
            )
            if r.returncode == 0 and "ok" in r.stdout:
                platform = r.stdout.strip().split()[-1].lower()
                if platform not in ("cpu",):
                    return True
                # JAX picked the CPU: that is NOT an accelerator
                sys.stderr.write(
                    f"bench: probe initialized platform {platform!r}, not TPU\n"
                )
                return False
            sys.stderr.write(
                f"bench: TPU probe {i + 1}/{attempts} failed rc={r.returncode}: "
                f"{(r.stderr or r.stdout)[-200:]}\n"
            )
        except subprocess.TimeoutExpired:
            sys.stderr.write(f"bench: TPU probe {i + 1}/{attempts} timed out\n")
        if i < attempts - 1:
            time.sleep(backoff)
    return False


def _run_child(timeout: int):
    """Run measure() in a child; returns the JSON lines or None on failure."""
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--measure"],
            timeout=timeout, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        sys.stderr.write(
            f"bench: measurement child exceeded {timeout}s\n"
        )
        return None
    if r.returncode != 0:
        sys.stderr.write(f"bench: measurement child rc={r.returncode}:\n"
                         f"{r.stderr[-2000:]}\n")
        return None
    if r.stderr:
        # the child's stderr carries the QUOKKA_TRACE span summaries and the
        # obs-summary path; forward it (stdout stays machine-parseable)
        sys.stderr.write(r.stderr[-8000:])
    lines = [
        ln.strip() for ln in r.stdout.strip().splitlines()
        if ln.strip().startswith("{")
    ]
    if lines:
        return lines
    sys.stderr.write(f"bench: child produced no JSON: {r.stdout[-500:]}\n")
    return None


# ---------------------------------------------------------------------------
# --check: perf-regression gate
# ---------------------------------------------------------------------------
# Per-metric relative regression thresholds on the normalized vs_baseline
# ratios (all bench metrics are higher-is-better).  Defaults are sized for
# the shared-CI noise floor observed across BENCH_r01..r05; the geomean is
# tighter because noise averages out across queries.
CHECK_THRESHOLDS = {
    "tpch_q135_speedup_geomean_per_chip": 0.15,
    "tpch_q1_scan_gbps_per_chip": 0.30,
    "tick_asof_rows_per_s_per_chip": 0.30,
    "service_aggregate_speedup_geomean": 0.30,
    "service_mixed_load_throughput_ratio": 0.30,
    # multichip scaling efficiency: forced-host runs share one core pool,
    # so the ratio is noisier than the single-device walls
    "multichip_scaling_efficiency_geomean": 0.40,
}
CHECK_DEFAULT_THRESHOLD = 0.25

# Benched lines that MUST record the kernel strategy that actually ran
# (detail.strategy, from ops/strategy.note_used): the join queries and the
# tick asof are exactly where a platform-gated kernel once made the bench
# measure a path the target backend never runs.
STRATEGY_REQUIRED_METRICS = (
    "tpch_q3_speedup_vs_ref_per_chip",
    "tpch_q5_speedup_vs_ref_per_chip",
    "tick_asof_rows_per_s_per_chip",
)


def _iter_strategy_details(metric, d):
    """(heading, platform, strategy_dict) for a metric line and any nested
    per-query details (the geomean wrapper)."""
    detail = d.get("detail") or {}
    plat = detail.get("platform")
    if detail.get("strategy"):
        yield metric, plat, detail["strategy"]
    for qname, qd in sorted((detail.get("queries") or {}).items()):
        if isinstance(qd, dict) and qd.get("strategy"):
            yield f"{metric}:{qname}", plat, qd["strategy"]


def check_strategy_honesty(cur, require):
    """Bench-honesty gate rows: every recorded (operator -> kernel choice)
    must be RUNNABLE on the recorded bench platform
    (ops/strategy.invalid_for_platform), and — when ``require`` (fresh runs,
    whose emitter we control) — the join/asof lines must record strategies
    at all.  Returns (rows, violations): a violation exits --check nonzero,
    closing VERDICT r5 finding #2 permanently."""
    from quokka_tpu.ops import strategy as kstrategy

    rows, bad = [], []
    seen_with_strategy = set()
    for metric, d in sorted(cur.items()):
        for heading, plat, strat in _iter_strategy_details(metric, d):
            seen_with_strategy.add(metric)
            for op, ran in sorted(strat.items()):
                name = f"strategy[{heading}].{op}={ran}"
                why = kstrategy.invalid_for_platform(plat or "cpu", op, ran)
                if why:
                    rows.append((name, "GATED-OFF", why))
                    bad.append(name)
                else:
                    rows.append((name, "ok", f"runnable on {plat or 'cpu'}"))
    if require:
        for metric in STRATEGY_REQUIRED_METRICS:
            if metric in cur and metric not in seen_with_strategy:
                name = f"strategy[{metric}]"
                rows.append((name, "MISSING",
                             "benched line records no kernel strategy — "
                             "cannot verify the measured path is the one "
                             "this platform runs"))
                bad.append(name)
    return rows, bad


def check_operators_presence(cur, require):
    """EXPLAIN ANALYZE honesty rows: benched join/asof lines must carry
    the operator-statistics block (``detail.operators`` — per-operator
    rows/time + the skew report) when ``require`` (fresh runs, whose
    emitter we control).  A missing block means the opstats ledger went
    blind on that query — a regression, exactly like a vanished metric.
    Returns (rows, violations)."""
    rows, bad = [], []
    if not require:
        return rows, bad

    def _has_operators(d):
        detail = d.get("detail") or {}
        if detail.get("operators"):
            return True
        return any(isinstance(qd, dict) and qd.get("operators")
                   for qd in (detail.get("queries") or {}).values())

    for metric in STRATEGY_REQUIRED_METRICS:
        if metric not in cur:
            continue
        name = f"operators[{metric}]"
        if _has_operators(cur[metric]):
            ops = (cur[metric].get("detail") or {}).get("operators") or {}
            n = len(ops.get("operators") or []) if isinstance(ops, dict) \
                else 0
            rows.append((name, "ok",
                         f"opstats present ({n} operator(s))"))
        else:
            rows.append((name, "MISSING",
                         "benched line records no detail.operators — the "
                         "EXPLAIN ANALYZE ledger saw nothing for this "
                         "query (opstats regression)"))
            bad.append(name)
    return rows, bad


def check_efficiency_presence(cur, require):
    """Device-efficiency honesty rows: fresh join/asof lines must carry the
    ``detail.efficiency`` block (obs/devprof.py peaks + per-operator
    roofline figures) when ``require`` (fresh runs, whose emitter we
    control — bench --measure calibrates the peaks itself).  A missing
    block means the device-profile plane went blind on that query — same
    presence discipline as strategy/operators.  Returns (rows,
    violations)."""
    rows, bad = [], []
    if not require:
        return rows, bad

    def _efficiency(d):
        detail = d.get("detail") or {}
        if detail.get("efficiency"):
            return detail["efficiency"]
        for qd in (detail.get("queries") or {}).values():
            if isinstance(qd, dict) and qd.get("efficiency"):
                return qd["efficiency"]
        return None

    for metric in STRATEGY_REQUIRED_METRICS:
        if metric not in cur:
            continue
        name = f"efficiency[{metric}]"
        eff = _efficiency(cur[metric])
        if eff:
            n = len(eff.get("operators") or []) if isinstance(eff, dict) \
                else 0
            rows.append((name, "ok",
                         f"devprof present ({n} operator(s))"))
        else:
            rows.append((name, "MISSING",
                         "benched line records no detail.efficiency — the "
                         "device-profile plane saw nothing for this query "
                         "(devprof regression)"))
            bad.append(name)
    return rows, bad


# Benched join lines that MUST prove the whole-stage-fused plan actually
# ran (detail.fused_stages >= 1, counted off the opstats FusedStage rows):
# Q3/Q5 are exactly the linear probe chains ops/stagefuse.py collapses.
FUSION_REQUIRED_METRICS = (
    "tpch_q3_speedup_vs_ref_per_chip",
    "tpch_q5_speedup_vs_ref_per_chip",
)


def check_fused_stages_presence(cur, require):
    """Whole-stage-fusion honesty rows: fresh join lines must carry
    ``detail.fused_stages`` and report at least one fused stage that
    dispatched.  A missing field means the emitter predates stage fusion
    (or the opstats ledger went blind); a zero means the optimizer planned
    no fused chain on a query shaped exactly for one.  Either way the
    fusion win silently evaporated — a regression, same presence
    discipline as strategy/operators.  Returns (rows, violations)."""
    rows, bad = [], []
    if not require:
        return rows, bad
    for metric in FUSION_REQUIRED_METRICS:
        if metric not in cur:
            continue
        name = f"fused_stages[{metric}]"
        detail = cur[metric].get("detail") or {}
        n = detail.get("fused_stages")
        if n is None:
            rows.append((name, "MISSING",
                         "benched join line records no detail.fused_stages "
                         "— cannot verify the whole-stage-fused plan is "
                         "what was measured"))
            bad.append(name)
        elif n < 1:
            rows.append((name, "MISSING",
                         "detail.fused_stages == 0 — no fused stage "
                         "dispatched on a linear join chain (stage fusion "
                         "regressed or was disabled for the bench)"))
            bad.append(name)
        else:
            rows.append((name, "ok", f"{n} fused stage(s) dispatched"))
    return rows, bad


def check_skewjoin_gate(cur, require):
    """Adaptive-planning gate rows: a fresh run must carry the skewjoin
    line, its adaptive run must actually have re-partitioned mid-query
    (detail.adapted), and the speedup must clear SKEWJOIN_MIN_SPEEDUP.
    A missing line, a sleeping trigger, or a sub-floor ratio all mean the
    adaptive win evaporated — same presence discipline as fused_stages.
    Returns (rows, violations)."""
    rows, bad = [], []
    if not require:
        return rows, bad
    metric = "skewjoin_adaptive_speedup"
    name = f"skewjoin[{metric}]"
    d = cur.get(metric)
    if d is None:
        rows.append((name, "MISSING",
                     "fresh run emitted no skewjoin line — the adaptive-vs-"
                     "static benchmark did not run"))
        bad.append(name)
        return rows, bad
    detail = d.get("detail") or {}
    value = float(d.get("value") or 0.0)
    if not detail.get("adapted"):
        rows.append((name, "MISSING",
                     "the adaptive run never fired the skew trigger (no "
                     "adapt_runtime decision) — the measured 'adaptive' "
                     "path was the static one"))
        bad.append(name)
    elif value < SKEWJOIN_MIN_SPEEDUP:
        rows.append((name, "REGRESSED",
                     f"adaptive speedup {value:.2f}x under the required "
                     f"{SKEWJOIN_MIN_SPEEDUP:.0f}x floor "
                     f"(static {detail.get('seconds_static')}, adaptive "
                     f"{detail.get('seconds_adaptive')})"))
        bad.append(name)
    else:
        rows.append((name, "ok",
                     f"adaptive {value:.2f}x over static (floor "
                     f"{SKEWJOIN_MIN_SPEEDUP:.0f}x, adapted mid-query)"))
    return rows, bad


def _parse_artifact(path):
    """({metric: line-dict}, truncated) from any bench artifact shape: raw
    bench stdout (JSON lines), a single line, a list, or the driver's
    BENCH_r*.json wrapper ({"tail": "<stdout tail>", "parsed": <last
    line>}).  ``truncated`` is True for a wrapper whose stdout tail was
    cut mid-stream (its first kept line fails to parse): metrics absent
    from such an artifact fell off the tail — their absence says nothing
    about whether the benchmark ran."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    lines = []
    truncated = False
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict) and "tail" in obj:
        tail_lines = [ln.strip() for ln in str(obj["tail"]).splitlines()
                      if ln.strip()]
        for i, ln in enumerate(tail_lines):
            try:
                lines.append(json.loads(ln))
            except ValueError:
                if i == 0:
                    truncated = True
        if not tail_lines:
            truncated = True
        if isinstance(obj.get("parsed"), dict):
            lines.append(obj["parsed"])
    elif isinstance(obj, dict) and "metric" in obj:
        lines = [obj]
    elif isinstance(obj, list):
        lines = obj
    else:
        for ln in text.splitlines():
            ln = ln.strip()
            if ln.startswith("{"):
                try:
                    lines.append(json.loads(ln))
                except ValueError:
                    pass
    return ({d["metric"]: d for d in lines
             if isinstance(d, dict) and "metric" in d}, truncated)


def load_metrics(path):
    """{metric: line-dict} from any bench artifact shape (see
    ``_parse_artifact``)."""
    return _parse_artifact(path)[0]


def _artifact_truncated(path):
    try:
        return _parse_artifact(path)[1]
    except (OSError, ValueError):
        return False


def _metric_ratio(d):
    """The comparable number: vs_baseline (normalized, unit-free across
    metrics) when present, else the raw value."""
    v = d.get("vs_baseline")
    return float(v if v is not None else d["value"])


def _critpath_of(d):
    detail = d.get("detail") or {}
    cp = detail.get("critpath")
    if cp:
        return cp
    # geomean line: nested per-query details
    return None


def _print_critpath_diff(metric, base_d, cur_d, out):
    pairs = []  # (heading, base_cp_or_None, cur_cp)
    cur_cp = _critpath_of(cur_d)
    if cur_cp:
        pairs.append((metric, _critpath_of(base_d), cur_cp))
    else:
        # geomean-style line: per-query details nested under the summary
        cur_queries = (cur_d.get("detail") or {}).get("queries") or {}
        base_queries = (base_d.get("detail") or {}).get("queries") or {}
        for qname, qd in sorted(cur_queries.items()):
            cp = (qd or {}).get("critpath")
            if cp:
                pairs.append((qname,
                              (base_queries.get(qname) or {}).get("critpath"),
                              cp))
    if not pairs:
        out.write(f"    (no critical-path data in the current run for "
                  f"{metric})\n")
        return
    for heading, base_cp, cp in pairs:
        out.write(f"    critical path [{heading}] "
                  f"(wall {cp.get('wall_s', 0) * 1e3:.1f}ms):\n")
        base_buckets = (base_cp or {}).get("buckets") or {}
        for k, v in (cp.get("buckets") or {}).items():
            if not v and not base_buckets.get(k):
                continue
            b = base_buckets.get(k)
            delta = (f" (baseline {b * 1e3:.1f}ms, "
                     f"{(v - b) * 1e3:+.1f}ms)" if b is not None else "")
            out.write(f"      {k:<10} {v * 1e3:>9.1f}ms{delta}\n")


# Warmup gates (lower-is-better, pulled from per-query bench detail):
# (relative growth allowed, absolute slack).  real_compiles gets integer
# slack so a 0 -> 2 wobble on a warm cache doesn't trip the gate, while a
# 0 -> 11 signature-space regression does.
WARMUP_GATES = {
    "warmup_seconds": (0.5, 1.0),
    "real_compiles_warmup": (0.5, 2.0),
}


def _warmup_details(metrics):
    """{qname: {warmup_seconds, real_compiles_warmup}} from a bench metric
    map — prefers the geomean line's nested per-query details, falls back
    to the per-query lines."""
    out = {}
    for d in metrics.values():
        detail = d.get("detail") or {}
        queries = detail.get("queries")
        if isinstance(queries, dict):
            for q, qd in queries.items():
                for k in WARMUP_GATES:
                    if qd and qd.get(k) is not None:
                        out.setdefault(q, {})[k] = float(qd[k])
    if out:
        return out
    for metric, d in metrics.items():
        if not metric.startswith("tpch_q"):
            continue
        q = metric.split("_")[1]
        detail = d.get("detail") or {}
        for k in WARMUP_GATES:
            if detail.get(k) is not None:
                out.setdefault(q, {})[k] = float(detail[k])
    return out


def check_warmup_gates(base, cur, current_not_comparable=False):
    """Per-query warmup regression rows: warmup_seconds and
    real_compiles_warmup must not grow past their gate (lower-is-better;
    MISSING from the current run = regression — a silently vanished warmup
    metric is exactly how warmup regressions would hide)."""
    b_w, c_w = _warmup_details(base), _warmup_details(cur)
    rows, regressed = [], []
    for q in sorted(b_w):
        for k, (thr, slack) in WARMUP_GATES.items():
            if k not in b_w[q]:
                continue
            name = f"warmup[{q}].{k}"
            b = b_w[q][k]
            c = (c_w.get(q) or {}).get(k)
            if c is None:
                if current_not_comparable:
                    rows.append((name, b, None, None, None, "not-run"))
                else:
                    rows.append((name, b, None, None, thr, "MISSING"))
                    regressed.append(name)
                continue
            bad = c > b * (1.0 + thr) + slack
            delta = (c - b) / b if b else None
            rows.append((name, b, c, delta, thr,
                         "REGRESSED" if bad else "ok"))
            if bad:
                regressed.append(name)
    return rows, regressed


# Memory gates (lower-is-better, from per-query detail.memory): relative
# growth allowed plus absolute slack.  64 MiB of slack absorbs allocator /
# padding-bucket wobble on small scale factors while a genuine doubling of
# a query's device high-water mark still trips.
MEMORY_GATES = {
    "peak_bytes": (0.5, 64 << 20),
}


def _memory_details(metrics):
    """{qname: {peak_bytes}} from a bench metric map — same sourcing rules
    as _warmup_details (geomean nested details first, per-query lines as
    fallback)."""
    out = {}
    for d in metrics.values():
        detail = d.get("detail") or {}
        queries = detail.get("queries")
        if isinstance(queries, dict):
            for q, qd in queries.items():
                mem = (qd or {}).get("memory") or {}
                for k in MEMORY_GATES:
                    if mem.get(k) is not None:
                        out.setdefault(q, {})[k] = float(mem[k])
    if out:
        return out
    for metric, d in metrics.items():
        if not metric.startswith("tpch_q"):
            continue
        q = metric.split("_")[1]
        mem = (d.get("detail") or {}).get("memory") or {}
        for k in MEMORY_GATES:
            if mem.get(k) is not None:
                out.setdefault(q, {})[k] = float(mem[k])
    return out


def check_memory_gates(base, cur, current_not_comparable=False):
    """Per-query peak-memory regression rows — the warmup-gate machinery
    applied to detail.memory (lower-is-better; MISSING = regression, since
    a vanished memory detail is how a footprint regression would hide).
    Baselines recorded before the memory plane existed carry no
    detail.memory and gate nothing."""
    b_m, c_m = _memory_details(base), _memory_details(cur)
    rows, regressed = [], []
    for q in sorted(b_m):
        for k, (thr, slack) in MEMORY_GATES.items():
            if k not in b_m[q]:
                continue
            name = f"memory[{q}].{k}"
            b = b_m[q][k]
            c = (c_m.get(q) or {}).get(k)
            if c is None:
                if current_not_comparable:
                    rows.append((name, b, None, None, None, "not-run"))
                else:
                    rows.append((name, b, None, None, thr, "MISSING"))
                    regressed.append(name)
                continue
            bad = c > b * (1.0 + thr) + slack
            delta = (c - b) / b if b else None
            rows.append((name, b, c, delta, thr,
                         "REGRESSED" if bad else "ok"))
            if bad:
                regressed.append(name)
    return rows, regressed


def check_regressions(base, cur, threshold=None, not_run_prefixes=()):
    """Compare {metric: line} maps; returns (report_rows, regressed_list).
    A metric present in the baseline but missing from the current run
    counts as regressed (a silently vanished benchmark is the regression
    mode this gate exists for) — EXCEPT metrics under ``not_run_prefixes``,
    which the current run's mode could not have produced (a fresh --check
    runs only the --measure section, so a baseline that also captured
    --service metrics must not trip on them)."""
    rows, regressed = [], []
    for metric in sorted(base):
        b = _metric_ratio(base[metric])
        thr = threshold if threshold is not None else \
            CHECK_THRESHOLDS.get(metric, CHECK_DEFAULT_THRESHOLD)
        if metric not in cur:
            if not_run_prefixes and metric.startswith(
                    tuple(not_run_prefixes)):
                rows.append((metric, b, None, None, None, "not-run"))
            else:
                rows.append((metric, b, None, None, thr, "MISSING"))
                regressed.append(metric)
            continue
        c = _metric_ratio(cur[metric])
        delta = (c - b) / b if b else 0.0
        bad = c < b * (1.0 - thr)
        rows.append((metric, b, c, delta, thr,
                     "REGRESSED" if bad else "ok"))
        if bad:
            regressed.append(metric)
    for metric in sorted(set(cur) - set(base)):
        rows.append((metric, None, _metric_ratio(cur[metric]), None,
                     None, "new"))
    return rows, regressed


def check_main(argv):
    import argparse
    import glob

    ap = argparse.ArgumentParser(
        prog="bench.py --check",
        description="Perf-regression gate: compare a bench run against a "
                    "baseline artifact; exit 1 on regression.")
    ap.add_argument("--against", default=None,
                    help="baseline artifact (default: newest BENCH_r*.json "
                         "next to bench.py)")
    ap.add_argument("--current", default=None,
                    help="compare this artifact instead of running the "
                         "bench now (file-vs-file mode)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="override every per-metric relative threshold "
                         "(fraction, e.g. 0.2)")
    args = ap.parse_args(argv)

    against = args.against
    if against is None:
        here = os.path.dirname(os.path.abspath(__file__))
        cands = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
        if not cands:
            sys.stderr.write("bench --check: no --against and no "
                             "BENCH_r*.json found\n")
            return 2
        against = cands[-1]
    try:
        base, base_truncated = _parse_artifact(against)
    except OSError as e:
        sys.stderr.write(f"bench --check: cannot read {against}: {e}\n")
        return 2
    if not base:
        sys.stderr.write(f"bench --check: no metrics in {against}\n")
        return 2

    not_run_prefixes = ()
    if args.current is not None:
        try:
            cur, cur_truncated = _parse_artifact(args.current)
        except OSError as e:
            sys.stderr.write(f"bench --check: cannot read "
                             f"{args.current}: {e}\n")
            return 2
        cur_src = args.current
        if cur_truncated:
            # which metrics survived the wrapper's 2000-byte tail is
            # arbitrary: gate only the intersection instead of failing
            # on lines that merely fell off the tail
            sys.stderr.write(
                f"bench --check: {args.current} is a truncated driver "
                "tail; baseline metrics absent from it report as "
                "not-run, not REGRESSED\n")
            not_run_prefixes = ("",)
    else:
        ensure_data()
        lines = None
        for _ in range(2 if probe_tpu() else 0):
            lines = _run_child(MEASURE_TIMEOUT)
            if lines is not None:
                break
        if lines is None:
            sys.stderr.write("bench --check: measurement failed\n")
            return 2
        cur = {d["metric"]: d for d in map(json.loads, lines)
               if "metric" in d}
        cur_src = "fresh run"
        # the fresh run executes only the --measure section: baseline
        # metrics from other modes (--service, --multichip) are "not run",
        # not missing
        not_run_prefixes = ("service_", "multichip_")
    if not cur:
        sys.stderr.write("bench --check: no current metrics\n")
        return 2

    rows, regressed = check_regressions(base, cur, args.threshold,
                                        not_run_prefixes=not_run_prefixes)
    # warmup gates (lower-is-better): a truncated current tail cannot carry
    # the per-query details, so absence there reports as not-run
    w_rows, w_regressed = check_warmup_gates(
        base, cur, current_not_comparable=bool(not_run_prefixes == ("",)))
    regressed += w_regressed
    # peak-memory gates (lower-is-better, same truncation rules)
    m_rows, m_regressed = check_memory_gates(
        base, cur, current_not_comparable=bool(not_run_prefixes == ("",)))
    regressed += m_regressed
    # bench honesty: recorded strategies must be runnable on the bench
    # platform; fresh runs must record them on the join/asof lines (a
    # truncated --current tail cannot carry details, so presence is only
    # required when we produced the lines ourselves)
    s_rows, s_bad = check_strategy_honesty(
        cur, require=(args.current is None))
    regressed += s_bad
    # EXPLAIN ANALYZE honesty: fresh join/asof lines must carry operator
    # actuals (detail.operators) — same presence discipline as strategy
    o_rows, o_bad = check_operators_presence(
        cur, require=(args.current is None))
    regressed += o_bad
    # device-efficiency honesty: fresh join/asof lines must carry the
    # devprof digest (detail.efficiency) — same presence discipline
    e_rows, e_bad = check_efficiency_presence(
        cur, require=(args.current is None))
    regressed += e_bad
    # whole-stage-fusion honesty: fresh join lines must show the fused
    # plan actually dispatched (detail.fused_stages >= 1)
    f_rows, f_bad = check_fused_stages_presence(
        cur, require=(args.current is None))
    regressed += f_bad
    # adaptive-planning gate: the fresh skewjoin line must exist, must have
    # actually adapted mid-query, and must clear SKEWJOIN_MIN_SPEEDUP
    k_rows, k_bad = check_skewjoin_gate(
        cur, require=(args.current is None))
    regressed += k_bad
    s_rows = s_rows + o_rows + e_rows + f_rows + k_rows
    out = sys.stdout
    out.write(f"bench --check: {cur_src} vs {against}\n")
    if base_truncated:
        out.write("  note: the baseline is a truncated driver tail — "
                  "metrics missing from IT are not gated at all\n")
    for metric, b, c, delta, thr, status in rows:
        b_s = f"{b:.4f}" if b is not None else "-"
        c_s = f"{c:.4f}" if c is not None else "-"
        d_s = f"{delta:+.1%}" if delta is not None else "-"
        t_s = f"(allow -{thr:.0%})" if thr is not None else ""
        out.write(f"  {status:>9}  {metric:<42} {b_s:>9} -> {c_s:>9} "
                  f"{d_s:>8} {t_s}\n")
        if status == "REGRESSED":
            _print_critpath_diff(metric, base[metric], cur[metric], out)
    for metric, b, c, delta, thr, status in w_rows + m_rows:
        b_s = f"{b:.4f}" if b is not None else "-"
        c_s = f"{c:.4f}" if c is not None else "-"
        d_s = f"{delta:+.1%}" if delta is not None else "-"
        t_s = f"(allow +{thr:.0%})" if thr is not None else ""
        out.write(f"  {status:>9}  {metric:<42} {b_s:>9} -> {c_s:>9} "
                  f"{d_s:>8} {t_s}\n")
    for name, status, why in s_rows:
        if status == "ok":
            out.write(f"  {status:>9}  {name}\n")
        else:
            out.write(f"  {status:>9}  {name}\n              {why}\n")
    if regressed:
        out.write(f"REGRESSION: {len(regressed)} metric(s) regressed "
                  f"beyond threshold: {', '.join(regressed)}\n")
        return 1
    out.write("clean: no metric regressed beyond its threshold\n")
    return 0


def _trend_slope(points):
    """Least-squares slope of [(x, y)] (per-round change); 0.0 for < 2
    points."""
    n = len(points)
    if n < 2:
        return 0.0
    mx = sum(x for x, _ in points) / n
    my = sum(y for _, y in points) / n
    den = sum((x - mx) ** 2 for x, _ in points)
    if den == 0:
        return 0.0
    return sum((x - mx) * (y - my) for x, y in points) / den


def trend_main(argv):
    """``bench.py --trend``: the cross-round view no single --check gives.
    Reads EVERY committed BENCH_r*.json, prints each metric's trajectory
    (vs_baseline ratio per round, least-squares slope) and exits 1 when a
    metric declines strictly monotonically over its last ``--window``
    CONSECUTIVE rounds — a slow leak each individual --check stayed inside
    its threshold on.  Truncated driver tails contribute the metrics they
    kept; a metric absent from a round is a gap, never a regression (which
    round survives a 2000-byte tail is arbitrary), and a decline spanning
    a gap doesn't trip the gate either — artifacts across gaps often span
    box re-baselines, so the change is not attributable round-to-round."""
    import argparse
    import glob

    ap = argparse.ArgumentParser(
        prog="bench.py --trend",
        description="Cross-round trajectory over committed BENCH_r*.json "
                    "artifacts; exit 1 on a monotone multi-round decline.")
    ap.add_argument("--dir", default=None,
                    help="artifact directory (default: next to bench.py)")
    ap.add_argument("--window", type=int, default=3,
                    help="consecutive recorded declines that count as a "
                         "regression (default 3)")
    args = ap.parse_args(argv)

    here = args.dir or os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if len(paths) < 2:
        sys.stderr.write(f"bench --trend: need >= 2 BENCH_r*.json under "
                         f"{here}, found {len(paths)}\n")
        return 2
    rounds = []  # (label, {metric: ratio})
    for p in paths:
        label = os.path.basename(p)[len("BENCH_"):-len(".json")]
        try:
            metrics, _ = _parse_artifact(p)
        except (OSError, ValueError) as e:
            sys.stderr.write(f"bench --trend: skipping unreadable {p}: "
                             f"{e}\n")
            continue
        vals = {}
        for name, d in metrics.items():
            try:
                vals[name] = _metric_ratio(d)
            except (TypeError, ValueError, KeyError):
                pass
        rounds.append((label, vals))
    series = {}  # metric -> [(round_index, ratio)]
    for i, (_, vals) in enumerate(rounds):
        for name, v in vals.items():
            series.setdefault(name, []).append((i, v))

    out = sys.stdout
    labels = [lab for lab, _ in rounds]
    width = max(len(lab) for lab in labels)
    out.write(f"bench --trend: {len(rounds)} round(s) "
              f"({labels[0]}..{labels[-1]}), window={args.window}\n")
    regressed = []
    window = max(2, args.window)
    for name in sorted(series):
        pts = series[name]
        if len(pts) < 2:
            status = "sparse"  # one recorded round: no trajectory yet
        else:
            tail = pts[-window:]
            declining = (
                len(tail) >= window
                # consecutive rounds only: a decline across a recording
                # gap is not attributable to any single round
                and all(i2 == i1 + 1 for (i1, _), (i2, _)
                        in zip(tail, tail[1:]))
                and all(v1 > v2 for (_, v1), (_, v2)
                        in zip(tail, tail[1:])))
            status = "DECLINING" if declining else "ok"
            if declining:
                regressed.append(name)
        slope = _trend_slope(pts)
        by_round = dict(pts)
        cells = " ".join(
            f"{by_round[i]:>8.4f}" if i in by_round else f"{'-':>8}"
            for i in range(len(rounds)))
        out.write(f"  {status:>9}  {name:<42} {cells}  "
                  f"slope {slope:+.4f}/round\n")
    out.write("  rounds: " + " ".join(f"{lab:>8}" for lab in labels)
              + "\n")
    if regressed:
        out.write(f"TREND REGRESSION: {len(regressed)} metric(s) declined "
                  f"monotonically over their last {window} recorded "
                  f"round(s): {', '.join(regressed)}\n")
        return 1
    out.write("clean: no metric declined monotonically across rounds\n")
    return 0


# ---------------------------------------------------------------------------
# --multichip: timed N-device scaling line (mesh execution plane)
# ---------------------------------------------------------------------------
# Times every bench query once on ONE device (the embedded engine) and once
# across N devices (QuokkaContext(mesh=...): shard_map programs with
# all_to_all key shuffles, parallel/mesh_exec.py), and reports strong-scaling
# efficiency = (t_1 / t_N) / N per query.  On a real accelerator pod this is
# the ROADMAP's >= 0.6-at-8-chips line; on this box the 8 devices are
# XLA-forced host devices sharing the CPU cores, so the artifact carries
# forced_host + cpus so the number cannot be mistaken for chip scaling —
# the point is that the line is TIMED and the mesh path is exercised
# end-to-end, replacing five rounds of dry-run-only MULTICHIP artifacts.


def multichip_measure():
    """Child process: emits one JSON line per query + a geomean line."""
    import jax

    n = int(os.environ.get("QUOKKA_MULTICHIP_DEVICES", "8"))
    smoke = os.environ.get("QUOKKA_MULTICHIP_SMOKE") == "1"
    platform = jax.default_backend()
    if jax.device_count() < n:
        sys.stderr.write(
            f"bench --multichip: need {n} devices, have "
            f"{jax.device_count()} on {platform}\n")
        sys.exit(3)
    from quokka_tpu import QuokkaContext
    from quokka_tpu import obs as qk_obs
    from quokka_tpu.ops import strategy as kstrategy
    from quokka_tpu.parallel.mesh import make_mesh

    kstrategy.ensure_calibrated()
    paths = ensure_data()
    mesh = make_mesh(n)
    forced_host = platform == "cpu"
    builders = dict(BUILDERS)
    builders["asof"] = build_asof
    reps = 1 if smoke else 2
    effs, problems = [], []
    for qname, builder in builders.items():
        def run(ctx):
            q = builder(paths, ctx=ctx)
            t0 = time.time()
            q.collect()
            return time.time() - t0

        single = lambda: QuokkaContext(io_channels=3, exec_channels=2)  # noqa: E731
        run(single())  # warm: compiles + scan cache
        t1 = min(run(single()) for _ in range(reps))
        kstrategy.reset_used()
        mctx = QuokkaContext(mesh=mesh)
        run(mctx)  # warm the mesh programs
        warm_fallback = mctx.last_mesh_fallback
        snap0 = qk_obs.REGISTRY.snapshot()
        t_n = float("inf")
        for _ in range(reps):
            mctx = QuokkaContext(mesh=mesh)
            t_n = min(t_n, run(mctx))
        snap1 = qk_obs.REGISTRY.snapshot()
        host_syncs = int(snap1.get("shuffle.host_syncs", 0)
                         - snap0.get("shuffle.host_syncs", 0))
        fallback = mctx.last_mesh_fallback or warm_fallback
        speedup = t1 / t_n if t_n > 0 else 0.0
        eff = speedup / n
        effs.append(eff)
        if fallback:
            problems.append(f"{qname}: mesh fell back to the embedded "
                            f"engine ({fallback})")
        strategy_used = kstrategy.used_snapshot()
        if not strategy_used:
            problems.append(f"{qname}: no kernel strategy recorded")
        if host_syncs:
            problems.append(f"{qname}: {host_syncs} blocking host syncs on "
                            "the timed shuffle path")
        print(json.dumps({
            "metric": f"multichip_{qname}_scaling_efficiency",
            "value": round(eff, 4),
            "unit": "x",
            "vs_baseline": round(eff, 4),
            "detail": {
                "sf": SF, "platform": platform, "n_devices": n,
                "forced_host": forced_host, "cpus": os.cpu_count(),
                "seconds_1dev": round(t1, 4),
                "seconds_ndev": round(t_n, 4),
                "speedup": round(speedup, 4),
                "strategy": strategy_used,
                "shuffle_host_syncs": host_syncs,
                "mesh_fallback": fallback,
            },
        }))
        sys.stdout.flush()
    geomean = math.exp(sum(math.log(max(e, 1e-9)) for e in effs) / len(effs))
    print(json.dumps({
        "metric": "multichip_scaling_efficiency_geomean",
        "value": round(geomean, 4),
        "unit": "x",
        "vs_baseline": round(geomean, 4),
        "detail": {"sf": SF, "platform": platform, "n_devices": n,
                   "forced_host": forced_host, "cpus": os.cpu_count(),
                   "queries": list(builders),
                   "strategy_matrix": kstrategy.choices()},
    }))
    sys.stdout.flush()
    if problems:
        for p in problems:
            sys.stderr.write(f"bench --multichip: {p}\n")
        # a fallback/untracked-strategy/host-sync line is not a timed
        # multichip measurement — fail loudly rather than ship it
        sys.exit(4)


def multichip_main(argv):
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench.py --multichip",
        description="Timed N-device scaling bench over the mesh execution "
                    "plane; writes a MULTICHIP artifact with per-query "
                    "scaling efficiency.")
    ap.add_argument("--devices", type=int,
                    default=int(os.environ.get("QUOKKA_MULTICHIP_DEVICES",
                                               "8")))
    ap.add_argument("--smoke", action="store_true",
                    help="single timed rep + assertions (CI)")
    ap.add_argument("--out",
                    default=os.environ.get("QUOKKA_MULTICHIP_OUT")
                    or os.path.join(bench_out_dir(),
                                    "MULTICHIP_timed.json"))
    args = ap.parse_args(argv)
    ensure_data()
    env = dict(os.environ)
    env["QUOKKA_MULTICHIP_DEVICES"] = str(args.devices)
    if args.smoke:
        env["QUOKKA_MULTICHIP_SMOKE"] = "1"
    # real chips by default (the child checks the device COUNT and exits 3
    # if the host has too few); forced-host XLA devices only when the
    # caller's environment already says JAX_PLATFORMS=cpu (a rehearsal)
    if env.get("JAX_PLATFORMS", "") == "cpu":
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()
    elif not probe_tpu():
        sys.stderr.write("bench --multichip: no accelerator (set "
                         "JAX_PLATFORMS=cpu for a forced-host rehearsal)\n")
        return 1
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--multichip-measure"],
            timeout=MEASURE_TIMEOUT, capture_output=True, text=True,
            env=env,
        )
    except subprocess.TimeoutExpired:
        sys.stderr.write("bench --multichip: child exceeded "
                         f"{MEASURE_TIMEOUT}s\n")
        return 1
    if r.returncode != 0:
        sys.stderr.write(f"bench --multichip child rc={r.returncode}:\n"
                         f"{r.stderr[-2000:]}\n")
        return 1
    if r.stderr:
        sys.stderr.write(r.stderr[-4000:])
    lines = []
    for ln in r.stdout.strip().splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                lines.append(json.loads(ln))
            except ValueError:
                pass
    for d in lines:
        print(json.dumps(d))
    if not any(d.get("metric") == "multichip_scaling_efficiency_geomean"
               for d in lines):
        sys.stderr.write("bench --multichip: no geomean line produced\n")
        return 1
    artifact = {
        "n_devices": args.devices,
        "timed": True,
        "lines": lines,
    }
    try:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(artifact, f, indent=2)
        sys.stderr.write(f"bench --multichip: artifact written to "
                         f"{args.out}\n")
    except OSError as e:
        sys.stderr.write(f"bench --multichip: cannot write {args.out}: "
                         f"{e}\n")
        return 1
    return 0


def main():
    ensure_data()
    if not probe_tpu():
        sys.stderr.write("bench: no accelerator after probe retries; this "
                         "is a chip measurement and does not run without "
                         "one\n")
        sys.exit(1)
    for _ in range(2):  # one retry on a mid-run failure
        lines = _run_child(MEASURE_TIMEOUT)
        if lines is not None:
            print("\n".join(lines))
            return
    sys.stderr.write("bench: all measurement attempts failed\n")
    sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--measure":
        # a full TPC-H run records far more than the 4096-event default
        # ring; size it so the critical-path profile keeps the whole last
        # timed run (set BEFORE the first quokka_tpu import instantiates
        # the recorder)
        os.environ.setdefault("QK_TRACE_BUFFER", "262144")
        measure(ensure_data())
    elif len(sys.argv) > 1 and sys.argv[1] == "--service":
        # concurrent-service mode runs in-process (no TPU wedge supervision:
        # it is the CI smoke + local measurement path; CPU via JAX_PLATFORMS).
        # Failure mode is an exception (wedge -> QueryStallTimeout, failed
        # query -> its error, empty smoke result -> RuntimeError): any of
        # them exits nonzero
        measure_service(ensure_data(), smoke="--smoke" in sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "--multichip-measure":
        # runs INSIDE the supervised child: the parent sized the forced-host
        # device pool (XLA_FLAGS) when the caller asked for the CPU
        multichip_measure()
    elif len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        # timed N-device scaling line over the mesh plane (forced-host
        # devices on a plain box, real chips when available); writes the
        # MULTICHIP artifact and exits nonzero on fallback/untimed lines
        sys.exit(multichip_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--check":
        # perf-regression gate: fresh run (or --current file) vs the
        # newest BENCH_r*.json (or --against); exit 1 on regression with
        # the regressed queries' critical-path diffs printed
        sys.exit(check_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--trend":
        # cross-round trajectory over every committed BENCH_r*.json; exit 1
        # when a metric declined monotonically across the last N rounds —
        # the slow leak each individual --check stayed under threshold on
        sys.exit(trend_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--chaos":
        # seeded mixed-fault soak (the chaos plane, quokka_tpu/chaos):
        # bit-exact-under-injection is a robustness benchmark, so it rides
        # the bench entry point too; extra args pass through (--runs/--seed)
        from quokka_tpu.chaos.soak import main as chaos_main

        sys.exit(chaos_main(sys.argv[2:]))
    else:
        main()
