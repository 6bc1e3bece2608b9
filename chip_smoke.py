#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that quokka-tpu starts and answers
right on one TPU chip.

    python chip_smoke.py [--sf 0.1] [--seed 42] [--out DIR]
    python chip_smoke.py --mesh 4        # the sharded path only, four chips

It drives the main query path through the entry points a user calls
(``QuokkaContext().read_parquet(...)...collect()`` and ``QueryService``) over
TPC-H data at ``--sf`` and a tick table pair of the matching size, and holds
every answer to a plain pandas reference computed from the same Arrow tables
(exact on keys and counts, rtol 1e-3 on the chip's float32 sums).

Five phases, one JAX process at a time.  This top level is a parent that
NEVER imports jax or quokka_tpu (a parent that has touched jax holds the chip
and its child then fails or hangs): it runs ``--phase main`` (device, data,
one-shot Q1/Q3/asof cold+warm, the served path) and then, strictly after that
child has exited, ``--phase restart`` (Q3 again in a fresh process, from the
persisted AOT store and jax cache).  ``--mesh N`` runs one child instead:
Q3 and the asof query on ``make_mesh(N)`` and on one device, compared.

It is a chip check, not a CPU demo: a child exits non-zero the moment
``jax.devices()[0].platform != "tpu"``, the moment a phase raises, and the
moment an answer mismatches, and the parent exits non-zero with it.  Nothing
here sets the platform.  ``--rehearse`` (never given by the driver) lets the
same phases run wherever jax lands, for the CPU rehearsal of
``make chip-smoke-rehearse``; its last line still names the platform jax
reported, so it cannot pass for a chip run.

Last line of stdout, only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
All timings are smoke readings (host wall clock around a call that ends in a
result in host memory), not benchmark numbers.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-3  # float32 sums on the chip vs float64 in pandas
# Cut from SF 1.0 (the benchmark's size) by the run's time limit alone: a
# cold run is compilation (multi-operand sorts cost the TPU's compiler one
# to three minutes each, whatever the row count), and at SF 1 the ~105
# programs of these phases take about 25 minutes to compile on the chip's
# host against a 20-minute limit.  At 0.1 every table is one scan batch
# (lineitem 600,916 rows in the 1<<20 bucket the SF 1 run uses too), the
# program set shrinks to ~64, and a cold run takes about half the limit.
DEFAULT_SF = 0.1

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
TPCH_TABLES = ("lineitem", "orders", "customer")
ASOF_SYMBOLS = 100


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help="TPC-H scale factor (1.0: lineitem 6.0M rows, "
                         "6.0M quotes x 1.15M trades)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="directory for the generated Parquet files")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run ONLY the sharded path on make_mesh(N) beside "
                         "one device (needs exactly N devices)")
    ap.add_argument("--rehearse", action="store_true",
                    help="do not require a TPU (CPU rehearsal)")
    ap.add_argument("--phase", choices=("main", "restart", "mesh"),
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def say(*a):
    print("chip_smoke:", *a, flush=True)


# ---------------------------------------------------------------------------
# parent: no jax, children strictly one after the other
# ---------------------------------------------------------------------------


def parent(args, argv) -> int:
    t0 = time.time()
    os.makedirs(args.out, exist_ok=True)
    result_path = os.path.join(args.out, "device.json")
    device = None
    for phase in (("mesh",) if args.mesh else ("main", "restart")):
        if os.path.exists(result_path):
            os.remove(result_path)
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *argv,
             "--phase", phase]).returncode
        if rc != 0:
            say(f"phase process '{phase}' failed rc={rc}")
            return rc if 0 < rc < 256 else 1
        with open(result_path, encoding="utf-8") as f:
            device = json.load(f)
    say(f"total wall {time.time() - t0:.1f} s (cold: data generation, "
        "compilation and every phase included)")
    out = {"ok": True, "device": device}
    if args.rehearse:
        out["rehearsal"] = True
    print(json.dumps(out), flush=True)
    return 0


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(args) -> dict:
    import importlib.metadata

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"[device] {json.dumps(device)}")
    if device["platform"] != "tpu" and not args.rehearse:
        say("[device] FAIL: jax found no TPU; this is a chip check, not a "
            "CPU demo")
        sys.exit(1)
    say("[device] versions " + json.dumps(
        {p: importlib.metadata.version(p)
         for p in ("jax", "jaxlib", "libtpu")}))

    from quokka_tpu import config
    from quokka_tpu.ops import strategy
    from quokka_tpu.utils import native

    say(f"[device] cache root {config.CACHE_ROOT} "
        f"(jax_compilation_cache_dir={jax.config.jax_compilation_cache_dir}, "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    say(f"[device] x64={config.x64_enabled()} "
        f"batch_rows={config.DEFAULT_BATCH_ROWS}")
    say(f"[device] strategy {json.dumps(strategy.choices())} "
        f"from {json.dumps(strategy.sources())}")
    say(f"[device] {native.status()}")
    return device


# ---------------------------------------------------------------------------
# phase 2: data (made from --seed; Parquet under --out)
# ---------------------------------------------------------------------------


def data_paths(args) -> dict:
    return {name: os.path.join(
        args.out, f"{name}_sf{args.sf}_seed{args.seed}.parquet")
        for name in TPCH_TABLES + ("trades", "quotes")}


def make_data(args) -> dict:
    """Generate TPC-H lineitem/orders/customer and the tick tables, write
    them to Parquet, return {name: path}."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    sys.path.insert(0, os.path.join(HERE, "tests"))
    import tpch_data

    t0 = time.time()
    os.makedirs(args.out, exist_ok=True)
    tables = tpch_data.generate(sf=args.sf, seed=args.seed)
    paths = data_paths(args)
    for name in TPCH_TABLES:
        pq.write_table(tables[name], paths[name], row_group_size=1 << 20)
    # the tick backtest pair: one trading day in ms, ~5.2 quotes per trade
    syms = np.array([f"S{i:03d}" for i in range(ASOF_SYMBOLS)])
    for name, n_rows, salt in (("trades", int(1_150_000 * args.sf), 1),
                               ("quotes", int(6_000_000 * args.sf), 2)):
        r = np.random.default_rng([args.seed, salt])
        cols = {
            "time": np.sort(r.integers(0, 86_400_000, n_rows)).astype(np.int64),
            "symbol": syms[r.integers(0, ASOF_SYMBOLS, n_rows)],
        }
        if name == "trades":
            cols["size"] = r.integers(1, 500, n_rows).astype(np.int64)
        else:
            cols["bid"] = r.uniform(10, 500, n_rows).round(3)
        pq.write_table(pa.table(cols), paths[name], row_group_size=1 << 20)
    rows = {n: pq.read_metadata(p).num_rows for n, p in paths.items()}
    say(f"[data] sf={args.sf} seed={args.seed} rows {json.dumps(rows)} "
        f"in {time.time() - t0:.1f} s under {args.out}")
    return paths


# ---------------------------------------------------------------------------
# the queries (copied into benchmarks/queries/) and their plain references
# ---------------------------------------------------------------------------


def build_q1(ctx, paths):
    return (
        ctx.read_parquet(paths["lineitem"], columns=[
            "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate"])
        .filter_sql("l_shipdate <= date '1998-12-01' - interval '90' day")
        .groupby(["l_returnflag", "l_linestatus"])
        .agg_sql(Q1_AGGS)
    )


def build_q3(ctx, paths):
    from quokka_tpu.expression import col

    lineitem = ctx.read_parquet(paths["lineitem"], columns=[
        "l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"])
    orders = ctx.read_parquet(paths["orders"], columns=[
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    customer = ctx.read_parquet(
        paths["customer"], columns=["c_custkey", "c_mktsegment"])
    return (
        lineitem.filter_sql("l_shipdate > date '1995-03-15'")
        .join(orders.filter_sql("o_orderdate < date '1995-03-15'"),
              left_on="l_orderkey", right_on="o_orderkey")
        .join(customer.filter(col("c_mktsegment") == "BUILDING"),
              left_on="o_custkey", right_on="c_custkey")
        .groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
        .agg_sql("sum(l_extendedprice * (1 - l_discount)) as revenue")
        .top_k(["revenue"], 10, [True])
    )


def build_asof(ctx, paths):
    t = ctx.read_sorted_parquet(paths["trades"], sorted_by="time")
    q = ctx.read_sorted_parquet(paths["quotes"], sorted_by="time")
    return (
        t.join_asof(q, on="time", by="symbol")
        .with_columns_sql("bid * size as notional")
        .groupby("symbol")
        .agg_sql("sum(notional) as total, count(*) as n")
    )


BUILDERS = {"q1": build_q1, "q3": build_q3, "asof": build_asof}


def _read(paths, name, columns):
    import pyarrow.parquet as pq

    return pq.read_table(paths[name], columns=columns).to_pandas()


def ref_q1(paths):
    import datetime

    li = _read(paths, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
    li = li[li.l_shipdate <= datetime.date(1998, 9, 2)]
    disc = li.l_extendedprice * (1 - li.l_discount)
    li = li.assign(disc_price=disc, charge=disc * (1 + li.l_tax))
    g = li.groupby(["l_returnflag", "l_linestatus"])
    return g.agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index()


def ref_q3(paths):
    import datetime

    cut = datetime.date(1995, 3, 15)
    li = _read(paths, "lineitem", [
        "l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"])
    o = _read(paths, "orders", [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    c = _read(paths, "customer", ["c_custkey", "c_mktsegment"])
    j = (li[li.l_shipdate > cut]
         .merge(o[o.o_orderdate < cut], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(c[c.c_mktsegment == "BUILDING"], left_on="o_custkey",
                right_on="c_custkey"))
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    g = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
         .revenue.sum().reset_index())
    return g.sort_values("revenue", ascending=False).head(10)


def ref_asof(paths):
    import pandas as pd

    t = _read(paths, "trades", None)
    q = _read(paths, "quotes", None)
    j = pd.merge_asof(t, q, on="time", by="symbol",
                      direction="backward").dropna(subset=["bid"])
    j = j.assign(notional=j.bid * j["size"])
    return j.groupby("symbol").agg(
        total=("notional", "sum"), n=("notional", "size")).reset_index()


REFS = {"q1": ref_q1, "q3": ref_q3, "asof": ref_asof}
# (sort keys or None to keep the query's own order, exact columns)
SHAPES = {
    "q1": (["l_returnflag", "l_linestatus"],
           ["l_returnflag", "l_linestatus", "count_order"]),
    "q3": (None, ["l_orderkey", "o_orderdate", "o_shippriority"]),
    "asof": (["symbol"], ["symbol", "n"]),
}


def check(name, got, ref, label, against="the pandas reference"):
    """Exact on keys and counts, RTOL on sums; raises on any mismatch."""
    import numpy as np

    keys, exact = SHAPES[name]
    assert len(got) == len(ref) and len(ref) > 0, (
        f"{label}: {len(got)} rows, reference has {len(ref)}")
    if keys:
        got, ref = got.sort_values(keys), ref.sort_values(keys)
    for c in ref.columns:
        g, r = got[c].to_numpy(), ref[c].to_numpy()
        if c in exact:
            assert list(map(str, g)) == list(map(str, r)), (
                f"{label}: column {c} differs:\n{g}\n{r}")
        else:
            g = g.astype(np.float64)
            assert np.isfinite(g).all(), f"{label}: {c} is not finite: {g}"
            np.testing.assert_allclose(
                g, r.astype(np.float64), rtol=RTOL,
                err_msg=f"{label}: column {c}")
    say(f"[check] {label}: {len(ref)} rows match {against} "
        f"(keys/counts exact, sums rtol={RTOL})")


# ---------------------------------------------------------------------------
# phase 3: one-shot queries, cold then warm
# ---------------------------------------------------------------------------


def _ctx(**kw):
    from quokka_tpu import QuokkaContext

    return QuokkaContext(io_channels=3, exec_channels=2, **kw)


def timed_collect(name, paths, label, **ctx_kw):
    """One collect() ending in a pandas frame in host memory; prints wall
    seconds, real compiles and the compile plane's counters."""
    from quokka_tpu.runtime import compileplane
    from quokka_tpu.utils import compilestats

    ctx = _ctx(**ctx_kw)
    c0 = compilestats.snapshot()
    t0 = time.time()
    df = BUILDERS[name](ctx, paths).collect()
    wall = time.time() - t0
    c1 = compilestats.snapshot()
    say(f"[query] {label}: {wall:.3f} s wall (smoke reading), "
        f"real_compiles={c1['real_compiles'] - c0['real_compiles']} "
        f"xla_cache_hits={c1['cache_hits'] - c0['cache_hits']} "
        f"compileplane={json.dumps(compileplane.stats(), sort_keys=True)}")
    return df, ctx


def phase_oneshot(paths, refs) -> dict:
    results = {}
    for name in BUILDERS:
        for run in ("cold", "warm"):
            df, _ = timed_collect(name, paths, f"{name} {run}")
            check(name, df, refs[name], f"{name} {run}")
        results[name] = df
    return results


# ---------------------------------------------------------------------------
# phase 4: the served path
# ---------------------------------------------------------------------------


def phase_served(args, paths, refs) -> None:
    from quokka_tpu.service import QueryService

    svc = QueryService(pool_size=2,
                       spill_dir=os.path.join(args.out, "service_spill"))
    t0 = time.time()
    h1 = svc.submit(build_q1(_ctx(), paths))
    h3 = svc.submit(build_q3(_ctx(), paths))
    check("q1", h1.to_df(timeout=900), refs["q1"], "served q1 (concurrent)")
    check("q3", h3.to_df(timeout=900), refs["q3"], "served q3 (concurrent)")
    say(f"[served] q1+q3 submitted together: {time.time() - t0:.3f} s wall "
        "(smoke reading)")
    t0 = time.time()
    again = svc.submit(build_q1(_ctx(), paths))
    check("q1", again.to_df(timeout=900), refs["q1"], "served q1 (repeat)")
    scan = again.scan_cache_stats()
    say(f"[served] q1 repeat: {time.time() - t0:.3f} s wall (smoke reading), "
        f"scan cache {json.dumps(scan)}")
    assert scan["hits"] > 0, f"served repeat missed the scan cache: {scan}"
    svc.shutdown()


# ---------------------------------------------------------------------------
# phase 5: restart — a second process, from the persisted caches
# ---------------------------------------------------------------------------


def phase_restart(paths) -> None:
    from quokka_tpu.runtime import compileplane

    df, _ = timed_collect("q3", paths, "q3 after restart")
    check("q3", df, ref_q3(paths), "q3 after restart")
    stats = compileplane.stats()
    hits = stats.get("cache_hit", 0) + stats.get("prewarm_hit", 0)
    say(f"[restart] cache_hit={stats.get('cache_hit', 0)} "
        f"prewarm_hit={stats.get('prewarm_hit', 0)} "
        f"aot_mismatch={stats.get('aot_mismatch', 0)} "
        f"miss={stats.get('miss', 0)}")
    assert hits > 0, (
        "the restarted process loaded nothing from the AOT store: the cache "
        f"moved or was not written ({stats})")


# ---------------------------------------------------------------------------
# --mesh N: the sharded path beside one device, and nothing else
# ---------------------------------------------------------------------------


def phase_mesh(args, device, paths) -> None:
    from quokka_tpu.parallel import mesh_exec
    from quokka_tpu.parallel.mesh import make_mesh

    assert device["count"] == args.mesh, (
        f"--mesh {args.mesh} needs exactly {args.mesh} devices, jax has "
        f"{device['count']}")
    mesh = make_mesh(args.mesh)
    # steer from the script, not through a program option: note where each
    # scanned column's shards land as the mesh plane places them
    placed = {}
    shard_batch = mesh_exec._shard_batch

    def recording_shard_batch(batch, *a, **kw):
        out = shard_batch(batch, *a, **kw)
        for cname, c in out.columns.items():
            arr = c.codes if hasattr(c, "codes") else c.data
            placed[cname] = sorted(
                s.device.id for s in arr.addressable_shards)
        return out

    mesh_exec._shard_batch = recording_shard_batch
    for name in ("q3", "asof"):
        ref = REFS[name](paths)
        placed.clear()
        got_mesh, mctx = timed_collect(
            name, paths, f"{name} mesh x{args.mesh}", mesh=mesh)
        assert mctx.last_mesh_fallback is None, (
            f"{name} fell back from the mesh: {mctx.last_mesh_fallback}")
        assert placed, f"{name}: the mesh plane scanned nothing"
        for cname, ids in placed.items():
            assert len(set(ids)) == args.mesh, (
                f"{name}: scanned column {cname} sits on devices {ids}, "
                f"not spread over {args.mesh}")
        say(f"[mesh] {name}: {len(placed)} scanned columns each spread over "
            f"devices {sorted(set(sum(placed.values(), [])))}")
        check(name, got_mesh, ref, f"{name} mesh x{args.mesh}")
        got_one, _ = timed_collect(name, paths, f"{name} one device")
        check(name, got_one, ref, f"{name} one device")
        check(name, got_mesh, got_one[list(ref.columns)],
              f"{name} mesh x{args.mesh}", against="one device")
    mesh_exec._shard_batch = shard_batch


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def child(args) -> int:
    device = phase_device(args)
    if args.phase == "main":
        paths = make_data(args)
        t0 = time.time()
        refs = {name: REFS[name](paths) for name in BUILDERS}
        say(f"[data] pandas references in {time.time() - t0:.1f} s")
        phase_oneshot(paths, refs)
        phase_served(args, paths, refs)
    elif args.phase == "restart":
        phase_restart(data_paths(args))
    else:
        phase_mesh(args, device, make_data(args))
    from quokka_tpu.runtime import compileplane

    # persists are asynchronous: the next process reads what this one wrote
    compileplane.drain_writes(timeout=300.0)
    say(f"[{args.phase}] done; compileplane "
        f"{json.dumps(compileplane.stats(), sort_keys=True)}")
    with open(os.path.join(args.out, "device.json"), "w",
              encoding="utf-8") as f:
        json.dump(device, f)
    return 0


if __name__ == "__main__":
    _argv = sys.argv[1:]
    _args = parse_args(_argv)
    sys.exit(child(_args) if _args.phase else parent(_args, _argv))
