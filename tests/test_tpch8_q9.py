"""TPC-H Q9 over the eight-table deployment ``tpch8_sf1`` (ISSUE 34), at the
benchmark's rehearsal size.

(a) the generator's contract (``benchmarks/datagen/tpch8.py``): dbgen's
part-supplier rule and price rule, so that Q9's composite join keeps every
row; (b) the served answer equals the benchmark's plain reference for three
colours, and the join is not almost empty; (c) ``hash_join_pk`` on a
two-column key against ``pandas.merge`` on both ``join_build`` branches;
(d) property P of the join chain: the programs a request asks for do not
follow the order in which its batches arrive; (e) the query record's join
and string-predicate counters and the ``join.*`` spans."""

import os
import sys
import threading
import types

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from quokka_tpu import QuokkaContext, obs
from quokka_tpu.executors import sql_execs
from quokka_tpu.executors.sql_execs import BuildProbeJoinExecutor
from quokka_tpu.obs import querylog
from quokka_tpu.ops import bridge, kernels, sigkey
from quokka_tpu.ops import join as join_ops
from quokka_tpu.ops.batch import DeviceBatch
from quokka_tpu.service import QueryService

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SEED, SF = 34, 0.01
COLOURS = ["green", "almond", "yellow"]
SORT = "groupby=sort,join_build=sort"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark's own files for this deployment, read and not touched:
    its generator, its query and the comparison that decides ``correct``;
    the eight tables at the rehearsal size as Parquet and as frames."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, tables

        conf = spec.load_json(os.path.join(BENCH, "configs",
                                           "tpch8_sf1.json"))
        gen = spec.load_module("datagen", conf["datagen"]["module"])
        q9 = spec.load_module("queries", "q9")
    finally:
        sys.path.remove(BENCH)
    assert conf["datagen"]["rehearsal_args"] == {"sf": SF}
    made = gen.generate(SEED, SF)
    paths = tables.ensure(str(tmp_path_factory.mktemp("tpch8") / "t"),
                          conf["datagen"]["module"], {"sf": SF}, SEED)
    return types.SimpleNamespace(
        conf=conf, gen=gen, q9=q9, check=check, tables=made, paths=paths,
        frames={t: made[t].to_pandas() for t in made})


@pytest.fixture(scope="module")
def service():
    svc = QueryService(pool_size=2)
    yield svc
    svc.shutdown()


def _context(conf, **over):
    svc = dict(conf["service"], **over)
    return QuokkaContext(io_channels=svc["io_channels"],
                         exec_channels=svc["exec_channels"])


# -- (a) the generator's contract --------------------------------------------


def test_row_counts_follow_the_scale_factor(bench):
    rows = {t: bench.tables[t].num_rows for t in bench.tables}
    assert {t: rows[t] for t in rows if t != "lineitem"} == {
        "orders": 15_000, "customer": 1_500, "part": 2_000,
        "partsupp": 8_000, "supplier": 100, "nation": 25, "region": 5}
    assert 15_000 <= rows["lineitem"] <= 7 * 15_000
    assert bench.tables["part"].column_names == [
        "p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
        "p_container", "p_retailprice"]


def test_every_line_item_finds_its_partsupp_row(bench):
    li, ps = bench.frames["lineitem"], bench.frames["partsupp"]
    assert not ps.duplicated(["ps_partkey", "ps_suppkey"]).any()
    assert (ps.groupby("ps_partkey").size() == 4).all()
    assert ps.ps_suppkey.between(1, 100).all()
    joined = li.merge(ps, left_on=["l_partkey", "l_suppkey"],
                      right_on=["ps_partkey", "ps_suppkey"])
    assert len(joined) == len(li), "dbgen's rule: a part's four suppliers"
    # and all four are used
    assert li.groupby("l_partkey").l_suppkey.nunique().max() == 4


def test_part_names_are_five_distinct_list_words(bench):
    words = bench.gen.P_NAME_WORDS
    assert len(words) == len(set(words)) == 92
    names = bench.frames["part"].p_name.str.split(" ")
    assert (names.str.len() == 5).all()
    assert names.map(lambda w: len(set(w)) == 5 and set(w) <= set(words)).all()
    share = bench.frames["part"].p_name.str.contains("green").mean()
    assert 0.03 < share < 0.08  # 5 / 92


def test_prices_follow_the_part(bench):
    li, part = bench.frames["lineitem"], bench.frames["part"]
    key = part.p_partkey
    np.testing.assert_allclose(
        part.p_retailprice,
        (90000 + (key // 10) % 20001 + 100 * (key % 1000)) / 100)
    price = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    np.testing.assert_allclose(price.l_extendedprice,
                               price.l_quantity * price.p_retailprice,
                               rtol=0, atol=0.005)


def test_same_seed_same_tables(bench):
    again = bench.gen.generate(SEED, SF)
    assert all(again[t].equals(bench.tables[t]) for t in bench.tables)
    other = bench.gen.generate(SEED + 1, SF)
    assert not other["lineitem"].equals(bench.tables["lineitem"])
    assert not other["part"].equals(bench.tables["part"])


# -- (b) the served answer and the plain reference ---------------------------


@pytest.mark.parametrize("colour", COLOURS)
def test_served_q9_equals_the_reference(colour, bench, service):
    q9, params = bench.q9, {"color": colour}
    got = service.submit(
        q9.build(_context(bench.conf), bench.paths, params)).to_df(
            timeout=600)
    ref = q9.reference(bench.paths, params)
    assert list(got.columns) == ["nation", "o_year", "sum_profit"]
    assert len(ref) > 100, "an almost empty join would pass anything"
    numbers, _ = bench.check.compare([got], ref, q9.SORT_KEYS, q9.EXACT)
    assert numbers["wrong_cells"] == 0
    assert numbers["sum_rel_err"] <= q9.LIMITS["sum_rel_err"]
    # the specification's order: nation, then year descending
    exp = ref.sort_values(["nation", "o_year"], ascending=[True, False])
    assert list(got.nation) == list(exp.nation)
    assert list(got.o_year) == list(exp.o_year)
    assert q9.MATCHED_ROWS[colour] > 100 * len(ref) / 175


# -- (c) the primary-key join on a two-column key ----------------------------


def _two_column_case(r):
    """A unique build on (a, b) whose keys differ in ``b`` alone for many
    ``a``; a probe with present keys, absent ones (an ``a`` the build has
    with a ``b`` it has not), and null keys."""
    a = np.repeat(np.arange(1, 301, dtype=np.int64), 4)
    b = np.tile(np.arange(4, dtype=np.int64), 300) * 7 + a % 5
    build = pd.DataFrame({"a": a, "b": b, "cost": r.uniform(1, 9, len(a))})
    n = 2_000
    pick = r.integers(0, len(build), n)
    probe = pd.DataFrame({
        "x": build.a.to_numpy()[pick], "y": build.b.to_numpy()[pick],
        "q": r.integers(1, 50, n).astype(np.int64)})
    probe.loc[::7, "y"] += 1000          # the first column alone matches
    probe.loc[::11, "x"] = 100_000       # neither does
    nulls = np.zeros(n, dtype=bool)
    nulls[::13] = True
    table = pa.table({
        "x": pa.array(probe.x, mask=nulls), "y": pa.array(probe.y),
        "q": pa.array(probe.q)})
    return build, probe[~nulls], table


@pytest.mark.parametrize("how", ["inner", "semi", "anti"])
@pytest.mark.parametrize("branch", ["sort", "hashtable"])
def test_two_column_key_matches_pandas(branch, how, monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY", f"join_build={branch}")
    build_df, probe_df, probe_table = _two_column_case(
        np.random.default_rng(9))
    build = bridge.arrow_to_device(pa.Table.from_pandas(build_df))
    probe = bridge.arrow_to_device(probe_table)
    assert join_ops.build_keys_unique(build, ["a", "b"])
    out = join_ops.hash_join_pk(probe, build, ["x", "y"], ["a", "b"], how,
                                [] if how != "inner" else ["cost"])
    got = bridge.device_to_arrow(kernels.compact(out)).to_pandas()
    merged = probe_df.merge(build_df, left_on=["x", "y"],
                            right_on=["a", "b"], how="left", indicator=True)
    if how == "inner":
        exp = merged[merged._merge == "both"][["x", "y", "q", "cost"]]
    elif how == "semi":
        exp = merged[merged._merge == "both"][["x", "y", "q"]]
    else:  # a null key matches nothing, so the anti join keeps its row
        exp = merged[merged._merge == "left_only"][["x", "y", "q"]]
        got = got.dropna(subset=["x"])
    by = ["x", "y", "q"]
    got = got[exp.columns].sort_values(by).reset_index(drop=True)
    exp = exp.sort_values(by).reset_index(drop=True)
    assert len(got) == len(exp) > 0
    assert (got[by].to_numpy().astype(np.int64)
            == exp[by].to_numpy().astype(np.int64)).all()
    if how == "inner":
        np.testing.assert_allclose(got.cost, exp.cost)
    if branch == "sort":
        # two key columns: no direct-address table, the search probes
        assert build._pk_direct_cache[("a", "b")] is None


# -- (d) the program set does not follow arrival -----------------------------

KINDS = ("pk_probe_sorted", "pk_probe_direct", "pk_direct_build",
         "sort_build_keys", "compact_idx", "gather", "fused_concat",
         "mask_count", "partition_ids", "split_masks")


def _fresh(b: DeviceBatch) -> DeviceBatch:
    """A batch as the engine hands it over: its own object, count unread."""
    return DeviceBatch(dict(b.columns), b.valid, None, b.sorted_by,
                       b.nrows_dev)


def _keys():
    return {kind: set(sigkey.ledger_keys(kind)) for kind in KINDS}


def _chain(bench, batches, order):
    """Q9's first two joins as their executors run them, one probe batch a
    dispatch: the semi join with the filtered part, each output split over
    two channels on (l_partkey, l_suppkey) as the exchange splits it, then
    the composite-key join with partsupp on each channel."""
    part = bench.frames["part"]
    part = part[part.p_name.str.contains("green")][["p_partkey"]]
    semi = BuildProbeJoinExecutor(["l_partkey"], ["p_partkey"], how="semi")
    semi.execute([bridge.arrow_to_device(pa.Table.from_pandas(
        part, preserve_index=False))], 1, 0)
    semi.source_done(1, 0)
    ps = bridge.arrow_to_device(bench.tables["partsupp"].select(
        ["ps_partkey", "ps_suppkey", "ps_supplycost"]))
    pids = kernels.partition_ids(ps, ["ps_partkey", "ps_suppkey"], 2)
    composite = []
    for ch, piece in enumerate(kernels.split_by_partition(ps, pids, 2)):
        ex = BuildProbeJoinExecutor(["l_partkey", "l_suppkey"],
                                    ["ps_partkey", "ps_suppkey"])
        ex.execute([piece], 1, ch)
        ex.source_done(1, ch)
        composite.append(ex)
    frames = []
    for i in order:
        kept = semi.execute([_fresh(batches[i])], 0, 0)
        pids = kernels.partition_ids(kept, ["l_partkey", "l_suppkey"], 2)
        for ch, piece in enumerate(
                kernels.split_by_partition(kept, pids, 2)):
            out = composite[ch].execute([piece], 0, ch)
            frames.append(bridge.device_to_arrow(
                kernels.compact(out)).to_pandas())
    return pd.concat(frames)


@pytest.mark.parametrize("order", [[4, 3, 2, 1, 0], [2, 0, 4, 1, 3]],
                         ids=["reversed", "shuffled"])
def test_join_chain_asks_for_the_same_programs_under_every_arrival(
        order, bench, monkeypatch):
    """Five lineitem batches of 12,288 rows; about one row in twenty passes
    the semi join, so with the threshold at a test's size every output is
    compacted before the composite-key search."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY", SORT)
    monkeypatch.setattr(sql_execs, "SHRINK_ABOVE", 1 << 10)
    li = bench.tables["lineitem"].select(bench.q9.COLUMNS["lineitem"])
    step = 12_288
    batches = [bridge.arrow_to_device(li.slice(i * step, step))
               for i in range(5)]
    assert BuildProbeJoinExecutor.MAX_PIPELINE_BATCHES == 1
    sigkey.reset_ledger()
    first_rows = _chain(bench, batches, range(5))
    first = _keys()
    rows = _chain(bench, batches, order)
    later = _keys()
    new = {k: sorted(later[k] - first[k], key=repr) for k in KINDS
           if later[k] - first[k]}
    assert not new, f"asked for programs outside the first set: {new}"
    assert first["pk_probe_sorted"] and first["pk_probe_direct"]
    assert first["compact_idx"], "no semi-join output was compacted"
    assert not first["fused_concat"], "nothing is concatenated by arrival"
    # the search ran over compacted batches, never over a scan batch's slots
    assert all(16_384 not in k for k in first["pk_probe_sorted"]), first
    by = ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity"]
    assert len(rows) == len(first_rows) > 1_000
    assert (rows.sort_values(by).to_numpy()
            == first_rows.sort_values(by).to_numpy()).all()


def test_a_stage_with_a_join_takes_one_batch_a_dispatch():
    """The head member receives what the stage was dispatched with, so a
    fused stage takes the tightest cap a member states."""
    import functools

    from quokka_tpu.ops.stagefuse import FusedStageExecutor, StageSpec

    join = functools.partial(BuildProbeJoinExecutor, ["a"], ["b"])
    plain = functools.partial(sql_execs.UDFExecutor, lambda b: b)
    with_join = FusedStageExecutor(StageSpec(
        [("map", plain), ("join", join)], {0: (0, 0), 1: (1, 1)}))
    without = FusedStageExecutor(StageSpec(
        [("map", plain), ("map", plain)], {0: (0, 0)}))
    assert with_join.MAX_PIPELINE_BATCHES == 1
    assert without.MAX_PIPELINE_BATCHES == 32


def test_later_requests_ask_the_compile_plane_for_nothing(bench, monkeypatch,
                                                          tmp_path):
    """Eight lineitem row groups a request, two clients at once: what the
    first requests compiled is all that the later ones ask for."""
    monkeypatch.setenv("QK_KERNEL_STRATEGY", SORT)
    monkeypatch.setattr(sql_execs, "SHRINK_ABOVE", 1 << 10)
    paths = dict(bench.paths, lineitem=str(tmp_path / "lineitem.parquet"))
    pq.write_table(bench.tables["lineitem"], paths["lineitem"],
                   row_group_size=8_192)
    svc = QueryService(pool_size=2)
    answers, t0 = [], (querylog.records()[-1]["done"] if querylog.size()
                       else 0.0)

    def client(n):
        for _ in range(n):
            answers.append(svc.submit(bench.q9.build(
                _context(bench.conf), paths, {"color": "green"})).to_df(
                    timeout=600))

    try:
        client(2)  # the warm-up: every program of the plan
        threads = [threading.Thread(target=client, args=(3,))
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = querylog.records(since=t0)
    finally:
        svc.shutdown()
    assert len(recs) == 8 and all(r["status"] == "done" for r in recs)
    late = {r["q"]: r["compiled"] for r in recs[2:] if r["compiled"]}
    assert not late, late
    ref = bench.q9.reference(bench.paths, {"color": "green"})
    for got in answers:
        numbers, _ = bench.check.compare([got], ref, bench.q9.SORT_KEYS,
                                         bench.q9.EXACT)
        assert numbers["wrong_cells"] == 0
        assert numbers["sum_rel_err"] <= bench.q9.LIMITS["sum_rel_err"]


# -- (e) the record, the counters and the spans ------------------------------


def test_record_carries_the_joins_counters_and_spans(bench, monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY", SORT)
    events = obs.RECORDER.snapshot()
    seq = events[-1][0] if events else -1
    svc = QueryService(pool_size=2)
    try:
        # one channel: each of the five joins finalises one build
        handle = svc.submit(bench.q9.build(
            _context(bench.conf, exec_channels=1), bench.paths,
            {"color": "green"}))
        assert len(handle.to_df(timeout=600)) > 100
    finally:
        svc.shutdown()
    rec = querylog.records()[-1]
    assert rec["q"] == handle.query_id and rec["status"] == "done"
    assert rec["join_builds"] == 5
    assert rec["join_probe_search"] > 0, "the composite key takes the search"
    assert rec["join_probe_direct"] > 0 and rec["join_probe_general"] == 0
    assert rec["str_pred_dict_rows"] == bench.frames["part"].p_name.nunique()
    assert sum(rec[k] for k in (
        "runtime.dispatch_self", "executors.exec_self", "runtime.push",
        "io.read", "emit.d2h", "compile.acquire", "other")) == pytest.approx(
            rec["task_s"])
    spans = [ev for ev in obs.RECORDER.snapshot(since=seq)
             if ev[2] == "span" and ev[3].startswith("join.")]
    assert {ev[3] for ev in spans} == {"join.build", "join.probe"}
    assert all(ev[6]["q"] == handle.query_id for ev in spans)
    assert sum(ev[3] == "join.build" for ev in spans) == 5
    # nested in the executors' spans, and theirs in the record's layer
    assert all(ev[6]["p"].startswith(("exec.", "done.", "join."))
               for ev in spans), {ev[6]["p"] for ev in spans}
    from quokka_tpu.obs import spans as tracing

    assert tracing._layer("join.build") == tracing._layer(
        "join.probe") == "executors.exec_self"
