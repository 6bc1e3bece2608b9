"""TPC-H Q21 at a small size, and the streaming distinct under it.

- Q21 served through a ``QueryService`` equals ``benchmarks/queries/q21.py``'s
  reference, for three seeds and two nations each (seeds 42101-42103 at
  SF 0.01), and its query record carries the distinct's three counters,
  equal to the rows and pairs the reference counted;
- that reference equals a literal row-by-row evaluation of the SQL's EXISTS
  and NOT EXISTS (seed 42104 at SF 0.002);
- ``datagen/tpch_q21.py`` derives ``o_orderstatus`` by clause 4.2.3 and
  leaves every other column as ``datagen/tpch8.py`` makes it;
- ``DistinctExecutor`` equals ``pandas.drop_duplicates`` (first rows, in
  order) over many small batches: one key, two keys, a string key (two hash
  limbs), floats with NaN and signed zeros, an empty batch, a batch of
  duplicates only, a state that leaves the windowed probe for the binary
  search, and batches longer than a fold's piece;
- the probe's window and its binary search each answer as numpy's search
  does, and the slots it counts name the route it took;
- the programs a fold runs, and their shapes, do not follow the order the
  batches arrive in;
- its sorted or compared slots per input row stay flat from 4 to 16
  batches of a clustered table, and grow with the log of the state on
  random keys.

The benchmark's row groups are 1<<20 rows, so a served query at this size
folds one batch a channel; the many-batch cases drive the executor itself.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from quokka_tpu import QuokkaContext
from quokka_tpu.executors.sql_execs import DistinctExecutor
from quokka_tpu.obs import querylog
from quokka_tpu.ops import bridge, kernels
from quokka_tpu.ops import distinct as distinct_ops
from quokka_tpu.service import QueryService

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SEEDS = (42101, 42102, 42103)
LITERAL_SEED = 42104


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The benchmark's query, generators and comparison, read and not
    touched, over tables of this file's own."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check, spec, tables

        q21 = spec.load_module("queries", "q21")
        tpch8 = spec.load_module("datagen", "tpch8")
        tpch_q21 = spec.load_module("datagen", "tpch_q21")
    finally:
        sys.path.remove(BENCH)
    root = tmp_path_factory.mktemp("tpch_q21")
    paths = {seed: tables.ensure(str(root / str(seed)), "tpch_q21",
                                 {"sf": 0.01}, seed) for seed in SEEDS}
    return types.SimpleNamespace(check=check, q21=q21, tpch8=tpch8,
                                 tpch_q21=tpch_q21, paths=paths)


@pytest.fixture(scope="module")
def svc():
    service = QueryService(pool_size=2)
    yield service
    service.shutdown()


def _nations(paths, rank):
    """The nation with the ``rank``-th most suppliers (ties by name)."""
    s = pd.read_parquet(paths["supplier"])
    n = pd.read_parquet(paths["nation"])
    counts = (s.merge(n, left_on="s_nationkey", right_on="n_nationkey")
              .groupby("n_name").size().reset_index(name="k")
              .sort_values(["k", "n_name"], ascending=[False, True]))
    return counts.n_name.iloc[rank]


def _served(svc, bench, paths, params):
    handle = svc.submit(bench.q21.build(
        QuokkaContext(io_channels=3, exec_channels=2), paths, params))
    return handle, handle.to_df(timeout=600)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("rank", [0, 1])
def test_served_q21_equals_the_reference(seed, rank, bench, svc):
    paths = bench.paths[seed]
    params = {"nation": _nations(paths, rank)}
    _, got = _served(svc, bench, paths, params)
    ref = bench.q21.reference(paths, params)
    assert len(ref) > 0
    numbers, _ = bench.check.compare([got], ref, bench.q21.SORT_KEYS,
                                     bench.q21.EXACT)
    assert bench.check.judge(numbers, bench.q21.LIMITS), (numbers, got, ref)
    assert list(got.columns) == ["s_name", "numwait"]


def test_a_served_query_records_the_distinct_counters(bench, svc):
    paths = bench.paths[SEEDS[0]]
    handle, _ = _served(svc, bench, paths,
                        {"nation": _nations(paths, 0)})
    bench.q21.reference(paths, {})
    counted = bench.q21.DISTINCT
    rec = [r for r in querylog.records() if r["q"] == handle.query_id][0]
    # both distincts: every line item and the late ones in, their pairs out
    assert rec["distinct_rows_in"] == (counted["all"]["rows"]
                                       + counted["late"]["rows"])
    assert rec["distinct_rows_out"] == (counted["all"]["pairs"]
                                        + counted["late"]["pairs"])
    assert rec["distinct_rows_out"] < rec["distinct_rows_in"]
    # two sorts and at least a halving of the bounds' search a padded slot
    assert 3 * rec["distinct_rows_in"] <= rec["distinct_sort_slots"]


# -- the reference against the SQL, row by row -------------------------------


def test_the_reference_equals_a_literal_exists_evaluation(bench,
                                                          tmp_path):
    sys.path.insert(0, BENCH)
    try:
        from harness import tables

        paths = tables.ensure(str(tmp_path / "literal"), "tpch_q21",
                              {"sf": 0.002}, LITERAL_SEED)
    finally:
        sys.path.remove(BENCH)
    li = pd.read_parquet(paths["lineitem"])
    o = pd.read_parquet(paths["orders"])
    s = pd.read_parquet(paths["supplier"])
    n = pd.read_parquet(paths["nation"])
    lines = {}  # order -> [(supplier, late)]
    for key, supp, commit, receipt in zip(
            li.l_orderkey, li.l_suppkey, li.l_commitdate, li.l_receiptdate):
        lines.setdefault(key, []).append((supp, receipt > commit))
    status = dict(zip(o.o_orderkey, o.o_orderstatus))
    name_of = dict(zip(s.s_suppkey, s.s_name))
    nation_of = dict(zip(s.s_suppkey, s.s_nationkey))
    compared = 0
    for nation_key, nation in zip(n.n_nationkey, n.n_name):
        numwait = {}
        for key, supp, commit, receipt in zip(
                li.l_orderkey, li.l_suppkey, li.l_commitdate,
                li.l_receiptdate):
            if not (receipt > commit and status[key] == "F"
                    and nation_of[supp] == nation_key):
                continue
            exists = any(s2 != supp for s2, _ in lines[key])
            not_exists = not any(s3 != supp and late3
                                 for s3, late3 in lines[key])
            if exists and not_exists:
                numwait[name_of[supp]] = numwait.get(name_of[supp], 0) + 1
        literal = sorted(numwait.items(), key=lambda kv: (-kv[1], kv[0]))
        ref = bench.q21.reference(paths, {"nation": nation})
        assert list(zip(ref.s_name, ref.numwait)) == literal[:100]
        compared += len(literal)
    assert compared > 0


# -- the generator -----------------------------------------------------------


def test_orderstatus_follows_clause_4_2_3_and_nothing_else_moves(bench):
    seed = SEEDS[0]
    base = bench.tpch8.generate(seed, 0.01)
    q21 = bench.tpch_q21.generate(seed, 0.01)
    assert sorted(base) == sorted(q21)
    for name in base:
        if name != "orders":
            assert q21[name].equals(base[name]), name
    assert q21["orders"].drop_columns(["o_orderstatus"]).equals(
        base["orders"].drop_columns(["o_orderstatus"]))
    li = q21["lineitem"].to_pandas()
    by_order = {}
    for key, status in zip(li.l_orderkey, li.l_linestatus):
        by_order.setdefault(key, set()).add(status)
    orders = q21["orders"].to_pandas()
    for key, got in zip(orders.o_orderkey, orders.o_orderstatus):
        seen = by_order[key]
        assert got == ("F" if seen == {"F"} else
                       "O" if seen == {"O"} else "P"), key
    shares = orders.o_orderstatus.value_counts(normalize=True)
    assert shares["P"] < 0.1 < shares["F"]


# -- the executor against pandas ---------------------------------------------


def _fold_all(keys, frames, masks=None):
    """Each frame a batch through one executor (a mask of False rows for an
    empty batch); the concatenated outputs as a frame."""
    ex = DistinctExecutor(keys)
    outs = []
    for i, frame in enumerate(frames):
        batch = bridge.arrow_to_device(pa.Table.from_pandas(
            frame, preserve_index=False))
        if masks is not None and masks[i] is not None:
            mask = jnp.zeros(batch.padded_len, bool).at[
                :len(frame)].set(jnp.asarray(masks[i]))
            batch = kernels.apply_mask(batch, mask)
        out = ex.execute([None, batch], 0, 0)
        if out is not None:
            outs.append(bridge.device_to_arrow(out).to_pandas())
    got = (pd.concat(outs) if outs else pd.DataFrame(columns=keys))
    return got[keys].reset_index(drop=True)


def _expected(keys, frames, masks=None):
    kept = [f[m] if masks is not None and m is not None else f
            for f, m in zip(frames, masks or [None] * len(frames))]
    return pd.concat(kept)[keys].drop_duplicates().reset_index(drop=True)


def _assert_same(got, exp):
    assert len(got) == len(exp)
    for c in exp.columns:
        g, e = got[c].to_numpy(), exp[c].to_numpy()
        if e.dtype.kind == "f":
            np.testing.assert_array_equal(g, e)  # NaN == NaN here
        else:
            assert (g.astype(str) == e.astype(str)).all(), c


def _pairs(r, batches, rows, hi=400):
    return [pd.DataFrame({"a": r.integers(0, hi, rows),
                          "b": r.integers(0, 30, rows)})
            for _ in range(batches)]


@pytest.mark.parametrize("case", ["one_key", "two_keys", "string_key",
                                  "floats", "empty_and_duplicates",
                                  "widely_spread", "longer_than_a_piece"])
def test_distinct_executor_equals_drop_duplicates(case, monkeypatch):
    r = np.random.default_rng(17)
    masks = None
    if case == "longer_than_a_piece":
        # batches of 4,096 slots folded as four pieces of 1,024
        monkeypatch.setattr(distinct_ops, "FOLD_SLOTS", 1024)
        keys, frames = ["a", "b"], _pairs(r, 5, 3000, hi=2000)
    elif case == "one_key":
        keys, frames = ["a"], _pairs(r, 12, 700, hi=9000)
    elif case == "two_keys":
        keys, frames = ["a", "b"], _pairs(r, 12, 700)
    elif case == "string_key":
        keys = ["s", "b"]
        frames = [pd.DataFrame({"s": [f"k{v}" for v in r.integers(0, 300, 600)],
                                "b": r.integers(0, 3, 600)})
                  for _ in range(10)]
    elif case == "floats":
        keys = ["x"]
        vals = np.array([0.0, -0.0, np.nan, 1.5, -2.25, 3.0, np.inf])
        frames = [pd.DataFrame({"x": np.concatenate(
            [vals[r.integers(0, len(vals), 50)], r.normal(size=300)])})
                  for _ in range(6)]
    elif case == "empty_and_duplicates":
        keys = ["a", "b"]
        frames = _pairs(r, 6, 500)
        frames.insert(2, frames[1].copy())  # every row seen before
        frames.insert(4, frames[3].iloc[:1].copy())
        masks = [None] * len(frames)
        masks[5] = np.zeros(len(frames[5]), bool)  # no live row
        masks[6] = r.random(len(frames[6])) < 0.3
    else:  # a state the next batches' keys spread past: the binary search
        keys = ["a", "b"]
        frames = [pd.DataFrame({"a": np.arange(5000) % 2500 + 10_000,
                                "b": np.arange(5000) // 2500})]
        frames += [pd.DataFrame({"a": r.integers(-10**6, 10**6, 4096),
                                 "b": r.integers(0, 2, 4096)})
                   for _ in range(3)]
        frames.append(frames[0].sample(frac=0.5, random_state=3))
    _assert_same(_fold_all(keys, frames, masks),
                 _expected(keys, frames, masks))


@pytest.mark.parametrize("n", [1000, 4096, 1 << 21])
def test_the_merge_prefix_sum_by_rows_equals_numpy(n):
    """The merge's prefix sum: one piece below a row or off its multiple,
    rows of 1,024 above, and rows of rows at 2 M slots."""
    x = np.random.default_rng(n).integers(0, 3, n).astype(np.int32)
    got = jax.jit(distinct_ops._cumsum)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x))


def _slots_by_route(n, cap):
    """The slots a probe of ``n`` padded slots against a state of ``cap``
    counts on each route, by hand: two sorts a slot, the bounds' halvings
    for a key a chunk and the last live key, then a window of ``NARROW``
    chunks a slot, or the search's halvings and its last read a slot."""
    chunk = min(distinct_ops.CHUNK, n & -n)
    steps = cap.bit_length()
    base = 2 * n + (n // chunk + 1) * steps
    window = min(cap, distinct_ops.NARROW * chunk)
    return {base + n * window: "window", base + n * (steps + 1): "search"}


def _probe(state, m, cap, batch, valid):
    """The probe of one batch (a tuple of limbs) in one sorted state, and
    the route its slot count names; the bounds and new-key flags a binary
    search gives, from numpy, for the live sorted slots."""
    dedupe, probe = (distinct_ops.jitted(distinct_ops.distinct_dedupe),
                     distinct_ops.jitted(distinct_ops.distinct_probe))
    keys, rows, starts, live = dedupe(batch, jnp.asarray(valid))
    n = len(valid)
    out = probe(state, jnp.asarray(m, jnp.int32), keys, starts, rows, live,
                min(distinct_ops.CHUNK, n & -n))
    route = _slots_by_route(n, cap).get(int(out[4]))
    assert route is not None, int(out[4])
    return out, np.asarray(keys[0]), int(live), route


def _assert_searched(out, state_keys, sorted_keys, k, batch, valid):
    lb = np.asarray(out[0])
    np.testing.assert_array_equal(
        lb[:k], np.searchsorted(state_keys, sorted_keys[:k], "left"))
    assert (lb[k:] == len(state_keys)).all()
    first = valid & ~pd.Series(np.where(valid, batch, -1)).duplicated(
    ).to_numpy()
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  first & ~np.isin(batch, state_keys))
    assert int(out[3]) == int(np.asarray(out[2]).sum())


def test_probe_takes_the_search_where_a_window_would_not_hold_the_gap():
    """A batch of one int64 limb whose keys spread over the whole state:
    the search, and its answers a binary search's."""
    r = np.random.default_rng(5)
    state = np.unique(r.integers(0, 10**6, 3000))
    cap = 4096
    limbs = (jnp.zeros(cap, jnp.int64).at[:len(state)].set(state),)
    batch = np.concatenate([r.choice(state, 500), r.integers(0, 10**6, 524)])
    valid = np.ones(len(batch), bool)
    out, sorted_keys, k, route = _probe(limbs, len(state), cap,
                                        (jnp.asarray(batch),), valid)
    assert route == "search"
    _assert_searched(out, state, sorted_keys, k, batch, valid)


@pytest.mark.parametrize("layout, route", [
    ("beyond", "window"), ("one_gap", "window"), ("interleaved", "window"),
    ("sparse", "window"), ("empty_state", "window"), ("part_live", "search"),
    ("one_chunk_wide", "search")])
def test_every_window_the_probe_picks_equals_the_search(layout, route):
    """Keys beyond the state, or in one gap of it (what a table clustered on
    the key sends), interleaved with the state's, or sparser than it, and
    an empty state: the window; a batch whose live keys spread over the
    state, dead slots after them, and a chunk whose keys span more of the
    state than its window: the search.  Each route's bounds and flags a
    binary search's, over two int32 limbs."""
    r = np.random.default_rng(11)
    n, cap = 4096, 1 << 14
    state = np.arange(6000) * 4
    valid = np.ones(n, bool)
    if layout == "beyond":
        batch = 30_000 + np.arange(n)
    elif layout == "one_gap":
        state = np.concatenate([np.arange(3000), 100_000 + np.arange(3000)])
        batch = 50_000 + np.arange(n)
    elif layout == "interleaved":
        batch = 1 + 4 * np.arange(n)
    elif layout == "sparse":
        batch = 1 + 12 * np.arange(n) // 2
    elif layout == "empty_state":
        state, batch = state[:0], r.integers(0, 9000, n)
    elif layout == "part_live":
        batch = r.permutation(np.concatenate([r.choice(state, 1000),
                                              30_000 + np.arange(n - 1000)]))
        valid = r.random(n) < 0.6
    else:
        batch = np.concatenate([np.linspace(0, 23_996, 128).astype(int),
                                50_000 + np.arange(n - 128)])
    m = len(state)
    limbs = tuple(jnp.zeros(cap, jnp.int32).at[:m].set(x.astype(np.int32))
                  for x in (state, state % 7))
    b = jnp.asarray(batch, jnp.int32)
    out, sorted_keys, k, got = _probe(limbs, m, cap, (b, b % 7), valid)
    assert got == route
    _assert_searched(out, state, sorted_keys, k, batch, valid)


def test_program_shapes_do_not_follow_the_arrival_order(monkeypatch):
    """SF 1's batches in small: five full scan batches and a short one
    (72.5 % of its slots), each a contiguous range of keys, every row live
    (the all-lines distinct) or 63 % of them (the late lines'): the programs
    a fold runs, their shapes and static arguments, are the same whichever
    order the batches arrive in, and when two of them arrive concatenated
    by an upstream stage."""
    n = 1 << 17
    monkeypatch.setattr(distinct_ops, "FOLD_SLOTS", n)
    calls = []
    call = distinct_ops._call

    def record(body, args, statics=()):
        calls.append((body.__name__, tuple(
            jnp.shape(a) for a in jax.tree_util.tree_leaves(args)),
            tuple(statics)))
        return call(body, args, statics)

    monkeypatch.setattr(distinct_ops, "_call", record)
    late = np.random.default_rng(21).random((6, n)) < 0.63

    def batch(i, fill):
        key = jnp.arange(i * n, (i + 1) * n, dtype=jnp.int32)
        valid = np.arange(n) < (n if i < 5 else int(0.725 * n))
        return (key, key % 7), valid & late[i] if fill else valid

    for fill in (False, True):
        seen_shapes = []
        for order in ([0, 1, 2, 3, 4, 5], [5, 0, 1, 2, 3, 4],
                      [2, 0, 5, 1, 4, 3], [(0, 1), 2, 3, (4, 5)]):
            calls.clear()
            keys, total = distinct_ops.SortedKeys(), 0
            for item in order:
                parts = [batch(i, fill) for i in np.atleast_1d(item)]
                limbs = tuple(jnp.concatenate(x)
                              for x in zip(*(p[0] for p in parts)))
                valid = np.concatenate([p[1] for p in parts])
                total += int(valid.sum())
                keys.fold(limbs, jnp.asarray(valid))
            assert keys.count == total
            seen_shapes.append(set(calls))
        assert all(s == seen_shapes[0] for s in seen_shapes), fill


# -- slots per row -----------------------------------------------------------


def test_sorted_or_searched_slots_per_row_stay_flat(monkeypatch):
    """The slots the probe counts, read from the device, per input row over
    4 and over 16 batches of 4,000 (a, b) rows.  Clustered: each batch a
    range of keys of its own, arriving out of order (every batch's keys in
    one gap of the state: the window): within 10 %.  Random keys: the
    window while the state is about as dense as a batch, then the search,
    whose halvings grow with the log of the state: the reading falls, and
    may not rise by more than 10 %.  (The executor that sorted the batch
    with all of its state read 6.40 and 26.18 on random keys.)"""
    noted = []
    monkeypatch.setattr(distinct_ops.opstats, "note",
                        lambda **f: noted.append(f))

    def per_row(batches, clustered):
        noted.clear()
        r = np.random.default_rng(batches)
        order = r.permutation(batches)
        frames = [pd.DataFrame({
            "a": (i * 3000 + r.integers(0, 3000, 4000)) if clustered
            else r.integers(0, 10**6, 4000),
            "b": r.integers(0, 100, 4000)}) for i in order]
        _fold_all(["a", "b"], frames)
        slots = [int(f["distinct_sort_slots"]) for f in noted]
        rows = [int(f["distinct_rows_in"]) for f in noted]
        return sum(slots) / sum(rows)

    few, many = per_row(4, True), per_row(16, True)
    assert abs(many / few - 1) <= 0.10, (few, many)
    few, many = per_row(4, False), per_row(16, False)
    assert many <= 1.1 * few, (few, many)
