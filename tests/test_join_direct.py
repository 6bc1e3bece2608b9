"""The sort branch of ``hash_join_pk`` (ISSUE 31): a unique build whose key
is one dense integer limb is probed through a direct-address table
(``_pk_direct_build`` / ``_pk_probe_direct``: one gather a probe row), every
other build by ``_pk_probe_sorted``'s binary search.  Both answer the same on
every input; which one answered is read from the build batch's cache and,
through a ``QueryService``, from the query record's two counters.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax.numpy as jnp

from quokka_tpu import config
from quokka_tpu.ops import bridge
from quokka_tpu.ops import join as J
from quokka_tpu.ops.batch import NULL_I32, DeviceBatch, NumCol

I32_MIN, I32_MAX = -(2**31), 2**31 - 1
HOWS = ("inner", "semi", "anti")


@pytest.fixture(autouse=True)
def sort_branch(monkeypatch):
    monkeypatch.setenv("QK_KERNEL_STRATEGY", "join_build=sort")


class Side:
    """One side of a join as the test states it: key columns (their logical
    values for pandas, their device columns if those are not plain), a row
    mask, and which rows hold a null key."""

    def __init__(self, keys, kind="i", dtype=np.int32, valid=None, null=None,
                 device=None):
        self.keys = [np.asarray(k) for k in keys]
        self.n = len(self.keys[0])
        self.kind, self.dtype, self.device = kind, dtype, device
        self.valid = (np.ones(self.n, bool) if valid is None
                      else np.asarray(valid, bool))
        self.null = (np.zeros(self.n, bool) if null is None
                     else np.asarray(null, bool))

    def batch(self, tag):
        pad = config.bucket_size(self.n)
        cols = {}
        for i, k in enumerate(self.keys):
            if self.device is not None:
                cols[f"k{i}"] = self.device(k, self.null, pad)
                continue
            a = np.where(self.null, NULL_I32, k) if self.kind != "f" else (
                np.where(self.null, np.nan, k))
            cols[f"k{i}"] = NumCol(jnp.asarray(
                np.pad(a.astype(self.dtype), (0, pad - self.n))), self.kind)
        cols[tag] = NumCol(jnp.arange(pad, dtype=jnp.int32), "i")
        return DeviceBatch(cols, jnp.asarray(
            np.pad(self.valid, (0, pad - self.n))))

    def frame(self, tag):
        f = pd.DataFrame({f"k{i}": k for i, k in enumerate(self.keys)})
        f[tag] = np.arange(self.n)
        return f


def _wide(vals, null, pad):
    hi, lo = bridge._wide_int_limbs(np.asarray(vals, np.int64), pad)
    return NumCol(jnp.asarray(lo), "i", hi=jnp.asarray(hi))


def _strings(vals, null, pad):
    arr = pa.chunked_array([pa.array(vals, pa.string()).dictionary_encode()])
    return bridge.arrow_column_to_device(arr, pad)


R = np.random.default_rng(31)


def _probe_around(build_keys, n=3000, margin=50):
    lo, hi = int(min(build_keys)), int(max(build_keys))
    return R.integers(max(lo - margin, I32_MIN + 1), min(hi + margin, I32_MAX),
                      n, endpoint=True)


def _dense():
    b = R.permutation(np.arange(1, 2001))
    return Side([b]), Side([_probe_around(b)])


def _dbgen_sparse():
    # dbgen's order keys: every fourth value; probes hit the holes too
    b = R.permutation(np.arange(1, 1501) * 4)
    return Side([b]), Side([_probe_around(b)])


def _negative():
    b = R.permutation(np.arange(-700, 700, 3))
    return Side([b]), Side([_probe_around(b)])


def _int32_edges():
    # kmin just above the null sentinel, probes up to INT32_MAX: key - kmin
    # overflows int32 for every probe row above kmax and must not match
    b = I32_MIN + 1 + R.permutation(np.arange(0, 1200, 2))
    p = np.concatenate([b[:300], b[:300] + 1, [I32_MAX, I32_MAX - 1, 0, -1],
                        I32_MAX - R.integers(0, 2400, 400)])
    return Side([b]), Side([p])


def _int32_top():
    # the mirror image: kmax at INT32_MAX, probes down to INT32_MIN + 1
    b = I32_MAX - R.permutation(np.arange(0, 1200, 2))
    p = np.concatenate([b[:300], b[:300] - 1, [I32_MIN + 1, I32_MIN + 2, 0],
                        I32_MIN + 1 + R.integers(0, 2400, 400)])
    return Side([b]), Side([p])


def _nulls_and_masks():
    b = R.permutation(np.arange(10, 1510))
    bn = np.zeros(len(b), bool)
    bn[7] = True  # one null build key: still unique
    bv = R.random(len(b)) > 0.3
    p = _probe_around(b)
    pn = R.random(len(p)) < 0.05
    pv = R.random(len(p)) > 0.2
    return Side([b], valid=bv, null=bn), Side([p], valid=pv, null=pn)


def _all_invalid_build():
    b = np.arange(1, 301)
    return Side([b], valid=np.zeros(300, bool)), Side([_probe_around(b)])


def _one_row_build():
    return Side([np.array([42])]), Side([np.array([41, 42, 43, 42, -5])])


def _date32():
    b = 9000 + R.permutation(np.arange(0, 900))
    return Side([b], kind="d"), Side([_probe_around(b)], kind="d")


def _int64_limb():
    # the x64 regime's one int64 limb (pytest): beyond int32, still dense
    b = (1 << 40) + R.permutation(np.arange(0, 4000, 4))
    p = (1 << 40) + R.integers(-40, 4040, 3000)
    return Side([b], dtype=np.int64), Side([p], dtype=np.int64)


def _two_limbs():
    b0 = np.repeat(np.arange(40), 25)
    b1 = np.tile(np.arange(25), 40)
    p0, p1 = R.integers(-1, 42, 3000), R.integers(-1, 27, 3000)
    return Side([b0, b1]), Side([p0, p1])


def _wide_int64():
    b = (5 << 32) + R.permutation(np.arange(0, 1000))
    p = (5 << 32) + R.integers(-20, 1020, 3000)
    return Side([b], device=_wide), Side([p], device=_wide)


def _float_key():
    b = R.permutation(np.arange(1, 901)).astype(np.float64)
    p = R.integers(-10, 920, 3000).astype(np.float64)
    return (Side([b], kind="f", dtype=np.float64),
            Side([p], kind="f", dtype=np.float64))


def _string_key():
    b = np.array([f"c{i:05d}" for i in R.permutation(800)], dtype=object)
    p = np.array([f"c{i:05d}" for i in R.integers(0, 1000, 3000)],
                 dtype=object)
    return Side([b], device=_strings), Side([p], device=_strings)


def _span_beyond_the_multiple():
    # 1,024 build slots may hold a table of 32,768, and any build one of
    # 8 M (DIRECT_MIN_SLOTS): a span of 12 M may not
    b = R.permutation(np.arange(0, 12_000_000, 12_000))
    return Side([b]), Side([np.concatenate([_probe_around(b, n=3000), b])])


def _few_rows_wide_span():
    # the orders a HAVING keeps (Q18): ten keys over 6.0 M, a table of 8 M
    b = R.choice(6_000_000, 10, replace=False)
    return Side([b]), Side([np.concatenate([_probe_around(b, n=3000),
                                            np.repeat(b, 30)])])


def _span_beyond_max_bucket():
    # a build of 1 << 20 slots would be allowed 32 x that: MAX_BUCKET bounds
    n = 1 << 20
    b = np.arange(n) * 17  # span 17.8 M > 1 << 24
    p = R.integers(-5, n * 17 + 5, 5000)
    return Side([b]), Side([p])


def _duplicate_build_keys():
    b = R.integers(0, 300, 900)  # called directly: the first row of a key
    return Side([b]), Side([_probe_around(b)])


CASES = {
    "dense": (_dense, True),
    "dbgen_sparse": (_dbgen_sparse, True),
    "negative": (_negative, True),
    "int32_edges": (_int32_edges, True),
    "int32_top": (_int32_top, True),
    "nulls_and_masks": (_nulls_and_masks, True),
    "one_row_build": (_one_row_build, True),
    "date32": (_date32, True),
    "int64_limb": (_int64_limb, True),
    "all_invalid_build": (_all_invalid_build, False),
    "two_limbs": (_two_limbs, False),
    "wide_int64": (_wide_int64, False),
    "float_key": (_float_key, False),
    "string_key": (_string_key, False),
    "few_rows_wide_span": (_few_rows_wide_span, True),
    "span_beyond_the_multiple": (_span_beyond_the_multiple, False),
    "span_beyond_max_bucket": (_span_beyond_max_bucket, False),
    "duplicate_build_keys": (_duplicate_build_keys, False),
}


def _expected(build, probe, how):
    keys = [f"k{i}" for i in range(len(build.keys))]
    b = build.frame("pay")[build.valid & ~build.null].drop_duplicates(
        keys, keep="first")
    p = probe.frame("x")
    m = p.merge(b, on=keys, how="left")
    hit = (m["pay"].notna() & probe.valid & ~probe.null).to_numpy()
    if how == "inner":
        return sorted(zip(m.x[hit], m.pay[hit].astype(int)))
    if how == "semi":
        return sorted(m.x[hit])
    return sorted(m.x[probe.valid & ~hit])


def _rows(out, how):
    v = np.asarray(out.valid)
    x = np.asarray(out.columns["x"].data)[v]
    if how == "inner":
        return sorted(zip(x, np.asarray(out.columns["pay"].data)[v]))
    return sorted(x)


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_table_search_and_pandas_agree(case, how, monkeypatch):
    make, direct = CASES[case]
    build, probe = make()
    keys = [f"k{i}" for i in range(len(build.keys))]
    bb, pb = build.batch("pay"), probe.batch("x")
    got = J.hash_join_pk(pb, bb, keys, keys, how, ["pay"])
    took = bb._pk_direct_cache[tuple(keys)]
    assert (took is not None) == direct, case
    assert _rows(got, how) == _expected(build, probe, how)
    # the same join with the table refused: bit for bit the same batch
    monkeypatch.setattr(J, "_direct_table_cached", lambda *a: None)
    want = J.hash_join_pk(pb, build.batch("pay"), keys, keys, how, ["pay"])
    v = np.asarray(want.valid)
    assert (np.asarray(got.valid) == v).all()
    for name in ("x", "pay") if how == "inner" else ("x",):
        assert (np.asarray(got.columns[name].data)[v]
                == np.asarray(want.columns[name].data)[v]).all(), name


@pytest.mark.parametrize("case", sorted(c for c, (_, d) in CASES.items() if d))
def test_probe_kernels_agree_bit_for_bit(case):
    build, probe = CASES[case][0]()
    bb, pb = build.batch("pay"), probe.batch("x")
    (sk,), perm, n_valid = J._build_sorted_cached(bb, ["k0"])
    table, kmin, kmax = J._direct_table_cached(bb, ["k0"])
    k = pb.columns["k0"].data.astype(sk.dtype)
    ok = J._nonnull_valid(pb, ["k0"])
    idx_d, hit_d = J._pk_probe_direct(table, kmin, kmax, k, ok)
    idx_s, hit_s = J._pk_probe_sorted(
        (sk,), perm, n_valid, (k,), ok, steps=bb.padded_len.bit_length())
    hit = np.asarray(hit_s)
    assert (np.asarray(hit_d) == hit).all()
    assert (np.asarray(idx_d)[hit] == np.asarray(idx_s)[hit]).all()
    # the table itself: every valid build row at its key's offset, -1 else
    t = np.asarray(table)
    live = build.valid & ~build.null
    assert (t >= 0).sum() == live.sum()
    assert (t[build.keys[0][live] - int(kmin)] == np.arange(build.n)[live]).all()
    assert table.shape[0] == config.bucket_size(int(kmax) - int(kmin) + 1)


def test_one_host_read_a_build_and_the_ledger_counts_the_table(monkeypatch):
    """``build_keys_unique`` decides uniqueness and the table from one
    cached read; the executor's ledger entry holds the table's bytes."""
    from quokka_tpu.executors.sql_execs import BuildProbeJoinExecutor
    from quokka_tpu.obs import memplane
    from quokka_tpu.runtime.cache import _batch_nbytes

    reads = []
    stats = J._sorted_build_stats
    monkeypatch.setattr(J, "_sorted_build_stats",
                        lambda *a: (reads.append(1), stats(*a))[1])
    build, probe = _dbgen_sparse()
    ex = BuildProbeJoinExecutor(["k0"], ["k0"], "inner")
    ex.build_parts, ex.build_done = [build.batch("pay")], True
    out = ex._probe([probe.batch("x")])
    ex._probe([probe.batch("x")])
    assert reads == [1] and ex.build_unique
    assert _rows(out, "inner") == _expected(build, probe, "inner")
    table = ex.build._pk_direct_cache[("k0",)][0]
    slots = config.bucket_size(5997)  # keys 4..6000
    assert table.shape == (slots,)
    entry = memplane.LEDGER._entries[("join_build", id(ex))]
    assert entry[2] == _batch_nbytes(ex.build) + 4 * slots
    # a build restored from a checkpoint decides the same way
    state = ex.checkpoint()
    again = BuildProbeJoinExecutor(["k0"], ["k0"], "inner")
    again.restore(state)
    assert again.build._pk_direct_cache[("k0",)] is not None
    # the build, its sort and its table are released at done(), not at a
    # collector's leisure (a served q3 held 2.4 GB of finished queries')
    ex.done(0)
    assert ex.build is None
    assert ("join_build", id(ex)) not in memplane.LEDGER._entries


# ---------------------------------------------------------------------------
# the served path: a Q3-shaped plan, its record's counters, its program set
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """tests/test_compileplane.py's Q3-shaped tables, the fact table in one
    row group: a probe call then holds one batch, and `_coalesce`, whose
    output rung follows arrival (PERF.md section 7), has nothing to join."""
    r = np.random.default_rng(7)
    n_fact, n_dim = 60_000, 5_000
    fact = pa.table({
        "fk": r.integers(0, n_dim, n_fact).astype(np.int64),
        "v": r.integers(0, 1000, n_fact).astype(np.int64),
        "flag": r.integers(0, 4, n_fact).astype(np.int64),
    })
    dim = pa.table({
        "pk": np.arange(n_dim, dtype=np.int64),
        "grp": r.integers(0, 64, n_dim).astype(np.int64),
    })
    d = tmp_path_factory.mktemp("star")
    paths = str(d / "fact.parquet"), str(d / "dim.parquet")
    pq.write_table(fact, paths[0], row_group_size=n_fact)
    pq.write_table(dim, paths[1])
    f, m = fact.to_pandas(), dim.to_pandas()
    j = f[f.flag < 3].merge(m, left_on="fk", right_on="pk")
    exp = j.groupby("grp").agg(sv=("v", "sum"), n=("v", "size")).reset_index()
    return paths, exp


def test_served_q3_shaped_query_probes_the_table_alone(star):
    from test_compileplane import SIG_BUDGETS

    from quokka_tpu import QuokkaContext
    from quokka_tpu.expression import col
    from quokka_tpu.obs import querylog
    from quokka_tpu.ops import sigkey
    from quokka_tpu.service import QueryService

    (fact, dim), exp = star

    def stream():
        ctx = QuokkaContext(io_channels=2, exec_channels=2)
        return (ctx.read_parquet(fact).filter(col("flag") < 3)
                .join(ctx.read_parquet(dim), left_on="fk", right_on="pk")
                .groupby("grp").agg_sql("sum(v) as sv, count(*) as n"))

    sigkey.reset_ledger()
    svc = QueryService(pool_size=2)
    try:
        t0 = querylog.records()[-1]["done"] if querylog.size() else 0.0
        for _ in range(2):
            got = svc.submit(stream()).to_df(timeout=300)
            got = got.sort_values("grp").reset_index(drop=True)
            assert (got[["grp", "sv", "n"]].to_numpy()
                    == exp[["grp", "sv", "n"]].to_numpy()).all()
        first, second = querylog.records(since=t0)[-2:]
    finally:
        svc.shutdown()
    for rec in (first, second):
        assert rec["join_probe_direct"] > 0 and rec["join_probe_search"] == 0
    # keyed on (table rung, probe rung), a function of the plan and the
    # tables: the second request probes the same slots and asks the compile
    # plane for nothing it has to compile or load
    assert first["join_probe_direct"] == second["join_probe_direct"]
    assert not second["compiled"], second["compiled"]
    counts = sigkey.ledger_counts()
    assert "pk_probe_sorted" not in counts
    for kind in ("pk_probe_direct", "pk_direct_build"):
        assert 0 < counts[kind] <= SIG_BUDGETS[kind], counts
