"""TPC-H Q2/Q7/Q8/Q9/Q11/Q15/Q16/Q17/Q18/Q20/Q21/Q22 vs pandas oracles.

Correlated and EXISTS subqueries are rewritten dataframe-style — aggregate +
join-back, semi/anti joins, broadcast scalars — the same rewrites the
reference codes by hand in apps/tpc-h/tpch.py:78-560.  Completes the 22-query
coverage started in test_tpch.py (VERDICT r1 item 4)."""

import datetime

import numpy as np
import pandas as pd
import pytest

from quokka_tpu import QuokkaContext

import tpch_data


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("tpch2")
    tables = tpch_data.generate(sf=0.003, seed=11)
    paths = tpch_data.write_parquet_dir(tables, str(root))
    ctx = QuokkaContext(io_channels=2, exec_channels=2)
    dfs = {k: t.to_pandas() for k, t in tables.items()}
    return ctx, paths, dfs


def streams(env):
    ctx, paths, _ = env
    return {name: ctx.read_parquet(p) for name, p in paths.items()}


def sorted_eq(got, exp, by, rtol=1e-8):
    got = got.sort_values(by).reset_index(drop=True)[list(exp.columns)]
    exp = exp.sort_values(by).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=rtol)


def test_q2(env):
    ctx, paths, dfs = env
    s = streams(env)
    # EUROPE partsupp universe: partsupp x supplier x nation x region
    nat_eu = s["nation"].join(
        s["region"].filter_sql("r_name = 'EUROPE'"),
        left_on="n_regionkey", right_on="r_regionkey", how="semi",
    )
    ps_eu = (
        s["partsupp"]
        .join(s["supplier"], left_on="ps_suppkey", right_on="s_suppkey")
        .join(nat_eu, left_on="s_nationkey", right_on="n_nationkey")
    )
    # correlated min(ps_supplycost) per part -> aggregate + join back
    minc = ps_eu.groupby("ps_partkey").agg_sql("min(ps_supplycost) as min_cost")
    p = s["part"].filter_sql("p_size = 15 and p_type like '%BRASS'")
    got = (
        ps_eu.join(p, left_on="ps_partkey", right_on="p_partkey")
        .join(minc.rename({"ps_partkey": "mc_partkey"}),
              left_on="ps_partkey", right_on="mc_partkey")
        .filter_sql("ps_supplycost = min_cost")
        .select(["s_acctbal", "s_name", "n_name", "ps_partkey", "p_mfgr"])
        .collect()
    )
    n, r, su, ps, pt = (dfs[k] for k in ("nation", "region", "supplier", "partsupp", "part"))
    eu = n.merge(r[r.r_name == "EUROPE"], left_on="n_regionkey", right_on="r_regionkey")
    pse = ps.merge(su, left_on="ps_suppkey", right_on="s_suppkey").merge(
        eu, left_on="s_nationkey", right_on="n_nationkey"
    )
    mc = pse.groupby("ps_partkey").ps_supplycost.min().reset_index(name="min_cost")
    pf = pt[(pt.p_size == 15) & pt.p_type.str.endswith("BRASS")]
    exp = (
        pse.merge(pf, left_on="ps_partkey", right_on="p_partkey")
        .merge(mc, on="ps_partkey")
    )
    exp = exp[exp.ps_supplycost == exp.min_cost][
        ["s_acctbal", "s_name", "n_name", "ps_partkey", "p_mfgr"]
    ]
    assert len(exp) > 0
    sorted_eq(got, exp, by=["ps_partkey", "s_name"])


def test_q7(env):
    ctx, paths, dfs = env
    s = streams(env)
    n1 = s["nation"].rename({"n_name": "supp_nation", "n_nationkey": "n1key"})
    n2 = s["nation"].rename({"n_name": "cust_nation", "n_nationkey": "n2key"})
    got = (
        s["lineitem"]
        .filter_sql("l_shipdate between date '1995-01-01' and date '1996-12-31'")
        .join(s["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .join(s["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .join(s["customer"], left_on="o_custkey", right_on="c_custkey")
        .join(n1.select(["supp_nation", "n1key"]), left_on="s_nationkey", right_on="n1key")
        .join(n2.select(["cust_nation", "n2key"]), left_on="c_nationkey", right_on="n2key")
        .filter_sql(
            "(supp_nation = 'FRANCE' and cust_nation = 'GERMANY') or "
            "(supp_nation = 'GERMANY' and cust_nation = 'FRANCE')"
        )
        .with_columns_sql(
            "extract(year from l_shipdate) as l_year, "
            "l_extendedprice * (1 - l_discount) as volume"
        )
        .groupby(["supp_nation", "cust_nation", "l_year"])
        .agg_sql("sum(volume) as revenue")
        .collect()
    )
    l, su, o, c, n = (dfs[k] for k in ("lineitem", "supplier", "orders", "customer", "nation"))
    f = l[(l.l_shipdate >= datetime.date(1995, 1, 1)) & (l.l_shipdate <= datetime.date(1996, 12, 31))]
    j = (
        f.merge(su, left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n.rename(columns={"n_name": "supp_nation"}), left_on="s_nationkey", right_on="n_nationkey")
        .merge(n.rename(columns={"n_name": "cust_nation"}), left_on="c_nationkey", right_on="n_nationkey")
    )
    j = j[((j.supp_nation == "FRANCE") & (j.cust_nation == "GERMANY"))
          | ((j.supp_nation == "GERMANY") & (j.cust_nation == "FRANCE"))]
    assert len(j) > 0
    j = j.assign(
        l_year=pd.to_datetime(j.l_shipdate).dt.year,
        volume=j.l_extendedprice * (1 - j.l_discount),
    )
    exp = (
        j.groupby(["supp_nation", "cust_nation", "l_year"])
        .volume.sum().reset_index(name="revenue")
    )
    sorted_eq(got, exp, by=["supp_nation", "cust_nation", "l_year"])


def test_q8(env):
    ctx, paths, dfs = env
    s = streams(env)
    nat_am = s["nation"].join(
        s["region"].filter_sql("r_name = 'AMERICA'"),
        left_on="n_regionkey", right_on="r_regionkey", how="semi",
    )
    n2 = s["nation"].rename({"n_name": "supp_nation", "n_nationkey": "n2key"})
    got = (
        s["lineitem"]
        .join(s["part"].filter_sql("p_type = 'ECONOMY ANODIZED STEEL'"),
              left_on="l_partkey", right_on="p_partkey", how="semi")
        .join(s["orders"].filter_sql(
            "o_orderdate between date '1995-01-01' and date '1996-12-31'"),
            left_on="l_orderkey", right_on="o_orderkey")
        .join(s["customer"], left_on="o_custkey", right_on="c_custkey")
        .join(nat_am, left_on="c_nationkey", right_on="n_nationkey", how="semi")
        .join(s["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .join(n2.select(["supp_nation", "n2key"]), left_on="s_nationkey", right_on="n2key")
        .with_columns_sql(
            "extract(year from o_orderdate) as o_year, "
            "l_extendedprice * (1 - l_discount) as volume, "
            "case when supp_nation = 'BRAZIL' then l_extendedprice * (1 - l_discount) "
            "else 0.0 end as brazil_volume"
        )
        .groupby("o_year")
        .agg_sql("sum(brazil_volume) / sum(volume) as mkt_share")
        .collect()
    )
    l, pt, o, c, su, n, r = (dfs[k] for k in
                             ("lineitem", "part", "orders", "customer", "supplier", "nation", "region"))
    am_keys = n.merge(r[r.r_name == "AMERICA"], left_on="n_regionkey",
                      right_on="r_regionkey").n_nationkey
    pk = pt[pt.p_type == "ECONOMY ANODIZED STEEL"].p_partkey
    f = l[l.l_partkey.isin(pk)]
    j = (
        f.merge(o[(o.o_orderdate >= datetime.date(1995, 1, 1))
                  & (o.o_orderdate <= datetime.date(1996, 12, 31))],
                left_on="l_orderkey", right_on="o_orderkey")
        .merge(c[c.c_nationkey.isin(am_keys)], left_on="o_custkey", right_on="c_custkey")
        .merge(su, left_on="l_suppkey", right_on="s_suppkey")
        .merge(n.rename(columns={"n_name": "supp_nation"}),
               left_on="s_nationkey", right_on="n_nationkey")
    )
    assert len(j) > 0
    j = j.assign(
        o_year=pd.to_datetime(j.o_orderdate).dt.year,
        volume=j.l_extendedprice * (1 - j.l_discount),
    )
    j["brazil_volume"] = np.where(j.supp_nation == "BRAZIL", j.volume, 0.0)
    g = j.groupby("o_year").agg(bv=("brazil_volume", "sum"), v=("volume", "sum"))
    exp = (g.bv / g.v).reset_index(name="mkt_share")
    sorted_eq(got, exp, by=["o_year"])


def test_q9(env):
    ctx, paths, dfs = env
    s = streams(env)
    got = (
        s["lineitem"]
        .join(s["part"].filter_sql("p_name like '%green%'"),
              left_on="l_partkey", right_on="p_partkey", how="semi")
        .join(s["partsupp"], left_on=["l_partkey", "l_suppkey"],
              right_on=["ps_partkey", "ps_suppkey"])
        .join(s["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .join(s["nation"], left_on="s_nationkey", right_on="n_nationkey")
        .join(s["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .with_columns_sql(
            "extract(year from o_orderdate) as o_year, "
            "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount"
        )
        .groupby(["n_name", "o_year"])
        .agg_sql("sum(amount) as sum_profit")
        .collect()
    )
    l, pt, ps, su, n, o = (dfs[k] for k in
                           ("lineitem", "part", "partsupp", "supplier", "nation", "orders"))
    pk = pt[pt.p_name.str.contains("green")].p_partkey
    j = (
        l[l.l_partkey.isin(pk)]
        .merge(ps, left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
        .merge(su, left_on="l_suppkey", right_on="s_suppkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
    )
    assert len(j) > 0
    j = j.assign(
        o_year=pd.to_datetime(j.o_orderdate).dt.year,
        amount=j.l_extendedprice * (1 - j.l_discount) - j.ps_supplycost * j.l_quantity,
    )
    exp = j.groupby(["n_name", "o_year"]).amount.sum().reset_index(name="sum_profit")
    sorted_eq(got, exp, by=["n_name", "o_year"])


def test_q11(env):
    ctx, paths, dfs = env
    s = streams(env)
    ps, su, n = (dfs[k] for k in ("partsupp", "supplier", "nation"))
    # spec names GERMANY; use the modal supplier nation for the mini dataset
    nat_key = int(su.s_nationkey.mode()[0])
    nat_name = n[n.n_nationkey == nat_key].n_name.iloc[0]
    ps_de = (
        s["partsupp"]
        .join(s["supplier"], left_on="ps_suppkey", right_on="s_suppkey")
        .join(s["nation"].filter_sql(f"n_name = '{nat_name}'"),
              left_on="s_nationkey", right_on="n_nationkey", how="semi")
        .with_columns_sql("ps_supplycost * ps_availqty as value")
    )
    de = su[su.s_nationkey == nat_key]
    j = ps[ps.ps_suppkey.isin(de.s_suppkey)]
    j = j.assign(value=j.ps_supplycost * j.ps_availqty)
    assert len(j) > 0
    g = j.groupby("ps_partkey").value.sum().reset_index()
    # spec uses fraction 0.0001/SF of the total; the mini dataset is too small
    # for that to select anything, so threshold at the oracle's 80th pctile —
    # same cutoff on both sides, still exercising scalar-subquery-as-literal
    cutoff = float(g.value.quantile(0.8))
    got = (
        ps_de.groupby("ps_partkey")
        .agg_sql("sum(value) as value")
        .filter_sql(f"value > {cutoff}")
        .collect()
    )
    exp = g[g.value > cutoff]
    assert len(exp) > 0
    sorted_eq(got, exp, by=["ps_partkey"])


def test_q15(env):
    ctx, paths, dfs = env
    s = streams(env)
    rev = (
        s["lineitem"]
        .filter_sql("l_shipdate >= date '1996-01-01' and l_shipdate < date '1996-01-01' + interval '3' month")
        .with_columns_sql("l_extendedprice * (1 - l_discount) as v")
        .groupby("l_suppkey")
        .agg_sql("sum(v) as total_revenue")
    )
    top = float(rev.agg_sql("max(total_revenue) as m").collect().m[0])
    got = (
        rev.filter_sql(f"total_revenue >= {top}")
        .join(s["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .select(["l_suppkey", "s_name", "total_revenue"])
        .collect()
    )
    l, su = dfs["lineitem"], dfs["supplier"]
    f = l[(l.l_shipdate >= datetime.date(1996, 1, 1)) & (l.l_shipdate < datetime.date(1996, 4, 1))]
    g = (f.l_extendedprice * (1 - f.l_discount)).groupby(f.l_suppkey).sum().rename("total_revenue")
    assert len(g) > 0
    winners = g[g == g.max()].reset_index()
    assert len(got) == len(winners) >= 1
    np.testing.assert_allclose(
        sorted(got.total_revenue), sorted(winners.total_revenue), rtol=1e-6
    )
    assert set(got.l_suppkey) == set(winners.l_suppkey)


def test_q16(env):
    ctx, paths, dfs = env
    s = streams(env)
    sizes = "(49, 14, 23, 45, 19, 3, 36, 9)"
    from quokka_tpu import col

    got = (
        s["partsupp"]
        .join(s["supplier"].filter(col("s_comment").str.contains("Customer Complaints")),
              left_on="ps_suppkey", right_on="s_suppkey", how="anti")
        .join(s["part"].filter_sql(
            f"p_brand != 'Brand#45' and not (p_type like 'MEDIUM POLISHED%') "
            f"and p_size in {sizes}"),
            left_on="ps_partkey", right_on="p_partkey")
        .groupby(["p_brand", "p_type", "p_size"])
        .agg_sql("count(distinct ps_suppkey) as supplier_cnt")
        .collect()
    )
    ps, su, pt = dfs["partsupp"], dfs["supplier"], dfs["part"]
    bad = su[su.s_comment.str.contains("Customer Complaints")].s_suppkey
    pf = pt[(pt.p_brand != "Brand#45")
            & ~pt.p_type.str.startswith("MEDIUM POLISHED")
            & pt.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    j = ps[~ps.ps_suppkey.isin(bad)].merge(pf, left_on="ps_partkey", right_on="p_partkey")
    assert len(j) > 0
    exp = (
        j.groupby(["p_brand", "p_type", "p_size"])
        .ps_suppkey.nunique().reset_index(name="supplier_cnt")
    )
    sorted_eq(got, exp, by=["p_brand", "p_type", "p_size"])


def test_q17(env):
    ctx, paths, dfs = env
    s = streams(env)
    # the spec also filters p_container = 'MED BOX', but brand x container is
    # too selective for the mini dataset; the correlated avg rewrite is the
    # point of the query and is fully exercised by the brand filter alone
    li_part = s["lineitem"].join(
        s["part"].filter_sql("p_brand = 'Brand#23'"),
        left_on="l_partkey", right_on="p_partkey", how="semi",
    )
    avg_qty = li_part.groupby("l_partkey").agg_sql("avg(l_quantity) as avg_qty")
    got = (
        li_part
        .join(avg_qty.rename({"l_partkey": "a_partkey"}),
              left_on="l_partkey", right_on="a_partkey")
        .filter_sql("l_quantity < 0.2 * avg_qty")
        .agg_sql("sum(l_extendedprice) / 7.0 as avg_yearly")
        .collect()
    )
    l, pt = dfs["lineitem"], dfs["part"]
    pk = pt[pt.p_brand == "Brand#23"].p_partkey
    f = l[l.l_partkey.isin(pk)]
    assert len(f) > 0
    a = f.groupby("l_partkey").l_quantity.mean().rename("avg_qty")
    j = f.merge(a, on="l_partkey")
    sel = j[j.l_quantity < 0.2 * j.avg_qty]
    exp = sel.l_extendedprice.sum() / 7.0
    np.testing.assert_allclose(got.avg_yearly[0], exp, rtol=1e-9)


def test_q18(env):
    ctx, paths, dfs = env
    s = streams(env)
    big = (
        s["lineitem"].groupby("l_orderkey")
        .agg_sql("sum(l_quantity) as sum_qty")
        .filter_sql("sum_qty > 250")
    )
    got = (
        s["orders"]
        .join(big.rename({"l_orderkey": "b_orderkey"}),
              left_on="o_orderkey", right_on="b_orderkey")
        .join(s["customer"], left_on="o_custkey", right_on="c_custkey")
        .select(["c_name", "o_orderkey", "o_orderdate", "o_totalprice", "sum_qty"])
        .collect()
    )
    l, o, c = dfs["lineitem"], dfs["orders"], dfs["customer"]
    g = l.groupby("l_orderkey").l_quantity.sum()
    keys = g[g > 250]
    assert len(keys) > 0  # threshold tuned to the mini dataset
    exp = (
        o[o.o_orderkey.isin(keys.index)]
        .merge(keys.reset_index(name="sum_qty"), left_on="o_orderkey", right_on="l_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
    )[["c_name", "o_orderkey", "o_orderdate", "o_totalprice", "sum_qty"]]
    sorted_eq(got, exp, by=["o_orderkey"])


def test_q20(env):
    ctx, paths, dfs = env
    s = streams(env)
    forest_parts = s["part"].filter_sql("p_name like 'forest%'")
    shipped = (
        s["lineitem"]
        .filter_sql("l_shipdate >= date '1994-01-01' and "
                    "l_shipdate < date '1994-01-01' + interval '1' year")
        .groupby(["l_partkey", "l_suppkey"])
        .agg_sql("sum(l_quantity) as qty")
    )
    excess = (
        s["partsupp"]
        .join(forest_parts, left_on="ps_partkey", right_on="p_partkey", how="semi")
        .join(shipped, left_on=["ps_partkey", "ps_suppkey"],
              right_on=["l_partkey", "l_suppkey"])
        .filter_sql("ps_availqty > 0.5 * qty")
    )
    got = (
        s["supplier"]
        .join(s["nation"].filter_sql("n_name = 'CANADA'"),
              left_on="s_nationkey", right_on="n_nationkey", how="semi")
        .join(excess, left_on="s_suppkey", right_on="ps_suppkey", how="semi")
        .select(["s_name", "s_address"])
        .collect()
    )
    pt, l, ps, su, n = (dfs[k] for k in ("part", "lineitem", "partsupp", "supplier", "nation"))
    fp = pt[pt.p_name.str.startswith("forest")].p_partkey
    f = l[(l.l_shipdate >= datetime.date(1994, 1, 1)) & (l.l_shipdate < datetime.date(1995, 1, 1))]
    sq = f.groupby(["l_partkey", "l_suppkey"]).l_quantity.sum().reset_index(name="qty")
    ex = ps[ps.ps_partkey.isin(fp)].merge(
        sq, left_on=["ps_partkey", "ps_suppkey"], right_on=["l_partkey", "l_suppkey"]
    )
    ex = ex[ex.ps_availqty > 0.5 * ex.qty]
    ca = n[n.n_name == "CANADA"].n_nationkey
    exp = su[su.s_nationkey.isin(ca) & su.s_suppkey.isin(ex.ps_suppkey)][["s_name", "s_address"]]
    sorted_eq(got, exp, by=["s_name"])


def test_q21(env):
    """The specification's question: numwait counts l1 LINE ITEMS (late
    lines of 'F' orders with another supplier and no other late supplier),
    EXISTS / NOT EXISTS as count(distinct l_suppkey) by order."""
    ctx, paths, dfs = env
    s = streams(env)
    # spec names SAUDI ARABIA; the mini dataset's 30 suppliers may not cover
    # every nation, so use the modal supplier nation (same value both sides)
    _su, _n = dfs["supplier"], dfs["nation"]
    nat_key = int(_su.s_nationkey.mode()[0])
    nat_name = _n[_n.n_nationkey == nat_key].n_name.iloc[0]
    late = s["lineitem"].filter_sql("l_receiptdate > l_commitdate")
    n_supp = (
        s["lineitem"].groupby("l_orderkey")
        .agg_sql("count(distinct l_suppkey) as n_supp")
        .rename({"l_orderkey": "ns_orderkey"})
    )
    n_late = (
        late.groupby("l_orderkey")
        .agg_sql("count(distinct l_suppkey) as n_late")
        .rename({"l_orderkey": "nl_orderkey"})
    )
    got = (
        late.select(["l_orderkey", "l_suppkey"])
        .join(s["orders"].filter_sql("o_orderstatus = 'F'"),
              left_on="l_orderkey", right_on="o_orderkey", how="semi")
        .join(n_supp, left_on="l_orderkey", right_on="ns_orderkey")
        .join(n_late, left_on="l_orderkey", right_on="nl_orderkey")
        .filter_sql("n_supp > 1 and n_late = 1")
        .join(s["supplier"], left_on="l_suppkey", right_on="s_suppkey")
        .join(s["nation"].filter_sql(f"n_name = '{nat_name}'"),
              left_on="s_nationkey", right_on="n_nationkey", how="semi")
        .groupby("s_name")
        .agg_sql("count(*) as numwait")
        .collect()
    )
    l, o, su = dfs["lineitem"], dfs["orders"], dfs["supplier"]
    ns = l.groupby("l_orderkey").l_suppkey.nunique().rename("n_supp")
    l1 = l[l.l_receiptdate > l.l_commitdate][["l_orderkey", "l_suppkey"]]
    nl = l1.groupby("l_orderkey").l_suppkey.nunique().rename("n_late")
    fkeys = set(o[o.o_orderstatus == "F"].o_orderkey)
    j = l1.merge(ns, on="l_orderkey").merge(nl, on="l_orderkey")
    j = j[j.l_orderkey.isin(fkeys) & (j.n_supp > 1) & (j.n_late == 1)]
    sa = set(su[su.s_nationkey == nat_key].s_suppkey)
    j = j[j.l_suppkey.isin(sa)]
    assert len(j) > 0
    exp = (
        j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
        .groupby("s_name").size().reset_index(name="numwait")
    )
    sorted_eq(got, exp, by=["s_name"])


def test_q22(env):
    ctx, paths, dfs = env
    s = streams(env)
    codes = ("13", "31", "23", "29", "30", "18", "17")
    in_list = ", ".join(f"'{c}'" for c in codes)
    cust = s["customer"].with_columns_sql(
        "substring(c_phone, 1, 2) as cntrycode"
    ).filter_sql(f"cntrycode in ({in_list})")
    avg_bal = float(
        cust.filter_sql("c_acctbal > 0.0")
        .agg_sql("avg(c_acctbal) as a").collect().a[0]
    )
    got = (
        cust.filter_sql(f"c_acctbal > {avg_bal}")
        .join(s["orders"], left_on="c_custkey", right_on="o_custkey", how="anti")
        .groupby("cntrycode")
        .agg_sql("count(*) as numcust, sum(c_acctbal) as totacctbal")
        .collect()
    )
    c, o = dfs["customer"], dfs["orders"]
    cc = c.assign(cntrycode=c.c_phone.str[:2])
    cf = cc[cc.cntrycode.isin(codes)]
    avg_e = cf[cf.c_acctbal > 0].c_acctbal.mean()
    sel = cf[(cf.c_acctbal > avg_e) & ~cf.c_custkey.isin(o.o_custkey)]
    assert len(sel) > 0
    exp = sel.groupby("cntrycode").agg(
        numcust=("c_custkey", "size"), totacctbal=("c_acctbal", "sum")
    ).reset_index()
    sorted_eq(got, exp, by=["cntrycode"])


class TestSkewedAndNullData:
    """VERDICT r1 item 4: distribution-sensitive data — Zipf-hot keys make
    giant groups/join fanouts, and nulls flow through real query shapes."""

    @pytest.fixture(scope="class")
    def skew_env(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("tpch_skew")
        tables = tpch_data.generate(sf=0.003, seed=3, skew=True, nulls=True)
        paths = tpch_data.write_parquet_dir(tables, str(root))
        ctx = QuokkaContext(io_channels=2, exec_channels=2)
        dfs = {k: t.to_pandas() for k, t in tables.items()}
        return ctx, paths, dfs

    def test_q1_with_nulls(self, skew_env):
        ctx, paths, dfs = skew_env
        li = ctx.read_parquet(paths["lineitem"])
        got = (
            li.filter_sql("l_shipdate <= date '1998-09-02'")
            .groupby(["l_returnflag", "l_linestatus"])
            .agg_sql(
                "sum(l_quantity) as sum_qty, "
                "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
                "avg(l_discount) as avg_disc, count(l_tax) as n_tax, "
                "count(*) as n"
            )
            .collect()
        )
        l = dfs["lineitem"]
        f = l[l.l_shipdate <= datetime.date(1998, 9, 2)]
        exp = (
            f.groupby(["l_returnflag", "l_linestatus"], dropna=False)
            .apply(lambda d: pd.Series({
                "sum_qty": d.l_quantity.sum(),
                "sum_disc_price": (d.l_extendedprice * (1 - d.l_discount)).sum(),
                "avg_disc": d.l_discount.mean(),
                "n_tax": float(d.l_tax.notna().sum()),
                "n": float(len(d)),
            }), include_groups=False)
            .reset_index()
        )
        got = got.sort_values(["l_returnflag", "l_linestatus"], na_position="last").reset_index(drop=True)
        exp = exp.sort_values(["l_returnflag", "l_linestatus"], na_position="last").reset_index(drop=True)
        assert len(got) == len(exp)
        # null group present (nulls enabled at ~3%)
        assert got.l_returnflag.isna().any()
        np.testing.assert_allclose(got.sum_qty.to_numpy(), exp.sum_qty.to_numpy(), rtol=1e-9)
        np.testing.assert_allclose(got.sum_disc_price.to_numpy(), exp.sum_disc_price.to_numpy(), rtol=1e-9)
        np.testing.assert_allclose(got.avg_disc.to_numpy(), exp.avg_disc.to_numpy(), rtol=1e-9)
        np.testing.assert_array_equal(got.n_tax.to_numpy(dtype=float), exp.n_tax.to_numpy())

    def test_skewed_join_groupby(self, skew_env):
        ctx, paths, dfs = skew_env
        li = ctx.read_parquet(paths["lineitem"])
        pt = ctx.read_parquet(paths["part"])
        got = (
            li.join(pt, left_on="l_partkey", right_on="p_partkey")
            .groupby("p_brand")
            .agg_sql("sum(l_quantity) as q, count(*) as n")
            .collect()
        )
        l, p = dfs["lineitem"], dfs["part"]
        j = l.merge(p, left_on="l_partkey", right_on="p_partkey")
        # zipf skew: the hottest part should dominate
        top_share = l.l_partkey.value_counts().iloc[0] / len(l)
        assert top_share > 0.1
        exp = j.groupby("p_brand").agg(q=("l_quantity", "sum"), n=("l_quantity", "size")).reset_index()
        sorted_eq(got, exp, by=["p_brand"])
