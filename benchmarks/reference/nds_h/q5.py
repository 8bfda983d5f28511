"""TPC-H Q5, local supplier volume: six tables, customer and supplier
of one nation, one region, one year of orders; revenue by nation."""
from benchmarks.reference.rawdata import days, plus_months


def reference(T, p, R):
    c = T("customer", ["c_custkey", "c_nationkey"])
    o = T("orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    li = T("lineitem", ["l_orderkey", "l_suppkey", "l_extendedprice",
                        "l_discount"])
    s = T("supplier", ["s_suppkey", "s_nationkey"])
    n = T("nation", ["n_nationkey", "n_name", "n_regionkey"])
    r = T("region", ["r_regionkey", "r_name"])
    lo, hi = days(p["date"]), plus_months(p["date"], 12)
    n = n.merge(r[r.r_name == p["region"]], left_on="n_regionkey",
                right_on="r_regionkey")
    m = li.merge(o[(o.o_orderdate >= lo) & (o.o_orderdate < hi)],
                 left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey").merge(
        s, left_on="l_suppkey", right_on="s_suppkey")
    m = m[m.c_nationkey == m.s_nationkey].merge(
        n, left_on="s_nationkey", right_on="n_nationkey")
    m["revenue"] = R.money(m.l_extendedprice) * (1 - R.money(m.l_discount))
    g = m.groupby("n_name", as_index=False)["revenue"].sum()
    return g.sort_values("revenue", ascending=False)[["n_name", "revenue"]]
