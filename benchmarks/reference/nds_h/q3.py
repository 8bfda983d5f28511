"""TPC-H Q3, shipping priority: two joins, group, top 10 by revenue."""
from benchmarks.reference.rawdata import days


def reference(T, p, R):
    c = T("customer", ["c_custkey", "c_mktsegment"])
    o = T("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"])
    li = T("lineitem", ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"])
    date = days(p["date"])
    m = li[li.l_shipdate > date].merge(
        o[o.o_orderdate < date], left_on="l_orderkey",
        right_on="o_orderkey").merge(
        c[c.c_mktsegment == p["segment"]], left_on="o_custkey",
        right_on="c_custkey")
    m["revenue"] = R.money(m.l_extendedprice) * (1 - R.money(m.l_discount))
    g = m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate", "l_orderkey"],
                      ascending=[False, True, True]).head(10)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
