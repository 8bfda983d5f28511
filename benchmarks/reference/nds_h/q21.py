"""TPC-H Q21, suppliers who kept orders waiting: semi and anti joins
of lineitem with itself, top 100."""


def reference(T, p, R):
    s = T("supplier", ["s_suppkey", "s_name", "s_nationkey"])
    li = T("lineitem", ["l_orderkey", "l_suppkey", "l_receiptdate",
                        "l_commitdate"])
    o = T("orders", ["o_orderkey", "o_orderstatus"])
    n = T("nation", ["n_nationkey", "n_name"])
    nk = n[n.n_name == p["nation"]].n_nationkey.iloc[0]
    late = li[li.l_receiptdate > li.l_commitdate]
    n_supp = li.groupby("l_orderkey")["l_suppkey"].nunique()
    late_supp = late.groupby("l_orderkey")["l_suppkey"].nunique()
    m = late.merge(o[o.o_orderstatus == "F"], left_on="l_orderkey",
                   right_on="o_orderkey").merge(
        s[s.s_nationkey == nk], left_on="l_suppkey", right_on="s_suppkey")
    m = m[(m.l_orderkey.map(n_supp) > 1)
          & (m.l_orderkey.map(late_supp) == 1)]
    out = m.groupby("s_name").size().reset_index(name="numwait")
    return out.sort_values(["numwait", "s_name"],
                           ascending=[False, True]).head(100)
