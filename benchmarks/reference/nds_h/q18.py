"""TPC-H Q18, large-volume customers: group-having subquery, two joins,
top 100."""


def reference(T, p, R):
    li = T("lineitem", ["l_orderkey", "l_quantity"])
    o = T("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"])
    c = T("customer", ["c_custkey", "c_name"])
    qty = li.groupby("l_orderkey")["l_quantity"].sum()
    big = qty[qty > int(p["quantity"]) * 100]
    m = o[o.o_orderkey.isin(big.index)].merge(
        c, left_on="o_custkey", right_on="c_custkey")
    m = m.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                      ascending=[False, True, True]).head(100)
    m["sum_qty"] = R.money(m.o_orderkey.map(big).to_numpy())
    m["o_totalprice"] = R.money(m.o_totalprice)
    return m[["c_name", "c_custkey", "o_orderkey", "o_orderdate",
              "o_totalprice", "sum_qty"]]
