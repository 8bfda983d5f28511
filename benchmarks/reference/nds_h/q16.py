"""TPC-H Q16, parts/supplier relationship: anti join on a LIKE
subquery, count distinct."""


def reference(T, p, R):
    ps = T("partsupp", ["ps_partkey", "ps_suppkey"])
    part = T("part", ["p_partkey", "p_brand", "p_type", "p_size"])
    s = T("supplier", ["s_suppkey", "s_comment"])
    bad = set(s[s.s_comment.str.contains("Customer.*Complaints",
                                         regex=True)].s_suppkey)
    sizes = [int(x) for x in str(p["sizes"]).split(",")]
    sel = part[(part.p_brand != p["brand"])
               & ~part.p_type.str.startswith(p["type"])
               & part.p_size.isin(sizes)]
    m = ps[~ps.ps_suppkey.isin(bad)].merge(
        sel, left_on="ps_partkey", right_on="p_partkey")
    out = m.groupby(["p_brand", "p_type", "p_size"])[
        "ps_suppkey"].nunique().reset_index(name="supplier_cnt")
    return out.sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True])
