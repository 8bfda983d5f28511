"""TPC-H Q13, customer distribution: left outer join with a NOT LIKE in
the join condition, two levels of grouping."""
import re


def reference(T, p, R):
    c = T("customer", ["c_custkey"])
    o = T("orders", ["o_custkey", "o_comment"])
    pat = re.escape(p["word1"]) + ".*" + re.escape(p["word2"])
    oo = o[~o.o_comment.str.contains(pat, regex=True)]
    cnt = oo.groupby("o_custkey").size()
    c_count = c.c_custkey.map(cnt).fillna(0).astype("int64")
    out = c_count.value_counts().rename_axis("c_count").reset_index(
        name="custdist")
    return out.sort_values(["custdist", "c_count"],
                           ascending=[False, False])
