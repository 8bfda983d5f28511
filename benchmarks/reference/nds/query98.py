"""TPC-DS Q98: store revenue an item of three categories over 31 days,
and each item's share of its class's revenue: sum(sum(x)) over
(partition by i_class) over the grouped rows. NULL group keys (a NULL
i_class is a class of its own) are kept; no LIMIT, every row comes
back. ``revenueratio`` is the engine's decimal division: the quotient
of the two exact sums as a real (float64) number, times 100."""
from benchmarks.reference.rawdata import days


def reference(T, p, R):
    ss = T("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                           "ss_ext_sales_price"])
    it = T("item", ["i_item_sk", "i_item_id", "i_item_desc", "i_category",
                    "i_class", "i_current_price"])
    dd = T("date_dim", ["d_date_sk", "d_date"])
    first = days(p["date"])
    m = ss.merge(dd[(dd.d_date >= first) & (dd.d_date <= first + 30)],
                 left_on="ss_sold_date_sk", right_on="d_date_sk")
    m = m.merge(it[it.i_category.isin([p["cat1"], p["cat2"], p["cat3"]])],
                left_on="ss_item_sk", right_on="i_item_sk")
    m["itemrevenue"] = R.money(m.ss_ext_sales_price)
    keys = ["i_item_id", "i_item_desc", "i_category", "i_class",
            "i_current_price"]
    g = m.groupby(keys, dropna=False, as_index=False)["itemrevenue"].sum(
        min_count=1)
    in_class = g.groupby("i_class", dropna=False)["itemrevenue"].transform(
        lambda s: s.sum(min_count=1))
    g["revenueratio"] = (g.itemrevenue * R.dtype.type(100)
                         / in_class.astype(R.dtype))
    g["i_current_price"] = R.money(g.i_current_price)
    g = g.sort_values(["i_category", "i_class", "i_item_id", "i_item_desc",
                       "revenueratio"], na_position="first", kind="stable")
    return g[keys + ["itemrevenue", "revenueratio"]]
