"""TPC-DS Q27: Q7's star join with store in promotion's place, grouped
by ROLLUP(i_item_id, s_state): the (item, state) groups, a subtotal an
item (s_state NULL, g_state 1) and the grand total (both keys NULL),
four averages each (AVG skips NULL measures), ordered by the two keys
with NULL lowest, first 100."""
import numpy as np
import pandas as pd


def reference(T, p, R):
    ss = T("store_sales", ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                           "ss_store_sk", "ss_quantity", "ss_list_price",
                           "ss_coupon_amt", "ss_sales_price"])
    cd = T("customer_demographics",
           ["cd_demo_sk", "cd_gender", "cd_marital_status",
            "cd_education_status"])
    dd = T("date_dim", ["d_date_sk", "d_year"])
    st = T("store", ["s_store_sk", "s_state"])
    it = T("item", ["i_item_sk", "i_item_id"])
    states = [p[f"s{i}"] for i in range(1, 7)]
    m = ss.merge(dd[dd.d_year == int(p["year"])],
                 left_on="ss_sold_date_sk", right_on="d_date_sk")
    m = m.merge(cd[(cd.cd_gender == p["gender"])
                   & (cd.cd_marital_status == p["marital"])
                   & (cd.cd_education_status == p["education"])],
                left_on="ss_cdemo_sk", right_on="cd_demo_sk")
    m = m.merge(st[st.s_state.isin(states)], left_on="ss_store_sk",
                right_on="s_store_sk")
    m = m.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    f = pd.DataFrame({
        "i_item_id": m.i_item_id.to_numpy(),
        "s_state": m.s_state.to_numpy(),
        "agg1": R.num(m.ss_quantity),
        "agg2": R.money(m.ss_list_price),
        "agg3": R.money(m.ss_coupon_amt),
        "agg4": R.money(m.ss_sales_price)})
    aggs = ["agg1", "agg2", "agg3", "agg4"]
    by_state = f.groupby(["i_item_id", "s_state"])[aggs].mean().reset_index()
    by_state["g_state"] = 0
    by_item = f.groupby("i_item_id")[aggs].mean().reset_index()
    by_item["s_state"], by_item["g_state"] = None, 1
    total = f[aggs].mean().to_frame().T if len(f) else f[aggs].iloc[:0]
    total["i_item_id"], total["s_state"], total["g_state"] = None, None, 1
    out = pd.concat([by_state, by_item, total], ignore_index=True)
    out["g_state"] = out["g_state"].astype(np.int64)
    out = out.sort_values(["i_item_id", "s_state"], na_position="first",
                          kind="stable").head(100)
    return out[["i_item_id", "s_state", "g_state"] + aggs]
