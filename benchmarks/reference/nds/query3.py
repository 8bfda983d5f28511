"""TPC-DS Q3: store_sales joined to date_dim and item, brand revenue
per year for one manufacturer in one month, first 100."""


def reference(T, p, R):
    ss = T("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                           "ss_ext_sales_price"])
    dd = T("date_dim", ["d_date_sk", "d_year", "d_moy"])
    it = T("item", ["i_item_sk", "i_brand_id", "i_brand",
                    "i_manufact_id"])
    m = ss.merge(dd[dd.d_moy == int(p["month"])],
                 left_on="ss_sold_date_sk", right_on="d_date_sk")
    m = m.merge(it[it.i_manufact_id == int(p["manufact"])],
                left_on="ss_item_sk", right_on="i_item_sk")
    m["sum_agg"] = R.money(m.ss_ext_sales_price)
    g = m.groupby(["d_year", "i_brand_id", "i_brand"],
                  as_index=False)["sum_agg"].sum(min_count=1)
    g = g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                      ascending=[True, False, True]).head(100)
    g = g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand"})
    return g[["d_year", "brand_id", "brand", "sum_agg"]]
