"""TPC-DS Q7: four-dimension star join, four averages per item (NULL
measures are skipped by AVG), first 100 items."""
import pandas as pd


def reference(T, p, R):
    ss = T("store_sales", ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                           "ss_promo_sk", "ss_quantity", "ss_list_price",
                           "ss_coupon_amt", "ss_sales_price"])
    cd = T("customer_demographics",
           ["cd_demo_sk", "cd_gender", "cd_marital_status",
            "cd_education_status"])
    dd = T("date_dim", ["d_date_sk", "d_year"])
    it = T("item", ["i_item_sk", "i_item_id"])
    pr = T("promotion", ["p_promo_sk", "p_channel_email",
                         "p_channel_event"])
    m = ss.merge(dd[dd.d_year == int(p["year"])],
                 left_on="ss_sold_date_sk", right_on="d_date_sk")
    m = m.merge(cd[(cd.cd_gender == p["gender"])
                   & (cd.cd_marital_status == p["marital"])
                   & (cd.cd_education_status == p["education"])],
                left_on="ss_cdemo_sk", right_on="cd_demo_sk")
    m = m.merge(pr[(pr.p_channel_email == "N")
                   | (pr.p_channel_event == "N")],
                left_on="ss_promo_sk", right_on="p_promo_sk")
    m = m.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    f = pd.DataFrame({
        "i_item_id": m.i_item_id.to_numpy(),
        "agg1": R.num(m.ss_quantity),
        "agg2": R.money(m.ss_list_price),
        "agg3": R.money(m.ss_coupon_amt),
        "agg4": R.money(m.ss_sales_price)})
    out = f.groupby("i_item_id", sort=True).mean().reset_index()
    return out.head(100)
