"""TPC-DS refresh function LF_SS (v3.2.0 clause 5.3): the store
purchases of the refresh set, with their line items, become store_sales
rows.  Each business id is looked up in its dimension (customer, the
current record of store and item, date_dim by date, time_dim by
seconds, promotion); an id with no match gives a NULL key, as the
function's LEFT OUTER JOINs do.  Decimals stay scaled integers: money
times a count keeps scale 2; money times a tax rate (scale 4) goes back
to the column's scale 2 by truncation toward zero, one of the two ways
SQL leaves to the implementation (Spark would round half up; the engine
truncates wherever it lowers a scale)."""
import numpy as np
import pandas as pd


def _left(m, dim, left_on, right_on):
    """LEFT OUTER JOIN on one key: a NULL key joins nothing."""
    return m.merge(dim[dim[right_on].notna()], how="left",
                   left_on=left_on, right_on=right_on)


def _cents(scaled4):
    """Scale 4 -> scale 2, truncated toward zero; NULL stays NULL."""
    return np.trunc(np.asarray(scaled4, dtype=np.float64) / 100)


def apply(T, p, R):
    purc = T("s_purchase", ["purc_purchase_id", "purc_store_id",
                            "purc_customer_id", "purc_purchase_date",
                            "purc_purchase_time"])
    plin = T("s_purchase_lineitem", [
        "plin_purchase_id", "plin_item_id", "plin_promotion_id",
        "plin_quantity", "plin_sale_price", "plin_coupon_amt"])
    m = purc.merge(plin, left_on="purc_purchase_id",
                   right_on="plin_purchase_id")
    cust = T("customer", ["c_customer_id", "c_customer_sk",
                          "c_current_cdemo_sk", "c_current_hdemo_sk",
                          "c_current_addr_sk"])
    store = T("store", ["s_store_id", "s_store_sk", "s_tax_precentage",
                        "s_rec_end_date"])
    item = T("item", ["i_item_id", "i_item_sk", "i_wholesale_cost",
                      "i_current_price", "i_rec_end_date"])
    m = _left(m, cust, "purc_customer_id", "c_customer_id")
    m = _left(m, store[store.s_rec_end_date.isna()], "purc_store_id",
              "s_store_id")
    m = _left(m, T("date_dim", ["d_date", "d_date_sk"]),
              "purc_purchase_date", "d_date")
    m = _left(m, T("time_dim", ["t_time", "t_time_sk"]),
              "purc_purchase_time", "t_time")
    m = _left(m, T("promotion", ["p_promo_id", "p_promo_sk"]),
              "plin_promotion_id", "p_promo_id")
    m = _left(m, item[item.i_rec_end_date.isna()], "plin_item_id",
              "i_item_id")
    f = lambda c: m[c].to_numpy(np.float64)  # noqa: E731 - NULL as NaN
    qty, sale, coupon = f("plin_quantity"), f("plin_sale_price"), \
        f("plin_coupon_amt")
    price, cost, tax = f("i_current_price"), f("i_wholesale_cost"), \
        f("s_tax_precentage")
    paid = sale * qty - coupon
    rows = pd.DataFrame({
        "ss_sold_date_sk": m.d_date_sk, "ss_sold_time_sk": m.t_time_sk,
        "ss_item_sk": m.i_item_sk, "ss_customer_sk": m.c_customer_sk,
        "ss_cdemo_sk": m.c_current_cdemo_sk,
        "ss_hdemo_sk": m.c_current_hdemo_sk,
        "ss_addr_sk": m.c_current_addr_sk, "ss_store_sk": m.s_store_sk,
        "ss_promo_sk": m.p_promo_sk, "ss_ticket_number": m.purc_purchase_id,
        "ss_quantity": qty, "ss_wholesale_cost": cost,
        "ss_list_price": price, "ss_sales_price": sale,
        "ss_ext_discount_amt": (price - sale) * qty,
        "ss_ext_sales_price": sale * qty,
        "ss_ext_wholesale_cost": cost * qty,
        "ss_ext_list_price": price * qty,
        "ss_ext_tax": _cents(price * tax),
        "ss_coupon_amt": coupon, "ss_net_paid": paid,
        "ss_net_paid_inc_tax": _cents(paid * (100 + tax)),
        "ss_net_profit": paid - qty * cost})
    cols = T.columns("store_sales")
    base = T("store_sales", cols)
    return {"store_sales": pd.concat(
        [base.astype(np.float64), rows[cols].astype(np.float64)],
        ignore_index=True)}
