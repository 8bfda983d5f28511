"""TPC-DS Q21: inventory on hand a warehouse and item, 30 days before
and after a date, for items priced 0.99 to 1.49; groups whose after /
before ratio lies in [0.666667, 1.5], first 100 by warehouse and item.
The ratio is the engine's documented division of two integer sums: a
real (float64) quotient, NULL where ``inv_before`` is not above 0, and
a NULL ratio passes neither bound. A NULL quantity adds nothing to its
CASE branch's sum; the other branch still adds its 0."""
import numpy as np

from benchmarks.reference.rawdata import days


def reference(T, p, R):
    inv = T("inventory", ["inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
                          "inv_quantity_on_hand"])
    wh = T("warehouse", ["w_warehouse_sk", "w_warehouse_name"])
    it = T("item", ["i_item_sk", "i_item_id", "i_current_price"])
    dd = T("date_dim", ["d_date_sk", "d_date"])
    day = days(p["date"])
    m = inv.merge(dd[(dd.d_date >= day - 30) & (dd.d_date <= day + 30)],
                  left_on="inv_date_sk", right_on="d_date_sk")
    m = m.merge(it[(it.i_current_price >= 99) & (it.i_current_price <= 149)],
                left_on="inv_item_sk", right_on="i_item_sk")
    m = m.merge(wh, left_on="inv_warehouse_sk", right_on="w_warehouse_sk")
    qty = m.inv_quantity_on_hand.to_numpy(np.float64)
    before = (m.d_date < day).to_numpy()
    m["inv_before"] = np.where(before, qty, 0.0)
    m["inv_after"] = np.where(before, 0.0, qty)
    g = m.groupby(["w_warehouse_name", "i_item_id"], dropna=False,
                  as_index=False)[["inv_before", "inv_after"]].sum(
                      min_count=1)
    ratio = (R.num(g.inv_after)
             / R.num(g.inv_before.where(g.inv_before > 0)))
    g = g[(ratio >= R.dtype.type(0.666667)) & (ratio <= R.dtype.type(1.5))]
    g = g.sort_values(["w_warehouse_name", "i_item_id"],
                      na_position="first", kind="stable").head(100)
    g["inv_before"] = g.inv_before.astype(np.int64)
    g["inv_after"] = g.inv_after.astype(np.int64)
    return g[["w_warehouse_name", "i_item_id", "inv_before", "inv_after"]]
