"""TPC-H Q1, pricing summary: wide grouped aggregate over lineitem."""
import pandas as pd

from benchmarks.reference.rawdata import days


def reference(T, p, R):
    li = T("lineitem", ["l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax",
                        "l_shipdate"])
    d = li[li.l_shipdate <= days("1998-12-01") - int(p["delta"])]
    price, disc = R.money(d.l_extendedprice), R.money(d.l_discount)
    f = pd.DataFrame({
        "l_returnflag": d.l_returnflag.to_numpy(),
        "l_linestatus": d.l_linestatus.to_numpy(),
        "qty": R.money(d.l_quantity), "price": price, "disc": disc,
        "disc_price": price * (1 - disc),
        "charge": price * (1 - disc) * (1 + R.money(d.l_tax))})
    g = f.groupby(["l_returnflag", "l_linestatus"], sort=True)
    out = g.agg(sum_qty=("qty", "sum"), sum_base_price=("price", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"), avg_qty=("qty", "mean"),
                avg_price=("price", "mean"), avg_disc=("disc", "mean"),
                count_order=("qty", "size")).reset_index()
    return out
