"""TPC-H Q6, forecasting revenue change: scan + filter + scalar sum."""
import pandas as pd

from benchmarks.reference.rawdata import days, plus_months


def reference(T, p, R):
    li = T("lineitem", ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"])
    lo, hi = days(p["date"]), plus_months(p["date"], 12)
    disc = round(float(p["discount"]) * 100)
    m = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)
           & (li.l_discount >= disc - 1) & (li.l_discount <= disc + 1)
           & (li.l_quantity < int(p["quantity"]) * 100)]
    rev = (R.money(m.l_extendedprice) * R.money(m.l_discount)).sum()
    return pd.DataFrame({"revenue": [rev if len(m) else None]})
