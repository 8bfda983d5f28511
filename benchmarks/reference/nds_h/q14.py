"""TPC-H Q14, promotion effect: one lookup join, ratio of two sums."""
import pandas as pd

from benchmarks.reference.rawdata import days, plus_months


def reference(T, p, R):
    li = T("lineitem", ["l_partkey", "l_shipdate", "l_extendedprice",
                        "l_discount"])
    part = T("part", ["p_partkey", "p_type"])
    lo, hi = days(p["date"]), plus_months(p["date"], 1)
    m = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)].merge(
        part, left_on="l_partkey", right_on="p_partkey")
    rev = R.money(m.l_extendedprice) * (1 - R.money(m.l_discount))
    promo = m.p_type.str.startswith("PROMO").to_numpy()
    total = rev.sum()
    value = (R.dtype.type(100) * rev[promo].sum() / total
             if len(m) else None)
    return pd.DataFrame({"promo_revenue": [value]})
