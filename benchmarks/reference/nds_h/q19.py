"""TPC-H Q19, discounted revenue: lookup join under an OR of three
conjunctions, scalar sum."""
import pandas as pd

BANDS = ((["SM CASE", "SM BOX", "SM PACK", "SM PKG"], 5),
         (["MED BAG", "MED BOX", "MED PKG", "MED PACK"], 10),
         (["LG CASE", "LG BOX", "LG PACK", "LG PKG"], 15))


def reference(T, p, R):
    li = T("lineitem", ["l_partkey", "l_quantity", "l_extendedprice",
                        "l_discount", "l_shipmode", "l_shipinstruct"])
    part = T("part", ["p_partkey", "p_brand", "p_container", "p_size"])
    li = li[li.l_shipmode.isin(["AIR", "AIR REG"])
            & (li.l_shipinstruct == "DELIVER IN PERSON")]
    m = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    keep = None
    for i, (containers, size) in enumerate(BANDS, 1):
        q = int(p[f"quantity{i}"]) * 100
        band = ((m.p_brand == p[f"brand{i}"])
                & m.p_container.isin(containers)
                & (m.l_quantity >= q) & (m.l_quantity <= q + 1000)
                & (m.p_size >= 1) & (m.p_size <= size))
        keep = band if keep is None else keep | band
    sel = m[keep]
    rev = (R.money(sel.l_extendedprice)
           * (1 - R.money(sel.l_discount))).sum()
    return pd.DataFrame({"revenue": [rev if len(sel) else None]})
