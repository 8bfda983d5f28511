"""TPC-DS Q38: customers (last name, first name) and days on which they
bought in all three channels within twelve months: three SELECT
DISTINCT sides, two INTERSECTs, COUNT(*). DISTINCT and INTERSECT take
NULL names as equal to each other."""
import pandas as pd

NULL = "\x00null"          # no generated name holds it
KEYS = ["c_last_name", "c_first_name", "d_date"]
CHANNELS = (("store_sales", "ss_sold_date_sk", "ss_customer_sk"),
            ("catalog_sales", "cs_sold_date_sk", "cs_bill_customer_sk"),
            ("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk"))


def sides(T, p):
    """The three SELECT DISTINCT sides, store, catalog, web."""
    dd = T("date_dim", ["d_date_sk", "d_date", "d_month_seq"])
    dms = int(p["dms"])
    dd = dd[(dd.d_month_seq >= dms) & (dd.d_month_seq <= dms + 11)]
    cu = T("customer", ["c_customer_sk", "c_last_name", "c_first_name"])
    cu = cu.fillna({"c_last_name": NULL, "c_first_name": NULL})
    out = []
    for fact, date_sk, customer_sk in CHANNELS:
        m = T(fact, [date_sk, customer_sk]).merge(
            dd, left_on=date_sk, right_on="d_date_sk")
        m = m.merge(cu, left_on=customer_sk, right_on="c_customer_sk")
        out.append(m[KEYS].drop_duplicates())
    return out


def reference(T, p, R):
    hot, catalog, web = sides(T, p)
    hot = hot.merge(catalog, on=KEYS).merge(web, on=KEYS)
    return pd.DataFrame({"cnt": [len(hot)]})
