"""TPC-DS Q96: filtered three-dimension star join, COUNT(*)."""
import pandas as pd


def reference(T, p, R):
    ss = T("store_sales", ["ss_sold_time_sk", "ss_hdemo_sk", "ss_store_sk"])
    td = T("time_dim", ["t_time_sk", "t_hour", "t_minute"])
    hd = T("household_demographics", ["hd_demo_sk", "hd_dep_count"])
    st = T("store", ["s_store_sk", "s_store_name"])
    m = ss.merge(td[(td.t_hour == int(p["hour"])) & (td.t_minute >= 30)],
                 left_on="ss_sold_time_sk", right_on="t_time_sk")
    m = m.merge(hd[hd.hd_dep_count == int(p["dep"])],
                left_on="ss_hdemo_sk", right_on="hd_demo_sk")
    m = m.merge(st[st.s_store_name == "ese"], left_on="ss_store_sk",
                right_on="s_store_sk")
    return pd.DataFrame({"cnt": [len(m)]})
