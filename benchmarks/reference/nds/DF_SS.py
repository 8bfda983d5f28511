"""TPC-DS refresh function DF_SS (v3.2.0 clause 5.3): the store
returns whose ticket has a sale dated in [date1, date2] go, then the
store sales whose date key lies between the first and the last date key
of that range.  A NULL key is never in range, so its row stays."""
import numpy as np

from benchmarks.reference.rawdata import days


def apply(T, p, R):
    dd = T("date_dim", ["d_date_sk", "d_date"])
    win = dd[(dd.d_date >= days(p["date1"]))
             & (dd.d_date <= days(p["date2"]))].d_date_sk
    ss = T("store_sales", T.columns("store_sales"))
    sold = ss.ss_sold_date_sk.to_numpy(np.float64)
    tickets = ss.ss_ticket_number[np.isin(sold, win.to_numpy())].dropna()
    sr = T("store_returns", T.columns("store_returns"))
    gone = np.isin(sr.sr_ticket_number.to_numpy(np.float64),
                   tickets.to_numpy(np.float64))
    out = {"store_returns": sr[~gone]}
    if len(win):          # an empty range: min and max are NULL
        out["store_sales"] = ss[~((sold >= win.min())
                                  & (sold <= win.max()))]
    else:
        out["store_sales"] = ss
    return out
