"""Share of the HBM roofline over the traced slice: the least time the
chip could take to read the bytes the slice's statements NEED (table
rows x widths of the columns each statement's SQL references, numbers in
the traffic file, so no change of encoding, upload or kernel moves the
count), over the time the device was busy in the slice.  Memory-bound by
construction: scans, gathers and sorts carry little arithmetic per byte.

The bytes are the SQL's, an upper bound on what the window's programs
read: a repeated statement runs on scan views the host filtered at its
first execution.  A statement that this reduces to almost nothing gives
no ``need`` and counts no bytes; a mix that gives none (``short``)
reports no share at all, never 0.  Source: device_trace."""

def read(run):
    trace, window, peaks = run["trace"], run["window"], run["peaks"]
    if not trace or not trace["busy_s"] or not window["slice"] or not peaks:
        return None
    n_traced = window["slice"][3]
    need = sum(r["stmt"].need_bytes for r in window["records"][:n_traced])
    if not need:          # a mix that gives no `need`: nothing to read
        return None
    least_s = need / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
