"""Seconds of set-up spent in make_session + load_warehouse (parquet to
host tables).  Source: program_span (the benchmark's own span)."""


def read(run):
    total = run["spans"].total("load")
    return total if total > 0 else None
