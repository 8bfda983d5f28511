"""Gigabytes the compiled programs of one pass access: ``bytes accessed``
of each executable's ``cost_analysis()`` from the ``device.launch`` spans
of the traced slice, over the slice's passes.  Beside `hbm_roofline_pct`,
which counts the bytes the SQL needs: this counts what the compiler says
the programs touch.  Left out, never 0, where the backend gives no such
analysis.  Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    nbytes = span_reduce.attr_sum(run, "device.launch", "bytes_accessed")
    sliced = run["window"]["slice"]
    if not nbytes or not sliced or not sliced[2]:
        return None
    return nbytes / sliced[2] / 1e9
