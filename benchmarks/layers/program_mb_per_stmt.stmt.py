"""Megabytes the statements' compiled programs access, a statement: the
``bytes accessed`` of each executable's ``cost_analysis()``, which the
program puts on every ``device.launch`` span, summed over the traced
slice and divided by its statements.  The compiler's count, not a
measurement: it may count a gather's whole operand.  Left out, never 0,
where the backend gives no such analysis.  Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    out = span_reduce.for_run(run)
    nbytes = span_reduce.attr_sum(run, "device.launch", "bytes_accessed")
    if not nbytes or not out["statements"]:
        return None
    return nbytes / out["statements"] / 1e6
