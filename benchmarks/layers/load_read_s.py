"""Seconds of set-up spent reading the warehouse's files into Arrow
tables: the program's ``load.read`` spans.  Source: program_span
(``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("load.read")
