"""Seconds of set-up spent turning Arrow tables into host tables
(decimals to scaled int64, string dictionaries, dates, null masks): the
program's ``load.build`` spans.  Source: program_span
(``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("load.build")
