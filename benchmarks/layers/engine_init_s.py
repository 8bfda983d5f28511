"""Seconds of set-up under the program's ``engine.init`` spans
(``make_session``: plan-cache and compile-cache activation, the backend's
first touch where the caller has not made it, pipeline construction),
every session of the process counted.  Source: program_span
(``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("engine.init")
