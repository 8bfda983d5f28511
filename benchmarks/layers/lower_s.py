"""Thread-seconds of set-up spent tracing and lowering programs, the wait
for the trace lock included: the program's ``compile.lower`` spans.
Source: program_span (``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("compile.lower")
