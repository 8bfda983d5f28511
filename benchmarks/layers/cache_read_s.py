"""Thread-seconds of set-up inside ``compile()``: a read of jax's
persistent cache on a warm run, the compiler itself on a cold one (the
program's ``compile.xla`` spans; ``persistent_cache_hit`` on each says
which).  Source: program_span (``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("compile.xla")
