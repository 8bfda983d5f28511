"""Device-idle milliseconds a statement of the traced slice that pass
after the read-back: under ``device.materialize`` (host rows),
``device.finish`` (memory sample, timings) and ``sched.note`` (the
scheduler's bookkeeping of the outcome).  Source: program_span
(benchmarks/span_reduce.py)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.idle_ms_per_stmt(run, "finish")
