"""Device-idle milliseconds a statement of the traced slice that pass
under ``device.dispatch`` and its children: staging check, plan
verification, bind (scan views, buffers, parameters), accounting, the
memory sample, the launch.  Source: program_span (benchmarks/span_reduce.py)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.idle_ms_per_stmt(run, "dispatch")
