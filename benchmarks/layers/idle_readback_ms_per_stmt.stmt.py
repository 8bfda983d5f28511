"""Device-idle milliseconds a statement of the traced slice that pass
under ``device.readback``: from the end of the statement's last program
to the return of the blocking device-to-host transfer.  Source:
program_span (benchmarks/span_reduce.py)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.idle_ms_per_stmt(run, "readback")
