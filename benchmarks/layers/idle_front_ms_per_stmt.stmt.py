"""Device-idle milliseconds a statement of the traced slice that pass
before the executor is reached: under the program's ``stmt`` span itself
(plan-cache lookup, the call into the pipeline), ``sql.parse`` /
``sql.plan``, ``sched.place`` (placement, governor) and ``sched.run``'s own
time (pre-dispatch; the owned ``device.execute`` span has no annotation,
so what it alone covers falls here too).  With the three other
``idle_*_ms_per_stmt.stmt`` it adds up to the slice's idle time a
statement, less what no span covers.  Source: program_span (the
program's ``nds.*`` annotations in the profile, benchmarks/span_reduce.py)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.idle_ms_per_stmt(run, "front")
