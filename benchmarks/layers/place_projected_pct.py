"""How near the scheduler believes the cell is to its device budget:
``projected_bytes`` (the device's live bytes at placement plus what the
memory governor expects the statement to add) over ``budget_bytes``,
both summed over the ``sched.place`` spans of the traced slice, in
percent.  At 100 the governor sends the next statement out of core.
Left out, never 0, where no ``sched.place`` span carries the two
attributes (a program older than them) or no budget is set.
Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    projected = span_reduce.attr_sum(run, "sched.place", "projected_bytes")
    budget = span_reduce.attr_sum(run, "sched.place", "budget_bytes")
    if projected is None or not budget:
        return None
    return 100.0 * projected / budget
