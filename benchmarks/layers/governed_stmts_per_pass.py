"""Statements a pass whose placement the scheduler took from the memory
governor or from its high-water history, and not from the plan's own
size: ``governed`` (0 / 1) summed over the ``sched.place`` spans of the
traced slice, over the slice's passes.  0 is the deployment as stated:
every statement starts on the placement its working set asks for.  A
governed statement runs the out-of-core path although the cell is
device-resident, and the harness does not count that as a failure.
Left out, never 0, where no ``sched.place`` span carries the attribute
(a program older than it).  Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    governed = span_reduce.attr_sum(run, "sched.place", "governed")
    sliced = run["window"]["slice"]
    if governed is None or not sliced or not sliced[2]:
        return None
    return governed / sliced[2]
