"""Megabytes ONE chip receives through the sharded trace's replicates
(``all_gather`` of a sharded relation to every chip: under a window, a
set operation, a DISTINCT, a sort or a top-N, a scalar subplan, the
root) in one pass: ``replicate_bytes`` (the other chips' slots of the
row mask, every column and every validity; static, from the program's
trace) summed over the ``device.launch`` spans of the traced slice, over
the slice's passes.  Capacity, not rows that were valid.  Beside
``exchange_mb_per_pass`` it says how much of a statement's wire is
replication and not repartition.  Left out, never 0, where no launch
span carries the attribute (a single-device program, or a program older
than the attribute).  Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    nbytes = span_reduce.attr_sum(run, "device.launch", "replicate_bytes")
    sliced = run["window"]["slice"]
    if not nbytes or not sliced or not sliced[2]:
        return None
    return nbytes / sliced[2] / 1e6
