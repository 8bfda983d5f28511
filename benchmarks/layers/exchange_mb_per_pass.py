"""Megabytes ONE chip hands to the exchange's all_to_all in one pass:
``exchange_bytes`` (bucket capacity x chips x item sizes of every array
sent, the ok mask included; static, from the program's trace) summed
over the ``device.launch`` spans of the traced slice, over the slice's
passes.  Capacity, not rows that were valid: what the wire carries
whatever the selectivity.  Left out, never 0, where no launch span
carries the attribute (a single-device program, or a program older than
the attribute).  Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    nbytes = span_reduce.attr_sum(run, "device.launch", "exchange_bytes")
    sliced = run["window"]["slice"]
    if not nbytes or not sliced or not sliced[2]:
        return None
    return nbytes / sliced[2] / 1e6
