"""Share of the chips' busy time spent in collective instructions
(all-to-all, all-gather, all-reduce, collective-permute, reduce-scatter
and their -start / -done halves), over the traced slice: seconds in
those instructions summed over the chips, over the chips' busy seconds.
The instruction kind is read off the op event's own text
(benchmarks/collectives.py).  Communication the device waits for; left
out, never 0, where the trace holds no collective.  Source:
device_trace."""

from benchmarks import collectives


def read(run):
    trace, found = run["trace"], collectives.for_run(run)
    if not found or not trace or not trace["busy_s"]:
        return None
    chips = found["chips"]
    seconds = sum(c["collective_s"] for c in chips)
    if not seconds:
        return None
    return 100.0 * seconds / (trace["busy_s"] * len(chips))
