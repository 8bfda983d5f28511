"""The exchange's share of its roofline over the traced slice: the least
time the interconnect could take to carry what the all-to-all and
all-gather instructions put on it, over the time the chips spent in
those instructions.  Bytes: (n - 1) / n of every such instruction's
result, read from the instruction's own shape in the op event's text
(benchmarks/collectives.py; the launch spans' ``exchange_bytes`` are not
used, so a program without them reads the same).  Peak:
``ici_bits_per_s / 8`` a chip (benchmarks/peaks.json).  Summed over the
chips, bytes and seconds alike.  Cannot pass 100; left out, never 0,
where the trace holds no such instruction.  Source: device_trace."""

from benchmarks import collectives


def read(run):
    found, peaks = collectives.for_run(run), run["peaks"]
    if not found or not peaks or not peaks.get("ici_bits_per_s"):
        return None
    chips = found["chips"]
    seconds = sum(c["wire_s"] for c in chips)
    nbytes = sum(c["wire_bytes"] for c in chips)
    share = collectives.wire_share(len(chips))
    if not seconds or not nbytes or not share:     # one chip: no wire
        return None
    least_s = nbytes * share / (peaks["ici_bits_per_s"] / 8)
    return 100.0 * least_s / seconds
