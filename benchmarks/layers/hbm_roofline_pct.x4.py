"""Share of the HBM roofline of ALL the cell's chips over the traced
slice: `hbm_roofline_pct` (the bytes the slice's statements NEED over
one chip's bandwidth over the mean time a chip was busy) over the cell's
chips.  A sharded statement reads a quarter of every sharded table on
each of four chips, so the four-chip line is the one-chip line over
four; the one-chip metric keeps its cells and its reader.  Source:
device_trace."""

from benchmarks.layers import hbm_roofline_pct


def read(run):
    one_chip = hbm_roofline_pct.read(run)
    return None if one_chip is None else one_chip / run["cell"]["chips"]
