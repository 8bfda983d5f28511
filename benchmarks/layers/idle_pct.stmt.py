"""``idle_pct`` as layers/idle_pct.py reads it, in the cells whose
end-to-end metric is stmt_mean_ms and not pass_s: a per-layer metric
moves one end-to-end metric, so the quantity is split by the cells'."""

from benchmarks.layers.idle_pct import read  # noqa: F401
