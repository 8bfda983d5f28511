"""Seconds of set-up spent in the binds that did work: the program's
``device.bind`` spans with ``first`` (a scan view filtered on the host, or
buffers uploaded), which the tracer sums apart under ``device.bind:first``.
Source: program_span (``Tracer.totals()``)."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.setup_seconds("device.bind:first")
