"""Blocking device-to-host transfers a statement: the ``syncs`` the
program counts on its ``device.readback`` spans (one ``device_get`` a
statement, two where the result is compacted on the device first), summed
over the traced slice and divided by its statements.  Source:
program_counter (read from the profile, benchmarks/span_reduce.py)."""

from benchmarks import span_reduce


def read(run):
    out = span_reduce.for_run(run)
    syncs = span_reduce.attr_sum(run, "device.readback", "syncs")
    if syncs is None or not out["statements"]:
        return None
    return syncs / out["statements"]
