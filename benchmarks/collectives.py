"""The collective instructions of a traced slice: how long each chip
spent in them, and the bytes their own shapes say they moved.

A device op event's name is its HLO instruction's text (PERF.md section
5), e.g. ``%all_to_all.71 = s32[4,1,750357]{...} all-to-all(...)`` (the
name is jax's, the opcode after the shape is XLA's), so the instruction
kind and the result's shape are both read off the event: no span or counter of the program is needed, and a parent of the
PR that added this file reads the same numbers.  Collective kinds:
``all-to-all``, ``all-gather``, ``all-reduce``, ``collective-permute``,
``reduce-scatter`` and their asynchronous ``-start`` / ``-done`` halves.

Bytes on the interconnect, for the roofline: of an all-to-all's
``[n, bucket]`` buffer a chip keeps one row and sends ``n - 1``; of an
all-gather's result a chip holds one shard and receives ``n - 1``, each
of which some chip sent, so one chip's share of the wire is again
``(n - 1) / n`` of the result.  A ``-start`` half is timed but its shape
(a tuple of operand and result) is not counted: its ``-done`` half
carries the result.  All-reduces are left out of the roofline, bytes and
seconds alike: they are scalars (counts, overflow), or the form the TPU
compiler gives a tiled all_gather (a ``dynamic-update-slice`` summed over
the chips), whose wire share is not the all-to-all's; ``collective_pct``
counts their seconds.

Pure functions over ``trace_reduce.read_planes``' dict plus a reader
memoized per file, as ``span_reduce.py`` is.
"""

from __future__ import annotations

import os
import re

from benchmarks import span_reduce
from benchmarks import trace_reduce as tr

KINDS = ("all-to-all", "all-gather", "all-reduce", "collective-permute",
         "reduce-scatter")
WIRE_KINDS = ("all-to-all", "all-gather")      # what the roofline counts
ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
              "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
              "f64": 8}
_INSTRUCTION = re.compile(
    r"^%?(?P<name>[\w.\-]+) = (?P<result>.*?) ?"
    r"(?P<kind>" + "|".join(KINDS) + r")(?P<half>-start|-done)?\(")
_ARRAY = re.compile(r"\b(" + "|".join(ITEM_BYTES) + r")\[([\d,]*)\]")


def parse(raw: str) -> "tuple | None":
    """(kind, half, result bytes) of a collective instruction's text;
    None for any other instruction.  ``half`` is '', '-start' or
    '-done'."""
    m = _INSTRUCTION.match(raw)
    if m is None:
        return None
    nbytes = 0
    for dtype, dims in _ARRAY.findall(m.group("result")):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * ITEM_BYTES[dtype]
    return m.group("kind"), m.group("half") or "", nbytes


def reduce_planes(planes: dict) -> "dict | None":
    """Per chip, inside the slice: seconds in every collective
    instruction, seconds in the wire kinds, and the bytes of the wire
    kinds' results.  None where the trace holds no device op."""
    slices = [a for a in planes["annotations"] if a[0] == tr.SLICE]
    window = (slices[0][1], slices[0][2]) if slices else None
    chips = []
    for name in sorted(planes["devices"]):
        lines = planes["devices"][name]
        ops = next((lines[n] for n in tr.OPS_LINES if lines.get(n)), [])
        if not ops:
            continue
        lo, hi = window or (min(o[1] for o in ops), max(o[2] for o in ops))
        row = {"collective_s": 0.0, "wire_s": 0.0, "wire_bytes": 0,
               "count": 0}
        for raw, s, e in ops:
            if e <= lo or s >= hi:
                continue
            found = parse(raw)
            if found is None:
                continue
            kind, half, nbytes = found
            seconds = (min(e, hi) - max(s, lo)) / 1e9
            row["collective_s"] += seconds
            row["count"] += 1
            if kind in WIRE_KINDS:
                row["wire_s"] += seconds
                if half != "-start":
                    row["wire_bytes"] += nbytes
        chips.append(row)
    return {"chips": chips} if chips else None


_memo: dict = {}


def for_run(run: dict) -> "dict | None":
    """The collectives of this run's traced slice, or None (no trace)."""
    if not run.get("trace"):
        return None
    path = span_reduce.find_xplane(run["cell"]["name"])
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _memo:
        _memo[key] = reduce_planes(tr.read_planes(path))
    return _memo[key]


def wire_share(n_chips: int) -> float:
    """Of a collective's buffer, the part that leaves the chip."""
    return (n_chips - 1) / n_chips if n_chips > 1 else 0.0
