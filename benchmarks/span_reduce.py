"""From a profiler trace (.xplane.pb) to the program's own spans: how
often each ran in the traced slice, how long, how much of that was its
own, and how much of the DEVICE'S IDLE TIME passed under it.

The program (``nds_tpu/obs/trace.py``, since PR 25) opens a
``jax.profiler.TraceAnnotation`` named ``nds.<span name>`` for every
context-managed span while a profile is live, with the span's numeric
attributes as the event's stats.  They land on the calling thread's
line of the host plane, on the clock of the benchmark's ``bench.stmt:``
annotations and of the PJRT launch events, nested as the spans were.
This module lays them over the device's planes: the device's events are
shifted onto the host's clock (``trace_reduce.clock_offset``), the
slice's idle intervals are what no device op covers, and every piece of
an idle interval belongs to the INNERMOST annotation open on the
statements' thread at that time:

* under an ``nds.*`` span: that span's ``idle_s``;
* inside a ``bench.stmt:`` but under no ``nds.*`` span: ``uncovered``
  (the harness's own loop, or a program without the spans);
* outside every statement: ``outside``.

So ``sum(idle_s) + uncovered + outside`` is the slice's idle time, to
the nanosecond.  A trace without a single ``nds.*`` event (the parent
of PR 25, ``NDS_TPU_OBS=0``) reduces to ``None``: a reader then leaves
its metric out.

A pure function over a planes dict plus a thin reader, as
``trace_reduce.py`` is; ``for_run`` is memoized per file, so that the
readers under ``layers/`` parse once, and writes the whole table to
``benchmarks/.work/<cell>/spans.json``.
"""

from __future__ import annotations

import json
import os

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "nds."
STMT_ROOT = "bench.stmt"          # pseudo-span: a statement's annotation

# which of the four `idle_*_ms_per_stmt.stmt` metrics an idle piece goes
# to: the group of the innermost span on its path that has one (a span
# this table does not name falls to its nearest ancestor that it does)
GROUP_OF = {
    "stmt": "front", "sql.parse": "front", "sql.plan": "front",
    "sched.place": "front", "sched.run": "front",
    "device.dispatch": "dispatch",
    "device.readback": "readback",
    "device.materialize": "finish", "device.finish": "finish",
    "sched.note": "finish",
}
GROUPS = ("front", "dispatch", "readback", "finish")


def read_planes(path: str) -> dict:
    """``trace_reduce.read_planes``' dict plus ``"spans"``: per host
    line, the ``nds.*`` events as (name without the prefix, start, end,
    {stat: number}), and ``"stmt_line"``: the line the benchmark's
    statement annotations are on."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, annotations, launches, spans = {}, [], [], {}
    on_line: dict = {}
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {line.name: tr._events(line)
                                   for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = []
                for e in line.events:
                    name = e.name
                    if name.startswith(PREFIX):
                        start = float(e.start_ns)
                        mine.append((name[len(PREFIX):], start,
                                     start + float(e.duration_ns),
                                     {k: v for k, v in e.stats
                                      if isinstance(v, (int, float))}))
                    elif name == tr.LAUNCH:
                        launches.append(_event(e))
                    elif name == tr.SLICE or name.startswith(tr.STMT):
                        annotations.append(_event(e))
                        on_line[line.name] = on_line.get(line.name, 0) + 1
                if mine:
                    spans[line.name] = mine
    return {"devices": devices, "annotations": annotations,
            "launches": launches, "spans": spans,
            "stmt_line": max(on_line, key=on_line.get) if on_line else None}


def _event(e) -> tuple:
    start = float(e.start_ns)
    return (e.name, start, start + float(e.duration_ns))


def innermost(spans: list) -> list:
    """[(start, end, path)]: disjoint pieces in time order, each with
    the names of the spans open over it, outermost first.  ``spans`` are
    (name, start, end, ...) of ONE thread, so properly nested; a child
    that outlasts its parent (clock rounding) is cut to it."""
    out: list = []
    stack: list = []                 # (name, end), outermost first
    at = 0.0                         # emitted up to here

    def emit(upto: float) -> None:
        if stack and upto > at:
            out.append((at, upto, tuple(n for n, _e in stack)))

    def close(before: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= before:
            emit(stack[-1][1])
            at = max(at, stack.pop()[1])

    for span in sorted(spans, key=lambda s: (s[1], -s[2])):
        name, start, end = span[0], span[1], span[2]
        close(start)
        emit(start)
        at = start
        if stack:
            end = min(end, stack[-1][1])
        if end > start:
            stack.append((name, end))
    close(float("inf"))
    return out


def idle_intervals(planes: dict):
    """((window start, end), [(start, end)] idle pieces of the first
    chip inside it, clock offset), on the host's clock; None where the
    trace holds no device op."""
    slices = [a for a in planes["annotations"] if a[0] == tr.SLICE]
    window = (slices[0][1], slices[0][2]) if slices else None
    for name in sorted(planes["devices"]):
        lines = planes["devices"][name]
        shift = tr.clock_offset(planes.get("launches", []),
                                lines.get(tr.MODULES_LINE, []))
        ops = next((lines[n] for n in tr.OPS_LINES if lines.get(n)), [])
        if not ops:
            continue
        ops = [(s - shift, e - shift) for _n, s, e in ops]
        lo, hi = window or (min(o[0] for o in ops), max(o[1] for o in ops))
        busy = tr.union(tr.clip(ops, lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        return (lo, hi), idle, shift
    return None


def overlap(pieces: list, idle: list):
    """For sorted disjoint ``pieces`` [(start, end, tag)] and sorted
    disjoint ``idle`` [(start, end)]: yields (tag, seconds-in-ns) for
    every intersection."""
    j = 0
    for start, end, tag in pieces:
        while j < len(idle) and idle[j][1] <= start:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < end:
            yield tag, min(end, idle[k][1]) - max(start, idle[k][0])
            k += 1


def reduce_planes(planes: dict) -> "dict | None":
    per_line = planes.get("spans") or {}
    if not per_line:
        return None                  # a program without the spans
    found = idle_intervals(planes)
    if found is None:
        return None
    (lo, hi), idle, shift = found
    line = planes.get("stmt_line")
    spans = (per_line.get(line, []) if line in per_line
             else [s for v in per_line.values() for s in v])
    stmts = [(STMT_ROOT, s, e, {}) for n, s, e in planes["annotations"]
             if n.startswith(tr.STMT) and s >= lo and e <= hi]
    inside = [s for s in spans if s[1] >= lo and s[2] <= hi]

    table: dict = {}
    for name, start, end, stats in inside:
        row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0, "idle_s": 0.0})
        row["count"] += 1
        row["total_s"] += (end - start) / 1e9
        for key, value in stats.items():
            row[key] = row.get(key, 0) + value
    pieces = innermost(stmts + inside)
    for _s, _e, path in pieces:
        if path[-1] != STMT_ROOT:
            table[path[-1]]["self_s"] += (_e - _s) / 1e9
    groups = dict.fromkeys(GROUPS, 0.0)
    uncovered = 0.0
    for path, ns in overlap(pieces, idle):
        if path[-1] != STMT_ROOT:
            table[path[-1]]["idle_s"] += ns / 1e9
        group = next((GROUP_OF[n] for n in reversed(path)
                      if n in GROUP_OF), None)
        if group is None:
            uncovered += ns / 1e9
        else:
            groups[group] += ns / 1e9
    idle_s = sum(b - a for a, b in idle) / 1e9
    return {"statements": len(stmts), "window_s": (hi - lo) / 1e9,
            "idle_s": idle_s, "spans": table, "groups": groups,
            "uncovered_s": uncovered,
            "outside_s": idle_s - uncovered - sum(groups.values()),
            "clock_offset_s": shift / 1e9}


def reduce(path: str) -> "dict | None":
    return reduce_planes(read_planes(path))


# ---------------------------------------------------- what readers call

_memo: dict = {}


def find_xplane(cell_name: str) -> "str | None":
    """The newest .xplane.pb under the cell's trace directory, where
    run.py has the profiler write it."""
    hits = []
    for base, _dirs, files in os.walk(
            os.path.join(HERE, ".work", cell_name, "trace")):
        hits += [os.path.join(base, f) for f in files
                 if f.endswith(".xplane.pb")]
    return max(hits, key=os.path.getmtime) if hits else None


def for_run(run: dict) -> "dict | None":
    """The reduced spans of this run's traced slice, or None (no trace,
    or a program without the spans).  Parsed once a file; the table
    goes to ``.work/<cell>/spans.json`` beside the cell's trace."""
    if not run.get("trace"):
        return None
    cell = run["cell"]["name"]
    path = find_xplane(cell)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _memo:
        _memo[key] = out = reduce(path)
        if out is not None:
            with open(os.path.join(HERE, ".work", cell, "spans.json"),
                      "w") as f:
                json.dump({**out, "setup": program_totals()}, f, indent=1)
    return _memo[key]


def idle_ms_per_stmt(run: dict, group: str) -> "float | None":
    out = for_run(run)
    if not out or not out["statements"]:
        return None
    return out["groups"][group] * 1e3 / out["statements"]


def attr_sum(run: dict, span: str, attr: str) -> "float | None":
    """Sum of a numeric attribute over the slice's spans of one name;
    None where no such span carried it."""
    out = for_run(run)
    return ((out["spans"].get(span) or {}).get(attr)) if out else None


def program_totals() -> "dict | None":
    """``Tracer.totals()`` of the program in this process: seconds by
    span name since the process began.  None from a program that keeps
    none."""
    try:
        from nds_tpu.obs.trace import get_tracer
        totals = getattr(get_tracer(), "totals", None)
        return totals() if totals else None
    except Exception:  # noqa: BLE001 - a program without the tracer
        return None


def setup_seconds(name: str) -> "float | None":
    """Total seconds of the program's spans of one name.  Load, compile
    and engine.init spans occur in set-up only (`window_compiles` 0 says
    so of the compiles), so the process's total is set-up's."""
    row = (program_totals() or {}).get(name)
    return row["total_s"] if row and row["count"] else None
