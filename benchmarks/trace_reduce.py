"""From a profiler trace (.xplane.pb) to device busy/idle, ops by time
and idle gaps by what the host was doing.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one per operation; their union is the
time in which an operation ran) and ``Async XLA Ops`` (copies, inside
the ops' union to within 0.01 %).  The host plane ``/host:CPU`` has one
line per thread; ``jax.profiler.TraceAnnotation`` events land on the
calling thread's line, on the same clock as the device events.

The benchmark writes ``bench.slice`` round the traced passes and
``bench.stmt:<name>#<variant>`` round each statement.  The slice is the
window; without one (a trace of something else) the window is the span
of the device's own events.

The two clocks agree only to a few milliseconds (the first traced run
showed q6's 1 ms program inside the NEXT statement's annotation), which
is as long as a short statement.  The host's own launch events
(``PJRT_LoadedExecutable_Execute``) are on the annotations' clock and
come one per program execution, in the device's order: where their
count equals the device's modules', the k-th launch is the k-th module
and the smallest gap between the two is taken for the clocks' offset,
by which the device's events are moved before anything is attributed.
"""

from __future__ import annotations

import bisect
import re

SLICE = "bench.slice"
STMT = "bench.stmt:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")     # first that has events
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"   # host event, one a program run


def union(intervals: list) -> list:
    """Sorted, merged copy of [(start, end), ...]."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {line: [(name, start, end)]}},
        "annotations": [(name, start, end)]} in nanoseconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, annotations, launches = {}, [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {line.name: _events(line)
                                   for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                annotations += [ev for ev in events
                                if ev[0] == SLICE or ev[0].startswith(STMT)]
                launches += [ev for ev in events
                             if ev[0] == LAUNCH]
    return {"devices": devices, "annotations": annotations,
            "launches": launches}


def clock_offset(launches: list, modules: list) -> float:
    """Nanoseconds by which the device's clock runs ahead of the
    host's: the smallest distance from a launch to its module.  0 where
    launches and modules cannot be paired one to one."""
    if not launches or len(launches) != len(modules):
        return 0.0
    starts = sorted(ev[1] for ev in launches)
    return min(m[1] - h for m, h in zip(sorted(modules, key=lambda m: m[1]),
                                        starts))


def op_name(raw: str) -> str:
    """'%fusion.2 = f32[..] fusion(...)' -> 'fusion.2'."""
    return raw.split(" = ")[0].lstrip("%")[:60]


def statement_at(t: float, stmts: list, starts: list):
    """The statement annotation (name, start, end) open at t, or None;
    ``stmts`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and stmts[i][1] <= t < stmts[i][2]:
        return stmts[i]
    return None


def short_name(stmt) -> str:
    """'bench.stmt:q6#2' -> 'q6': variants of one statement together."""
    return stmt[0][len(STMT):].split("#")[0]


def gap_name(t: float, stmts: list, starts: list, modules: list,
             mod_starts: list) -> str:
    """What the host was doing at ``t`` of an idle gap: the statement
    whose annotation was open, and where ``t`` lies among the programs
    that statement launched."""
    owner = statement_at(t, stmts, starts)
    if owner is None:
        return "between statements"
    m0 = bisect.bisect_left(mod_starts, owner[1])
    m1 = bisect.bisect_left(mod_starts, owner[2])
    mine = modules[m0:m1]
    if not mine or t < mine[0][0]:
        phase = "before first program"
    elif t >= mine[-1][1]:
        phase = "after last program"
    elif any(m[0] <= t < m[1] for m in mine):
        phase = "inside a program"
    else:
        phase = "between programs"
    return f"{short_name(owner)}: {phase}"


def reduce_planes(planes: dict) -> dict:
    ann = planes["annotations"]
    slices = [a for a in ann if a[0] == SLICE]
    stmts = sorted((a for a in ann if a[0].startswith(STMT)),
                   key=lambda a: a[1])
    starts = [a[1] for a in stmts]
    per_device = []
    ops_time: dict = {}
    gaps: dict = {}
    window = (slices[0][1], slices[0][2]) if slices else None
    offsets = []
    for name in sorted(planes["devices"]):
        lines = planes["devices"][name]
        shift = clock_offset(planes.get("launches", []),
                             lines.get(MODULES_LINE, []))
        offsets.append(shift)
        lines = {k: [(n, s - shift, e - shift) for n, s, e in v]
                 for k, v in lines.items()}
        ops = next((lines[n] for n in OPS_LINES if lines.get(n)), [])
        if not ops:
            continue
        # no slice annotation: the first device's own span is the window
        window = window or (min(o[1] for o in ops), max(o[2] for o in ops))
        lo, hi = window
        busy = union(clip([(s, e) for _n, s, e in ops], lo, hi))
        per_device.append(sum(e - s for s, e in busy))
        modules = sorted(clip([(s, e) for _n, s, e in
                               lines.get(MODULES_LINE, [])], lo, hi))
        mod_starts = [m[0] for m in modules]
        for raw, s, e in ops:
            if e <= lo or s >= hi:
                continue
            owner = statement_at(s, stmts, starts)
            key = (f"{short_name(owner)}/{op_name(raw)}" if owner
                   else op_name(raw))
            ops_time[key] = ops_time.get(key, 0.0) + (min(e, hi)
                                                      - max(s, lo))
        if len(per_device) > 1:
            continue                      # gaps: the first chip's only
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        bounds = sorted({t for a in stmts for t in a[1:]}
                        | {t for m in modules for t in m})
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            # a gap is split at the edges of statements and of programs,
            # so that each piece has one owner and one phase
            i0 = bisect.bisect_right(bounds, g0)
            i1 = bisect.bisect_left(bounds, g1)
            cuts = [g0] + bounds[i0:i1] + [g1]
            for p0, p1 in zip(cuts, cuts[1:]):
                if p1 > p0:
                    what = gap_name((p0 + p1) / 2, stmts, starts,
                                    modules, mod_starts)
                    gaps[what] = gaps.get(what, 0.0) + (p1 - p0)
    if not per_device:
        return {"busy_s": None, "window_s": None, "device_ops": [],
                "idle_gaps": [], "devices": 0, "clock_offset_s": 0.0}
    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]

    return {"busy_s": sum(per_device) / len(per_device) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9,
            "device_ops": top(ops_time), "idle_gaps": top(gaps),
            "devices": len(per_device),
            "clock_offset_s": offsets[0] / 1e9}


def reduce(path: str) -> dict:
    return reduce_planes(read_planes(path))
