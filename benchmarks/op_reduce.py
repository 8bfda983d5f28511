"""From a profiler trace (.xplane.pb) to the device's time by statement,
operator and mechanism.

A device op event is named by its HLO instruction (``fusion.27``,
``sort.185``; PERF.md section 5), which says nothing of the plan.  The
program names its device work at trace time (README "Observability"):
every plan node is traced in the scope ``op.<kind>`` (``op.join``,
``op.aggregate``, ...; ``op.subplan`` / ``op.root`` for the outputs),
the exchange in ``exchange``, a replicate's ``all_gather`` in
``replicate``, every ``jnp.take`` in ``gather``; each compiled
instruction carries the path in its ``op_name``.  The program's registry
(``nds_tpu.obs.costs``) numbers each executable at its first dispatch,
the ``device.launch`` span carries that ``program``, and
``costs.sites(program)`` reads the executable's text into
``{instruction: (op_name, opcode)}``, in this process, when asked.

The pairing: on each device plane the k-th ``XLA Modules`` event is the
k-th program execution, and so the k-th host launch event
(``PJRT_LoadedExecutable_Execute``, the pairing
``trace_reduce.clock_offset`` relies on); the launch lies inside the
``nds.device.launch`` annotation that made it, whose ``program`` stat
names the executable.  Where host launches and modules do not pair one
to one, the k-th ``device.launch`` annotation is paired with the k-th
module instead, if their counts agree.  An op event belongs to the
module that holds it.

An op whose instruction carries an ``op.*`` scope is *named*;
``named_pct`` is the share of the chips' busy seconds that named ops
cover.  A program served from a compile cache filled by an older tree
carries that tree's metadata, and so no scopes: jax leaves metadata out
of the cache key (``jax_compilation_cache_include_metadata_in_key`` is
False).  The readers of this table leave their metric out below 95 %.

Pure functions over ``span_reduce.read_planes``' dict plus a reader
memoized per file, as ``collectives.py`` is; ``for_run`` writes the
table to ``benchmarks/.work/<cell>/ops.json`` beside ``spans.json``.
"""

from __future__ import annotations

import bisect
import json
import os
import time

from benchmarks import span_reduce
from benchmarks import trace_reduce as tr

OP_PREFIX = "op."
MECHANISMS = ("exchange", "replicate", "gather")
LAUNCH_SPAN = "device.launch"
NAMED_MIN_PCT = 95.0        # below it the readers leave their metric out
TOP = 5                     # instructions kept a key


def scopes(op_name: str) -> tuple:
    """(operator path, mechanisms) of an instruction's ``op_name``: the
    ``op.*`` scopes outermost first, joined by '/', and the mechanism
    scopes in the order they first open (an exchange nests one in its
    hash partition).  The last part is the primitive (``gather``,
    ``sort``), no scope."""
    parts = op_name.split("/")[:-1]
    ops = [p for p in parts if p.startswith(OP_PREFIX)]
    return "/".join(ops), tuple(dict.fromkeys(
        p for p in parts if p in MECHANISMS))


def pair_modules(planes: dict, modules: list) -> list:
    """The ``program`` of each module of one plane (sorted by start),
    None where it cannot be said."""
    launches = sorted(planes.get("launches", []), key=lambda e: e[1])
    spans = sorted((s for line in (planes.get("spans") or {}).values()
                    for s in line if s[0] == LAUNCH_SPAN),
                   key=lambda s: s[1])
    if launches and len(launches) == len(modules):
        starts = [s[1] for s in spans]
        out = []
        for _name, t, _end in launches:
            i = bisect.bisect_right(starts, t) - 1
            inside = i >= 0 and spans[i][1] <= t <= spans[i][2]
            out.append(spans[i][3].get("program") if inside else None)
        return out
    if spans and len(spans) == len(modules):
        return [s[3].get("program") for s in spans]
    return [None] * len(modules)


def reduce_planes(planes: dict, sites) -> "dict | None":
    """The table for a planes dict; ``sites(program)`` gives a program's
    ``{instruction: (op_name, opcode)}`` or None.  None where the trace
    holds no device op."""
    slices = [a for a in planes["annotations"] if a[0] == tr.SLICE]
    window = (slices[0][1], slices[0][2]) if slices else None
    stmts = sorted((a for a in planes["annotations"]
                    if a[0].startswith(tr.STMT)), key=lambda a: a[1])
    stmt_starts = [a[1] for a in stmts]
    table: dict = {}
    busy = named = 0.0
    by_mechanism = dict.fromkeys(MECHANISMS + ("exchange|replicate",), 0.0)
    chips = 0
    for plane in sorted(planes["devices"]):
        lines = planes["devices"][plane]
        ops = lines.get("XLA Ops") or []
        if not ops:
            continue
        chips += 1
        modules = sorted(lines.get(tr.MODULES_LINE, []), key=lambda m: m[1])
        programs = pair_modules(planes, modules)
        shift = tr.clock_offset(planes.get("launches", []), modules)
        mod_starts = [m[1] for m in modules]
        lo, hi = window or (min(o[1] for o in ops) - shift,
                            max(o[2] for o in ops) - shift)
        hit: dict = {k: [] for k in ("busy", "named", *by_mechanism)}
        for raw, start, end in ops:
            s, e = max(start - shift, lo), min(end - shift, hi)
            if e <= s:
                continue
            hit["busy"].append((s, e))
            i = bisect.bisect_right(mod_starts, start) - 1
            program = (programs[i] if i >= 0 and start < modules[i][2]
                       else None)
            found = sites(program) if program is not None else None
            name = tr.op_name(raw)
            op_name, opcode = (found or {}).get(name, ("", ""))
            path, mechs = scopes(op_name)
            if path:
                hit["named"].append((s, e))
            for m in mechs:
                hit[m].append((s, e))
            if "exchange" in mechs or "replicate" in mechs:
                hit["exchange|replicate"].append((s, e))
            owner = tr.statement_at(s, stmts, stmt_starts)
            key = (tr.short_name(owner) if owner else "",
                   path or "(unnamed)",
                   "/".join(mechs) or opcode or name.split(".")[0])
            row = table.setdefault(key, {"seconds": 0.0, "count": 0,
                                         "top": {}})
            row["seconds"] += (e - s) / 1e9
            row["count"] += 1
            row["top"][name] = row["top"].get(name, 0.0) + (e - s) / 1e9
        seconds = {k: sum(b - a for a, b in tr.union(v)) / 1e9
                   for k, v in hit.items()}
        busy += seconds["busy"]
        named += seconds["named"]
        for k in by_mechanism:
            by_mechanism[k] += seconds[k]
    if not chips or not busy:
        return None
    rows = [{"statement": k[0], "operator": k[1], "mechanism": k[2],
             "seconds": v["seconds"], "count": v["count"],
             "top": sorted(([n, t] for n, t in v["top"].items()),
                           key=lambda x: -x[1])[:TOP]}
            for k, v in table.items()]
    rows.sort(key=lambda r: -r["seconds"])
    return {"chips": chips, "busy_s": busy, "named_s": named,
            "named_pct": 100.0 * named / busy,
            "mechanism_s": by_mechanism, "table": rows}


def busy_pct(found: "dict | None", mechanism: str) -> "float | None":
    """Seconds of ops whose path holds ``mechanism`` (summed over the
    chips) over the chips' busy seconds; None where the ops are not
    named (``NAMED_MIN_PCT``) or none holds it."""
    if not found or found["named_pct"] < NAMED_MIN_PCT:
        return None
    seconds = found["mechanism_s"].get(mechanism)
    return 100.0 * seconds / found["busy_s"] if seconds else None


def program_sites(program):
    """``nds_tpu.obs.costs.sites`` of this process; None from a program
    that has no registry."""
    try:
        from nds_tpu.obs import costs
        return costs.sites(program)
    except (ImportError, AttributeError):
        return None


_memo: dict = {}


def for_run(run: dict) -> "dict | None":
    """The table of this run's traced slice, or None (no trace, no
    device op).  Parsed once a file; written to ``ops.json``."""
    if not run.get("trace"):
        return None
    cell = run["cell"]["name"]
    path = span_reduce.find_xplane(cell)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _memo:
        memo: dict = {}
        parse_s = [0.0]

        def sites(program):
            if program not in memo:
                t = time.perf_counter()
                memo[program] = program_sites(program)
                parse_s[0] += time.perf_counter() - t
            return memo[program]

        out = reduce_planes(span_reduce.read_planes(path), sites)
        if out is not None:
            sliced = run["window"].get("slice")
            out["passes"] = sliced[2] if sliced else None
            out["sites_s"] = parse_s[0]
            out["programs"] = sorted(p for p in memo if memo[p])
            with open(os.path.join(span_reduce.HERE, ".work", cell,
                                   "ops.json"), "w") as f:
                json.dump(out, f, indent=1)
        _memo[key] = out
    return _memo[key]
