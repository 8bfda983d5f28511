"""CI gate for the observability JSON the engine emits: the Chrome
trace-event JSONL (``NDS_TPU_TRACE``, nds_tpu/obs/trace.py) and the
per-query BenchReport summaries (utils/report.py) the run-analysis
layer (obs/analyze.py, tools/ndsreport.py) consumes. Every documented
shape is validated here so downstream consumers — Perfetto after
array-wrapping, ndsreport, or anything parsing the files directly —
never meet a malformed record.

Trace event schema (one event per line):
  name: non-empty str      ph:  "X" (complete) or "C" (counter)
  cat:  str                ts:  number >= 0 (microseconds)
  pid:  int                tid: int
  args: object (optional)
  "X" events additionally require dur: number >= 0; "C" counter
  events (obs/trace.counter_event — the device-memory lanes) carry no
  dur and require a non-empty all-numeric args object instead.

BenchReport summary schema (``--summary``, README "Observability"):
  query/queryStatus/queryTimes/startTime/env required; optional blocks
  — spans (name/dur_ms/attrs/children tree; a span the catalogue
  SPAN_PARENTS names has to hang from one of the parents it lists),
  metrics (counters/gauges/
  histograms with count+sum and optional p50/p95/p99), memory
  (device_hwm_bytes + source), retries / retry_backoff_s /
  gave_up_reason / deadline_exceeded, the scheduling fields
  placement / reschedules / ladder / promoted_back / governed
  (engine/scheduler.py; README "Placement & degradation"), the resume
  fields incarnation / result_digest and the torn-state degradations
  block (resilience/journal.py; README "Preemption & resume"), and the
  plan-cache block cache (hits + misses required ints; optional
  errors / bytes_read / bytes_written / load_ms — nds_tpu/cache/;
  README "Plan cache"), the kernel-use block kernels (kernel
  name -> positive use count — engine/kernels.py; README "Kernels &
  roofline"), the XLA-capture block profile (path + trigger from the
  obs/profile.py trigger vocabulary, optional bytes), the
  flight-recorder pointer flight (path + optional reason/entries —
  obs/fleet.py; README "Fleet & profiling"), the compiler-cost block
  cost (flops/bytes_accessed/transcendentals sums + a positive
  programs census; optional memory maxima / platform / ops_est
  cross-check — obs/costs.py; README "Cost ledger & telemetry"), and
  the device-memory time-series block telemetry (samples/interval_ms
  + the hbm min/max/mean/series summary — obs/telemetry.py).

Exit 0 when every record validates; prints each offense otherwise.
Run by tests/test_observability.py and tools/static_checks.py as a
tier-1 gate.
"""

from __future__ import annotations

import json
import sys

REQUIRED = {
    "name": str,
    "cat": str,
    "ph": str,
    "ts": (int, float),
    "pid": int,
    "tid": int,
}


def validate_event(obj: object) -> list[str]:
    """Schema errors for one parsed event ([] = valid). Two phases
    are legal: "X" complete events (non-negative dur required) and
    "C" counter events (no dur; a non-empty all-numeric args object
    is the payload — obs/trace.counter_event)."""
    errs = []
    if not isinstance(obj, dict):
        return [f"event is {type(obj).__name__}, not an object"]
    for key, typ in REQUIRED.items():
        if key not in obj:
            errs.append(f"missing key {key!r}")
        elif not isinstance(obj[key], typ) or isinstance(obj[key], bool):
            errs.append(f"{key!r} has type {type(obj[key]).__name__}")
    if not errs:
        if not obj["name"]:
            errs.append("empty name")
        if obj["ts"] < 0:
            errs.append("negative ts")
        if obj["ph"] == "X":
            dur = obj.get("dur")
            if not _num(dur):
                errs.append(f"bad dur {dur!r}")
            elif dur < 0:
                errs.append("negative dur")
        elif obj["ph"] == "C":
            cargs = obj.get("args")
            if (not isinstance(cargs, dict) or not cargs
                    or any(not _num(v) for v in cargs.values())):
                errs.append(f"counter event needs non-empty numeric "
                            f"args, got {cargs!r}")
        else:
            errs.append(f"ph {obj['ph']!r} not in ('X', 'C')")
    if "args" in obj and not isinstance(obj.get("args"), dict):
        errs.append("args is not an object")
    return errs


def validate_file(path: str) -> list[str]:
    """All schema errors in a trace file, prefixed with line numbers
    ([] = valid). An empty file is an error: a power run with tracing
    enabled must emit at least one event."""
    errors = []
    n = 0
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            n += 1
            try:
                obj = json.loads(line)
            except ValueError as exc:
                errors.append(f"line {lineno}: not JSON ({exc})")
                continue
            for e in validate_event(obj):
                errors.append(f"line {lineno}: {e}")
    if n == 0:
        errors.append("no events: file is empty")
    return errors


_STATUS_VOCAB = {"Completed", "CompletedWithTaskFailures", "Failed"}
_HWM_SOURCES = {"device", "accounted"}
# obs/profile.py TRIGGERS — duplicated by value, not imported: this
# validator must stay runnable standalone with no package import
_PROFILE_TRIGGERS = {"query", "slow", "stall", "stream"}


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


# The span catalogue at the layer boundaries (README "Observability";
# duplicated by value: this validator stays runnable standalone): span
# name -> the parents it may hang from in a statement's tree. A name
# not listed here (sql.*, stage.*, cache.load, compile.*, load.*,
# chunk.*, exchange.*, a tool's own) may hang anywhere. A sharded
# statement (dist_exec) has the single-device tree; a retry at doubled
# slack, on either executor, adds a second device.dispatch /
# device.readback pair under the same device.execute. A chunked
# statement's per-chunk programs launch and read back under their
# chunk.* span. Attributes the catalogue names beyond the README's
# table: device.launch carries exchanges / resized / exchange_rows /
# exchange_bytes / send_words for a sharded program, device.readback
# overflow_rows / skew. sched.place says what the placement was made
# from (PLACE_ATTRS; checked where a span carries them).
_ROOTS = ("stmt", "query")
PLACE_BYTES = ("est_bytes", "live_bytes", "projected_bytes",
               "budget_bytes")
PLACEMENTS = ("device", "sharded", "chunked", "cpu")
SPAN_PARENTS = {
    "sched.place": _ROOTS,
    "sched.run": _ROOTS,
    "sched.note": _ROOTS,
    "device.execute": _ROOTS + ("sched.run", "stage.sub", "chunk.reduce",
                                "chunk.partial_agg"),
    "device.dispatch": ("device.execute",),
    "device.compile": ("device.dispatch", "device.execute"),
    "device.bind": ("device.dispatch", "device.compile", "cache.load"),
    "device.launch": ("device.dispatch", "device.execute",
                      "chunk.partial_agg", "chunk.reduce"),
    "device.readback": ("device.execute", "chunk.partial_agg",
                        "chunk.reduce"),
    "device.run": ("device.execute",),
    "device.materialize": ("device.execute",),
    "device.finish": ("device.execute",),
}


def _place_errors(attrs: dict) -> list[str]:
    """``sched.place``'s attributes, all six or none (a span exported
    by a tree older than them carries none)."""
    errs = []
    if attrs.get("placement") not in PLACEMENTS:
        errs.append(f"bad placement {attrs.get('placement')!r}")
    if attrs.get("governed") not in (0, 1):
        errs.append(f"governed {attrs.get('governed')!r} not 0 / 1")
    for key in PLACE_BYTES:
        if not _num(attrs.get(key)) or attrs[key] < 0:
            errs.append(f"bad {key} {attrs.get(key)!r}")
    return errs


def _validate_span_tree(node: object, path: str,
                        parent: "str | None" = None) -> list[str]:
    if not isinstance(node, dict):
        return [f"{path}: span node is {type(node).__name__}"]
    errs = []
    if not node.get("name") or not isinstance(node.get("name"), str):
        errs.append(f"{path}: missing/empty span name")
    elif (parent is not None
          and parent not in SPAN_PARENTS.get(node["name"], (parent,))):
        errs.append(f"{path}: span {node['name']!r} hangs from "
                    f"{parent!r}, the catalogue allows "
                    f"{SPAN_PARENTS[node['name']]}")
    if not _num(node.get("dur_ms")) or node.get("dur_ms", 0) < 0:
        errs.append(f"{path}: bad dur_ms {node.get('dur_ms')!r}")
    if "attrs" in node and not isinstance(node["attrs"], dict):
        errs.append(f"{path}: attrs is not an object")
    elif node.get("name") == "sched.place" and node.get("attrs"):
        errs.extend(f"{path}: sched.place {e}"
                    for e in _place_errors(node["attrs"]))
    kids = node.get("children", [])
    if not isinstance(kids, list):
        errs.append(f"{path}: children is not a list")
        kids = []
    for i, k in enumerate(kids):
        errs.extend(_validate_span_tree(k, f"{path}.children[{i}]",
                                        node.get("name")))
    return errs


def validate_summary(obj: object) -> list[str]:
    """Schema errors for one BenchReport summary dict ([] = valid)."""
    if not isinstance(obj, dict):
        return [f"summary is {type(obj).__name__}, not an object"]
    errs = []
    if not isinstance(obj.get("query"), str) or not obj.get("query"):
        errs.append("missing/empty 'query'")
    status = obj.get("queryStatus")
    if (not isinstance(status, list) or not status
            or any(s not in _STATUS_VOCAB for s in status)):
        errs.append(f"bad queryStatus {status!r}")
    times = obj.get("queryTimes")
    if (not isinstance(times, list) or not times
            or any(not _num(t) or t < 0 for t in times)):
        errs.append(f"bad queryTimes {times!r}")
    if not isinstance(obj.get("startTime"), int):
        errs.append("missing/invalid startTime")
    if not isinstance(obj.get("env"), dict):
        errs.append("missing env object")
    if "spans" in obj:
        errs.extend(_validate_span_tree(obj["spans"], "spans"))
    m = obj.get("metrics", {})
    if not isinstance(m, dict):
        errs.append("metrics is not an object")
    else:
        for block in ("counters", "gauges"):
            vals = m.get(block, {})
            if not isinstance(vals, dict) or any(
                    not _num(v) for v in vals.values()):
                errs.append(f"metrics.{block} has non-numeric values")
        for name, h in (m.get("histograms") or {}).items():
            if (not isinstance(h, dict) or not _num(h.get("count"))
                    or not _num(h.get("sum"))):
                errs.append(f"metrics.histograms[{name!r}] lacks "
                            f"numeric count/sum")
            elif any(k in h and not _num(h[k])
                     for k in ("p50", "p95", "p99")):
                errs.append(f"metrics.histograms[{name!r}] has "
                            f"non-numeric percentile")
    mem = obj.get("memory")
    if mem is not None:
        if (not isinstance(mem, dict)
                or not isinstance(mem.get("device_hwm_bytes"), int)
                or mem["device_hwm_bytes"] < 0
                or mem.get("source") not in _HWM_SOURCES):
            errs.append(f"bad memory block {mem!r}")
    # serving-layer fields (nds_tpu/serve/): tenant attribution on
    # per-request summaries; stale_device_times marks banked (not
    # freshly measured) numbers — a bool that must never be false-y
    # noise
    if "tenant" in obj and (not isinstance(obj["tenant"], str)
                            or not obj["tenant"]):
        errs.append(f"bad tenant {obj.get('tenant')!r}")
    # fleet serving (nds_tpu/serve/fleet.py): which replica answered
    if "replica" in obj and (not isinstance(obj["replica"], str)
                             or not obj["replica"]):
        errs.append(f"bad replica {obj.get('replica')!r}")
    if "stale_device_times" in obj and obj["stale_device_times"] \
            is not True:
        errs.append(f"bad stale_device_times "
                    f"{obj['stale_device_times']!r}")
    if "retries" in obj and (not isinstance(obj["retries"], int)
                             or obj["retries"] < 0):
        errs.append(f"bad retries {obj['retries']!r}")
    if "retry_backoff_s" in obj and (
            not _num(obj["retry_backoff_s"])
            or obj["retry_backoff_s"] < 0):
        errs.append(f"bad retry_backoff_s {obj['retry_backoff_s']!r}")
    if "deadline_exceeded" in obj and not isinstance(
            obj["deadline_exceeded"], bool):
        errs.append("deadline_exceeded is not a bool")
    # scheduling fields (engine/scheduler.py; README "Placement &
    # degradation"): placement + reschedules travel together,
    # ladder only appears on rescheduled queries
    if "placement" in obj and (
            not isinstance(obj["placement"], str)
            or not obj["placement"]):
        errs.append(f"bad placement {obj.get('placement')!r}")
    if "reschedules" in obj and (
            not isinstance(obj["reschedules"], int)
            or obj["reschedules"] < 0):
        errs.append(f"bad reschedules {obj['reschedules']!r}")
    if "ladder" in obj and (
            not isinstance(obj["ladder"], list)
            or not all(isinstance(x, str) for x in obj["ladder"])):
        errs.append(f"bad ladder {obj['ladder']!r}")
    if "promoted_back" in obj and obj["promoted_back"] is not True:
        errs.append(f"bad promoted_back {obj['promoted_back']!r}")
    if "governed" in obj and obj["governed"] is not True:
        # memory-governor pre-admission demotion
        # (engine/scheduler.MemoryGovernor)
        errs.append(f"bad governed {obj['governed']!r}")
    if "prefetch_depth" in obj and (
            not isinstance(obj["prefetch_depth"], int)
            or isinstance(obj["prefetch_depth"], bool)
            or obj["prefetch_depth"] < 0):
        # governor depth admission lowered the phase-A prefetch depth
        # for this query (engine/pipeline_io.py)
        errs.append(f"bad prefetch_depth {obj['prefetch_depth']!r}")
    # resume fields (resilience/journal.QueryJournal; README
    # "Preemption & resume"): which incarnation served the query and
    # the result's content digest
    if "incarnation" in obj and (
            not isinstance(obj["incarnation"], int)
            or isinstance(obj["incarnation"], bool)
            or obj["incarnation"] < 0):
        errs.append(f"bad incarnation {obj['incarnation']!r}")
    if "result_digest" in obj and (
            not isinstance(obj["result_digest"], str)
            or not obj["result_digest"]):
        errs.append(f"bad result_digest {obj['result_digest']!r}")
    # torn-state degradations surfaced per summary
    # (journal_resets_total / snapshot_resets_total)
    deg = obj.get("degradations")
    if deg is not None:
        if (not isinstance(deg, dict) or not deg
                or not set(deg) <= {"journal_resets",
                                    "snapshot_resets"}
                or any(not isinstance(v, int)
                       or isinstance(v, bool) or v <= 0
                       for v in deg.values())):
            errs.append(f"bad degradations block {deg!r}")
    # plan-cache block (nds_tpu/cache/; README "Plan cache"): hits +
    # misses always travel together; byte counts / errors / load_ms
    # are optional and non-negative
    cache = obj.get("cache")
    if cache is not None:
        if (not isinstance(cache, dict)
                or not isinstance(cache.get("hits"), int)
                or not isinstance(cache.get("misses"), int)
                or cache["hits"] < 0 or cache["misses"] < 0):
            errs.append(f"bad cache block {cache!r}")
        else:
            for k in ("errors", "bytes_read", "bytes_written"):
                if k in cache and (not isinstance(cache[k], int)
                                   or cache[k] < 0):
                    errs.append(f"bad cache.{k} {cache[k]!r}")
            if "load_ms" in cache and (not _num(cache["load_ms"])
                                       or cache["load_ms"] < 0):
                errs.append(f"bad cache.load_ms {cache['load_ms']!r}")
    # kernel-use block (engine/kernels.py; README "Kernels &
    # roofline"): kernel name -> positive trace-time use count
    kern = obj.get("kernels")
    if kern is not None:
        if (not isinstance(kern, dict)
                or not all(isinstance(k, str) and isinstance(v, int)
                           and v > 0 for k, v in kern.items())):
            errs.append(f"bad kernels block {kern!r}")
    # XLA-capture block (obs/profile.py; README "Fleet & profiling"):
    # path + trigger always travel together, bytes is optional
    prof = obj.get("profile")
    if prof is not None:
        if (not isinstance(prof, dict)
                or not isinstance(prof.get("path"), str)
                or not prof.get("path")
                or prof.get("trigger") not in _PROFILE_TRIGGERS):
            errs.append(f"bad profile block {prof!r}")
        elif "bytes" in prof and (not isinstance(prof["bytes"], int)
                                  or isinstance(prof["bytes"], bool)
                                  or prof["bytes"] < 0):
            errs.append(f"bad profile.bytes {prof['bytes']!r}")
    # flight-recorder pointer (obs/fleet.py): the failed query's
    # summary names its post-mortem dump
    flight = obj.get("flight")
    if flight is not None:
        if (not isinstance(flight, dict)
                or not isinstance(flight.get("path"), str)
                or not flight.get("path")):
            errs.append(f"bad flight block {flight!r}")
        else:
            if "reason" in flight and not isinstance(
                    flight["reason"], str):
                errs.append(f"bad flight.reason "
                            f"{flight['reason']!r}")
            if "entries" in flight and (
                    not isinstance(flight["entries"], int)
                    or isinstance(flight["entries"], bool)
                    or flight["entries"] < 0):
                errs.append(f"bad flight.entries "
                            f"{flight['entries']!r}")
    # compiler-cost block (obs/costs.py; README "Cost ledger &
    # telemetry"): the three per-dispatch sums always travel as
    # non-negative numbers next to a positive programs census;
    # memory maxima / platform / ops_est cross-check are optional
    cost = obj.get("cost")
    if cost is not None:
        progs = cost.get("programs") if isinstance(cost, dict) else None
        if (not isinstance(cost, dict)
                or not isinstance(progs, dict) or not progs
                or any(not isinstance(k, str) or not k
                       or not isinstance(v, int)
                       or isinstance(v, bool) or v <= 0
                       for k, v in progs.items())
                or any(not _num(cost.get(k)) or cost[k] < 0
                       for k in ("flops", "bytes_accessed",
                                 "transcendentals"))):
            errs.append(f"bad cost block {cost!r}")
        else:
            for k in ("temp_bytes", "argument_bytes", "output_bytes",
                      "ops_est", "flops_per_op"):
                if k in cost and (not _num(cost[k]) or cost[k] < 0):
                    errs.append(f"bad cost.{k} {cost[k]!r}")
            if "platform" in cost and (
                    not isinstance(cost["platform"], str)
                    or not cost["platform"]):
                errs.append(f"bad cost.platform "
                            f"{cost.get('platform')!r}")
            if "ops_est_drift" in cost and \
                    cost["ops_est_drift"] is not True:
                errs.append(f"bad cost.ops_est_drift "
                            f"{cost['ops_est_drift']!r}")
    # device-memory time-series block (obs/telemetry.py): sample
    # count + interval, with the hbm min/max/mean and the decimated
    # [t_offset_ms, bytes] series
    tel = obj.get("telemetry")
    if tel is not None:
        if (not isinstance(tel, dict)
                or not isinstance(tel.get("samples"), int)
                or isinstance(tel.get("samples"), bool)
                or tel["samples"] <= 0
                or not _num(tel.get("interval_ms"))
                or tel["interval_ms"] <= 0):
            errs.append(f"bad telemetry block {tel!r}")
        else:
            hbm = tel.get("hbm")
            if hbm is not None and (
                    not isinstance(hbm, dict)
                    or any(not _num(hbm.get(k)) or hbm[k] < 0
                           for k in ("min_bytes", "max_bytes",
                                     "mean_bytes"))
                    or not isinstance(hbm.get("series"), list)
                    or not hbm["series"]
                    or any(not isinstance(p, list) or len(p) != 2
                           or not _num(p[0]) or not _num(p[1])
                           for p in hbm["series"])):
                errs.append(f"bad telemetry.hbm block {hbm!r}")
    return errs


def validate_summary_file(path: str) -> list[str]:
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as exc:
        return [f"{path}: not JSON ({exc})"]
    return [f"{path}: {e}" for e in validate_summary(obj)]


def validate_flight(obj: object) -> list[str]:
    """Schema errors for one flight-recorder dump
    (``flight-r<rank>.json``, obs/fleet.py): rank/pid/reason/ts
    header, a list of ring entries (query + status + ts, optional
    span tree), and the metrics/heartbeats snapshots."""
    if not isinstance(obj, dict):
        return [f"flight dump is {type(obj).__name__}, not an object"]
    errs = []
    if not isinstance(obj.get("rank"), int) or obj["rank"] < 0:
        errs.append(f"bad rank {obj.get('rank')!r}")
    if not isinstance(obj.get("pid"), int):
        errs.append("missing/invalid pid")
    if not isinstance(obj.get("reason"), str) or not obj.get("reason"):
        errs.append("missing/empty reason")
    if not _num(obj.get("ts")):
        errs.append("missing/invalid ts")
    entries = obj.get("entries")
    if not isinstance(entries, list):
        errs.append(f"entries is {type(entries).__name__}, not a list")
        entries = []
    for i, e in enumerate(entries):
        where = f"entries[{i}]"
        if not isinstance(e, dict):
            errs.append(f"{where}: not an object")
            continue
        if not isinstance(e.get("query"), str) or not e.get("query"):
            errs.append(f"{where}: missing/empty query")
        if e.get("status") not in _STATUS_VOCAB:
            errs.append(f"{where}: bad status {e.get('status')!r}")
        if not _num(e.get("ts")):
            errs.append(f"{where}: missing/invalid ts")
        if "wall_ms" in e and (not _num(e["wall_ms"])
                               or e["wall_ms"] < 0):
            errs.append(f"{where}: bad wall_ms {e['wall_ms']!r}")
        if "spans" in e:
            errs.extend(_validate_span_tree(e["spans"],
                                            f"{where}.spans"))
    if not isinstance(obj.get("metrics"), dict):
        errs.append("missing metrics object")
    if "heartbeats" in obj and not isinstance(obj["heartbeats"], dict):
        errs.append("heartbeats is not an object")
    return errs


def validate_flight_file(path: str) -> list[str]:
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as exc:
        return [f"{path}: not JSON ({exc})"]
    return [f"{path}: {e}" for e in validate_flight(obj)]


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) == 2 and argv[0] == "--summary":
        errors = validate_summary_file(argv[1])
        target = argv[1]
    elif len(argv) == 2 and argv[0] == "--flight":
        errors = validate_flight_file(argv[1])
        target = argv[1]
    elif len(argv) == 1:
        errors = validate_file(argv[0])
        target = argv[0]
    else:
        print("usage: check_trace_schema.py [--summary|--flight] FILE")
        return 2
    for e in errors:
        print(e)
    print(f"{'FAIL' if errors else 'OK'}: {len(errors)} schema error(s) "
          f"in {target}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
