"""Run analysis: time attribution, cross-run diff/regression gate, HTML.

PR 1 made every run EMIT spans and per-query metric deltas; nothing in
the repo consumed them — rounds were compared by eyeballing one scalar.
This module is the consumer.  It ingests a *run directory* (the
``json_summary_folder`` a power/throughput run writes: one BenchReport
JSON per query, plus any ``*.jsonl`` Chrome trace the run exported) and
produces three artifacts:

- **Time attribution** (``attribute_query``): each query's wall-clock
  decomposed over fixed categories — parse/plan, compile, device
  execute, materialize, host staging, exchange, retry backoff — by
  walking the span tree with *exclusive* (self-time) accounting: a
  span's self time bills to its own category, or to its nearest
  categorized ancestor (so a staged sub-program's dispatch overhead
  bills to host_staging, not nowhere).  Whatever no span covers lands
  in an explicit ``residual_ms``, so categories + residual sum to the
  reported wall-clock BY CONSTRUCTION — the breakdown can never
  quietly overlap or undercount ("Query Processing on Tensor
  Computation Runtimes" attributes TCR cost the same way: compile
  amortization vs steady-state must be separable or the numbers lie).
- **Cross-run diff + gate** (``diff_runs`` / ``diff_times``): compare
  two runs query-by-query on *steady-state* time (wall minus compile
  minus retry backoff), ignore sub-threshold absolute deltas as noise,
  flag compile-count changes separately, and report added/removed
  queries.  ``tools/ndsreport.py diff A B --gate pct=10`` exits
  non-zero on regression, so CI and future bench rounds gate on it.
- **HTML report** (``render_html``): self-contained stdlib HTML —
  per-query stacked attribution bars, slowest-N table, metrics, and a
  stream-overlap timeline from the trace JSONL for throughput runs.

No new dependencies; everything here is stdlib + the repo's own JSON
shapes (README "Observability" documents them; ``tools/
check_trace_schema.py --summary`` validates them).
"""

from __future__ import annotations

import html as _html
import json
import os

# attribution categories, in display order (retry_backoff comes from
# the summary's retry accounting, straggler_wait from cross-rank span
# pairing in fleet runs — both not from this rank's spans; prefetch_wait
# is the chunked engine's device-waited-on-host stall, carved out of
# the host_staging window by its own spans; residual is computed)
CATEGORIES = ("parse_plan", "compile", "execute", "materialize",
              "host_staging", "prefetch_wait", "exchange",
              "straggler_wait", "retry_backoff")

# span name -> category (exact names; see README span catalogue)
_SPAN_CATEGORY = {
    "sql.parse": "parse_plan",
    "sql.plan": "parse_plan",
    "device.compile": "compile",
    "device.run": "execute",
    "device.materialize": "materialize",
    "stage.sub": "host_staging",
    "chunk.partial_agg": "host_staging",
    "chunk.reduce": "host_staging",
    "prefetch.wait": "prefetch_wait",
}

# summary files that live in run dirs but are not BenchReports
_IGNORE_BASENAMES = {"analysis.json", "bench_state.json"}


def span_category(name: str) -> str | None:
    cat = _SPAN_CATEGORY.get(name)
    if cat is None and name.startswith("exchange"):
        return "exchange"
    return cat


def is_report_basename(name: str) -> bool:
    """Whether a run-dir file name can be a BenchReport summary (the
    single place that decision lives — static_checks' fixture gate and
    load_summaries both use it). ``merged-*`` phase reports
    (utils/report.merge_incarnations) are DERIVED from the per-query
    summaries — ingesting them would double-bill every merged query —
    and ``*_queries.json`` files are resume journals
    (resilience/journal.QueryJournal), not reports."""
    return (name.endswith(".json") and name not in _IGNORE_BASENAMES
            and not name.startswith("merged-")
            and not name.endswith("_queries.json"))


# ---------------------------------------------------------- attribution

def _accumulate(node: dict, inherited: str | None, acc: dict) -> None:
    """Exclusive-time walk: each span's self time (dur minus direct
    children) bills to its own category, else to the nearest
    categorized ancestor, else nowhere (-> residual)."""
    cat = span_category(node.get("name", "")) or inherited
    kids = node.get("children") or []
    self_ms = (node.get("dur_ms") or 0.0) - sum(
        (k.get("dur_ms") or 0.0) for k in kids)
    if cat and self_ms > 0:
        acc[cat] += self_ms
    for k in kids:
        _accumulate(k, cat, acc)


def attribute_query(summary: dict) -> dict:
    """One BenchReport summary -> attribution row. Invariant:
    ``sum(categories.values()) + residual_ms == wall_ms`` exactly
    (residual is DEFINED as the difference — negative residual means
    span totals exceeded the bracket, a clock-skew signal worth seeing,
    not hiding)."""
    times = summary.get("queryTimes") or [0]
    wall_ms = float(times[-1])
    cats = {c: 0.0 for c in CATEGORIES}
    spans = summary.get("spans")
    if isinstance(spans, dict):
        _accumulate(spans, None, cats)
    cats["retry_backoff"] = float(
        summary.get("retry_backoff_s", 0.0)) * 1000.0
    counters = (summary.get("metrics") or {}).get("counters", {})
    status = summary.get("queryStatus") or ["Unknown"]
    row = {
        "query": summary.get("query", "?"),
        "status": status[-1],
        "start_time": summary.get("startTime"),
        "wall_ms": wall_ms,
        "categories": cats,
        "residual_ms": wall_ms - sum(cats.values()),
        "compiles": int(counters.get("compiles_total", 0)
                        + counters.get("recompiles_total", 0)),
        "retries": int(summary.get("retries", 0)),
    }
    mem = summary.get("memory")
    if isinstance(mem, dict) and "device_hwm_bytes" in mem:
        row["hwm_bytes"] = int(mem["device_hwm_bytes"])
    # scheduling decisions (engine/scheduler.py): which placement
    # served the query and how far the degradation ladder walked
    if "placement" in summary:
        row["placement"] = str(summary["placement"])
        row["reschedules"] = int(summary.get("reschedules", 0))
        if summary.get("ladder"):
            row["ladder"] = list(summary["ladder"])
        if summary.get("promoted_back"):
            row["promoted_back"] = True
    # plan-cache activity (nds_tpu/cache/; README "Plan cache"):
    # hits/misses per query — absent when no cache was active, so
    # pre-cache run dirs analyze byte-identically
    cache = summary.get("cache")
    if isinstance(cache, dict) and "hits" in cache:
        row["cache_hits"] = int(cache.get("hits", 0))
        row["cache_misses"] = int(cache.get("misses", 0))
    # kernel use + roofline model (engine/kernels.py; README "Kernels
    # & roofline"): which relational kernels the compiled program ran
    # with, and the query's arithmetic intensity / bandwidth fraction
    if isinstance(summary.get("kernels"), dict):
        row["kernels"] = {str(k): int(v)
                          for k, v in summary["kernels"].items()}
    et = summary.get("engineTimings") or {}
    for k in ("ops_per_byte", "roofline_frac"):
        if isinstance(et.get(k), (int, float)):
            row[k] = float(et[k])
    # columnar compression (nds_tpu/columnar/): encoded bytes the
    # query actually scanned, plus the ratio vs raw when the
    # compressed store was active (absent rows keep pre-columnar run
    # dirs analyzing byte-identically)
    for k in ("bytes_scanned", "compression_ratio"):
        if isinstance(et.get(k), (int, float)):
            row[k] = float(et[k])
    # writable-warehouse deltas (nds_tpu/columnar/delta.py): how many
    # append-only segments and masked (deleted) rows rode under the
    # tables this query scanned. Absent on delta-free runs, so
    # pre-maintenance run dirs keep analyzing byte-identically
    for k in ("delta_segments", "delta_appended_rows",
              "delta_masked_rows"):
        if isinstance(et.get(k), (int, float)):
            row[k] = int(et[k])
    # pipelined execution (engine/pipeline_io.py): host staging time
    # the prefetch overlapped under compute, and the derived device
    # occupancy (1 - prefetch_wait/wall — what fraction of the query's
    # wall the device was NOT stalled on host staging). Absent on
    # pre-pipeline runs, so old dirs keep analyzing byte-identically
    if isinstance(et.get("prefetch_hidden_s"), (int, float)):
        row["prefetch_hidden_s"] = float(et["prefetch_hidden_s"])
    if cats["prefetch_wait"] > 0 or "prefetch_hidden_s" in row:
        row["occupancy"] = (round(1.0 - cats["prefetch_wait"] / wall_ms,
                                  4) if wall_ms > 0 else 1.0)
    # on-demand XLA capture (obs/profile.py; README "Fleet &
    # profiling"): which trigger fired and where the capture landed
    prof = summary.get("profile")
    if isinstance(prof, dict) and prof.get("path"):
        row["profile"] = {"trigger": str(prof.get("trigger", "query")),
                          "path": str(prof["path"])}
    # compiler-truth cost ledger (obs/costs.py): the query's summed
    # XLA flops/bytes, the roofline-model predicted time against the
    # recorded platform's peaks, and the achieved fraction (predicted
    # over measured execute — how close the run came to the model's
    # ceiling). Absent on pre-cost run dirs, which keep analyzing
    # byte-identically
    cost = summary.get("cost")
    if isinstance(cost, dict) and isinstance(cost.get("programs"),
                                             dict):
        row["cost"] = dict(cost)
        from nds_tpu.obs import costs as _costs
        pred = _costs.predicted_ms(cost)
        if pred is not None:
            row["predicted_ms"] = round(pred, 3)
            measured = (cats["execute"] if cats["execute"] > 0
                        else wall_ms - cats["compile"]
                        - cats["retry_backoff"])
            if measured > 0:
                row["achieved_frac"] = round(pred / measured, 4)
    # HBM occupancy telemetry (obs/telemetry.py): series shape summary
    tl = summary.get("telemetry")
    if isinstance(tl, dict) and tl.get("samples"):
        row["telemetry_samples"] = int(tl["samples"])
        hbm = tl.get("hbm") or {}
        if isinstance(hbm.get("max_bytes"), (int, float)):
            row["hbm_max_bytes"] = int(hbm["max_bytes"])
    return row


def _quantiles(samples: list) -> dict:
    """Nearest-rank p50/p95/p99 over a sample list ({} when empty) —
    the serving layer's per-tenant latency summary."""
    s = sorted(samples)
    if not s:
        return {}
    n = len(s)
    return {f"p{q}": round(
        s[min(n - 1, max(0, (q * n + 99) // 100 - 1))], 3)
        for q in (50, 95, 99)}


def steady_ms(row: dict) -> float:
    """Steady-state time: wall minus compile minus retry backoff — the
    quantity the regression gate compares (compile-count changes are
    flagged separately; a run that merely recompiled more is a
    different finding than one whose execution got slower)."""
    return (row["wall_ms"] - row["categories"]["compile"]
            - row["categories"]["retry_backoff"])


# ------------------------------------------------------------ ingestion

def load_summaries(run_dir: str) -> list[dict]:
    """Every BenchReport JSON under ``run_dir`` (recursive), in
    startTime order. Non-report JSONs (journals, analysis output,
    unparseable files) are skipped silently — run dirs are shared."""
    out = []
    for root, _dirs, files in os.walk(run_dir):
        for fname in sorted(files):
            if not is_report_basename(fname):
                continue
            try:
                with open(os.path.join(root, fname)) as f:
                    obj = json.load(f)
            except (OSError, ValueError):
                continue
            if (isinstance(obj, dict) and "queryStatus" in obj
                    and "query" in obj):
                out.append(obj)
    out.sort(key=lambda s: (s.get("startTime") or 0))
    return out


def load_trace_events(run_dir: str,
                      fleet_meta: "list[dict] | None" = None
                      ) -> list[dict]:
    """All Chrome trace events from ``*.jsonl`` files under
    ``run_dir`` (the power loop's NDS_TPU_TRACE export). When the run
    dir carries fleet sidecars (``fleet-r<rank>.json``, obs/fleet.py),
    each rank shard's timestamps are CLOCK-ALIGNED onto rank 0's
    timeline by subtracting that rank's handshake offset — the merge
    that makes one fleet timeline out of per-host clocks."""
    offsets_us: dict[str, float] = {}
    for meta in fleet_meta or []:
        shard = meta.get("trace_shard")
        off = meta.get("boot_offset_s")
        if shard and meta.get("aligned") and off:
            offsets_us[str(shard)] = float(off) * 1e6
    events = []
    for root, _dirs, files in os.walk(run_dir):
        for fname in sorted(files):
            if not fname.endswith(".jsonl"):
                continue
            shift = offsets_us.get(fname, 0.0)
            try:
                with open(os.path.join(root, fname)) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            ev = json.loads(line)
                        except ValueError:
                            continue
                        if isinstance(ev, dict) and ev.get("ph") == "X":
                            if shift and isinstance(ev.get("ts"),
                                                    (int, float)):
                                ev["ts"] = ev["ts"] - shift
                            events.append(ev)
            except OSError:
                continue
    return events


# ------------------------------------------------------ fleet stragglers

def straggler_stats(events: list[dict]) -> dict:
    """Cross-rank pairing of per-query spans in a clock-aligned fleet
    trace: for every query that ran on 2+ ranks (pid = rank, the
    obs/fleet export contract), pair each rank's ARRIVAL at the
    executor (its first ``device.execute`` event inside the query
    span; the query span start as fallback) and derive the straggler
    shape: the collective program cannot complete anywhere before the
    LAST rank arrives, so per-rank wait = last_arrival - own_arrival,
    the slowest rank is the last to arrive, and the skew is the full
    arrive spread. Returns ``{query: {"wait_ms_by_rank": {rank: ms},
    "slowest_rank", "skew_ms"}}`` — queries appearing more than once
    on a rank are skipped (pairing instances across ranks would be
    guesswork)."""
    by_rank_q: dict = {}
    dev_by_rank: dict = {}
    for ev in events:
        if not isinstance(ev.get("ts"), (int, float)):
            continue
        if ev.get("name") == "query":
            q = (ev.get("args") or {}).get("query")
            if q:
                by_rank_q.setdefault(ev.get("pid"), {}).setdefault(
                    str(q), []).append(ev)
        elif ev.get("name") == "device.execute":
            dev_by_rank.setdefault(ev.get("pid"), []).append(ev)
    out: dict = {}
    queries = set()
    for qmap in by_rank_q.values():
        queries.update(qmap)
    for q in queries:
        arrivals: dict = {}
        for rank, qmap in by_rank_q.items():
            evs = qmap.get(q) or []
            if len(evs) != 1:
                continue
            ev = evs[0]
            t0, t1 = ev["ts"], ev["ts"] + ev.get("dur", 0.0)
            inside = [d["ts"] for d in dev_by_rank.get(rank, [])
                      if t0 <= d["ts"] <= t1]
            arrivals[rank] = min(inside) if inside else t0
        if len(arrivals) < 2:
            continue
        last = max(arrivals.values())
        slowest = max(arrivals, key=lambda r: arrivals[r])
        out[q] = {
            "wait_ms_by_rank": {r: round((last - t) / 1000.0, 3)
                                for r, t in arrivals.items()},
            "slowest_rank": slowest,
            "skew_ms": round((last - min(arrivals.values())) / 1000.0,
                             3),
        }
    return out


def merge_resumed(summaries: list[dict]) -> "tuple[list[dict], dict]":
    """Bill merged incarnations once: a resumed run
    (utils/power_core ``--resume``) can report the same query from two
    incarnations — the first process died in the window between
    writing the summary and appending the journal, and the resumed
    incarnation re-ran it. Keep the LATEST (incarnation, startTime)
    report per query, so totals/diffs never double-count; returns
    (summaries, {query: dropped_count}). Runs that never resumed
    (every ``incarnation`` is 0 or absent — including multi-stream
    throughput dirs, whose repeated names are legitimate separate
    executions) pass through untouched."""
    if not any((s.get("incarnation") or 0) > 0 for s in summaries
               if isinstance(s.get("incarnation"), int)):
        return summaries, {}
    out: list = []
    best: dict = {}
    dropped: dict = {}
    for s in summaries:
        if not isinstance(s.get("incarnation"), int):
            out.append(s)  # not journal-stamped: leave it alone
            continue
        q = str(s.get("query"))
        key = (s["incarnation"], s.get("startTime") or 0)
        cur = best.get(q)
        if cur is None:
            best[q] = (key, s)
        else:
            dropped[q] = dropped.get(q, 0) + 1
            if key > cur[0]:
                best[q] = (key, s)
    out.extend(s for _k, s in best.values())
    out.sort(key=lambda s: (s.get("startTime") or 0))
    return out, dropped


def _dedupe_names(rows: list[dict]) -> None:
    """Throughput dirs repeat query names across streams; suffix
    repeats (#2, #3...) so per-name maps stay lossless. Suffixes are
    assigned by wall-clock RANK, not arrival order: stream-scheduling
    jitter must not re-label instances between two runs, or diff_runs
    would pair mismatched instances and report phantom regressions —
    rank pairing compares fastest-to-fastest, slowest-to-slowest."""
    groups: dict[str, list] = {}
    for row in rows:
        groups.setdefault(row["query"], []).append(row)
    for name, g in groups.items():
        if len(g) > 1:
            ranked = sorted(g, key=lambda r: (r["wall_ms"],
                                              r["start_time"] or 0))
            for i, row in enumerate(ranked[1:], 2):
                row["query"] = f"{name}#{i}"


def analyze_run(run_dir: str, with_trace: bool = True) -> dict:
    """Full run analysis: attribution rows, category totals, slowest-N,
    run-level metric aggregates, and trace events for the timeline.
    ``with_trace=False`` skips parsing the (potentially huge) trace
    JSONL — the diff gate only needs the BenchReport-derived rows
    (fleet dirs then also skip straggler attribution, which needs the
    merged shards)."""
    summaries = load_summaries(run_dir)
    if not summaries:
        raise ValueError(f"no BenchReport JSONs under {run_dir!r}")
    # resumed runs: bill each merged-incarnation query exactly once
    # (the same latest-incarnation-wins rule the merged phase report
    # applies, utils/report.merge_incarnations)
    summaries, merged_dropped = merge_resumed(summaries)
    rows = [attribute_query(s) for s in summaries]
    _dedupe_names(rows)
    # fleet runs (obs/fleet.py sidecars): merge the per-rank shards
    # onto one clock-aligned timeline and re-bill the recording rank's
    # execute time that was really WAITING on the slowest rank into
    # the straggler_wait category. The move is execute -> straggler,
    # so categories + residual still sum to wall-clock by construction
    from nds_tpu.obs import fleet as _fleet
    fleet_meta = _fleet.load_fleet(run_dir)
    events = (load_trace_events(run_dir, fleet_meta) if with_trace
              else [])
    fleet_info = None
    if fleet_meta:
        fleet_info = {
            "world": max(m.get("world", 1) for m in fleet_meta),
            "ranks": [{k: m.get(k) for k in
                       ("rank", "host", "pid", "boot_offset_s",
                        "aligned", "trace_shard")}
                      for m in fleet_meta],
        }
    if (fleet_info and fleet_info["world"] > 1 and events
            and all(m.get("aligned") for m in fleet_meta)):
        # an unaligned fleet (failed handshake) still merges, but
        # arrival pairing against skewed clocks would invent
        # stragglers — attribution needs the aligned timeline
        strag = straggler_stats(events)
        # summaries come from the primary (rank 0) recorder: its wait
        # on the fleet's slowest rank is what re-bills
        for row in rows:
            s = strag.get(row["query"])
            if not s:
                continue
            wait = float(s["wait_ms_by_rank"].get(0, 0.0))
            wait = max(0.0, min(wait, row["categories"]["execute"]))
            row["categories"]["straggler_wait"] = wait
            row["categories"]["execute"] -= wait
            row["straggler"] = {"skew_ms": s["skew_ms"],
                                "slowest_rank": s["slowest_rank"]}
    totals = {c: 0.0 for c in CATEGORIES}
    residual = 0.0
    for row in rows:
        for c in CATEGORIES:
            totals[c] += row["categories"][c]
        residual += row["residual_ms"]
    counters: dict = {}
    hists: dict = {}
    for s in summaries:
        m = s.get("metrics") or {}
        for name, v in m.get("counters", {}).items():
            counters[name] = counters.get(name, 0) + v
        for name, h in m.get("histograms", {}).items():
            agg = hists.setdefault(name, {"count": 0, "sum": 0.0})
            agg["count"] += h.get("count", 0)
            agg["sum"] += h.get("sum", 0.0)
            # quantiles are point-in-time: keep the latest reported
            agg.update({k: h[k] for k in ("p50", "p95", "p99")
                        if k in h})
    out = {
        "run_dir": os.path.abspath(run_dir),
        "queries": rows,
        "totals": {"wall_ms": sum(r["wall_ms"] for r in rows),
                   "categories": totals, "residual_ms": residual},
        "slowest": [r["query"] for r in sorted(
            rows, key=lambda r: -r["wall_ms"])],
        "failed": [r["query"] for r in rows
                   if r["status"] != "Completed"],
        "metrics": {"counters": counters, "histograms": hists},
        "trace_events": events,
    }
    # serving runs (nds_tpu/serve/): per-tenant request latency
    # quantiles over the per-request summaries' wall clocks
    tenant_walls: dict = {}
    for s in summaries:
        t = s.get("tenant")
        if t and s.get("queryTimes"):
            tenant_walls.setdefault(t, []).append(
                float(s["queryTimes"][-1]))
    if tenant_walls:
        out["tenants"] = {
            t: {"requests": len(walls),
                **{f"{q}_ms": v
                   for q, v in _quantiles(walls).items()}}
            for t, walls in sorted(tenant_walls.items())}
    # fleet serving runs (nds_tpu/serve/fleet.py): the same rollup
    # keyed by replica, plus divergence flagging — one replica whose
    # tail is far off the fleet's is a sick member (thermal, noisy
    # neighbor, wedged cache), not a workload property
    replica_walls: dict = {}
    for s in summaries:
        rep = s.get("replica")
        if rep and s.get("queryTimes"):
            replica_walls.setdefault(rep, []).append(
                float(s["queryTimes"][-1]))
    if replica_walls:
        reps = {
            rep: {"requests": len(walls),
                  **{f"{q}_ms": v
                     for q, v in _quantiles(walls).items()}}
            for rep, walls in sorted(replica_walls.items())}
        p99s = sorted(d["p99_ms"] for d in reps.values())
        fleet_median_p99 = p99s[len(p99s) // 2]
        for d in reps.values():
            if fleet_median_p99 > 0 and (
                    d["p99_ms"] > 2.0 * fleet_median_p99):
                d["outlier"] = True
        out["replicas"] = reps
        out["fleet_median_p99_ms"] = fleet_median_p99
    # banked/stale metrics must never flow silently into analysis
    # consumers (ROADMAP item 2): surface the marker loudly; ndsreport
    # diff refuses to gate on it
    stale = [s.get("query") or s.get("filename", "?")
             for s in summaries if s.get("stale_device_times")]
    if stale:
        out["stale_device_times"] = stale
    if merged_dropped:
        out["merged_incarnations"] = merged_dropped
    incs = [s.get("incarnation") for s in summaries
            if isinstance(s.get("incarnation"), int)]
    if incs and max(incs) > 0:
        out["incarnations"] = max(incs) + 1
    if fleet_info:
        out["fleet"] = fleet_info
    return out


# ------------------------------------------------------------- CLI text

def format_attribution(analysis: dict, top: int | None = None) -> str:
    """Fixed-width per-query attribution table (the ``ndsreport
    analyze`` stdout contract): categories + residual per query, sum
    column provably equal to wall-clock."""
    short = {"parse_plan": "parse", "compile": "compile",
             "execute": "exec", "materialize": "mat",
             "host_staging": "stage", "prefetch_wait": "pfwait",
             "exchange": "exch", "straggler_wait": "stragl",
             "retry_backoff": "retry"}
    rows = analysis["queries"]
    if top:
        order = {q: i for i, q in enumerate(analysis["slowest"])}
        rows = sorted(rows, key=lambda r: order[r["query"]])[:top]
    w = max([len(r["query"]) for r in rows] + [5])
    has_placement = any("placement" in r for r in rows)
    has_cache = any("cache_hits" in r for r in rows)
    has_roofline = any("ops_per_byte" in r or "roofline_frac" in r
                       for r in rows)
    has_bytes = any("bytes_scanned" in r for r in rows)
    has_delta = any("delta_segments" in r for r in rows)
    has_profile = any("profile" in r for r in rows)
    has_occup = any("occupancy" in r for r in rows)
    has_cost = any("cost" in r for r in rows)
    cols = list(CATEGORIES) + ["residual", "wall"]
    head = (f"{'query':<{w}} " + " ".join(
        f"{short.get(c, c):>9}" for c in cols)
        + ("  placement" if has_placement else "")
        + ("  cache" if has_cache else "")
        + ("   roofline" if has_roofline else "")
        + ("         bytes" if has_bytes else "")
        + ("        delta" if has_delta else "")
        + ("  occup" if has_occup else "")
        + ("  predicted  achieved" if has_cost else "")
        + ("  profile" if has_profile else "") + "  status")
    lines = [head, "-" * len(head)]
    for r in rows:
        vals = [r["categories"][c] for c in CATEGORIES]
        vals += [r["residual_ms"], r["wall_ms"]]
        place = ""
        if has_placement:
            p = r.get("placement", "?")
            if r.get("reschedules"):
                p += f"(+{r['reschedules']})"
            place = f"  {p:>9}"
        cache_col = ""
        if has_cache:
            if "cache_hits" in r:
                # hit when every consult hit; miss when any compile
                # fell through; "err" when the block exists with zero
                # consults (fingerprint failure — attach_cache only
                # emits an all-zero block when errors moved); "-" for
                # queries the cache never saw
                hits, misses = r["cache_hits"], r["cache_misses"]
                verdict = ("err" if not hits and not misses else
                           "hit" if misses == 0 else
                           "miss" if hits == 0 else "part")
            else:
                verdict = "-"
            cache_col = f"  {verdict:>5}"
        roof_col = ""
        if has_roofline:
            # "<ops/byte>@<bandwidth fraction>": distance from the
            # roofline — a LOW ops/byte at a LOW fraction means the
            # query moves bytes it barely computes on (README "Kernels
            # & roofline" reads this column)
            ob = r.get("ops_per_byte")
            rf = r.get("roofline_frac")
            cell = ("-" if ob is None and rf is None else
                    (f"{ob:.2f}" if ob is not None else "?")
                    + "@"
                    + (f"{rf * 100.0:.0f}%" if rf is not None else "?"))
            roof_col = f"  {cell:>9}"
        bytes_col = ""
        if has_bytes:
            # encoded scan bytes + compression ratio ("1.9M x5.0"):
            # how much the columnar store shrank this query's HBM
            # traffic (README "Compressed columnar store")
            bs = r.get("bytes_scanned")
            cell = "-" if bs is None else _fmt_bytes(bs)
            cr = r.get("compression_ratio")
            if cr is not None:
                cell += f" x{cr:.1f}"
            bytes_col = f"  {cell:>12}"
        delta_col = ""
        if has_delta:
            # delta state under the query's scanned tables:
            # "<segments>s +<appended> -<masked>" — a nonzero cell
            # means the query ran over a mutated warehouse without a
            # re-encode (README "Writable warehouse")
            if "delta_segments" in r:
                cell = (f"{r['delta_segments']}s "
                        f"+{r.get('delta_appended_rows', 0)} "
                        f"-{r.get('delta_masked_rows', 0)}")
            else:
                cell = "-"
            delta_col = f"  {cell:>12}"
        occup_col = ""
        if has_occup:
            # device occupancy under pipelined execution: 100% means
            # the device never waited on host chunk staging (README
            # "Pipelined execution")
            occ = r.get("occupancy")
            occup_col = ("  {:>5}".format(
                f"{occ * 100.0:.0f}%" if occ is not None else "-"))
        cost_col = ""
        if has_cost:
            # compiler-truth roofline model: predicted execute time
            # (flops/bytes against the platform's peaks) and the
            # achieved fraction of that ceiling — a LOW fraction means
            # the query left the modeled hardware idle (README "Cost
            # ledger & telemetry")
            pm = r.get("predicted_ms")
            af = r.get("achieved_frac")
            cost_col = ("  {:>9}  {:>8}".format(
                f"{pm:.1f}ms" if pm is not None else "-",
                f"{af * 100.0:.0f}%" if af is not None else "-"))
        prof_col = ""
        if has_profile:
            prof_col = ("  {:>7}".format(
                r["profile"]["trigger"] if "profile" in r else "-"))
        lines.append(
            f"{r['query']:<{w}} "
            + " ".join(f"{v:>9.1f}" for v in vals)
            + place + cache_col + roof_col + bytes_col + delta_col
            + occup_col + cost_col + prof_col + f"  {r['status']}")
    t = analysis["totals"]
    tvals = [t["categories"][c] for c in CATEGORIES]
    tvals += [t["residual_ms"], t["wall_ms"]]
    lines.append("-" * len(head))
    lines.append(f"{'TOTAL':<{w}} "
                 + " ".join(f"{v:>9.1f}" for v in tvals) + "  (ms)")
    if analysis.get("incarnations"):
        note = f"resumed run: {analysis['incarnations']} incarnations"
        md = analysis.get("merged_incarnations")
        if md:
            note += (", merged (billed once): "
                     + ", ".join(f"{q} (x{n + 1})"
                                 for q, n in sorted(md.items())))
        lines.append(note)
    fl = analysis.get("fleet")
    if fl:
        ranks = ", ".join(
            f"r{r.get('rank')}@{r.get('host')}"
            f"{'' if r.get('aligned') else ' (UNALIGNED)'}"
            for r in fl.get("ranks", []))
        lines.append(f"fleet: {fl.get('world')} rank(s): {ranks}")
        # ALL rows, not the top-N slice: the worst-skew query need
        # not be among the slowest by wall-clock
        blamed = [(r["query"], r["straggler"])
                  for r in analysis["queries"]
                  if r.get("straggler")]
        for q, s in sorted(blamed,
                           key=lambda e: -e[1]["skew_ms"])[:5]:
            lines.append(f"  straggler {q}: rank "
                         f"{s['slowest_rank']} arrived last "
                         f"(skew {s['skew_ms']:.1f} ms)")
    return "\n".join(lines)


# ------------------------------------------------------------ diff/gate

def parse_gate(spec: str | None) -> dict:
    """``pct=10`` / ``pct=10,abs_ms=50`` -> thresholds dict.  A delta
    must exceed BOTH the relative and the absolute floor to count —
    that's the noise model (sub-threshold absolute wobble on fast
    queries must not fail a gate). ``cost_pct`` is the COST-DRIFT
    threshold: compiler flops/bytes for an unchanged query moving by
    more than this fails the gate even when wall-clock noise hides
    the regression (compiler numbers are deterministic — their noise
    floor is ~0, so the default can be generous and still be a
    tripwire)."""
    gate = {"pct": 10.0, "abs_ms": 50.0, "cost_pct": 25.0}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        if key not in gate:
            raise ValueError(f"unknown gate key {key!r} "
                             f"(known: {sorted(gate)})")
        gate[key] = float(val)
    return gate


def diff_times(base: dict, cur: dict, pct: float = 10.0,
               abs_ms: float = 50.0) -> dict:
    """Core noise-aware comparison over two {name: ms} maps (the same
    code path gates fixture run-dirs in CI and the round bench's
    per-query block). Regression: cur exceeds base by BOTH >pct% and
    >=abs_ms. Symmetric for improvements; everything else is noise."""
    regressions, improvements, noise = [], [], []
    for name in sorted(set(base) & set(cur)):
        b, c = float(base[name]), float(cur[name])
        d = c - b
        entry = {"query": name, "base_ms": round(b, 3),
                 "cur_ms": round(c, 3), "delta_ms": round(d, 3),
                 "pct": round(d / b * 100.0, 2) if b > 0 else None}
        # a zero/negative baseline (clock-skew steady-state, zeroed
        # BASELINE entry) makes the relative test vacuous: any growth
        # past the absolute floor is then a regression, not noise
        if d >= abs_ms and (b <= 0 or c > b * (1 + pct / 100.0)):
            regressions.append(entry)
        elif -d >= abs_ms and b > 0 and c < b * (1 - pct / 100.0):
            improvements.append(entry)
        else:
            noise.append(entry)
    regressions.sort(
        key=lambda e: -(e["pct"] if e["pct"] is not None
                        else float("inf")))
    improvements.sort(key=lambda e: (e["pct"] or 0))
    return {
        "regressions": regressions,
        "improvements": improvements,
        "noise": noise,
        "added": sorted(set(cur) - set(base)),
        "removed": sorted(set(base) - set(cur)),
        "gate": {"pct": pct, "abs_ms": abs_ms},
    }


# the slow-path kernels (engine/kernels.py catalog): a per-query
# increase in these counts between runs is a DEMOTION — the planner
# (or a feasibility check) silently dropped the query off the fast
# kernels — and fails the diff gate like a removed query does
SLOW_KERNELS = ("join.sortmerge", "semi.sortmerge", "agg.scatter")


def _slow_uses(row: dict) -> int:
    kern = row.get("kernels") or {}
    return sum(int(kern.get(k, 0)) for k in SLOW_KERNELS)


def kernel_changes(base_rows: dict, cur_rows: dict) -> list:
    """Per-query kernel-choice changes between two runs (the same
    mechanism as the compile-count flag): any difference in the
    ``kernels`` block is reported; entries whose slow-path use COUNT
    grew carry ``demoted: True`` and fail the gate. Queries with no
    kernel block on either side (pre-kernel run dirs) are skipped, so
    old fixtures keep diffing byte-identically — and a side MISSING
    the block entirely (a baseline recorded before the kernel layer
    existed) is flagged as a change but never as a demotion: the gate
    must not hard-fail the first diff across the feature boundary
    when the absent counts merely read as zero."""
    out = []
    for name in sorted(set(base_rows) & set(cur_rows)):
        b, c = base_rows[name], cur_rows[name]
        bk, ck = b.get("kernels"), c.get("kernels")
        if bk is None and ck is None:
            continue
        if bk == ck:
            continue
        entry = {"query": name, "base": bk or {}, "cur": ck or {}}
        if (bk is not None and ck is not None
                and _slow_uses(c) > _slow_uses(b)):
            entry["demoted"] = True
        out.append(entry)
    return out


# absolute floor for the bytes_scanned gate: sub-MiB wobble (a reduced
# scan view flipping on a borderline survivor count) is noise, a MiB+
# growth is a real bandwidth regression
BYTES_ABS_FLOOR = 1 << 20


def bytes_changes(base_rows: dict, cur_rows: dict,
                  pct: float = 10.0) -> list:
    """Per-query ``bytes_scanned`` changes between two runs, gated the
    same way steady-state time is: a query whose scanned bytes grew by
    BOTH >pct% and >=1 MiB carries ``regressed: True`` and fails the
    diff — the engine is bandwidth-bound, so silently re-inflating the
    scan working set (an encoding demoted to raw, a reduced view lost)
    is a perf regression even when the fixture machine hid the time.
    Queries without the field on either side (pre-columnar run dirs)
    are skipped; a side MISSING it entirely is flagged but never
    fails the gate (first diff across the feature boundary)."""
    out = []
    for name in sorted(set(base_rows) & set(cur_rows)):
        b = base_rows[name].get("bytes_scanned")
        c = cur_rows[name].get("bytes_scanned")
        if b is None and c is None:
            continue
        if b == c:
            continue
        entry = {"query": name, "base_bytes": b, "cur_bytes": c}
        if (b is not None and c is not None
                and c - b >= BYTES_ABS_FLOOR
                and c > b * (1 + pct / 100.0)):
            entry["regressed"] = True
        out.append(entry)
    return out


# occupancy-regression threshold: the prefetch_wait SHARE of a query's
# wall rising by more than this many points between runs means the
# pipeline stopped hiding host staging (a lost overlap, a depth
# demotion gone sticky, a stage function that got slower) — flagged
# PIPELINE-STALLED and failed like a kernel demotion
STALL_SHARE_POINTS = 0.10


def _prefetch_share(row: dict) -> float:
    wall = row.get("wall_ms") or 0.0
    if wall <= 0:
        return 0.0
    return (row.get("categories", {}).get("prefetch_wait", 0.0)
            or 0.0) / wall


def pipeline_changes(base_rows: dict, cur_rows: dict) -> list:
    """Per-query prefetch-stall changes between two runs: entries only
    for queries where a side actually carried pipeline evidence
    (nonzero ``prefetch_wait`` or a ``prefetch_hidden_s`` field), so
    pre-pipeline run dirs keep diffing byte-identically. A query whose
    ``prefetch_wait`` share of wall-clock ROSE by more than
    ``STALL_SHARE_POINTS`` carries ``stalled: True`` and fails the
    gate."""
    out = []

    def _evidence(r) -> bool:
        return _prefetch_share(r) > 0 or "prefetch_hidden_s" in r

    for name in sorted(set(base_rows) & set(cur_rows)):
        b, c = base_rows[name], cur_rows[name]
        if not _evidence(b) and not _evidence(c):
            continue
        bs, cs = _prefetch_share(b), _prefetch_share(c)
        if abs(cs - bs) < 0.01:
            continue
        entry = {"query": name, "base_share": round(bs, 4),
                 "cur_share": round(cs, 4)}
        # the feature boundary never hard-fails (the kernel_changes /
        # bytes_changes precedent): a base recorded pre-pipeline — or
        # with prefetch off — has no occupancy claim to regress from
        if _evidence(b) and cs - bs > STALL_SHARE_POINTS:
            entry["stalled"] = True
        out.append(entry)
    return out


# absolute floor for the compiler-flops drift gate: a megaflop of
# movement on a tiny query is a constant-folding wobble, not a plan
# change worth failing CI over
FLOPS_ABS_FLOOR = 1e6


def cost_changes(base_rows: dict, cur_rows: dict,
                 pct: float = 25.0) -> list:
    """Per-query compiler-cost drift between two runs: entries for
    queries whose ``cost`` block flops or bytes_accessed moved, with
    ``drifted: True`` (gate failure) when either moved by BOTH >pct%
    and >= the absolute floor in EITHER direction — compiler numbers
    are deterministic for an unchanged query, so a swing either way
    means the compiled program changed, even when wall-clock noise
    hides it. Queries without the block on either side (pre-cost run
    dirs) are skipped; a side MISSING it entirely is flagged but
    never fails the gate (the kernel_changes / bytes_changes
    feature-boundary precedent)."""
    out = []
    for name in sorted(set(base_rows) & set(cur_rows)):
        b = base_rows[name].get("cost")
        c = cur_rows[name].get("cost")
        if b is None and c is None:
            continue
        moved = False
        drifted = False
        entry: dict = {"query": name}
        for key, floor in (("flops", FLOPS_ABS_FLOOR),
                           ("bytes_accessed", BYTES_ABS_FLOOR)):
            bv = (b or {}).get(key)
            cv = (c or {}).get(key)
            if bv == cv:
                continue
            moved = True
            entry[f"base_{key}"] = bv
            entry[f"cur_{key}"] = cv
            if (b is not None and c is not None
                    and isinstance(bv, (int, float))
                    and isinstance(cv, (int, float))
                    and abs(cv - bv) >= floor
                    and bv > 0
                    and abs(cv - bv) / bv > pct / 100.0):
                drifted = True
        if b is None or c is None:
            entry["missing"] = "base" if b is None else "cur"
            out.append(entry)
            continue
        if not moved:
            continue
        if drifted:
            entry["drifted"] = True
        out.append(entry)
    return out


def cache_hit_rate(analysis: dict) -> "dict | None":
    """Run-level plan-cache summary from the per-query rows:
    ``{"hits", "misses", "rate"}`` (rate = hits / consults), or None
    when no query carried a cache block (cache off — pre-cache run
    dirs keep diffing byte-identically)."""
    hits = misses = 0
    seen = False
    for r in analysis.get("queries", []):
        if "cache_hits" in r:
            seen = True
            hits += r["cache_hits"]
            misses += r["cache_misses"]
    if not seen:
        return None
    total = hits + misses
    return {"hits": hits, "misses": misses,
            "rate": round(hits / total, 4) if total else None}


# the 11 maintenance refresh functions (nds/maintenance.py INSERT/
# DELETE/INVENTORY_DELETE_FUNCS — listed literally: this module stays
# importable without the engine stack). Their per-function BenchReport
# summaries land in run dirs like query reports do, but they are DML:
# the steady-state decomposition doesn't apply, so they diff on FULL
# refresh wall-clock under their own MAINT-REGRESSED gate — the TPC
# metric charges Tdm for exactly this time
MAINT_FUNCS = frozenset((
    "LF_CR", "LF_CS", "LF_I", "LF_SR", "LF_SS", "LF_WR", "LF_WS",
    "DF_CS", "DF_SS", "DF_WS", "DF_I"))


def _is_maint_fn(name: str) -> bool:
    return name.partition("#")[0] in MAINT_FUNCS


def maint_changes(base_rows: dict, cur_rows: dict, pct: float = 10.0,
                  abs_ms: float = 50.0) -> list:
    """Per-function refresh-time changes between two runs holding
    maintenance summaries: the same noise model as steady-state time
    but over FULL wall-clock (DML has no compile/steady split worth
    separating), with ``regressed: True`` failing the gate. A function
    present in base but MISSING from cur also fails — a refresh
    function that vanished is strictly worse than one that got slower.
    Runs with no maintenance summaries on either side emit nothing, so
    query-only run dirs keep diffing byte-identically."""
    b = {q: r["wall_ms"] for q, r in base_rows.items()
         if _is_maint_fn(q)}
    c = {q: r["wall_ms"] for q, r in cur_rows.items()
         if _is_maint_fn(q)}
    if not b and not c:
        return []
    d = diff_times(b, c, pct=pct, abs_ms=abs_ms)
    out = []
    for e in d["regressions"]:
        out.append({**e, "regressed": True})
    out += d["improvements"]
    for q in d["removed"]:
        out.append({"query": q, "removed": True, "regressed": True})
    for q in d["added"]:
        out.append({"query": q, "added": True})
    return out


def diff_runs(base: dict, cur: dict, pct: float = 10.0,
              abs_ms: float = 50.0, cost_pct: float = 25.0) -> dict:
    """Query-by-query diff of two ``analyze_run`` results, gated on
    STEADY-STATE time; compile-count and compile-time changes are
    reported in their own ``compile_changes`` list so a recompile
    shows up as what it is, not as an execution regression.  The gate
    fails (``passed=False``) on any steady-state regression or any
    removed query (a query that vanished is strictly worse than one
    that got slower)."""
    b_rows = {r["query"]: r for r in base["queries"]}
    c_rows = {r["query"]: r for r in cur["queries"]}
    # maintenance refresh functions gate on their own wall-clock
    # (MAINT-REGRESSED) and leave the query-side comparisons — a
    # refresh summary has no kernels/bytes/cost surface to diff
    mchanges = maint_changes(b_rows, c_rows, pct=pct, abs_ms=abs_ms)
    maint_regressed = [e["query"] for e in mchanges
                       if e.get("regressed")]
    b_rows = {q: r for q, r in b_rows.items() if not _is_maint_fn(q)}
    c_rows = {q: r for q, r in c_rows.items() if not _is_maint_fn(q)}
    d = diff_times({q: steady_ms(r) for q, r in b_rows.items()},
                   {q: steady_ms(r) for q, r in c_rows.items()},
                   pct=pct, abs_ms=abs_ms)
    compile_changes = []
    for name in sorted(set(b_rows) & set(c_rows)):
        b, c = b_rows[name], c_rows[name]
        if (b["compiles"] != c["compiles"]
                or abs(b["categories"]["compile"]
                       - c["categories"]["compile"]) >= abs_ms):
            compile_changes.append({
                "query": name,
                "base_compiles": b["compiles"],
                "cur_compiles": c["compiles"],
                "base_compile_ms": round(b["categories"]["compile"], 3),
                "cur_compile_ms": round(c["categories"]["compile"], 3),
            })
    newly_failed = sorted(
        set(cur.get("failed", [])) - set(base.get("failed", [])))
    # kernel-choice changes (engine/kernels.py): flagged like compile
    # counts; a slow-path DEMOTION fails the gate — a planner
    # regression that quietly re-sorts q21 must not pass just because
    # the fixture machine was fast that day
    kchanges = kernel_changes(b_rows, c_rows)
    demoted = [e["query"] for e in kchanges if e.get("demoted")]
    # bytes_scanned regressions gate like steady-state time: the
    # roofline says these queries are bandwidth-bound, so scanned
    # bytes ARE a perf surface (README "Compressed columnar store")
    bchanges = bytes_changes(b_rows, c_rows, pct=pct)
    bytes_regressed = [e["query"] for e in bchanges
                       if e.get("regressed")]
    # occupancy regressions (engine/pipeline_io.py): a prefetch_wait
    # share rising >STALL_SHARE_POINTS means the pipeline stopped
    # hiding host staging — PIPELINE-STALLED fails the gate; run dirs
    # with no pipeline evidence on either side emit nothing here
    pchanges = pipeline_changes(b_rows, c_rows)
    stalled = [e["query"] for e in pchanges if e.get("stalled")]
    # compiler-cost drift (obs/costs.py): deterministic flops/bytes
    # moving >cost_pct for an unchanged query is a plan/program change
    # — COST-DRIFT fails the gate even when wall-clock noise hides it
    cchanges = cost_changes(b_rows, c_rows, pct=cost_pct)
    cost_drifted = [e["query"] for e in cchanges if e.get("drifted")]
    d["gate"]["cost_pct"] = cost_pct
    d.update({
        "base_dir": base.get("run_dir"),
        "cur_dir": cur.get("run_dir"),
        "compile_changes": compile_changes,
        "kernel_changes": kchanges,
        "bytes_changes": bchanges,
        "pipeline_changes": pchanges,
        "cost_changes": cchanges,
        "maint_changes": mchanges,
        "newly_failed": newly_failed,
        "passed": not d["regressions"] and not d["removed"]
                  and not newly_failed and not demoted
                  and not bytes_regressed and not stalled
                  and not cost_drifted and not maint_regressed,
    })
    # plan-cache hit-rate per run, the compile-count-change flag's
    # natural companion: a run whose compile counts dropped to 0
    # should show a warm cache explaining WHY (README "Plan cache").
    # Only when a side actually carried a cache block — pre-cache run
    # dirs keep diffing byte-identically
    chr_base, chr_cur = cache_hit_rate(base), cache_hit_rate(cur)
    if chr_base is not None or chr_cur is not None:
        d["cache_hit_rate"] = {"base": chr_base, "cur": chr_cur}
    # banked/stale device times are not comparable evidence: a diff
    # over them must FAIL loudly, never gate-pass on numbers nobody
    # measured this run
    stale = {side: a["stale_device_times"]
             for side, a in (("base", base), ("cur", cur))
             if a.get("stale_device_times")}
    if stale:
        d["stale_device_times"] = stale
        d["passed"] = False
    return d


def format_diff(d: dict) -> str:
    lines = [f"gate: >{d['gate']['pct']:g}% and "
             f">={d['gate']['abs_ms']:g} ms (steady-state)"]
    for label, key, sign in (("REGRESSION", "regressions", "+"),
                             ("improvement", "improvements", "")):
        for e in d[key]:
            rel = ("n/a" if e["pct"] is None
                   else f"{sign}{e['pct']:g}%")
            lines.append(
                f"  {label:<11} {e['query']:<14} "
                f"{e['base_ms']:>10.1f} -> {e['cur_ms']:>10.1f} ms "
                f"({rel})")
    for q in d["removed"]:
        lines.append(f"  REMOVED     {q}")
    for q in d.get("newly_failed", []):
        lines.append(f"  NEWLY-FAILED {q}")
    for q in d["added"]:
        lines.append(f"  added       {q}")
    for e in d["compile_changes"]:
        lines.append(
            f"  compile     {e['query']:<14} "
            f"{e['base_compiles']} compile(s)/"
            f"{e['base_compile_ms']:.0f} ms -> {e['cur_compiles']}/"
            f"{e['cur_compile_ms']:.0f} ms")
    for e in d.get("kernel_changes", []):
        def _mix(kern):
            return ",".join(f"{k}x{v}" for k, v in sorted(kern.items())) \
                or "none"
        label = "KERNEL-DEMOTED" if e.get("demoted") else "kernel"
        lines.append(
            f"  {label:<11} {e['query']:<14} "
            f"{_mix(e['base'])} -> {_mix(e['cur'])}")
    for e in d.get("bytes_changes", []):
        # widest label in this block is BYTES-REGRESSED (15): pad the
        # whole block to it so flagged rows don't shear the columns
        label = "BYTES-REGRESSED" if e.get("regressed") else "bytes"
        def _b(v):
            return "-" if v is None else _fmt_bytes(v)
        lines.append(
            f"  {label:<15} {e['query']:<14} "
            f"{_b(e['base_bytes'])} -> {_b(e['cur_bytes'])}")
    for e in d.get("pipeline_changes", []):
        # occupancy regression: the device's prefetch_wait share of
        # wall rose — the overlap stopped hiding host staging
        label = "PIPELINE-STALLED" if e.get("stalled") else "pipeline"
        lines.append(
            f"  {label:<16} {e['query']:<14} "
            f"stall share {e['base_share'] * 100.0:.0f}% -> "
            f"{e['cur_share'] * 100.0:.0f}%")
    for e in d.get("cost_changes", []):
        # compiler-cost drift: deterministic flops/bytes moved for an
        # unchanged query — the compiled program itself changed
        label = "COST-DRIFT" if e.get("drifted") else "cost"
        if e.get("missing"):
            lines.append(f"  {label:<11} {e['query']:<14} "
                         f"cost block missing on {e['missing']} side")
            continue
        parts = []
        for key, fmt in (("flops", "{:.3g}"),
                         ("bytes_accessed", None)):
            if f"base_{key}" in e or f"cur_{key}" in e:
                def _v(v, _fmt=fmt):
                    if v is None:
                        return "-"
                    return (_fmt.format(v) if _fmt
                            else _fmt_bytes(v))
                parts.append(f"{key} {_v(e.get(f'base_{key}'))} -> "
                             f"{_v(e.get(f'cur_{key}'))}")
        lines.append(f"  {label:<11} {e['query']:<14} "
                     + "; ".join(parts))
    for e in d.get("maint_changes", []):
        # per-function refresh wall-clock (the Tdm the TPC metric
        # charges): a regression here is a write-path slowdown even
        # when every query held steady
        label = "MAINT-REGRESSED" if e.get("regressed") else "maint"
        if e.get("removed"):
            lines.append(f"  {label:<15} {e['query']:<14} "
                         f"refresh function missing from cur run")
        elif e.get("added"):
            lines.append(f"  {label:<15} {e['query']:<14} "
                         f"refresh function new in cur run")
        else:
            rel = ("n/a" if e["pct"] is None else f"{e['pct']:+g}%")
            lines.append(
                f"  {label:<15} {e['query']:<14} "
                f"{e['base_ms']:>10.1f} -> {e['cur_ms']:>10.1f} ms "
                f"({rel})")
    chr_ = d.get("cache_hit_rate") or {}
    if any(chr_.get(k) for k in ("base", "cur")):
        def _rate(r):
            if not r:
                return "off"
            if r["rate"] is None:
                return "0 consults"
            return (f"{r['rate'] * 100.0:.0f}% "
                    f"({r['hits']}/{r['hits'] + r['misses']})")
        lines.append(f"  cache       hit-rate "
                     f"{_rate(chr_.get('base'))} -> "
                     f"{_rate(chr_.get('cur'))}")
    lines.append(f"  {len(d['noise'])} querie(s) within noise threshold")
    for side, names in d.get("stale_device_times", {}).items():
        lines.append(f"  STALE       {side}: banked device times "
                     f"({len(names)} summar"
                     f"{'y' if len(names) == 1 else 'ies'}) — not "
                     f"comparable evidence")
    lines.append("DIFF " + ("OK" if d["passed"] else "FAILED"))
    return "\n".join(lines)


# ----------------------------------------------------------------- HTML

# categorical slots (documented default palette, fixed order — the
# 7-slot adjacent sequence passes the CVD/normal-vision gates in both
# modes per the palette doc); residual wears neutral gray, not a
# series hue
_LIGHT = {"parse_plan": "#2a78d6", "compile": "#eb6834",
          "execute": "#1baf7a", "materialize": "#eda100",
          "host_staging": "#e87ba4", "prefetch_wait": "#0e8a9e",
          "exchange": "#008300",
          "straggler_wait": "#8a6d3b", "retry_backoff": "#4a3aa7",
          "residual": "#b9b8b3"}
_DARK = {"parse_plan": "#3987e5", "compile": "#d95926",
         "execute": "#199e70", "materialize": "#c98500",
         "host_staging": "#d55181", "prefetch_wait": "#23a9bf",
         "exchange": "#008300",
         "straggler_wait": "#b0905a", "retry_backoff": "#9085e9",
         "residual": "#6e6d69"}

_CSS = """
:root { color-scheme: light dark; }
body { font: 13px/1.45 system-ui, sans-serif; margin: 24px;
       background: #fcfcfb; color: #0b0b0b; }
h1 { font-size: 18px; } h2 { font-size: 15px; margin-top: 28px; }
table { border-collapse: collapse; margin: 8px 0; }
th, td { padding: 3px 10px; text-align: right;
         border-bottom: 1px solid #e4e3df; }
th { color: #52514e; font-weight: 600; }
td.q, th.q { text-align: left; font-family: ui-monospace, monospace; }
.bar { display: flex; width: 620px; height: 14px; gap: 2px; }
.bar span { display: block; height: 100%; border-radius: 3px;
            min-width: 0; }
.legend { display: flex; gap: 16px; flex-wrap: wrap; margin: 8px 0;
          color: #52514e; }
.legend i { display: inline-block; width: 10px; height: 10px;
            border-radius: 3px; margin-right: 5px; }
.lane { position: relative; height: 18px; margin: 3px 0;
        background: #f0efec; border-radius: 3px; }
.lane b { position: absolute; top: 2px; bottom: 2px;
          border-radius: 3px; opacity: 0.9; }
.muted { color: #52514e; }
%LIGHT%
@media (prefers-color-scheme: dark) {
  body { background: #1a1a19; color: #ffffff; }
  th { color: #c3c2b7; } th, td { border-color: #383835; }
  .legend { color: #c3c2b7; } .lane { background: #242423; }
  .muted { color: #c3c2b7; }
  %DARK%
}
"""


def _css_vars() -> str:
    light = " ".join(f".c-{k} {{ background: {v}; }}"
                     for k, v in _LIGHT.items())
    dark = " ".join(f".c-{k} {{ background: {v}; }}"
                    for k, v in _DARK.items())
    return _CSS.replace("%LIGHT%", light).replace("%DARK%", dark)


def _esc(s) -> str:
    return _html.escape(str(s))


def _bar(row: dict) -> str:
    wall = max(row["wall_ms"], 1e-9)
    segs = []
    parts = list(row["categories"].items())
    parts.append(("residual", max(row["residual_ms"], 0.0)))
    for cat, ms in parts:
        if ms <= 0:
            continue
        pct = 100.0 * ms / wall
        segs.append(
            f'<span class="c-{cat}" style="width:{pct:.2f}%" '
            f'title="{_esc(row["query"])} {cat}: {ms:.1f} ms '
            f'({pct:.1f}%)"></span>')
    return f'<div class="bar">{"".join(segs)}</div>'


def _legend() -> str:
    items = "".join(
        f'<span><i class="c-{c}"></i>{c}</span>'
        for c in list(CATEGORIES) + ["residual"])
    return f'<div class="legend">{items}</div>'


def _fmt_bytes(n) -> str:
    if n is None:
        return ""
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.0f} {unit}" if unit == "B" else f"{n:.1f} {unit}"
        n /= 1024


def _timeline(events: list[dict],
              fleet: "dict | None" = None) -> str:
    """Stream-overlap timeline: one lane per (pid, tid), one bar per
    root ``query`` event — concurrency (throughput streams) is visible
    as vertical overlap. Single-lane power runs render too (a gap map
    is still informative). Fleet runs (obs/fleet.py: pid = rank,
    shards clock-aligned at load) label each lane with its rank, so
    the per-rank lanes read as the fleet timeline."""
    qevents = [e for e in events if e.get("name") == "query"
               and isinstance(e.get("ts"), (int, float))]
    if not qevents:
        return ""
    ranks = {r.get("rank") for r in (fleet or {}).get("ranks", [])}
    t0 = min(e["ts"] for e in qevents)
    t1 = max(e["ts"] + e.get("dur", 0) for e in qevents)
    span_us = max(t1 - t0, 1.0)
    lanes: dict = {}
    for e in qevents:
        lanes.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    rows = []
    for i, (lane, evs) in enumerate(sorted(
            lanes.items(), key=lambda kv: (str(kv[0][0]),
                                           str(kv[0][1]))), 1):
        bars = "".join(
            f'<b class="c-execute" '
            f'style="left:{100.0 * (e["ts"] - t0) / span_us:.2f}%;'
            f'width:{max(100.0 * e.get("dur", 0) / span_us, 0.15):.2f}%"'
            f' title="{_esc(e.get("args", {}).get("query", "?"))}'
            f' {e.get("dur", 0) / 1000.0:.1f} ms"></b>'
            for e in sorted(evs, key=lambda e: e["ts"]))
        label = (f"rank {lane[0]}" if lane[0] in ranks
                 else f"stream {i}")
        rows.append(
            f'<div class="lane" title="{_esc(label)}">{bars}</div>')
    title = ("Fleet timeline (clock-aligned)" if ranks
             else "Stream overlap timeline")
    return (f"<h2>{title}</h2>"
            f'<p class="muted">{len(lanes)} lane(s), '
            f"{span_us / 1e6:.2f} s span; hover a bar for the query."
            f"</p>{''.join(rows)}")


def render_html(analysis: dict, diff: dict | None = None,
                top: int = 10) -> str:
    """Self-contained report (no external assets, stdlib only)."""
    t = analysis["totals"]
    out = [
        "<!DOCTYPE html><html><head><meta charset='utf-8'>",
        "<title>ndsreport</title>",
        f"<style>{_css_vars()}</style></head><body>",
        f"<h1>Run analysis — {_esc(analysis['run_dir'])}</h1>",
        f"<p class='muted'>{len(analysis['queries'])} quer(ies), "
        f"{t['wall_ms'] / 1000.0:.2f} s total wall-clock, "
        f"{len(analysis['failed'])} failed</p>",
    ]
    fleet = analysis.get("fleet")
    if fleet:
        ranks = ", ".join(
            f"rank {r.get('rank')} @ {_esc(r.get('host'))} "
            f"(offset {r.get('boot_offset_s', 0):+.3f} s"
            f"{'' if r.get('aligned') else ', UNALIGNED'})"
            for r in fleet.get("ranks", []))
        out.append(f"<p class='muted'>fleet: {fleet.get('world')} "
                   f"rank(s) — {ranks}</p>")
    out += [
        "<h2>Per-query time attribution</h2>", _legend(),
        "<table><tr><th class='q'>query</th><th>wall ms</th>"
        "<th>breakdown</th><th>residual ms</th><th>compiles</th>"
        "<th>cache</th><th>retries</th><th>placement</th>"
        "<th>kernels</th><th>roofline</th><th>bytes</th>"
        "<th>predicted</th><th>achieved</th>"
        "<th>straggler</th><th>profile</th>"
        "<th>mem HWM</th><th>status</th></tr>",
    ]
    for row in analysis["queries"]:
        place = row.get("placement", "")
        if row.get("ladder"):
            # the walked ladder is the interesting story: show the
            # whole path, not only where the query landed
            place = "&rarr;".join(_esc(r) for r in row["ladder"])
        elif place:
            place = _esc(place)
        if row.get("promoted_back"):
            place += " &uarr;"
        if "cache_hits" in row:
            cache = (f"{row['cache_hits']} hit / "
                     f"{row['cache_misses']} miss")
        else:
            cache = ""
        kern = ", ".join(
            f"{_esc(k)}&times;{v}"
            for k, v in sorted((row.get("kernels") or {}).items()))
        ob, rf = row.get("ops_per_byte"), row.get("roofline_frac")
        roof = ""
        if ob is not None or rf is not None:
            roof = ((f"{ob:.2f}" if ob is not None else "?") + " @ "
                    + (f"{rf * 100.0:.0f}%" if rf is not None else "?"))
        # encoded scan bytes + compression ratio (nds_tpu/columnar/)
        bcell = ""
        if row.get("bytes_scanned") is not None:
            bcell = _fmt_bytes(row["bytes_scanned"])
            if row.get("compression_ratio") is not None:
                bcell += f" &times;{row['compression_ratio']:.1f}"
        strag = ""
        if row.get("straggler"):
            s = row["straggler"]
            strag = (f"rank {_esc(s['slowest_rank'])} "
                     f"(+{s['skew_ms']:.1f} ms)")
        prof = ""
        if row.get("profile"):
            p = row["profile"]
            prof = (f"<span title='{_esc(p['path'])}'>"
                    f"{_esc(p['trigger'])}</span>")
        # predicted-vs-measured (obs/costs roofline model): blank on
        # pre-cost rows and on platforms without a peaks entry
        pred = ("" if row.get("predicted_ms") is None
                else f"{row['predicted_ms']:.1f} ms")
        ach = ("" if row.get("achieved_frac") is None
               else f"{row['achieved_frac'] * 100.0:.0f}%")
        out.append(
            f"<tr><td class='q'>{_esc(row['query'])}</td>"
            f"<td>{row['wall_ms']:.1f}</td><td>{_bar(row)}</td>"
            f"<td>{row['residual_ms']:.1f}</td>"
            f"<td>{row['compiles']}</td><td>{cache}</td>"
            f"<td>{row['retries']}</td>"
            f"<td>{place}</td>"
            f"<td class='q'>{kern}</td><td>{roof}</td>"
            f"<td>{bcell}</td>"
            f"<td>{pred}</td><td>{ach}</td>"
            f"<td>{strag}</td><td>{prof}</td>"
            f"<td>{_fmt_bytes(row.get('hwm_bytes'))}</td>"
            f"<td>{_esc(row['status'])}</td></tr>")
    out.append("</table>")
    out.append(f"<h2>Slowest {min(top, len(analysis['queries']))}</h2>")
    out.append("<table><tr><th class='q'>query</th><th>wall ms</th>"
               "<th>steady ms</th><th>compile ms</th></tr>")
    by_name = {r["query"]: r for r in analysis["queries"]}
    for q in analysis["slowest"][:top]:
        r = by_name[q]
        out.append(f"<tr><td class='q'>{_esc(q)}</td>"
                   f"<td>{r['wall_ms']:.1f}</td>"
                   f"<td>{steady_ms(r):.1f}</td>"
                   f"<td>{r['categories']['compile']:.1f}</td></tr>")
    out.append("</table>")
    if diff:
        out.append("<h2>Diff vs "
                   f"{_esc(diff.get('base_dir') or 'baseline')}</h2>")
        out.append(f"<pre>{_esc(format_diff(diff))}</pre>")
    m = analysis["metrics"]
    if m["counters"] or m["histograms"]:
        out.append("<h2>Metrics</h2>")
        out.append("<table><tr><th class='q'>counter</th>"
                   "<th>total</th></tr>")
        for name, v in sorted(m["counters"].items()):
            out.append(f"<tr><td class='q'>{_esc(name)}</td>"
                       f"<td>{v:g}</td></tr>")
        out.append("</table>")
        if m["histograms"]:
            out.append("<table><tr><th class='q'>histogram</th>"
                       "<th>count</th><th>sum</th><th>p50</th>"
                       "<th>p95</th><th>p99</th></tr>")
            for name, h in sorted(m["histograms"].items()):
                cells = "".join(
                    f"<td>{h.get(k):g}</td>" if h.get(k) is not None
                    else "<td></td>"
                    for k in ("count", "sum", "p50", "p95", "p99"))
                out.append(f"<tr><td class='q'>{_esc(name)}</td>"
                           f"{cells}</tr>")
            out.append("</table>")
    out.append(_timeline(analysis["trace_events"],
                         analysis.get("fleet")))
    out.append("</body></html>")
    return "".join(out)


# ------------------------------------------------------------ artifacts

def write_outputs(analysis: dict, out_dir: str,
                  diff: dict | None = None) -> dict:
    """Persist ``analysis.json`` + ``report.html`` into ``out_dir``;
    returns {kind: path}. Trace events stay out of the JSON (they are
    already on disk next to it)."""
    os.makedirs(out_dir, exist_ok=True)
    doc = {k: v for k, v in analysis.items() if k != "trace_events"}
    if diff:
        doc["diff"] = diff
    paths = {"analysis": os.path.join(out_dir, "analysis.json"),
             "report": os.path.join(out_dir, "report.html")}
    # atomic (NDS109): live dashboards poll analysis.json while runs
    # re-analyze; a torn read must be impossible
    from nds_tpu.io.integrity import write_json_atomic
    write_json_atomic(paths["analysis"], doc)
    # pid-suffixed tmp, same as write_json_atomic: two analyzers
    # re-analyzing one run dir must each rename a COMPLETE file
    tmp = f"{paths['report']}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        f.write(render_html(analysis, diff))
    os.replace(tmp, paths["report"])
    return paths
