"""CLI for the run-analysis layer (nds_tpu/obs/analyze.py).

Two verbs over run directories (a run dir = the folder a power or
throughput run wrote its per-query BenchReport JSONs into, plus any
Chrome-trace ``*.jsonl``):

  python tools/ndsreport.py analyze RUN_DIR [--out DIR] [--top N]
      Print the per-query time-attribution table (categories +
      residual sum to wall-clock by construction) and write
      ``analysis.json`` + self-contained ``report.html`` to --out
      (default: RUN_DIR).

  python tools/ndsreport.py diff BASE_DIR CUR_DIR [--gate pct=10,abs_ms=50,cost_pct=25]
      Query-by-query steady-state comparison with a noise-aware
      regression gate (plus the COST-DRIFT gate over compiler
      flops/bytes). Exit 0 when the gate passes, 1 on regression /
      removed query / newly-failed query — so CI and bench rounds can
      gate on it directly.

  python tools/ndsreport.py bank RUN_DIR [--out PATH]
      Mint a BENCH-record-shaped JSON mechanically from a run dir,
      stamped with provenance (platform, engine version, config
      digest, code_epoch, compiler cost totals) — BENCH_r06 is one
      command, not hand-rolled numbers (the r04/r05 rot class).
      REFUSES loudly when any summary carries ``stale_device_times``:
      exit 4 (a record minted from numbers nobody measured this run
      is exactly the rot this exists to stop); exit 5 when the dir
      has no completed measurements.

``self_check()`` is the tier-1 entry (tools/static_checks.py section
6): analyze + diff over the committed fixture run-dirs under
``tests/fixtures/`` — the attribution-sum invariant and both gate
verdicts are asserted against known-good data on every run.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from nds_tpu.obs import analyze  # noqa: E402

# bank refusal exit codes: a banked number must be a LOUD failure when
# its inputs were stale or absent, never a quietly-zero record
EXIT_STALE_BANK = 4
EXIT_NO_METRIC = 5

# engineConf keys that describe the live process, not the bench
# configuration — excluded from the banked config digest so the same
# config banks the same digest across hosts/device counts
_VOLATILE_CONF_KEYS = ("platform", "device_kind", "device_count")


def bank_record(run_dir: str) -> "tuple[dict | None, str]":
    """(record, error) for a run dir — record is None exactly when the
    dir must not bank (the error says why). Everything in the record
    is derived mechanically from the summaries ALREADY on disk: no
    live jax calls — banking a finished run must work from any host,
    and must name the device the run recorded, not the banker's."""
    import time

    from nds_tpu.cache.fingerprint import code_epoch
    from nds_tpu.resilience.journal import config_digest
    try:
        a = analyze.analyze_run(run_dir, with_trace=False)
    except ValueError as exc:
        return None, str(exc)
    if a.get("stale_device_times"):
        names = ", ".join(a["stale_device_times"])
        return None, (f"run dir carries banked/stale device times "
                      f"({names}) — refusing to mint a BENCH record "
                      f"from numbers nobody measured this run")
    rows = [r for r in a["queries"] if r["status"] == "Completed"]
    if not rows:
        return None, "no completed query summaries to bank"
    summaries = analyze.load_summaries(run_dir)
    env = (summaries[0].get("env") or {}) if summaries else {}
    conf = {k: v for k, v in (env.get("engineConf") or {}).items()
            if k not in _VOLATILE_CONF_KEYS}
    # platform: the cost blocks' device-kind stamp when the run
    # carried the cost ledger, else the recorded live platform
    platforms = sorted({r["cost"]["platform"] for r in rows
                       if isinstance(r.get("cost"), dict)
                       and r["cost"].get("platform")})
    provenance = {
        "platform": (platforms[0] if len(platforms) == 1
                     else (env.get("engineConf") or {}).get(
                         "platform", "unknown")),
        "engine_version": env.get("engineVersion") or "unknown",
        "config_digest": config_digest(conf),
        "code_epoch": code_epoch(),
        "banked_at": int(time.time()),
        "run_dir": a["run_dir"],
    }
    totals: dict = {}
    programs = 0
    with_cost = 0
    for r in rows:
        cost = r.get("cost")
        if not isinstance(cost, dict):
            continue
        with_cost += 1
        for k in ("flops", "bytes_accessed", "transcendentals"):
            v = cost.get(k)
            if isinstance(v, (int, float)) and v > 0:
                totals[k] = totals.get(k, 0.0) + float(v)
        programs += sum(int(n) for n in
                        (cost.get("programs") or {}).values())
    record = {
        "metric": "power_total",
        "value": round(sum(r["wall_ms"] for r in rows) / 1000.0, 4),
        "unit": "s",
        "queries_completed": len(rows),
        "queries_total": len(a["queries"]),
        "per_query": {r["query"]: round(r["wall_ms"] / 1000.0, 4)
                      for r in rows},
        "provenance": provenance,
    }
    if with_cost:
        record["cost_totals"] = {**{k: totals[k] for k in sorted(totals)},
                                 "programs": programs,
                                 "queries_with_cost": with_cost}
    if a.get("failed"):
        record["failed"] = list(a["failed"])
    return record, ""


def cmd_bank(args) -> int:
    import json
    record, err = bank_record(args.run_dir)
    if record is None:
        stale = "stale" in err
        print(f"BANK REFUSED: {err}")
        return EXIT_STALE_BANK if stale else EXIT_NO_METRIC
    out = args.out or os.path.join(args.run_dir, "bench_record.json")
    from nds_tpu.io.integrity import write_json_atomic
    write_json_atomic(out, record)
    print(json.dumps(record))
    print(f"wrote {out}")
    return 0


def cmd_analyze(args) -> int:
    a = analyze.analyze_run(args.run_dir)
    print(analyze.format_attribution(a, top=args.top))
    for name, h in sorted(a["metrics"]["histograms"].items()):
        qs = "".join(f" {k}={h[k]:g}" for k in ("p50", "p95", "p99")
                     if h.get(k) is not None)
        print(f"hist {name}: count={h['count']:g} "
              f"sum={h['sum']:g}{qs}")
    for tenant, q in sorted((a.get("tenants") or {}).items()):
        # serving run dirs (nds_tpu/serve/): per-tenant latency line
        print(f"tenant {tenant}: requests={q['requests']} "
              f"p50={q.get('p50_ms')}ms p95={q.get('p95_ms')}ms "
              f"p99={q.get('p99_ms')}ms")
    for rep, q in sorted((a.get("replicas") or {}).items()):
        # fleet run dirs: per-replica latency line; OUTLIER means the
        # replica's p99 diverges >2x from the fleet median — a sick
        # member, not a workload property
        flag = "  OUTLIER(p99>2x fleet median)" if q.get(
            "outlier") else ""
        print(f"replica {rep}: requests={q['requests']} "
              f"p50={q.get('p50_ms')}ms p95={q.get('p95_ms')}ms "
              f"p99={q.get('p99_ms')}ms{flag}")
    if a.get("stale_device_times"):
        print(f"WARNING: {len(a['stale_device_times'])} summar"
              f"{'y' if len(a['stale_device_times']) == 1 else 'ies'} "
              f"carry banked/stale device times — not fresh "
              f"measurements (ndsreport diff refuses to gate on them)")
    out_dir = args.out or args.run_dir
    paths = analyze.write_outputs(a, out_dir)
    print(f"wrote {paths['analysis']} and {paths['report']}")
    return 1 if a["failed"] and args.strict else 0


def cmd_diff(args) -> int:
    gate = analyze.parse_gate(args.gate)
    # the gate only compares BenchReport-derived rows; parsing two
    # full Chrome traces would double its wall-clock for nothing —
    # load the current run's trace only when writing the HTML report
    base = analyze.analyze_run(args.base_dir, with_trace=False)
    cur = analyze.analyze_run(args.cur_dir,
                              with_trace=bool(args.out))
    d = analyze.diff_runs(base, cur, **gate)
    print(analyze.format_diff(d))
    if args.out:
        paths = analyze.write_outputs(cur, args.out, diff=d)
        print(f"wrote {paths['analysis']} and {paths['report']}")
    return 0 if d["passed"] else 1


def self_check(repo_root: str | None = None) -> int:
    """Tier-1 gate over the committed fixtures: the attribution
    invariant holds, the regression pair fails the gate for the right
    reasons, and the identity diff passes."""
    repo = repo_root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    run_a = os.path.join(repo, "tests", "fixtures", "run_a")
    run_b = os.path.join(repo, "tests", "fixtures", "run_b")
    errors = []
    try:
        a = analyze.analyze_run(run_a)
        b = analyze.analyze_run(run_b)
    except Exception as exc:  # noqa: BLE001 - report, don't crash CI
        print(f"FAIL: fixture analysis raised {type(exc).__name__}: "
              f"{exc}")
        return 1
    for run in (a, b):
        for row in run["queries"]:
            total = (sum(row["categories"].values())
                     + row["residual_ms"])
            if abs(total - row["wall_ms"]) > 1e-6:
                errors.append(
                    f"{row['query']}: categories+residual "
                    f"{total:.3f} != wall {row['wall_ms']:.3f}")
    html = analyze.render_html(a)
    if "</html>" not in html or "attribution" not in html:
        errors.append("render_html produced no report body")
    d = analyze.diff_runs(a, b, pct=10.0, abs_ms=50.0)
    if d["passed"]:
        errors.append("regression fixture pair PASSED the gate")
    if not any(e["query"] == "query1" for e in d["regressions"]):
        errors.append("query1 regression not detected")
    if any(e["query"] == "query3" for e in
           d["regressions"] + d["improvements"]):
        errors.append("query3 noise misclassified as signal")
    ident = analyze.diff_runs(a, a, pct=10.0, abs_ms=50.0)
    if not ident["passed"]:
        errors.append("identity diff failed the gate")
    for e in errors:
        print(f"FAIL: {e}")
    print(f"{'FAIL' if errors else 'OK'}: ndsreport self-check, "
          f"{len(errors)} error(s)")
    return 1 if errors else 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="analyze/diff benchmark run directories")
    sub = p.add_subparsers(dest="cmd", required=True)
    pa = sub.add_parser("analyze", help="attribution table + report")
    pa.add_argument("run_dir")
    pa.add_argument("--out", help="artifact dir (default: run_dir)")
    pa.add_argument("--top", type=int, default=None,
                    help="only the N slowest queries in the table")
    pa.add_argument("--strict", action="store_true",
                    help="exit 1 when any query failed")
    pd = sub.add_parser("diff", help="cross-run regression gate")
    pd.add_argument("base_dir")
    pd.add_argument("cur_dir")
    pd.add_argument("--gate", default=None,
                    help="thresholds, e.g. pct=10,abs_ms=50,"
                         "cost_pct=25")
    pd.add_argument("--out",
                    help="also write analysis.json/report.html with "
                         "the diff embedded")
    pb = sub.add_parser(
        "bank", help="mint a provenance-stamped BENCH record")
    pb.add_argument("run_dir")
    pb.add_argument("--out",
                    help="record path (default: "
                         "RUN_DIR/bench_record.json)")
    sub.add_parser("self-check", help="fixture-based CI self-check")
    args = p.parse_args(argv)
    if args.cmd == "analyze":
        return cmd_analyze(args)
    if args.cmd == "diff":
        return cmd_diff(args)
    if args.cmd == "bank":
        return cmd_bank(args)
    return self_check()


if __name__ == "__main__":
    sys.exit(main())
