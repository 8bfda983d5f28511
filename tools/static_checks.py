"""One-shot static gate: headers + trace schema + ndslint + plan verify.

The single entrypoint tier-1 runs (tests/test_static_analysis.py) and
the one to run locally before pushing:

  1. check_headers      every module opens with a design-intent docstring
  2. check_trace_schema the obs tracer's Chrome-trace JSONL export
                        round-trips through the schema validator (a
                        real trace is generated in-process — no
                        accelerator, no jax import)
  3. ndslint            hazard-class lint over nds_tpu/ + tools/
                        (rules + waiver semantics:
                        nds_tpu/analysis/lint_rules.py)
  3b. ndsraces          concurrency audit over nds_tpu/ (guard
                        inference, static lock-order graph,
                        signal-handler safety, thread-shared mutation;
                        rules NDSR201-204:
                        nds_tpu/analysis/concurrency.py) — zero
                        unwaived findings, stale waivers fail
  3c. ndsjit            recompile & transfer hazard audit over
                        nds_tpu/ (traced-value leaks into Python
                        control flow, fingerprint-blind closure
                        captures, implicit device->host syncs in
                        dispatch code, weak-typed literals at jit
                        boundaries; rules NDSJ301-304:
                        nds_tpu/analysis/jit_hazards.py) — zero
                        unwaived findings, stale waivers fail
  4. ndsverify          plan + verify all 103 NDS and 22 NDS-H
                        statements on CPU (invariants:
                        nds_tpu/analysis/plan_verify.py), each with a
                        placement assigned by the scheduler cost model
                        (engine/scheduler.py) — no accelerator
  5. chaos              3-query NDS power stream on CPU under a fixed
                        fault schedule: one transient injection must
                        retry and complete, one deterministic must
                        fail fast; plus the resume-journal round-trip,
                        a FULL-LADDER walk under injected device OOM
                        (every query completes at the floor with rows
                        identical to a clean CPU run), a virtual-mesh
                        CONSENSUS demotion (sharded OOM reschedules
                        through the vote, the stream start demotes,
                        no deadlock), a SUPERVISED 4-stream throughput
                        round with an injected hang (watchdog catches
                        it within 2x stall_s, stream restarts once,
                        round completes degraded), and an injected
                        io.read byte-flip (digest verification fails
                        the load fast with CorruptArtifact, zero
                        retries) (tools/chaos_check.py)
  6. ndsreport          run-analysis self-check over the committed
                        fixture run-dirs (tests/fixtures/run_*):
                        attribution sums to wall-clock, the regression
                        pair fails the gate, the identity diff passes,
                        and every fixture BenchReport validates against
                        the summary schema (tools/ndsreport.py,
                        nds_tpu/obs/analyze.py)
  7. ndsperf            operator-kernel microbenchmark smoke
                        (tools/ndsperf.py --smoke): every lane runs
                        BOTH the legacy sort-based path and the
                        tensorized kernel (engine/kernels.py) at tiny
                        sizes and cross-checks their results — tier-1
                        proves both kernel paths stay runnable; the
                        speed acceptance runs on real accelerators
  8. fleet              2-process NDS-H power run on a virtual mesh
                        with 30s artificial clock skew and an induced
                        stall: per-rank trace shards merge into ONE
                        clock-aligned timeline with straggler
                        attribution, every rank's watchdog dumps a
                        schema-valid flight-r<rank>.json plus an
                        on-demand XLA capture pointed at from the
                        stall report, and a profile-triggered query's
                        BenchReport carries a nonzero profile block
                        (tools/fleet_check.py; obs/fleet.py +
                        obs/profile.py)
  9. soak               chaos soak smoke (tools/soak_check.py): a
                        real NDS power-run subprocess is SIGTERM'd
                        mid-query (drain deadline -> journaled
                        not-done -> exit 75) and kill -9'd mid-query,
                        each then resumed with --resume; the gate
                        asserts every statement completed exactly
                        once, result digests are byte-identical to an
                        uninterrupted run, the merged phase report +
                        ndsreport bill merged incarnations once, and
                        the torn-state path never fired
 10. compress           columnar compression gate
                        (tools/compress_check.py): a 3-query NDS-H
                        power stream runs on the device placement
                        encoded (columnar.encode=auto) and raw, rows
                        must be IDENTICAL with >=2x measured
                        bytes_scanned drop on at least one query and
                        a compression_ratio on every encoded summary;
                        plus the table_cache manifest round-trip of
                        per-column encoding specs and its mode-change
                        invalidation (nds_tpu/columnar/; README
                        "Compressed columnar store")
 10c. cost             compiler-cost-ledger gate (tools/cost_check.py):
                        a 3-query NDS-H power stream against a fresh
                        plan-cache dir runs cold then warm — every
                        query's BenchReport cost block carries
                        flops > 0 on the cold compile AND on the warm
                        cache hit (zero compiles: the cost dicts ride
                        the AOT manifest), categories+residual ==
                        wall-clock stays intact, the no-stats CPU
                        backend grows no telemetry block, and
                        ndsreport bank mints a provenance-stamped
                        record yet refuses (exit 4) a stale-marked dir
 10b. pipeline          pipelined-execution gate
                        (tools/pipeline_check.py): a 3-query NDS-H
                        power stream FORCED onto the chunked placement
                        (8+ chunks per streamed table) runs serial vs
                        prefetch depth 2 (engine/pipeline_io.py) —
                        rows byte-identical, identical compile counts
                        (the pipeline must not perturb chunkscan
                        fingerprints), measured prefetch_hidden_s > 0,
                        wall-clock no worse; the prefetch run's
                        attribution keeps categories+residual ==
                        wall-clock with the new prefetch_wait
                        category; and an engine.prefetch.boundary=on
                        run (query N+1 dispatched while N's result is
                        in flight) stays byte-identical with
                        schema-valid summaries + a complete journal
 11. serve              query-server smoke (tools/serve_check.py): a
                        warmed QueryServer (nds_tpu/serve/) handles a
                        mixed NDS+NDS-H literal-variant load at >=4
                        concurrent in-flight requests with ZERO
                        compiles and zero plan-cache misses
                        (parameterized fingerprints: variants share
                        one cache entry), responses digest-identical
                        to a sequential oracle, tenant-labeled
                        OpenMetrics + schema-clean per-request
                        summaries + per-tenant p50/p99 via ndsreport
                        analyze, an overload burst sheds
                        (server_shed_total > 0) without a single
                        error, and the TCP JSON-lines front answers
 10d. maint             crash-safe writable-warehouse gate
                        (tools/maint_check.py): a real full-bench run
                        (load -> power -> throughput -> maintenance ->
                        validate -> metric, SF0.01, 3-query streams)
                        is SIGKILLed mid-maintenance while a fault
                        injection wedges LF_WS inside dml.apply, then
                        resumed — the maintenance commit journal must
                        show ZERO double-applied functions (committed
                        ones keep starts==[0], the victim re-runs
                        exactly once), the validate phase must match a
                        CPU oracle on the maintained warehouse, the
                        metric folds both Tdm terms, every mutated
                        table keeps its BASELINE parts + _v*/ delta
                        segments (base never rewritten) with device
                        compression_ratio > 1, rollback restores the
                        pre-maintenance power digests byte-identically,
                        and DML invalidation is table-scoped (an
                        unrelated query re-runs with zero compiles)
 11b. serve-fleet       replicated fleet gate
                        (tools/fleet_serve_check.py): 3 real replica
                        PROCESSES (one started after warmup, warm
                        from the shared AOT store) behind the
                        FleetRouter take a mixed literal-variant load
                        at >=40 concurrency while one replica is
                        SIGKILLed and another SIGTERMed mid-load
                        (drain -> exit 75 -> warm resume ->
                        re-admission); every request completes,
                        traffic redistributes, the request journal
                        proves zero lost / zero double-answered,
                        every response is digest-identical to a
                        sequential single-engine oracle, every
                        post-warmup incarnation reports ZERO compiles
                        / cache misses, and ndsreport analyze derives
                        the per-replica latency rollup
 12. locksan            runtime lock-order sanitizer verdict
                        (nds_tpu/analysis/locksan.py): a SEEDED
                        inversion + re-entrant acquire on a private
                        graph must be caught (the detector provably
                        fires), the chaos/soak/serve/fleet workloads
                        above — which all ran with NDS_TPU_LOCKSAN=1 —
                        must have witnessed ZERO inversions in this
                        process, and every child-process report swept
                        from NDS_TPU_LOCKSAN_REPORT must be
                        inversion-free too
 13. jitsan             runtime jit sanitizer verdict
                        (nds_tpu/analysis/jitsan.py): a SEEDED
                        post-warmup compile + hidden .item() on a
                        private sanitizer must be caught, every
                        measurement window armed by the cost/serve
                        sections above — which ran with
                        NDS_TPU_JITSAN=1 — must be free of post-warmup
                        compiles and undeclared implicit transfers
                        while crossing at least one guarded dispatch
                        site, and every child report swept from
                        NDS_TPU_JITSAN_REPORT must be clean too

Exit 0 only when every section passes; each section prints its own
verdict line so CI logs show exactly which gate broke.
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# before ANY nds_tpu import: module-level locks (obs/trace,
# resilience/watchdog, the metrics registry) are created at import
# time, and they must be sanitizer-wrapped for the locksan section's
# inversion-free verdict over the chaos/soak/serve workloads to mean
# anything. FORCED, not setdefault: an ambient NDS_TPU_LOCKSAN=0 (the
# pytest debugging opt-out) would make section 12's verdict vacuous.
os.environ["NDS_TPU_LOCKSAN"] = "1"
# same reasoning for the jit sanitizer: cost_check's warm stream and
# serve_check's post-warmup phases arm measurement windows, and the
# jitsan section's verdict over them is only meaningful if the env was
# on for the whole process
os.environ["NDS_TPU_JITSAN"] = "1"

import chaos_check  # noqa: E402
import check_headers  # noqa: E402
import check_trace_schema  # noqa: E402
import compress_check  # noqa: E402
import cost_check  # noqa: E402
import fleet_check  # noqa: E402
import fleet_serve_check  # noqa: E402
import maint_check  # noqa: E402
import ndslint  # noqa: E402
import ndsperf  # noqa: E402
import ndsjit  # noqa: E402
import ndsraces  # noqa: E402
import ndsreport  # noqa: E402
import ndsverify  # noqa: E402
import pipeline_check  # noqa: E402
import serve_check  # noqa: E402
import soak_check  # noqa: E402


def run_trace_schema_check() -> int:
    """Exercise the tracer end-to-end: emit a real span tree to a JSONL
    file and validate it against the documented event schema."""
    from nds_tpu.obs.trace import Tracer, export_chrome
    tracer = Tracer(enabled=True)
    with tracer.span("static_checks.trace_selftest", gate="tier-1") as root:
        with tracer.span("static_checks.child", n=1):
            pass
    if not root or len(tracer.totals()) != 2:
        # tracer API drift: fail loudly, not silently
        print("FAIL: tracer produced no root span")
        return 1
    with tempfile.NamedTemporaryFile("w", suffix=".jsonl",
                                     delete=False) as f:
        path = f.name
    try:
        export_chrome(root, path)
        errors = check_trace_schema.validate_file(path)
        for e in errors:
            print(e)
        print(f"{'FAIL' if errors else 'OK'}: {len(errors)} schema "
              f"error(s) in generated trace")
        return 1 if errors else 0
    finally:
        os.unlink(path)


def run_ndsreport_check() -> int:
    """Section 6: analyze + diff over the committed fixtures, plus the
    BenchReport summary-schema gate over every fixture report."""
    import glob
    import pathlib
    repo = pathlib.Path(__file__).resolve().parent.parent
    rc = ndsreport.self_check(str(repo))
    errors = []
    from nds_tpu.obs import analyze
    for path in sorted(glob.glob(
            str(repo / "tests" / "fixtures" / "run_*" / "*.json"))):
        # a local `ndsreport analyze tests/fixtures/run_a` drops its
        # analysis.json into the run dir — an artifact, not a fixture
        if not analyze.is_report_basename(os.path.basename(path)):
            continue
        errors.extend(check_trace_schema.validate_summary_file(path))
    for e in errors:
        print(e)
    if errors:
        print(f"FAIL: {len(errors)} summary schema error(s) in "
              f"fixtures")
    return 1 if (rc or errors) else 0


def run_locksan_check() -> int:
    """Section 12: the runtime sanitizer verdict. Three parts:
    (1) a seeded AB/BA inversion plus a re-entrant acquire on a
    PRIVATE graph must be caught — the detector provably fires;
    (2) this process, which ran the chaos/compress/serve workloads
    with every engine lock wrapped, must hold zero inversions;
    (3) child processes (fleet/soak subprocess runs) wrote
    locksan-<pid>.json reports into NDS_TPU_LOCKSAN_REPORT at exit —
    sweep them, all must be inversion-free."""
    import glob
    import json
    from nds_tpu.analysis import locksan
    if not locksan.enabled():
        # belt for the forced env above: an unsanitized run has no
        # inversion-free claim to make, and silence would fake one
        print(f"FAIL: {locksan.ENV} is off — the workloads above ran "
              f"unsanitized, so this verdict would be vacuous")
        return 1
    if not locksan.selftest():
        print("FAIL: locksan missed the seeded inversion")
        return 1
    inproc = locksan.inversion_count()
    child_inv = 0
    reports = 0
    report_dir = os.environ.get(locksan.REPORT_ENV)
    if report_dir:
        for path in sorted(glob.glob(
                os.path.join(report_dir, "locksan-*.json"))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            reports += 1
            for inv in doc.get("inversions", []):
                child_inv += 1
                print(f"  child inversion ({os.path.basename(path)}): "
                      f"{' -> '.join(inv.get('cycle', []))}")
    bad = inproc + child_inv
    print(f"{'FAIL' if bad else 'OK'}: seeded inversion caught; "
          f"{inproc} in-process + {child_inv} child inversion(s) "
          f"across {reports} child report(s)")
    return 1 if bad else 0


def run_jitsan_check() -> int:
    """Section 13: the jit sanitizer verdict. Three parts:
    (1) a seeded post-warmup compile + hidden ``.item()`` on a private
    sanitizer must be caught — the detector provably fires;
    (2) every measurement window closed in this process (cost_check's
    warm stream, serve_check's post-warmup phases, both armed because
    NDS_TPU_JITSAN is forced above) must be violation-free AND at
    least one must have crossed a guarded dispatch site — a clean
    verdict over zero dispatches proves only that the guard is
    unwired;
    (3) child-process reports swept from NDS_TPU_JITSAN_REPORT must be
    violation-free too."""
    import glob
    import json
    from nds_tpu.analysis import jitsan
    if not jitsan.enabled():
        print(f"FAIL: {jitsan.ENV} is off — the cost/serve windows "
              f"above ran unsanitized, so this verdict would be "
              f"vacuous")
        return 1
    if not jitsan.selftest():
        print("FAIL: jitsan missed the seeded compile/transfer")
        return 1
    wins = jitsan.windows()
    inproc = jitsan.violation_count()
    dispatches = sum(w.get("dispatches", 0) for w in wins)
    if not wins or dispatches == 0:
        print(f"FAIL: no armed window crossed a dispatch site "
              f"({len(wins)} window(s)) — the cost/serve sections "
              f"above did not measure anything")
        return 1
    child_bad = 0
    reports = 0
    report_dir = os.environ.get(jitsan.REPORT_ENV)
    if report_dir:
        for path in sorted(glob.glob(
                os.path.join(report_dir, "jitsan-*.json"))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            reports += 1
            for w in doc.get("windows", []):
                for c in w.get("compiles", []):
                    child_bad += 1
                    print(f"  child compile "
                          f"({os.path.basename(path)}): "
                          f"{w.get('label')}: {c.get('kind')}")
                for t in w.get("undeclared_transfers", []):
                    child_bad += 1
                    print(f"  child transfer "
                          f"({os.path.basename(path)}): "
                          f"{w.get('label')}: {t.get('what')}")
    bad = inproc + child_bad
    print(f"{'FAIL' if bad else 'OK'}: seeded compile+transfer "
          f"caught; {inproc} in-process + {child_bad} child "
          f"violation(s) across {len(wins)} window(s) "
          f"({dispatches} guarded dispatches) and {reports} child "
          f"report(s)")
    return 1 if bad else 0


def main() -> int:
    import pathlib
    repo = pathlib.Path(__file__).resolve().parent.parent
    # child runs (fleet/soak/serve subprocesses) inherit this dir and
    # write their sanitizer reports into it at exit; section 12 sweeps
    # it (children killed with -9 or os._exit leave none — best effort)
    os.environ.setdefault(
        "NDS_TPU_LOCKSAN_REPORT",
        tempfile.mkdtemp(prefix="nds_tpu_locksan_"))
    os.environ.setdefault(
        "NDS_TPU_JITSAN_REPORT",
        tempfile.mkdtemp(prefix="nds_tpu_jitsan_"))
    sections = [
        ("headers", check_headers.main),
        ("trace-schema", run_trace_schema_check),
        ("ndslint", lambda: ndslint.run(repo)),
        ("ndsraces", lambda: ndsraces.run(repo)),
        ("ndsjit", lambda: ndsjit.run(repo)),
        ("ndsverify", lambda: ndsverify.main([])),
        ("chaos", chaos_check.main),
        ("ndsreport", run_ndsreport_check),
        ("ndsperf", lambda: ndsperf.main(["--smoke"])),
        ("fleet", fleet_check.main),
        ("soak", lambda: soak_check.main([])),
        ("compress", lambda: compress_check.main([])),
        ("pipeline", lambda: pipeline_check.main([])),
        ("cost", lambda: cost_check.main([])),
        ("maint", lambda: maint_check.main([])),
        ("serve", lambda: serve_check.main([])),
        ("serve-fleet", lambda: fleet_serve_check.main([])),
        ("locksan", run_locksan_check),
        ("jitsan", run_jitsan_check),
    ]
    failed = []
    for name, fn in sections:
        print(f"== {name} ==")
        if fn() != 0:
            failed.append(name)
    if failed:
        print(f"STATIC CHECKS FAILED: {', '.join(failed)}")
        return 1
    print(f"STATIC CHECKS OK: {len(sections)} section(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
