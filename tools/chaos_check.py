"""Deterministic chaos gate: a tiny NDS power stream under injected
faults, asserted end-to-end.

tier-1 (via tools/static_checks.py) runs a 3-query NDS power stream on
the CPU backend with a FIXED fault schedule — one transient
device.execute OOM (must be retried and succeed, ``retries=1``,
status ``Completed``) and one deterministic plan fault (must fail
FAST: one attempt, ``gave_up_reason=deterministic``) — then checks the
per-query JSON summaries, the TimeLog CSV (the stream never aborts),
the resilience metrics counters, and the PhaseJournal resume
round-trip. The schedule is seeded, so every CI run replays the exact
same failure sequence; a regression in classification, retry
accounting, or journaling fails here before any differential tier
spins up a device.

Two placement/ladder scenarios (engine/scheduler.py) ride on the same
generated data:

- **ladder** — a 3-query power stream on the tpu backend with device
  OOM injected at BOTH device-side placements (scoped to the executor
  class names, so the CPU floor stays healthy): every query must walk
  the full degradation ladder (device -> chunked -> cpu), complete
  with ``reschedules: 2`` recorded in its summary, and produce result
  rows IDENTICAL to a clean cpu-backend run of the same stream.

- **consensus** — the same stream on the distributed backend (8-device
  virtual mesh) with OOM injected at the sharded placement: the first
  queries reschedule through a consensus vote (degenerate one-rank
  world — the same code path real multi-process runs take), the
  reschedule streak demotes the stream's starting placement, the run
  completes degraded with no deadlock, and
  ``placement_consensus_total`` / ``placement_demotions_total`` move.

One plan-cache scenario (nds_tpu/cache/; README "Plan cache") rides on
the same generated data:

- **cache-corruption** — byte-flip every persisted AOT payload between
  two identical device-placement streams: the second run must treat
  every corrupt entry as a warned miss (``compile_cache_errors_total``
  moves, zero hits, fresh compiles), complete every query with
  ``retries=0`` and rows identical to the cold run, and re-persist —
  a third run serves fully warm with ZERO compiles.

Two watchdog/integrity scenarios ride on the same generated data:

- **hang** — a 4-stream SUPERVISED subprocess throughput round with a
  ``stream.query:hang`` injected into one stream: the child watchdog
  must catch the stall within 2x ``stall_s`` (exit ``EXIT_STALLED``,
  stall report dumped), the supervisor must restart the stream ONCE
  from its last completed query, and the round must complete with the
  stall + restart recorded in ``throughput_summary.json``.

- **corrupt** — an ``io.read:corrupt`` byte-flip in one raw chunk with
  digest verification on: the warehouse load must fail FAST with
  ``CorruptArtifact`` naming the file and both digests, zero retries,
  and a Failed ``load_warehouse`` BenchReport on disk. Runs LAST — the
  flip really mutates the shared raw data.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCALE = 0.01
TEMPLATES = [96, 7, 93]
# query7 dies once with an injected device OOM (transient: retried);
# query93 dies at plan time (deterministic: never retried)
SCHEDULE = "device.execute:oom@query7,plan:deterministic@query93"


def _fail(msg: str) -> int:
    print(f"FAIL: {msg}")
    return 1


def run_chaos_stream(workdir: str) -> int:
    from nds_tpu.nds import gen_data, streams
    from nds_tpu.nds.power import SUITE
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.resilience import faults
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    from nds_tpu.utils.timelog import TimeLog

    raw = os.path.join(workdir, "raw")
    sdir = os.path.join(workdir, "streams")
    jsons = os.path.join(workdir, "json")
    tlog = os.path.join(workdir, "time.csv")
    gen_data.generate_data_local(SCALE, 2, raw, workers=2)
    streams.generate_query_streams(sdir, 1, templates=TEMPLATES)

    cfg = EngineConfig(overrides={
        "engine.backend": "cpu",
        "engine.retry.base_delay_s": "0.01",
        "engine.retry.max_attempts": "3",
    })
    before = obs_metrics.snapshot()
    plan = faults.install(SCHEDULE, seed=7)
    try:
        failures = power_core.run_query_stream(
            SUITE, raw, os.path.join(sdir, "query_0.sql"), tlog,
            config=cfg, input_format="raw",
            json_summary_folder=jsons)
    finally:
        faults.clear()
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    counters = delta.get("counters", {})

    if failures != 1:
        return _fail(f"expected exactly the deterministic failure, "
                     f"got {failures}")
    summaries = _stream_summaries(jsons)
    q96, q7, q93 = (summaries.get(f"query{n}") for n in TEMPLATES)
    if not (q96 and q7 and q93):
        return _fail(f"missing summaries: {sorted(summaries)}")
    if q96["queryStatus"] != ["Completed"] or q96.get("retries") != 0:
        return _fail(f"query96 should complete untouched: {q96}")
    if q7["queryStatus"] != ["Completed"] or q7.get("retries") != 1:
        return _fail(f"query7 should complete after ONE retry: "
                     f"status={q7['queryStatus']} "
                     f"retries={q7.get('retries')}")
    if (q93["queryStatus"] != ["Failed"]
            or q93.get("gave_up_reason") != "deterministic"
            or q93.get("retries") != 0):
        return _fail(f"query93 should fail fast without retry: {q93}")
    if "injected deterministic fault" not in " ".join(q93["exceptions"]):
        return _fail(f"query93 exception text lost: {q93['exceptions']}")
    # the stream never aborts: every query has a TimeLog row
    names = [q for _a, q, _ms in TimeLog.read(tlog)]
    for n in TEMPLATES:
        if f"query{n}" not in names:
            return _fail(f"query{n} missing from TimeLog {names}")
    if counters.get("query_retries_total") != 1:
        return _fail(f"query_retries_total delta: {counters}")
    if counters.get("faults_injected_total") != 2:
        return _fail(f"faults_injected_total delta: {counters}")
    fired = {(sp.site, sp.fired) for sp in plan.specs}
    if fired != {("device.execute", 1), ("plan", 1)}:
        return _fail(f"unexpected firing counts {fired}")
    print("OK: chaos stream (1 transient retried, 1 deterministic "
          "fail-fast, stream completed)")
    return 0


def run_journal_check(workdir: str) -> int:
    from nds_tpu.resilience.journal import (
        JournalMismatch, PhaseJournal, config_digest,
    )
    path = os.path.join(workdir, "bench_state.json")
    digest = config_digest({"scale_factor": 0.01, "backend": "cpu"})
    j = PhaseJournal(path, digest)
    j.reset()
    j.complete("load_test", load_time_s=12.5, rngseed=42)
    j.complete("power_test", power_time_s=3.25)
    # a fresh journal object (the resumed process) replays the state
    j2 = PhaseJournal(path, digest)
    if not j2.load():
        return _fail("journal did not persist")
    if not (j2.done("load_test") and j2.done("power_test")):
        return _fail(f"phases lost: {j2.state}")
    if j2.done("throughput_1"):
        return _fail("phantom phase in journal")
    if j2.timings("load_test") != {"load_time_s": 12.5, "rngseed": 42}:
        return _fail(f"timings drifted: {j2.timings('load_test')}")
    # a different config must refuse to resume (digest guard)
    j3 = PhaseJournal(path, config_digest({"scale_factor": 3000}))
    try:
        j3.load()
    except JournalMismatch:
        pass
    else:
        return _fail("journal accepted a mismatched config digest")
    print("OK: phase journal round-trip + config-digest guard")
    return 0


def _stream_summaries(jsons: str) -> dict:
    """BenchReport summaries in a run dir — failed queries drop
    flight-recorder dumps (obs/fleet.py) next to them, so only files
    with the summary keys count."""
    out = {}
    for f in os.listdir(jsons):
        with open(os.path.join(jsons, f)) as fh:
            s = json.load(fh)
        if isinstance(s, dict) and "query" in s and "queryStatus" in s:
            out[s["query"]] = s
    return out


def run_ladder_stream(workdir: str) -> int:
    """Injected device OOM at every device-side placement: each query
    walks the FULL ladder (device -> chunked -> cpu), completes, and
    its rows match a clean CPU run bit-for-bit."""
    from nds_tpu.io.result_io import read_result
    from nds_tpu.nds.power import SUITE
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.resilience import faults
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig

    raw = os.path.join(workdir, "raw")
    sdir = os.path.join(workdir, "streams")
    stream = os.path.join(sdir, "query_0.sql")

    # clean reference rows: the same stream, cpu backend, no faults
    clean_out = os.path.join(workdir, "ladder_clean")
    power_core.run_query_stream(
        SUITE, raw, stream, os.path.join(workdir, "ladder_clean.csv"),
        config=EngineConfig(overrides={"engine.backend": "cpu"}),
        input_format="raw", output_prefix=clean_out)

    jsons = os.path.join(workdir, "json_ladder")
    chaos_out = os.path.join(workdir, "ladder_chaos")
    cfg = EngineConfig(overrides={
        "engine.backend": "tpu",
        "engine.retry.base_delay_s": "0.01",
        # keep the sticky demotion OUT of this scenario: every query
        # must start at the top and walk the whole ladder itself
        "engine.placement.demote_after": "99",
    })
    before = obs_metrics.snapshot()
    # scope by executor CLASS: the device and chunked placements die
    # with OOM on every attempt, the CPU floor never fires. The
    # chunked rung streams for real now (the scheduler lowers the
    # stream threshold on relief entries), so its dispatches run in
    # the phase A/B sub-executors — fail those too or the ladder
    # (correctly!) stops at chunked
    faults.install("device.execute:oom*99@DeviceExecutor,"
                   "device.execute:oom*99@ChunkedExecutor,"
                   "device.execute:oom*99@_PhaseBExecutor,"
                   "device.execute:oom*99@_PartialAggExecutor", seed=7)
    try:
        power_core.run_query_stream(
            SUITE, raw, stream,
            os.path.join(workdir, "ladder_time.csv"), config=cfg,
            input_format="raw", json_summary_folder=jsons,
            output_prefix=chaos_out)
    finally:
        faults.clear()
    # run_query_stream counts CompletedWithTaskFailures as non-success
    # (the chunked rung's internal chunk-halving notifies the
    # collector), so the gate keys on per-query statuses: every query
    # must COMPLETE — with or without recovered task failures
    sums = _stream_summaries(jsons)
    for n in TEMPLATES:
        s = sums.get(f"query{n}")
        if not s:
            return _fail(f"query{n} summary missing: {sorted(sums)}")
        if s["queryStatus"][-1] not in ("Completed",
                                        "CompletedWithTaskFailures"):
            return _fail(f"query{n} did not complete: "
                         f"{s['queryStatus']}")
        if s.get("placement") != "cpu" or s.get("reschedules") != 2:
            return _fail(
                f"query{n} should land on cpu after 2 reschedules: "
                f"placement={s.get('placement')} "
                f"reschedules={s.get('reschedules')}")
        if s.get("ladder") != ["device", "chunked", "cpu"]:
            return _fail(f"query{n} ladder wrong: {s.get('ladder')}")
    # correctness across the whole walk: identical rows to the clean
    # CPU run, query by query
    for n in TEMPLATES:
        a = read_result(os.path.join(clean_out, f"query{n}"))
        b = read_result(os.path.join(chaos_out, f"query{n}"))
        if not a.equals(b):
            return _fail(f"query{n} rows diverged from the clean CPU "
                         f"run after the ladder walk")
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    counters = delta.get("counters", {})
    if counters.get("query_reschedules_total", 0) < 2 * len(TEMPLATES):
        return _fail(f"query_reschedules_total delta: {counters}")
    print("OK: ladder stream (device OOM walked device->chunked->cpu "
          "per query, all completed, rows identical to clean CPU run)")
    return 0


def run_consensus_demotion(workdir: str) -> int:
    """Virtual-mesh consensus demotion: sharded-placement OOM
    reschedules through the consensus vote, the stream's starting
    placement demotes (all ranks together — degenerate 1-rank world
    here, same code path as a real pod), and the run completes
    degraded without deadlock."""
    from nds_tpu.nds.power import SUITE
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.resilience import faults
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig

    raw = os.path.join(workdir, "raw")
    stream = os.path.join(workdir, "streams", "query_0.sql")
    jsons = os.path.join(workdir, "json_consensus")
    cfg = EngineConfig(overrides={
        "engine.backend": "distributed",
        "engine.retry.base_delay_s": "0.01",
        "engine.placement.demote_after": "2",
    })
    before = obs_metrics.snapshot()
    faults.install("device.execute:oom*99@DistributedExecutor", seed=7)
    try:
        failures = power_core.run_query_stream(
            SUITE, raw, stream,
            os.path.join(workdir, "consensus_time.csv"), config=cfg,
            input_format="raw", json_summary_folder=jsons)
    finally:
        faults.clear()
    if failures != 0:
        return _fail(f"consensus stream should complete degraded, "
                     f"{failures} failed")
    sums = _stream_summaries(jsons)
    walked = [s for s in sums.values() if s.get("reschedules")]
    if not walked:
        return _fail(f"no query rescheduled off the sharded "
                     f"placement: { {q: s.get('placement') for q, s in sums.items()} }")
    for s in walked:
        if s.get("placement") == "sharded":
            return _fail(f"{s['query']} still reports the sharded "
                         f"placement after rescheduling: {s}")
    # after demote_after rescheduled queries the START demotes: the
    # last query must begin off-sharded with no ladder walk of its own
    last = sums.get(f"query{TEMPLATES[-1]}")
    if not last or last.get("reschedules") != 0 \
            or last.get("placement") == "sharded":
        return _fail(f"stream start should be demoted by the streak: "
                     f"{last}")
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    counters = delta.get("counters", {})
    if not counters.get("placement_consensus_total"):
        return _fail(f"placement_consensus_total delta: {counters}")
    if counters.get("placement_demotions_total") != 1:
        return _fail(f"placement_demotions_total delta: {counters}")
    print("OK: consensus demotion (sharded OOM rescheduled via "
          "consensus, stream start demoted, run completed degraded, "
          "no deadlock)")
    return 0


def run_cache_corruption(workdir: str) -> int:
    """Byte-flip every persisted plan-cache payload between two runs of
    the same stream: the second run must degrade every corrupt entry to
    a warned fresh compile (``compile_cache_errors_total`` moves, zero
    hits), complete every query with ``retries=0`` and rows identical
    to the cold run, quarantine the bad entries, and re-persist fresh
    ones — a third run serves fully warm with zero compiles."""
    from nds_tpu import cache as plan_cache
    from nds_tpu.cache.store import PAYLOAD_PREFIX
    from nds_tpu.io.result_io import read_result
    from nds_tpu.nds.power import SUITE
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig

    raw = os.path.join(workdir, "raw")
    stream = os.path.join(workdir, "streams", "query_0.sql")
    cache_dir = os.path.join(workdir, "plan_cache")

    def _cfg():
        # force the device placement so every query compiles through
        # the cache (the cost model may otherwise pick the cacheless
        # cpu rung for tiny inputs)
        return EngineConfig(overrides={
            "engine.backend": "tpu",
            "engine.placement.force": "device",
            "cache.dir": cache_dir,
        })

    def _one_run(tag: str):
        jsons = os.path.join(workdir, f"json_cache_{tag}")
        out = os.path.join(workdir, f"cache_rows_{tag}")
        before = obs_metrics.snapshot()
        failures = power_core.run_query_stream(
            SUITE, raw, stream,
            os.path.join(workdir, f"cache_{tag}.csv"), config=_cfg(),
            input_format="raw", json_summary_folder=jsons,
            output_prefix=out)
        delta = obs_metrics.delta(before, obs_metrics.snapshot())
        return failures, _stream_summaries(jsons), \
            delta.get("counters", {}), out

    try:
        fail_cold, _sums, cold, cold_out = _one_run("cold")
        if fail_cold:
            return _fail(f"cold cache run failed {fail_cold} queries")
        if not cold.get("compile_cache_bytes_written_total"):
            return _fail(f"cold run persisted nothing: {cold}")

        # flip one byte in EVERY payload: every later consult must see
        # the sha256 mismatch
        flipped = 0
        for root, _dirs, files in os.walk(cache_dir):
            for f in files:
                if not f.startswith(PAYLOAD_PREFIX) \
                        or f.endswith(".tmp"):
                    continue
                p = os.path.join(root, f)
                with open(p, "r+b") as fh:
                    fh.seek(137)
                    b = fh.read(1)
                    fh.seek(137)
                    fh.write(bytes([b[0] ^ 0xFF]))
                flipped += 1
        if not flipped:
            return _fail("no cache payloads found to corrupt")

        fail_cor, sums, cor, cor_out = _one_run("corrupt")
        if fail_cor:
            return _fail(f"corrupt cache must NEVER fail a query: "
                         f"{fail_cor} failed")
        for q, s in sums.items():
            if s["queryStatus"][-1] != "Completed" \
                    or s.get("retries") != 0:
                return _fail(f"{q} should complete with retries=0 "
                             f"despite the corrupt cache: "
                             f"status={s['queryStatus']} "
                             f"retries={s.get('retries')}")
        if not cor.get("compile_cache_errors_total"):
            return _fail(f"corruption must warn via "
                         f"compile_cache_errors_total: {cor}")
        if cor.get("compile_cache_hits_total"):
            return _fail(f"a flipped payload must never hit: {cor}")
        if not cor.get("compiles_total"):
            return _fail(f"corrupt entries must recompile fresh: {cor}")
        for n in TEMPLATES:
            a = read_result(os.path.join(cold_out, f"query{n}"))
            b = read_result(os.path.join(cor_out, f"query{n}"))
            if not a.equals(b):
                return _fail(f"query{n} rows diverged after the "
                             f"corrupt-cache recompile")

        # recovery: the fresh compiles re-persisted; a third run is
        # fully warm (0 compiles) and the store verifies clean
        fail_warm, _sums, warm, _out = _one_run("warm")
        if fail_warm:
            return _fail(f"warm rerun failed {fail_warm} queries")
        if warm.get("compiles_total") or warm.get("recompiles_total"):
            return _fail(f"warm rerun should compile NOTHING: {warm}")
        if not warm.get("compile_cache_hits_total"):
            return _fail(f"warm rerun should serve from cache: {warm}")
        store = plan_cache.PlanCache(cache_dir, readonly=True)
        bad = store.verify()
        if bad:
            return _fail(f"re-persisted store should verify clean: "
                         f"{bad}")
    finally:
        plan_cache.reset()
    print("OK: cache corruption (byte-flipped entries warned + "
          "recompiled fresh, queries Completed retries=0 with "
          "identical rows, store re-persisted and fully warm)")
    return 0


def run_watchdog_stream(workdir: str) -> int:
    """Supervised 4-stream throughput round with one hung stream: the
    watchdog catches it, the supervisor restarts it once, the round
    completes degraded — never wedged."""
    from nds_tpu.nds import streams
    from nds_tpu.nds.throughput import run_streams
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.resilience import faults
    from nds_tpu.resilience.watchdog import EXIT_STALLED

    raw = os.path.join(workdir, "raw")
    sdir = os.path.join(workdir, "tstreams")
    out = os.path.join(workdir, "tp")
    streams.generate_query_streams(sdir, 4, templates=[96, 7])
    paths = [os.path.join(sdir, f"query_{i}.sql") for i in range(4)]
    # generous budget: 4 concurrent children on a loaded CI box can see
    # multi-second gaps between legitimate beats (a single big-table
    # parse is one C call — no beat can land mid-parse, and on a 1-core
    # box four children serialize it to 4x the isolated time); the
    # injected hang is 120 s, so detection headroom costs nothing
    stall_s = 30.0
    before = obs_metrics.snapshot()
    saved = os.environ.get(faults.FAULTS_ENV)
    # the schedule reaches the CHILDREN via the environment; the scope
    # matches stream query_1's NDS_TPU_STREAM context — and only its
    # first incarnation (the restart renames itself query_1#r1)
    os.environ[faults.FAULTS_ENV] = "stream.query:hang=120@query_1"
    try:
        _elapse, codes = run_streams(
            raw, paths, out, backend="cpu", input_format="raw",
            stall_s=stall_s)
    finally:
        if saved is None:
            os.environ.pop(faults.FAULTS_ENV, None)
        else:
            os.environ[faults.FAULTS_ENV] = saved
        faults.clear()

    if any(codes):
        return _fail(f"supervised round should complete: codes={codes}")
    with open(os.path.join(out, "throughput_summary.json")) as f:
        summary = json.load(f)
    s1 = summary["streams"].get("query_1")
    if not s1:
        return _fail(f"query_1 missing from summary: {summary}")
    if s1["exit_codes"][0] != EXIT_STALLED:
        return _fail(f"child watchdog should have caught the hang "
                     f"(exit {EXIT_STALLED}): {s1['exit_codes']}")
    if s1["restarts"] != 1 or not s1["degraded"]:
        return _fail(f"query_1 should restart ONCE and be marked "
                     f"degraded: {s1}")
    if not s1["stalls"]:
        return _fail(f"stall record missing from summary: {s1}")
    if s1["stalls"][0].get("age_s", 1e9) > 2 * stall_s:
        return _fail(f"stall detected too late (> 2x stall_s): "
                     f"{s1['stalls']}")
    for name, s in summary["streams"].items():
        if name != "query_1" and s["restarts"]:
            return _fail(f"healthy stream {name} restarted: {s}")
        if s["completed"] != 2:
            return _fail(f"{name} should complete 2 queries: {s}")
    # the hung child's watchdog dumped an all-thread stall report
    # (streams permute query order, so find it by content: only the
    # in-process watchdog can capture thread stacks)
    reports = [f for f in os.listdir(out) if f.startswith("stall-")]
    child_dump = None
    for f in reports:
        with open(os.path.join(out, f)) as fh:
            doc = json.load(fh)
        if "threads" in doc:
            child_dump = doc
            break
    if child_dump is None:
        return _fail(f"no child stall report with thread stacks "
                     f"in {reports}")
    for key in ("unit", "query", "phase", "age_s", "stall_s",
                "threads", "metrics"):
        if key not in child_dump:
            return _fail(f"stall report missing {key!r}: "
                         f"{sorted(child_dump)}")
    if (child_dump["unit"] != "query_1"
            or not child_dump["threads"]):
        return _fail(f"stall report should blame stream query_1 with "
                     f"non-empty stacks: unit={child_dump['unit']}")
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    counters = delta.get("counters", {})
    if counters.get("stream_restarts_total") != 1:
        return _fail(f"stream_restarts_total delta: {counters}")
    print("OK: watchdog stream (hang caught by child watchdog, "
          "killed, restarted once, round completed degraded)")
    return 0


def run_corrupt_load(workdir: str) -> int:
    """Byte-flip one raw chunk under digest verification: the load
    fails fast with CorruptArtifact, zero retries, reported."""
    from nds_tpu.io import integrity
    from nds_tpu.nds.power import SUITE
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.resilience import faults
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig

    raw = os.path.join(workdir, "raw")
    sdir = os.path.join(workdir, "streams")
    jsons = os.path.join(workdir, "json_corrupt")
    tlog = os.path.join(workdir, "time_corrupt.csv")
    table = "catalog_page"
    integrity.write_manifest(os.path.join(raw, table))
    integrity.set_verify(True)
    cfg = EngineConfig(overrides={"engine.backend": "cpu"})
    before = obs_metrics.snapshot()
    faults.install(f"io.read:corrupt@{table}", seed=7)
    err = None
    try:
        power_core.run_query_stream(
            SUITE, raw, os.path.join(sdir, "query_0.sql"), tlog,
            config=cfg, input_format="raw",
            json_summary_folder=jsons)
    except integrity.CorruptArtifact as exc:
        err = exc
    finally:
        faults.clear()
        integrity.set_verify(None)
    if err is None:
        return _fail("corrupt chunk should fail the load with "
                     "CorruptArtifact")
    msg = str(err)
    if table not in msg or "sha256 expected" not in msg:
        return _fail(f"CorruptArtifact should name the file and "
                     f"digests: {msg}")
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    counters = delta.get("counters", {})
    if counters.get("query_retries_total"):
        return _fail(f"corruption must NEVER be retried: {counters}")
    if counters.get("corrupt_artifacts_total") != 1:
        return _fail(f"corrupt_artifacts_total delta: {counters}")
    loads = [f for f in os.listdir(jsons) if "load_warehouse" in f]
    if not loads:
        return _fail(f"no load_warehouse BenchReport in {jsons}")
    with open(os.path.join(jsons, loads[0])) as f:
        rep = json.load(f)
    if rep["queryStatus"] != ["Failed"] or rep.get("retries") != 0:
        return _fail(f"load report should be Failed with retries=0: "
                     f"{rep['queryStatus']} retries={rep.get('retries')}")
    if not any("corrupt artifact" in e for e in rep["exceptions"]):
        return _fail(f"load report lost the corruption text: "
                     f"{rep['exceptions']}")
    print("OK: corrupt chunk (load failed fast with CorruptArtifact, "
          "0 retries, reported)")
    return 0


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="nds_chaos_") as workdir:
        rc = run_chaos_stream(workdir)
        rc |= run_journal_check(workdir)
        rc |= run_ladder_stream(workdir)
        rc |= run_consensus_demotion(workdir)
        rc |= run_cache_corruption(workdir)
        rc |= run_watchdog_stream(workdir)
        # LAST: really mutates the shared raw data
        rc |= run_corrupt_load(workdir)
    return rc


if __name__ == "__main__":
    sys.exit(main())
