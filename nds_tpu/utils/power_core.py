"""Suite-independent power-run core.

The reference duplicates its power loop between the NDS and NDS-H suites
(`nds/nds_power.py:184-322`, `nds-h/nds_h_power.py`); SURVEY.md §1 calls
out that the shared layer should be built once — this module is that
single copy. Each suite's driver supplies a ``Suite`` descriptor (schema
getter, stream parser, raw extension) and gets: warehouse registration
with CreateTempView-analog timings, the timed query loop with per-query
JSON summaries and the CSV time log, the ``--allow_failure`` contract
(`nds/nds_power.py:391-393`), warmup handling, and EngineConfig-driven
session construction (template < property file precedence,
`nds/spark-submit-template:24-33` + `nds_power.py:324-330`).

Observability: each query runs inside a root span (nds_tpu/obs) whose
tree — engine compile/execute/materialize and staged sub-programs
included — is attached to the JSON summary (``spans``) together with
the per-query metrics delta (``metrics``); ``NDS_TPU_TRACE=path``
additionally appends every tree to a Chrome trace-event JSONL.

Resilience: every backend now runs through the unified execution
pipeline (``nds_tpu/engine/scheduler.py``) — per query, a cost model
picks the initial placement (single-device / sharded / out-of-core /
CPU), classified transient failures walk a degradation ladder as a
reschedule of that one query, and the pipeline owns the retry policy
(``engine.retry.*`` / ``engine.query_deadline_s``). The per-query
summary records ``retries`` / ``gave_up_reason`` /
``deadline_exceeded`` plus the scheduling decisions: ``placement``,
``reschedules``, ``promoted_back`` (README "Placement &
degradation"). ``engine.fallback=cpu`` survives as an alias forcing
the ladder floor to the CPU oracle. Fault injection context
(``NDS_TPU_FAULTS``) carries the query name — and the stream name
(``NDS_TPU_STREAM``) when a supervisor launched this process as one
throughput stream.

Preemption safety (README "Preemption & resume"): every completed
statement appends to a per-phase QueryJournal (name, wall, status,
result digest — resilience/journal.py) AFTER its summary lands, a
chaining SIGTERM/SIGINT drain (resilience/drain.py) lets the in-flight
query finish under ``engine.drain_s`` before exiting 75 (resumable),
and ``resume=True`` replays journaled statements and restarts
mid-phase at the next unfinished one, then writes a merged phase
report (``merged-<unit>.json``) billing every incarnation's statements
exactly once.

Hang detection (resilience/watchdog.py): the loop publishes heartbeats
(query, phase, attempt) around every dispatch and retry; with
``engine.watchdog.stall_s`` (or ``NDS_TPU_WATCHDOG=stall_s[:action]``)
a daemon watchdog dumps all-thread stacks + live metrics to
``stall-<query>.json`` in the run dir when the heartbeats go silent,
and ``action=kill`` hard-exits so a stream supervisor can restart the
process. The warehouse load runs under the same retry policy and —
with ``io.verify_digests`` — digest verification: a corrupt artifact
fails the load fast, with a diagnosable BenchReport naming the file.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from nds_tpu import obs
from nds_tpu.engine.session import Session
from nds_tpu.obs import costs as obs_costs
from nds_tpu.obs import fleet as obs_fleet
from nds_tpu.obs import memwatch
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs import profile as obs_profile
from nds_tpu.obs import telemetry as obs_telemetry
from nds_tpu.obs import trace as obs_trace
from nds_tpu.obs.trace import get_tracer
from nds_tpu.resilience import drain, faults, watchdog
from nds_tpu.resilience.journal import QueryJournal, config_digest
from nds_tpu.resilience.retry import (
    DETERMINISTIC, TRANSIENT, RetryPolicy, RetryStats, classify,
)
from nds_tpu.utils.config import EngineConfig
from nds_tpu.utils.report import BenchReport
from nds_tpu.utils.timelog import TimeLog


def _front_door_retry(policy, pipeline, unit, qname, body):
    """Retry TRANSIENT failures that never reached the pipeline
    (parse/plan phase — the executor-phase retry + ladder live inside
    engine/scheduler.py): a plan-site chaos injection or a flaky
    catalog read retries with the same backoff policy, a deterministic
    planner bug fails fast. Accounting merges into the pipeline's
    per-query stats so the summary reports ONE recovery budget."""
    from nds_tpu.obs import metrics as obs_metrics
    attempts = 0
    front_retries = 0
    front_backoff = 0.0
    start = time.monotonic()

    def _merge(st):
        if st is not None:
            st.retries += front_retries
            st.backoff_s += front_backoff

    def _flag_deadline(st):
        if st is not None and not st.deadline_exceeded:
            st.deadline_exceeded = True
            obs_metrics.counter("query_deadline_exceeded_total").inc()

    # ndslint: waive[NDS108] -- capped (attempts >= policy.max_attempts raises) with policy.delay_for backoff; while-True only because the cap check needs the classified exception first
    while True:
        try:
            out = body()
        except Exception as exc:  # noqa: BLE001 - classified below
            st = getattr(pipeline, "last_stats", None)
            pre_dispatch = (st is not None and st.attempts == 0
                            and not st.gave_up_reason)
            if not pre_dispatch:
                # the pipeline saw this query: its classification and
                # ladder already ran — nothing to add but the bill
                _merge(st)
                raise
            attempts += 1
            st.errors.append(f"{type(exc).__name__}: {exc}")
            if classify(exc) != TRANSIENT:
                st.gave_up_reason = DETERMINISTIC
                _merge(st)
                raise
            if attempts >= policy.max_attempts:
                st.gave_up_reason = f"attempts_exhausted({attempts})"
                _merge(st)
                raise
            d = policy.delay_for(front_retries)
            if (policy.deadline_s is not None
                    and time.monotonic() - start + d
                    > policy.deadline_s):
                # same pre-sleep deadline check policy.call enforces:
                # the plan window must not back off past the query's
                # wall-clock budget
                st.gave_up_reason = "deadline"
                _flag_deadline(st)
                _merge(st)
                raise
            front_retries += 1
            front_backoff += d
            obs_metrics.counter("query_retries_total").inc()
            watchdog.beat(unit, query=qname, phase="retry",
                          attempt=front_retries)
            if d > 0:
                time.sleep(d)
            continue
        _merge(getattr(pipeline, "last_stats", None))
        return out


@dataclass
class Suite:
    """What a benchmark suite must provide to the shared drivers."""
    name: str                      # "nds" | "nds_h"
    get_schemas: object            # callable(**kw) -> {table: Schema}
    parse_query_stream: object     # callable(path) -> OrderedDict
    session_for: object            # callable(factory, **kw) -> Session
    raw_ext: str = ".tbl"          # dbgen .tbl / dsdgen .dat
    # query names whose warmup is skipped (stateful parts, e.g. q15 view
    # lifecycle in NDS-H)
    warmup_skip_prefixes: tuple = ()
    schema_kwargs: dict = field(default_factory=dict)
    # suite honors the --floats/engine.floats toggle (NDS decimal vs
    # double schemas, `nds/nds_schema.py:43-47`)
    floats_toggle: bool = False


def schema_kwargs_for(suite: Suite, config: EngineConfig) -> dict:
    kwargs = dict(suite.schema_kwargs)
    if suite.floats_toggle:
        kwargs["use_decimal"] = not config.get_bool("engine.floats")
    return kwargs


def suite_schemas(suite: Suite, config: EngineConfig) -> dict:
    """Config-aware schemas — table LOADING must agree with the session
    catalog on decimal-vs-float, or money columns load as scaled ints
    under a float catalog."""
    return suite.get_schemas(**schema_kwargs_for(suite, config))


def cpu_pinned() -> bool:
    """Whether the user set ``JAX_PLATFORMS=cpu`` themselves: the one
    sanctioned way to run the device backends without a chip (the
    tests' and the CLI rehearsal's route)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def require_accelerator(backend: str) -> None:
    """``engine.backend=tpu|distributed`` means "run on a TPU", not
    "use the device executor on whatever jax finds": with no chip
    visible jax falls back to the CPU and the drivers would time
    XLA:CPU and exit 0. The one sanctioned CPU route is the user's own
    pin (:func:`cpu_pinned`) — every summary of such a run then says
    ``platform: cpu``."""
    import jax
    platform = jax.devices()[0].platform
    if platform == "tpu" or (platform == "cpu" and cpu_pinned()):
        return
    raise RuntimeError(
        f"engine.backend={backend!r} needs a TPU, but the live jax "
        f"platform is {platform!r} (JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}). Set JAX_PLATFORMS=cpu "
        f"yourself to rehearse the device executor on the CPU.")


def prepare_engine(config: EngineConfig) -> None:
    """Engine-wide activation shared by every session-construction
    path (the power drivers' make_session and the query server's
    QueryServer._build_engine): plan-cache configuration plus the
    plan-cache/XLA-compile-cache interplay the backend requires."""
    backend = config.get("engine.backend", "cpu")
    # columnar.encode/columnar.dict_union_cap activate the compressed
    # device-resident store (nds_tpu/columnar/; README "Compressed
    # columnar store"); configs without the keys defer to
    # NDS_TPU_COLUMNAR, and `off` keeps byte-identical raw behavior
    from nds_tpu import columnar
    columnar.configure_from(config)
    # cache.dir/cache.readonly activate the persistent AOT plan cache
    # for every executor this session schedules (README "Plan cache");
    # configs without the keys leave the NDS_TPU_PLAN_CACHE env
    # resolution in charge
    from nds_tpu import cache as plan_cache
    active_cache = plan_cache.configure_from(config)
    if backend in ("tpu", "distributed"):
        from nds_tpu.utils import xla_cache
        multiproc = False
        if backend == "distributed":
            # idempotent (session construction calls it again); needed
            # NOW because the cache decision below depends on world
            # size, which only exists after the runtime initializes
            from nds_tpu.parallel import multihost
            multiproc = multihost.maybe_initialize()
        require_accelerator(backend)
        if active_cache is None or multiproc or active_cache.readonly:
            # compiles amortize across driver invocations (jax's
            # persistent cache); harmless for repeated in-process queries.
            # Multi-rank worlds keep this EVEN with a plan cache: the
            # plan cache refuses multi-controller sharded programs
            # (per-rank deserialization against a local client is not
            # a supported jax path), so jax's own cache is the only
            # compile amortization those programs get. READONLY plan
            # caches keep it too: their misses never persist (the
            # reloadability hazard below only bites blobs we write),
            # so without jax's cache every miss would pay a full
            # compile on every process start
            xla_cache.enable()
        else:
            # NOT layered under the plan cache: an executable jax's
            # compile cache serves back re-serializes into a blob that
            # cannot reload ("Symbols not found" on XLA:CPU), so a
            # plan-cache session must see only REAL compiles — and a
            # prior session's enable() is process-sticky, so disable
            # explicitly
            xla_cache.disable()
    elif backend != "cpu":
        raise ValueError(f"unknown engine.backend {backend!r}")


def make_session(suite: Suite, config: EngineConfig) -> Session:
    """Session from an EngineConfig — the template/property-file layer
    actually driving engine choice (closes the reference's
    spark-submit-template contract). EVERY backend routes through the
    unified execution pipeline (engine/scheduler.py): the backend picks
    the placement *universe* (tpu -> device/chunked/cpu, distributed ->
    sharded/chunked/cpu, cpu -> cpu), and the pipeline's cost model +
    degradation ladder schedule each query within it."""
    backend = config.get("engine.backend", "cpu")
    kwargs = schema_kwargs_for(suite, config)
    # engine.init: engine-wide activation and, in a process that has
    # not yet asked jax for its devices, the backend's start
    with get_tracer().span("engine.init", backend=backend):
        prepare_engine(config)
        from nds_tpu.engine.scheduler import make_pipeline
        pipeline = make_pipeline(config, backend)
    return suite.session_for(pipeline, **kwargs)


def load_warehouse(suite: Suite, session: Session, data_dir: str,
                   fmt: str = "parquet",
                   tables: list[str] | None = None,
                   schemas: dict | None = None) -> dict:
    """Register every table from a warehouse directory; returns
    {table: seconds} setup timings (the CreateTempView analog,
    `nds/nds_power.py:95-105`)."""
    from nds_tpu.io.snapshots import MANIFEST, SnapshotLog
    if schemas is None:
        schemas = suite.get_schemas(**suite.schema_kwargs)
    log = (SnapshotLog(data_dir)
           if os.path.exists(os.path.join(data_dir, MANIFEST)) else None)
    timings = {}
    tracer = get_tracer()
    for name, schema in schemas.items():
        if tables is not None and name not in tables:
            continue
        # per-table liveness: a multi-minute warehouse load must not
        # read as a hang to the watchdog (resilience/watchdog.py)
        watchdog.beat("engine", phase="load_warehouse", table=name)
        t0 = time.perf_counter()
        # load.table: its children load.read (files to Arrow) and
        # load.build (Arrow to HostTable) open in io/csv_io.py
        with tracer.span("load.table", table=name) as span:
            table = _read_table(suite, data_dir, name, schema, fmt, log)
            span.set(rows=table.nrows, bytes=memwatch.table_bytes(table))
            # Arrow keeps what it freed: after a 30M-row table that is
            # 10 GiB of a 40 GiB host the warm-up's compiles then lack
            import pyarrow as pa
            pa.default_memory_pool().release_unused()
        session.register_table(table)
        timings[name] = time.perf_counter() - t0
    return timings


def _read_table(suite: Suite, data_dir: str, name: str, schema,
                fmt: str, log):
    """One warehouse table as a HostTable, from whichever layout
    ``data_dir`` holds it in."""
    from nds_tpu.io import csv_io
    tdir = os.path.join(data_dir, name)
    if fmt in csv_io.FORMAT_EXT:
        ext = csv_io.FORMAT_EXT[fmt]
        if log is not None and os.path.isdir(tdir):
            # versioned warehouse: the snapshot manifest names the
            # live files (maintenance commits new versions, always
            # as parquet — formats may mix, so read per-extension).
            # Delta lineages (files under <table>/_v<N>/) replay
            # through columnar.delta: base files load normally,
            # then each committed version's segments/bitmask apply
            # in order — rebuilding the same content digests and
            # merged-stats encoding specs the writer had
            paths = log.current([name]).get(name, [])
            from nds_tpu.columnar import delta
            if delta.has_delta_paths(paths):
                return delta.load_versioned(name, schema, paths, fmt)
            return csv_io.read_paths_auto(paths, name, schema, fmt)
        if os.path.isdir(tdir):
            # recursive: partitioned tables nest hive-style dirs
            paths = sorted(
                os.path.join(root, f)
                for root, _dirs, files in os.walk(tdir)
                for f in files if f.endswith(ext))
        else:
            paths = [os.path.join(data_dir, f"{name}{ext}")]
        return csv_io.read_table_fmt(paths, name, schema, fmt)
    if fmt == "raw":
        if os.path.isdir(tdir):
            from nds_tpu.io.integrity import MANIFEST_NAME
            paths = sorted(
                os.path.join(tdir, f) for f in os.listdir(tdir)
                if not f.startswith(".") and f != MANIFEST_NAME)
        else:
            paths = [os.path.join(data_dir, f"{name}{suite.raw_ext}")]
        return csv_io.read_tbl(paths, name, schema)
    raise ValueError(f"unknown input format {fmt!r}")


def run_one_query(session: Session, sql: str, qname: str = "",
                  output_prefix: str | None = None):
    result = session.sql(sql)
    if result is not None and output_prefix:
        from nds_tpu.io.result_io import write_result
        write_result(result, os.path.join(output_prefix, qname))
    return result


def run_query_stream(suite: Suite, data_dir: str, stream_path: str,
                     time_log_path: str,
                     config: EngineConfig | None = None,
                     input_format: str = "parquet",
                     json_summary_folder: str | None = None,
                     output_prefix: str | None = None,
                     warmup: int = 0,
                     query_subset: list[str] | None = None,
                     profile_dir: str | None = None,
                     extra_time_log: str | None = None,
                     resume: bool = False) -> int:
    """The power loop (`nds/nds_power.py:184-322`): every query runs
    regardless of earlier failures (the reference never aborts
    mid-stream; ``--allow_failure`` only downgrades the exit code,
    `nds/nds_power.py:391-393` — handled by the driver mains). Returns
    the number of failed queries.

    With ``NDS_TPU_METRICS_SNAP=path[:interval]`` set, a snapshot
    emitter (nds_tpu/obs/snapshot.py) publishes the metrics registry +
    run progress + heartbeat ages periodically while the stream runs,
    so long runs are observable in flight, not only post-mortem.

    Preemption safety (README "Preemption & resume"): every completed
    statement appends to a per-phase query journal, a SIGTERM/SIGINT
    drains gracefully (the in-flight query finishes under
    ``engine.drain_s``, then the process exits 75 = resumable), and
    ``resume=True`` replays journaled statements and restarts at the
    next unfinished one — an interruption loses at most the one
    in-flight query."""
    from contextlib import nullcontext

    from nds_tpu.obs.snapshot import MetricsSnapshotter
    config = config or EngineConfig()
    progress = {"suite": suite.name, "stream": stream_path,
                "queries_completed": 0, "current_query": None}
    snap = MetricsSnapshotter.from_env(progress)
    if snap:
        snap.start()
    # live device-memory telemetry (obs/telemetry.py): a no-op sampler
    # on backends without allocator stats; per-query readout happens in
    # the query loop, counter lanes export next to the span trees
    obs_telemetry.start_from_config(config)
    # compiler cost ledger on/off (obs.costs.enabled, default on)
    obs_costs.configure_from(config)
    # hang watchdog: stall reports land next to the run's artifacts
    run_dir = (json_summary_folder
               or os.path.dirname(time_log_path) or ".")
    wd = (watchdog.Watchdog.from_config(config, run_dir)
          or watchdog.Watchdog.from_env(run_dir))
    if wd:
        wd.start()
    # graceful preemption drain (resilience/drain.py): SIGTERM/SIGINT
    # lets the in-flight query finish under engine.drain_s, flushes
    # journal/trace/flight/snapshot state, and exits 75 (resumable)
    dm = drain.install(drain.drain_seconds(config), run_dir)
    if snap:
        # the force-exit path skips every finally: the final snapshot
        # must be flushed explicitly
        dm.add_flush_hook(snap.write_once)
    # supervised throughput streams carry their stream name into the
    # fault-injection context, so seeded chaos schedules can target
    # one stream (and one incarnation) of a fleet
    stream_name = os.environ.get(watchdog.STREAM_ENV)
    ctx = (faults.context(stream=stream_name) if stream_name
           else nullcontext())
    try:
        with ctx:
            return _run_query_stream(
                suite, data_dir, stream_path, time_log_path, config,
                input_format, json_summary_folder, output_prefix,
                warmup, query_subset, profile_dir, extra_time_log,
                progress, resume)
    finally:
        drain.uninstall()
        if wd:
            wd.stop()
        watchdog.clear_unit(stream_name or f"power-{suite.name}")
        # fleet teardown: the next run in this process re-arms its own
        # flight recorder / profiler against its own run dir
        obs_fleet.disarm_flight_recorder()
        obs_profile.teardown()
        obs_telemetry.stop()
        if snap:
            progress["current_query"] = None
            snap.stop()


def _run_query_stream(suite, data_dir, stream_path, time_log_path,
                      config, input_format, json_summary_folder,
                      output_prefix, warmup, query_subset, profile_dir,
                      extra_time_log, progress, resume=False) -> int:
    config = config or EngineConfig()
    if config.get_bool("io.verify_digests"):
        # sticky per process, like the env-var gate it mirrors: every
        # later read in this run verifies too (resume, maintenance)
        from nds_tpu.io import integrity
        integrity.set_verify(True)
    unit = (os.environ.get(watchdog.STREAM_ENV)
            or f"power-{suite.name}")
    run_dir_early = (json_summary_folder
                     or os.path.dirname(time_log_path) or ".")
    # query-granular resume journal (resilience/journal.py): one file
    # per phase, named by the stream unit with any restart-incarnation
    # suffix stripped (every incarnation of one stream shares a
    # journal). Fresh runs reset it; --resume replays it. Created here,
    # activated (reset/load) once the primary rank is known below.
    jname = unit.split("#")[0]
    os.makedirs(run_dir_early, exist_ok=True)
    journal = QueryJournal(
        os.path.join(run_dir_early, f"{jname}_queries.json"),
        phase=jname, digest=config_digest(config.as_dict()))
    session = make_session(suite, config)
    backend = config.get("engine.backend", "cpu")
    # multi-controller SPMD: every process computes every query, rank 0
    # records (reports/time logs/result files would otherwise collide
    # on shared storage)
    primary = True
    if backend == "distributed":
        from nds_tpu.parallel.multihost import is_primary
        primary = is_primary()
    run_dir = (json_summary_folder
               or os.path.dirname(time_log_path) or ".")
    # fleet wiring (obs/fleet.py): on a multi-rank world this runs the
    # clock handshake (every rank enters — the session above already
    # initialized the SPMD runtime), re-points NDS_TPU_TRACE at this
    # rank's trace-r<rank> shard, pins the Chrome export pid to the
    # rank, and drops the fleet-r<rank>.json sidecar ndsreport's merge
    # reads; single-rank worlds only pin the deterministic stream pid
    fleet_meta = obs_fleet.init_fleet(run_dir,
                                      distributed=(backend
                                                   == "distributed"))
    if fleet_meta and fleet_meta.get("rank"):
        # rank-0-writes holds for ANY multi-rank world, not only the
        # distributed backend: a fleet of rank-local sessions (each
        # rank executing on its own devices) still shares the run dir
        primary = False
    # activate the journal now that the primary rank is known:
    # non-primary ranks LOAD it (their replay decisions must match the
    # primary's) but never write the shared file. A supervisor-
    # relaunched incarnation (unit '<name>#rN' — restart OR exit-75
    # resume) implicitly resumes the journal too: its --query_subset
    # already scopes what re-runs, and a reset here would wipe the
    # first incarnation's completion records (digests, start marks —
    # exactly the evidence the journal exists to preserve)
    journal.readonly = not primary
    if resume or "#r" in unit:
        if journal.load():
            inc = journal.begin_incarnation()
            done = sorted(journal.completed())
            print(f"== resuming {jname} (incarnation {inc}): "
                  f"{len(done)} journaled quer"
                  f"{'y' if len(done) == 1 else 'ies'} replayed ==")
    else:
        journal.reset()
    dm = drain.manager()
    if dm is not None:
        # drain-deadline force exit: the abandoned in-flight query is
        # journaled explicitly not-done before the process dies
        dm.add_flush_hook(
            lambda: journal.mark_aborted(progress.get("current_query")))
    flight = obs_fleet.arm_flight_recorder(
        run_dir, rank=(fleet_meta or {}).get("rank", 0))
    # on-demand XLA profiler (obs/profile.py): trigger policy from
    # engine.profile.* / NDS_TPU_PROFILE; also arms the on-stall
    # capture hook the watchdog report points at
    profiler = obs_profile.configure(config)
    app_id = f"{suite.name}-tpu-{backend}-{int(time.time())}"
    tlog = TimeLog(app_id)
    total_start = time.perf_counter()

    # the warehouse load runs under the SAME retry policy shape as
    # queries — transient io hiccups retry, a CorruptArtifact (digest
    # mismatch, io/integrity.py) is deterministic and fails the run
    # FAST with a BenchReport naming the file and both digests,
    # retries=0 — but NOT under the per-QUERY deadline (a 25-table
    # load is not a query). Built by the pipeline module, the single
    # home of the engine retry wiring.
    from nds_tpu.engine.scheduler import load_policy as _mk_load_policy
    front_policy = RetryPolicy.from_config(config)
    load_policy = _mk_load_policy(front_policy)
    watchdog.beat(unit, phase="load_warehouse")
    lstats = RetryStats()
    load_hold: dict = {}

    def _load_bracket():
        def _load():
            return load_warehouse(suite, session, data_dir,
                                  input_format,
                                  schemas=suite_schemas(suite, config))
        try:
            load_hold["setup"] = load_policy.call(_load, stats=lstats)
        except Exception as exc:  # noqa: BLE001 - re-raised below
            load_hold["error"] = exc
            raise

    load_report = BenchReport("load_warehouse", config.as_dict())
    load_report.report_on(_load_bracket)
    load_report.attach_retry(lstats)
    load_report.attach_degradations()
    if "error" in load_hold:
        # post-mortem before the raise: a CorruptArtifact (or any
        # final load failure) dumps the flight ring so the run leaves
        # metrics + heartbeats even though no query ever ran
        if flight:
            err = load_hold["error"]
            fpath = flight.dump(
                f"load-failed:{type(err).__name__}")
            load_report.attach_flight(fpath,
                                      reason=f"{type(err).__name__}",
                                      entries=len(flight.ring))
        if json_summary_folder and primary:
            os.makedirs(json_summary_folder, exist_ok=True)
            load_report.write_summary(prefix=f"power-{app_id}",
                                      out_dir=json_summary_folder)
        raise load_hold["error"]
    setup = load_hold["setup"]
    for tname, secs in setup.items():
        tlog.add(f"CreateTempView {tname}", int(secs * 1000))

    queries = suite.parse_query_stream(stream_path)
    if query_subset:
        queries = type(queries)(
            (q, s) for q, s in queries.items() if q in query_subset)
    progress["app_id"] = app_id
    progress["queries_total"] = len(queries)
    if json_summary_folder:
        os.makedirs(json_summary_folder, exist_ok=True)
    # device-level traces for the whole stream (XLA op timeline per
    # query via named TraceAnnotations) — the jax-profiler analog of
    # the reference's setJobGroup Spark-UI hook; begin/end live in
    # obs/profile.py (NDS113: the engine's one jax.profiler owner),
    # and the outer finally's obs_profile.teardown() closes the trace
    # even when an exception carries past this loop
    from contextlib import nullcontext
    if profile_dir and profiler:
        # single-active-trace invariant: with the whole stream under
        # capture, every per-query/stall trigger would fail to start —
        # and a stall report would publish a capture path that could
        # never be filled. Explicitly one or the other, decided BEFORE
        # the stream trace starts (no junk capture from a start/stop/
        # restart dance).
        print("[obs] --profile_dir stream trace active: per-query/"
              "stall profile triggers disabled for this run")
        obs_profile.teardown()
        profiler = None
    stream_prof = obs_profile.begin_stream_trace(profile_dir)
    failures = 0
    # queries the ladder let finish on the CPU oracle under a device
    # backend: the exit code stays the reference's, so the closing
    # lines name them — a CPU wall-clock must not pass unseen
    ended_on_cpu: list = []
    replayed_ms = 0.0
    power_start = time.perf_counter()
    # query-boundary pipelining (engine/pipeline_io.py; README
    # "Pipelined execution"): with ``engine.prefetch.boundary`` on,
    # query N+1 dispatches while query N's compactor output is still
    # in flight D2H — the async handle's result() is the sync point,
    # and each query's bracket is its dispatch-start -> result-done
    # window (the same dispatch->result wall contract the in-process
    # throughput loop already bills pipelined queries under)
    from nds_tpu.engine import pipeline_io
    boundary = pipeline_io.boundary_enabled(config)
    tracer = get_tracer()
    pending: "dict | None" = None
    # per-query metric windows partition at finalize boundaries in
    # pipelined mode (query N's dispatch-side counters bill to N-1's
    # window; the per-run totals stay exact — README "Pipelined
    # execution"); None = fresh snapshot at the next dispatch
    mbase: "dict | None" = None

    def _resolve(p) -> None:
        """Blocking half of one dispatched query: result() is the sync
        point; failures bill to THIS query's bracket exactly as
        report_on's except-clause did."""
        err = p.pop("dispatch_error", None)
        if err is None:
            try:
                with tracer.attach(p["span"]), \
                        faults.context(query=p["qname"]), \
                        p["report"].focus_failures():
                    out = p["handle"].result()
                p["result"] = out
                if out is not None and p["out_pref"]:
                    from nds_tpu.io.result_io import write_result
                    write_result(out, os.path.join(p["out_pref"],
                                                   p["qname"]))
            except Exception as exc:  # noqa: BLE001 - billed below
                err = exc
        span = p["span"]
        if span:
            if err is not None:
                span.set(error=f"{type(err).__name__}: {err}")
            span.end()
        p["summary"] = p["report"].end_async(error=err)

    def _post(p) -> None:
        """Everything that used to follow the report bracket: summary
        attachments, metrics delta, flight/profiler bookkeeping, the
        TimeLog row, the summary write, and the journal append."""
        nonlocal failures, mbase
        qname = p["qname"]
        report, summary = p["report"], p["summary"]
        # engine-side perf accounting: compile vs execute vs
        # device->host materialization, fed by the query span tree
        # (obs.query_timings falls back to legacy last_timings; the
        # CPU oracle has neither). The pipeline's async handles
        # re-point the per-query obs surface at result(), so this
        # reads THIS query's numbers even under boundary overlap
        executor = session._executor_factory(session.tables)
        timings = obs.query_timings(executor)
        if timings:
            # dunder keys are executor-internal accounting state (the
            # memwatch release token), never part of the summary
            summary["engineTimings"] = {k: round(v, 3)
                                        for k, v in timings.items()
                                        if not k.startswith("__")}
        if p["span"]:
            summary["spans"] = p["span"].to_dict()
        # the pipeline owns retry + scheduling accounting; a bare
        # executor factory (tests driving run_query_stream with a
        # custom session) degrades to empty stats
        report.attach_retry(getattr(executor, "last_stats", None)
                            or RetryStats())
        report.attach_schedule(getattr(executor, "last_schedule",
                                       None))
        if backend != "cpu" and summary.get("placement") == "cpu":
            ended_on_cpu.append(qname)
        report.attach_memory(p.get("hwm") if p.get("hwm") is not None
                             else memwatch.high_water())
        # compiler-truth cost ledger + HBM-occupancy series (the
        # overlapped path snapshotted both at the successor's reset;
        # the sync path reads the live windows here), cross-checked
        # against the hand-rolled ops_est roofline input
        cost_block = (p.get("cost") if p.get("cost") is not None
                      else obs_costs.query_block())
        report.attach_cost(obs_costs.cross_check(
            cost_block, (timings or {}).get("ops_est")))
        report.attach_telemetry(
            p.get("telemetry") if p.get("telemetry") is not None
            else obs_telemetry.query_block())
        # resume bookkeeping: which incarnation served this query, the
        # result's content digest (what the soak gate diffs against a
        # clean run), and any torn-state degradations this process saw
        report.attach_incarnation(journal.incarnation)
        from nds_tpu.io.result_io import result_digest
        rdigest = result_digest(p.pop("result", None))
        report.attach_result_digest(rdigest)
        report.attach_degradations()
        elapsed_ms = summary["queryTimes"][-1]
        obs_metrics.counter("queries_total").inc()
        obs_metrics.histogram("query_seconds").observe(
            elapsed_ms / 1000.0)
        if not report.is_success():
            failures += 1
            obs_metrics.counter("query_failures_total").inc()
        before = (p["metrics_before"] if p["metrics_before"] is not None
                  else mbase) or obs_metrics.snapshot()
        mdelta = obs_metrics.delta(before, obs_metrics.snapshot())
        if mdelta:
            summary["metrics"] = mdelta
        # plan-cache activity for THIS query (hits/misses/bytes +
        # deserialize ms), derived from the same metrics delta
        report.attach_cache(mdelta, timings)
        # which relational kernels the compiled program actually used
        # (engine/kernels.py): the block ndsreport diff watches for
        # silent demotions to the slow paths. Read from the executor's
        # own dict — the span-fed timings strip dunder side-channels
        report.attach_kernels(getattr(executor, "last_timings", None)
                              or timings)
        # XLA capture bookkeeping: the profile block when a trigger
        # fired, and the wall-clock observation arming the slow
        # trigger for this query's NEXT run
        if p.get("cap_info"):
            report.attach_profile(p["cap_info"])
        elif p.get("stall_path") and profiler:
            # the drained reservation's capture never started: put it
            # back so a later query can still fill the stall report's
            # forward pointer
            profiler.requeue_pending(p["stall_path"])
        if profiler:
            profiler.observe(qname, elapsed_ms)
        # flight recorder (obs/fleet.py): the ring holds the last N
        # span trees; a FINAL-attempt failure dumps it so the failed
        # query's summary points at a post-mortem
        if flight:
            flight.record(qname, summary["queryStatus"][-1],
                          p.get("span"), wall_ms=elapsed_ms,
                          metrics_delta=mdelta)
            if summary["queryStatus"][-1] == "Failed":
                fpath = flight.dump(f"query-failed:{qname}")
                report.attach_flight(
                    fpath, reason=f"query-failed:{qname}",
                    entries=len(flight.ring))
        tlog.add(qname, elapsed_ms)
        progress["queries_completed"] += 1
        watchdog.beat(unit, query=qname, phase="done")
        print(f"====== Run {qname} ======")
        print(f"Time taken: {elapsed_ms} millis for {qname}")
        if json_summary_folder and primary:
            report.write_summary(prefix=f"power-{app_id}",
                                 out_dir=json_summary_folder)
        # journal AFTER the summary landed: resume must never skip a
        # statement whose summary is missing (the one-query loss window
        # is between this append and the previous instruction)
        journal.record(qname, elapsed_ms, summary["queryStatus"][-1],
                       result_digest=rdigest)
        # exports parked during the bracket flush now; the metric
        # window for the NEXT pipelined query starts here
        tracer.flush_exports()
        # device-memory counter lanes ride the same trace stream as
        # the spans: telemetry samples since the last drain, plus one
        # per-query HWM point — Perfetto renders them as memory tracks
        trace_path = os.environ.get(obs_trace.TRACE_ENV)
        if trace_path:
            events = [obs_trace.counter_event(
                "device_memory_bytes", {"bytes_in_use": b}, t=t)
                for t, b in obs_telemetry.drain_counter_events()]
            hwm_bytes = (summary.get("memory")
                         or {}).get("device_hwm_bytes")
            if hwm_bytes:
                events.append(obs_trace.counter_event(
                    "device_hwm_bytes", {"hwm": hwm_bytes}))
            try:
                obs_trace.export_counters(events, trace_path)
            except OSError:  # tracing must never fail the query
                pass
        mbase = obs_metrics.snapshot()

    def _finalize_pending() -> None:
        nonlocal pending
        if pending is None:
            return
        p, pending = pending, None
        _resolve(p)
        _post(p)

    # exports park while query brackets are open (even a ~ms inline
    # write would skew span totals vs the TimeLog row); _post flushes
    # after each bracket closes
    tracer.defer_exports = True
    try:
        for qname, sql in queries.items():
            watchdog.beat(unit, query=qname, phase="dispatch")
            # preemption drain checkpoint: once a SIGTERM/SIGINT was
            # seen, stop HERE — the finished queries (the overlapped
            # in-flight one resolves first, so the journal stays
            # consistent) are journaled, the process exits 75, and
            # --resume picks up at this statement
            if drain.requested():
                _finalize_pending()
            drain.check_boundary()
            if journal.done(qname):
                # resumed incarnation: replay the journaled outcome
                # (time log row + failure accounting) so the merged
                # phase totals match an uninterrupted run — never
                # re-execute
                e = journal.entry(qname)
                wall = float(e.get("wall_ms") or 0)
                replayed_ms += wall
                tlog.add(qname, int(wall))
                if e.get("status") == "Failed":
                    failures += 1
                progress["queries_completed"] += 1
                print(f"====== Replay {qname} (journaled "
                      f"{e.get('status')}, incarnation "
                      f"{e.get('incarnation', 0)}) ======")
                continue
            if warmup and not qname.startswith(
                    suite.warmup_skip_prefixes):
                # warmup executes synchronously through the session:
                # resolve any overlapped query first. Span recording
                # off during warmup: untimed passes would otherwise
                # append orphan root trees to the Chrome trace,
                # uncorrelated with any CSV row. Fault injection is
                # suppressed too — warmup must not consume the timed
                # query's fault budget
                _finalize_pending()
                wtracer = get_tracer()
                was_enabled = wtracer.enabled
                wtracer.enabled = False
                try:
                    with faults.suppress():
                        for _ in range(warmup):
                            try:
                                run_one_query(session, sql)
                            except Exception:
                                break
                finally:
                    wtracer.enabled = was_enabled
                mbase = None  # warmup counters are nobody's delta
            progress["current_query"] = qname
            # execution-start mark BEFORE dispatch: a kill -9 mid-query
            # leaves a start with no completion — the journal evidence
            # that exactly this one query was lost (under boundary
            # overlap: at most the TWO in-flight queries)
            journal.start(qname)
            # per-query XLA capture triggers force the sync path: a
            # capture bracket cannot span overlapped brackets
            trigger = profiler.trigger_for(qname) if profiler else None
            stall_path = profiler.take_pending() if profiler else None
            run_sync = (not boundary or bool(trigger)
                        or bool(stall_path) or bool(stream_prof))
            if run_sync:
                _finalize_pending()
            # fresh per-query memory/cost/telemetry windows: each is
            # monotone within the query and resets here; an overlapped
            # predecessor's readings snapshot into its record first
            # (the reset precedes this query's dispatch AND the
            # predecessor's _post, so dispatches land in the fresh
            # window and _post reads the snapshot)
            if pending is not None:
                pending["hwm"] = memwatch.high_water()
                pending["cost"] = obs_costs.query_block()
                pending["telemetry"] = obs_telemetry.query_block()
            memwatch.reset_query()
            obs_costs.reset_query()
            obs_telemetry.reset_query()
            report = BenchReport(qname, config.as_dict())
            out_pref = output_prefix if primary else None
            # a query that fails BEFORE reaching the executor
            # (parse/plan errors) must not inherit the previous
            # query's span/timings/stats into its summary — the
            # pipeline's reset covers exactly that window (an
            # overlapped predecessor's handle re-points the surface
            # back at resolve time)
            pre_ex = session._executor_factory(session.tables)
            if hasattr(pre_ex, "reset_query"):
                pre_ex.reset_query()
            else:
                pre_ex.last_query_span = None
                pre_ex.last_timings = {}
            # pipelined queries take their metric window from the
            # previous finalize (partition — no double counting);
            # sync queries snapshot here, exactly as before
            metrics_before = (obs_metrics.snapshot()
                              if run_sync or pending is None else None)
            # per-query root span: brackets EXACTLY what queryTimes/
            # TimeLog brackets (begin_async -> end_async), so span
            # totals and the CSV agree; forced root — under overlap
            # the next dispatch must not nest inside it
            qspan = tracer.begin("query", parent=None, keep=True,
                                 query=qname, suite=suite.name,
                                 backend=backend)
            p = {"qname": qname, "report": report, "span": qspan,
                 "out_pref": out_pref, "metrics_before": metrics_before,
                 "hwm": None, "cost": None, "telemetry": None,
                 "stall_path": stall_path}
            report.begin_async()

            def _dispatch(_p=p, _sql=sql, _ex=pre_ex):
                # retry + the degradation ladder live INSIDE the
                # pipeline and surface at the handle (dispatch-time
                # transients rerun there; result-time transients rerun
                # at result()); _front_door_retry covers only the
                # pre-dispatch (parse/plan) window the pipeline cannot
                # see
                try:
                    with tracer.attach(_p["span"]), \
                            faults.context(query=_p["qname"]), \
                            _p["report"].focus_failures():
                        _p["handle"] = _front_door_retry(
                            front_policy, _ex, unit, _p["qname"],
                            lambda: session.sql_async(_sql))
                except Exception as exc:  # noqa: BLE001 - billed later
                    _p["dispatch_error"] = exc

            if run_sync:
                if trigger or stall_path:
                    # a stall reservation drains into THIS query's
                    # capture — into the reserved path (the stall
                    # report already points there), under the query's
                    # own trigger when it has one
                    cap_cm = profiler.capture(qname, trigger or "stall",
                                              path=stall_path)
                else:
                    cap_cm = nullcontext({})
                with cap_cm as cap_info:
                    if stream_prof:
                        with obs_profile.annotate(qname):
                            _dispatch()
                            _resolve(p)
                    else:
                        _dispatch()
                        _resolve(p)
                p["cap_info"] = cap_info
                _post(p)
            else:
                # the overlap: dispatch THIS query, then resolve the
                # previous one while this one's device work (and D2H)
                # is in flight
                _dispatch()
                _finalize_pending()
                pending = p
        _finalize_pending()
    finally:
        tracer.defer_exports = False
        if pending is not None:
            # exceptional unwind with a query still in flight: resolve
            # best-effort so neither the handle nor the journal strand
            try:
                _finalize_pending()
            except BaseException:  # noqa: BLE001 - already unwinding
                pending = None
        tracer.flush_exports()
    obs_profile.end_stream_trace()
    # resumed incarnations bill the replayed queries' journaled walls
    # into the phase total: the merged Power Test Time approximates the
    # uninterrupted loop (per-query walls, minus inter-query overhead)
    power_ms = int((time.perf_counter() - power_start) * 1000
                   + replayed_ms)
    tlog.add("Power Test Time", power_ms)
    total_ms = int((time.perf_counter() - total_start) * 1000)
    tlog.add("Total Time", total_ms)
    if primary:
        tlog.write(time_log_path)
        if extra_time_log:
            # second copy of the time log, e.g. on shared storage — the
            # reference's --extra_time_log writes the same rows via
            # Spark to a cloud path (`nds/nds_power.py:305-308`)
            tlog.write(extra_time_log)
    if journal.incarnation > 0 and primary and json_summary_folder:
        # one merged phase report over every incarnation's partial
        # BenchReports (utils/report.merge_incarnations): each
        # statement billed once, latest incarnation wins — the doc the
        # soak gate and downstream metric consumers read instead of
        # stitching incarnations themselves
        from nds_tpu.io.integrity import write_json_atomic
        from nds_tpu.obs import analyze as _analyze
        from nds_tpu.utils.report import merge_incarnations
        known = set(queries)
        merged = merge_incarnations(
            [s for s in _analyze.load_summaries(json_summary_folder)
             if s.get("query") in known], phase=jname)
        write_json_atomic(
            os.path.join(json_summary_folder, f"merged-{jname}.json"),
            merged)
    print(f"Power Test Time: {power_ms} millis")
    if ended_on_cpu:
        print(f"WARNING: {len(ended_on_cpu)} quer"
              f"{'y' if len(ended_on_cpu) == 1 else 'ies'} finished on "
              f"the cpu placement under engine.backend={backend} — "
              f"CPU wall-clocks, not device times: "
              f"{', '.join(ended_on_cpu)}")
    return failures


def subprocess_env(backend: str | None = None) -> dict:
    """Environment for phase subprocesses: nds_tpu importable regardless
    of the orchestrator's cwd (preserving the ambient PYTHONPATH).

    A cpu-backend (host-only) subprocess is pinned to
    ``JAX_PLATFORMS=cpu``: a chip belongs to one process at a time, so
    a datagen/transcode/validate/CPU-oracle child must never open the
    accelerator a sibling device phase (or its own parent) needs.
    Device-backend children inherit the caller's environment as is —
    a user's own ``JAX_PLATFORMS=cpu`` pin (tests, CLI rehearsal)
    carries through and is reported as ``platform: cpu`` in every
    summary (utils/report.py), never as a device time."""
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [root] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    if backend == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def add_config_args(parser) -> None:
    """The --template/--property_file/--trace CLI surface shared by
    every driver (reference: spark-submit-template sources the
    template, `nds_power.py:324-330` merges the property file)."""
    parser.add_argument("--template",
                        help="engine template file (k=v with ${ENV:-default})")
    parser.add_argument("--property_file",
                        help="k=v property file overriding the template")
    parser.add_argument("--trace",
                        help="append per-query Chrome trace-event JSONL "
                             "here (same as NDS_TPU_TRACE=path; see "
                             "README Observability)")
    parser.add_argument("--cache_dir",
                        help="persistent AOT plan-cache directory "
                             "(cache.dir; same as NDS_TPU_PLAN_CACHE — "
                             "README 'Plan cache')")
    parser.add_argument("--cache_readonly", action="store_true",
                        help="consult the plan cache but never write it "
                             "(cache.readonly)")


def config_from_args(args, default_backend: str = "tpu") -> EngineConfig:
    """CLI --backend > property file > template > the driver's default
    (matching spark-submit-template < --property_file precedence with
    spark-submit's own CLI last)."""
    if getattr(args, "trace", None):
        os.environ["NDS_TPU_TRACE"] = args.trace
    cli_backend = getattr(args, "backend", None)
    overrides = {}
    if cli_backend is not None:
        overrides["engine.backend"] = cli_backend
    if getattr(args, "cache_dir", None):
        overrides["cache.dir"] = args.cache_dir
    if getattr(args, "cache_readonly", False):
        overrides["cache.readonly"] = "1"
    cfg = EngineConfig(getattr(args, "template", None),
                       getattr(args, "property_file", None), overrides)
    if "engine.backend" not in cfg.explicit:
        cfg.conf["engine.backend"] = default_backend
    return cfg
