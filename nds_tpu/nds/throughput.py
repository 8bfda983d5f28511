"""NDS Throughput Run: N concurrent 99-query streams.

The reference does this with xargs -P spawning one spark-submit per
stream (`nds/nds-throughput:23`). Here each stream is one subprocess
running the NDS power driver (process isolation keeps per-stream XLA
compile caches and HBM pools independent — the analog of per-stream
Spark apps); throughput elapse is max(end) - min(start) rounded up to
0.1 s (`nds/nds_bench.py:138-157,207-208`).

Subprocess streams run SUPERVISED (resilience/supervise.py): each
child publishes heartbeats through its per-stream metrics-snapshot
file, a hung stream is killed (child watchdog self-exit, parent
SIGTERM→SIGKILL backstop) once ``--stall_s`` is set, a dead stream
restarts at most once from its last completed query, and exit codes /
signals / stalls / restarts land in ``throughput_summary.json``
instead of a bare failure count.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ELAPSE_FILE = "throughput_elapse.json"


def refuse_chip_fanout(backend: str, n_streams: int) -> None:
    """A chip belongs to one process at a time: ``backend=tpu`` streams
    launched as N subprocesses would each open the same device, and all
    but the first fail or hang. Fail fast, before anything is spawned,
    unless the user pinned ``JAX_PLATFORMS=cpu`` (the CPU rehearsal,
    where children share nothing)."""
    from nds_tpu.utils.power_core import cpu_pinned
    if backend != "tpu" or n_streams < 2 or cpu_pinned():
        return
    raise RuntimeError(
        f"{n_streams} subprocess streams cannot share one TPU chip "
        f"(--backend tpu): run the throughput test with --in_process "
        f"(one process time-shares the chip across all streams)")


def write_elapse(out_dir: str, elapse: float, codes: list) -> None:
    """The throughput phase's report file: the bench orchestrators run
    this driver as a child and read Ttt back from here (state passes
    between phases via files, never via the orchestrator's memory)."""
    from nds_tpu.io.integrity import write_json_atomic
    write_json_atomic(os.path.join(out_dir, ELAPSE_FILE),
                      {"elapse_s": elapse, "codes": list(codes)})


def read_elapse(out_dir: str) -> "tuple[float, list]":
    with open(os.path.join(out_dir, ELAPSE_FILE)) as f:
        doc = json.load(f)
    return float(doc["elapse_s"]), list(doc["codes"])


def _stream_specs(data_dir: str, stream_paths: list[str], out_dir: str,
                  backend: str, input_format: str,
                  allow_failure: bool, module: str, parse_stream):
    """Supervised-stream specs for a power-driver fleet (shared with
    NDS-H, which passes its own module + stream parser)."""
    from nds_tpu.obs.snapshot import SNAP_ENV, parse_spec
    from nds_tpu.obs.trace import TRACE_ENV
    from nds_tpu.resilience.supervise import StreamSpec
    from nds_tpu.utils.power_core import subprocess_env
    specs = []
    for sp in stream_paths:
        name = os.path.splitext(os.path.basename(sp))[0]
        env = subprocess_env(backend)
        hb = os.path.join(out_dir, f"{name}_hb.json")
        if env.get(TRACE_ENV):
            # one trace shard PER STREAM: N children appending to one
            # JSONL interleave partial lines under buffered writes.
            # Each child also pins its export pid to the stream index
            # (obs/fleet.py reads NDS_TPU_STREAM), so the merged
            # timeline's lanes are deterministic across runs
            troot, text = os.path.splitext(env[TRACE_ENV])
            env[TRACE_ENV] = f"{troot}_{name}{text or '.jsonl'}"
        if env.get(SNAP_ENV):
            # one snapshot file PER STREAM: N subprocesses inheriting
            # the same path would race on it (and on its .tmp),
            # exactly what the atomic-write contract forbids. The
            # re-pointed file doubles as the supervisor's heartbeat
            # source
            path, interval = parse_spec(env[SNAP_ENV])
            root, ext = os.path.splitext(path)
            hb = f"{root}_{name}{ext or '.json'}"
            env[SNAP_ENV] = f"{hb}:{interval}"

        def make_cmd(incarnation, remaining, _sp=sp, _name=name):
            suffix = "" if incarnation == 0 else f"_r{incarnation}"
            tlog = os.path.join(out_dir, f"{_name}{suffix}_time.csv")
            cmd = [sys.executable, "-m", module,
                   data_dir, _sp, tlog, "--backend", backend,
                   "--input_format", input_format]
            if allow_failure:
                cmd.append("--allow_failure")
            if remaining:
                cmd += ["--query_subset", *remaining]
            return cmd

        specs.append(StreamSpec(
            name=name, make_cmd=make_cmd, hb_path=hb,
            queries=list(parse_stream(sp)), env=env))
    return specs


def run_streams(data_dir: str, stream_paths: list[str], out_dir: str,
                backend: str = "tpu",
                input_format: str = "parquet",
                allow_failure: bool = False,
                stall_s: float | None = None,
                max_restarts: int | None = None
                ) -> tuple[float, list[int]]:
    """Launch one supervised power-run subprocess per stream; returns
    (throughput_elapse_seconds, per-stream final exit codes). With
    ``stall_s`` set, hung streams are killed and restarted (up to
    ``max_restarts`` times, default once) from their last completed
    query; ``throughput_summary.json`` in ``out_dir`` records the
    supervision verdicts either way — including the exact queries a
    degraded stream skipped."""
    from nds_tpu.nds.streams import parse_query_stream
    from nds_tpu.resilience.supervise import (
        StreamSupervisor, describe_summary,
    )
    refuse_chip_fanout(backend, len(stream_paths))
    os.makedirs(out_dir, exist_ok=True)
    specs = _stream_specs(data_dir, stream_paths, out_dir, backend,
                          input_format, allow_failure,
                          "nds_tpu.nds.power", parse_query_stream)
    # restarts need the heartbeat plumbing stall_s arms: without it a
    # completed-with-failures stream (exit 1, no snapshot) would be
    # indistinguishable from a crash and get re-run
    if max_restarts is None:
        max_restarts = 1 if stall_s else 0
    sup = StreamSupervisor(specs, out_dir, stall_s=stall_s,
                           max_restarts=max_restarts)
    elapse, codes, summary = sup.run()
    print(describe_summary(summary))
    # round up to 0.1 s, the reference's Ttt granularity
    elapse = math.ceil(elapse * 10) / 10.0
    return elapse, codes


def run_streams_inprocess(data_dir: str, stream_paths: list[str],
                          out_dir: str, backend: str = "tpu",
                          input_format: str = "parquet",
                          suite=None) -> tuple[float, list[int]]:
    """Single-process multi-stream throughput for ONE-chip runs
    (``suite``: a power_core.Suite; default NDS).

    The reference splits cluster executors between concurrent streams
    (`nds/README.md:530-535`); N subprocesses each opening the same
    single TPU chip would instead contend for (or fail to share) HBM.
    This mode time-shares the chip: the warehouse loads ONCE, one
    Session serves every stream (shared device buffers + compile cache
    — streams differ in parameter bindings, so each still compiles its
    own programs), and queries interleave round-robin so all streams
    progress together the way the xargs -P fan-out does. Per-stream time
    logs keep the reference format. Returns (elapse_s, failure counts).

    ``NDS_TPU_METRICS_SNAP`` is honored here too: this mode never
    enters ``run_query_stream`` (it drives ``session.sql_async``
    directly), so it owns its own snapshot emitter."""
    from nds_tpu.obs.snapshot import MetricsSnapshotter
    progress = {"mode": "throughput-inprocess",
                "streams": len(stream_paths),
                "queries_completed": 0, "current_query": None}
    snap = MetricsSnapshotter.from_env(progress)
    if snap:
        snap.start()
    if suite is None:
        from nds_tpu.nds.power import SUITE as suite
    try:
        return _run_streams_inprocess(suite, data_dir, stream_paths,
                                      out_dir, backend, input_format,
                                      progress)
    finally:
        if snap:
            progress["current_query"] = None
            snap.stop()


def _run_streams_inprocess(suite, data_dir, stream_paths, out_dir,
                           backend, input_format, progress
                           ) -> tuple[float, list[int]]:
    from nds_tpu.resilience import faults
    from nds_tpu.resilience.journal import QueryJournal, config_digest
    from nds_tpu.resilience.retry import (
        TRANSIENT, RetryPolicy, RetryStats, classify,
    )
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    from nds_tpu.utils.report import BenchReport
    from nds_tpu.utils.timelog import TimeLog

    os.makedirs(out_dir, exist_ok=True)
    # clock starts BEFORE the warehouse load: subprocess mode's window
    # (max(end) - min(start)) includes each stream's load, and the Ttt
    # terms must be measured under the same rule in both modes
    start = time.time()
    config = EngineConfig(overrides={"engine.backend": backend})
    policy = RetryPolicy.from_config(config)
    session = power_core.make_session(suite, config)
    pipeline = session._executor_factory(session.tables)
    power_core.load_warehouse(
        suite, session, data_dir, input_format,
        schemas=power_core.suite_schemas(suite, config))
    streams = []
    for sp in stream_paths:
        name = os.path.splitext(os.path.basename(sp))[0]
        # per-stream query journal (resilience/journal.py): every
        # completed statement lands on disk as it finishes, so an
        # interrupted round leaves a per-stream completion record with
        # result digests, not just whatever stdout survived
        qj = QueryJournal(
            os.path.join(out_dir, f"{name}_queries.json"), phase=name,
            digest=config_digest(config.as_dict()))
        qj.reset()
        streams.append({
            "name": name,
            "queries": list(suite.parse_query_stream(sp).items()),
            "tlog": TimeLog(f"{suite.name}-tpu-throughput-{name}"),
            "failures": 0,
            # per-stream BenchReport material: statuses/exception text
            # per query, so throughput failures are diagnosable from
            # the report JSON (the power path's `exceptions` contract)
            "statuses": [],
            "exceptions": [],
            "qtimes": [],
            "retries": 0,
            "reschedules": 0,
            "journal": qj,
        })
    # flatten round-robin, then run with `engine.concurrent_tasks`
    # queries in flight: dispatch is async on the device engine
    # (Session.sql_async), so device execution of query N+1 overlaps
    # host materialization of query N — the wired-up analog of
    # spark.rapids.sql.concurrentGpuTasks (`nds/power_run_gpu.template:38`)
    interleaved = []
    for k in range(max(len(s["queries"]) for s in streams)):
        for s in streams:
            if k < len(s["queries"]):
                interleaved.append((s, *s["queries"][k]))
    depth = max(config.get_int("engine.concurrent_tasks", 2), 1)
    inflight: list = []

    def _finish_one():
        s, qname, sql, t0, handle, err = inflight.pop(0)
        res = None
        if err is None:
            try:
                # retry + the degradation ladder run INSIDE the
                # pipeline (engine/scheduler.py): a transient failure
                # surfaces here at result() and reruns down the ladder
                # on this blocked call, so the stream keeps its
                # pipelining for the healthy queries and pays the
                # recovery only on the sick one
                with faults.context(query=qname, stream=s["name"]):
                    res = handle.result()
            except Exception as exc:  # noqa: BLE001
                err = exc
        # per-query recovery accounting comes from the pipeline's
        # handle-local stats (re-pointed at result() even under
        # interleaved dispatch); a dispatch-time failure (handle None:
        # parse/plan or a deterministic classify) never dispatched, so
        # it has nothing to read
        if handle is not None:
            st = getattr(pipeline, "last_stats", None)
            sched = getattr(pipeline, "last_schedule", None) or {}
            if st is not None:
                s["retries"] += st.retries
            if sched.get("reschedules"):
                s["reschedules"] += sched["reschedules"]
        if err is not None:
            import traceback
            traceback.print_exception(type(err), err, err.__traceback__)
            s["failures"] += 1
            # exception text into the stream's report summary: a
            # throughput failure used to be a bare count, invisible in
            # the report JSON
            s["exceptions"].append(
                f"{qname}: {type(err).__name__}: {err}")
            s["statuses"].append("Failed")
        else:
            s["statuses"].append("Completed")
        done = time.time()
        progress["queries_completed"] += 1
        # dispatch->result bracket; queue wait from pipelining is
        # inherent to a time-shared chip, exactly as a query inside a
        # reference throughput stream waits on cluster resources
        wall_ms = int((done - t0) * 1000)
        s["tlog"].add(qname, wall_ms)
        s["qtimes"].append(wall_ms)
        s["first_t0"] = min(s.get("first_t0", t0), t0)
        s["last_done"] = done
        # journal the completion (status + wall + result digest): the
        # same per-statement durability contract as the power loop
        from nds_tpu.io.result_io import result_digest
        s["journal"].record(qname, wall_ms, s["statuses"][-1],
                            result_digest=result_digest(res))

    from nds_tpu.resilience import watchdog
    for s, qname, sql in interleaved:
        progress["current_query"] = f"{s['name']}/{qname}"
        # heartbeat per dispatch: the in-process fleet shows liveness
        # to any armed watchdog exactly like a subprocess stream does
        watchdog.beat(s["name"], query=qname, phase="dispatch")
        s["journal"].start(qname)
        t0 = time.time()
        handle, err = None, None
        try:
            # the stream.query chaos site fires inside the pipeline's
            # per-attempt dispatch (engine/scheduler.py), under this
            # query/stream context
            with faults.context(query=qname, stream=s["name"]):
                handle = session.sql_async(sql)
        except Exception as exc:  # noqa: BLE001
            err = exc
            if classify(exc) == TRANSIENT and policy.max_attempts > 1:
                # a dispatch-time transient never reached the pipeline
                # (parse/plan window): re-run synchronously under the
                # remaining budget, same contract as the power path's
                # front-door retry
                st = RetryStats()
                from nds_tpu.obs import metrics as obs_metrics
                obs_metrics.counter("query_retries_total").inc()
                s["retries"] += 1
                rerun = policy.with_attempts(policy.max_attempts - 1)
                try:
                    with faults.context(query=qname, stream=s["name"]):
                        rerun.call(session.sql, sql, stats=st)
                    err = None
                except Exception as exc2:  # noqa: BLE001
                    err = exc2
                s["retries"] += st.retries
                # the rerun went through the pipeline: its internal
                # retries/reschedules belong to this query too (the
                # handle-None guard in _finish_one will skip them)
                st2 = getattr(pipeline, "last_stats", None)
                sched2 = getattr(pipeline, "last_schedule", None) or {}
                if st2 is not None:
                    s["retries"] += st2.retries
                if sched2.get("reschedules"):
                    s["reschedules"] += sched2["reschedules"]
        inflight.append((s, qname, sql, t0, handle, err))
        while len(inflight) >= depth:
            _finish_one()
    while inflight:
        _finish_one()
    for s in streams:
        # per-stream Power Test Time is the stream's WALL window (first
        # dispatch -> last result), not the sum of per-query brackets:
        # pipelined queries overlap, and a sum would double-count
        ptt = int((s.get("last_done", start) -
                   s.get("first_t0", start)) * 1000)
        s["tlog"].add("Power Test Time", ptt)
        s["tlog"].write(os.path.join(out_dir, f"{s['name']}_time.csv"))
        # one BenchReport JSON per stream (reference summary shape, one
        # entry per query): failures carry their exception text, the
        # resilience fields record recovery work
        rep = BenchReport(s["name"], config.as_dict())
        rep.capture_env()
        rep.summary["startTime"] = int(start * 1000)
        rep.summary["queryStatus"] = s["statuses"]
        rep.summary["exceptions"] = s["exceptions"]
        rep.summary["queryTimes"] = s["qtimes"]
        rep.summary["retries"] = s["retries"]
        if s["reschedules"]:
            rep.summary["reschedules"] = s["reschedules"]
        rep.write_summary(prefix="throughput", out_dir=out_dir)
    elapse = math.ceil((time.time() - start) * 10) / 10.0
    return elapse, [s["failures"] for s in streams]


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="NDS throughput run")
    p.add_argument("data_dir")
    p.add_argument("streams", nargs="+", help="query_N.sql stream files")
    p.add_argument("--out_dir", default="throughput_logs")
    p.add_argument("--backend", choices=["tpu", "cpu", "distributed"],
                   default="tpu")
    p.add_argument("--input_format", choices=["parquet", "raw"],
                   default="parquet")
    p.add_argument("--allow_failure", action="store_true")
    p.add_argument("--in_process", action="store_true",
                   help="time-share one device inside a single process "
                        "(required when all streams target one TPU chip)")
    p.add_argument("--stall_s", type=float, default=None,
                   help="supervise subprocess streams: kill a stream "
                        "whose heartbeats stall past this budget and "
                        "restart it from its last completed query "
                        "(README Resilience)")
    p.add_argument("--max_restarts", type=int, default=None,
                   help="restart budget per supervised stream (default "
                        "1 when --stall_s is set; graceful-drain exits "
                        "75 resume without charging it)")
    args = p.parse_args(argv)
    if args.in_process:
        elapse, codes = run_streams_inprocess(
            args.data_dir, args.streams, args.out_dir, args.backend,
            args.input_format)
    else:
        elapse, codes = run_streams(args.data_dir, args.streams,
                                    args.out_dir, args.backend,
                                    args.input_format,
                                    args.allow_failure,
                                    stall_s=args.stall_s,
                                    max_restarts=args.max_restarts)
    write_elapse(args.out_dir, elapse, codes)
    print(f"Throughput Time: {elapse} s over {len(args.streams)} streams")
    sys.exit(1 if any(codes) and not args.allow_failure else 0)


if __name__ == "__main__":
    main()
