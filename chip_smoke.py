"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the four-chip path only (builder-run)

ONE process owns the chip(s) for its whole life: this script touches jax
first thing, and every step that dispatches to a device runs inside THIS
process, by calling the drivers' own ``main``/library entry points.
Only host-only steps are children — datagen, transcode and the
``--backend cpu`` oracle power runs — each launched with
``JAX_PLATFORMS=cpu`` (power_core.subprocess_env("cpu")), so none of
them can ever ask for a chip this process holds. Every child is killed
on the way out.

Default phase (one chip), all at SF1 (BASELINE.json config 1; lineitem
6M rows, the 25-table NDS catalog loaded whole), data from the in-tree
seeded generators, nothing read that a clean checkout does not have:

  1. jax must report a TPU — else exit non-zero, no CPU continuation.
  2. NDS-H: gen_data -> transcode -> streams -> the power driver
     (``nds_h.power --backend tpu --template
     configs/power_run_tpu.template``: make_session ->
     ExecutionPipeline) over NDS_H_QUERIES; the same stream on
     ``--backend cpu``; ``nds_h.validate``: every query must MATCH.
  3. NDS: the same route over NDS_QUERIES, validated by ``nds.validate``.
  4. a second pass of step 2's stream in this process: 0 compiler runs.
  5. every per-query summary must say placement device|chunked, no
     ``cpu`` in any ladder, no degradations, live platform ``tpu``.
  6. last stdout line: {"ok": true, "device": {...}} — only if every
     step passed.

Why there is a warm-up before the drivers. The TPU compiler is
single-threaded and a 64-bit ``lax.sort`` costs it ~100 s at any size;
each program here carries 3-11 sorts, so the ten programs below
compile in ~32 minutes back to back (rehearsed seconds beside each
entry; CHANGES.md PR 21) — more than this script's whole limit. XLA
releases the GIL while it compiles, so the set-up compiles them
CONCURRENTLY: one session per statement, built exactly as the driver
builds its own (make_session + the same template), ``session.sql`` in a
thread pool, into jax's persistent compilation cache
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla_cache). The drivers
then run the normal sequential path and find their programs there —
the same warm path a second run against a kept cache directory takes.
"Compiles" below therefore means compiler RUNS (persistent-cache
misses, counted from jax.monitoring); a new session re-lowers each
program through the engine's compile funnel either way.
"""

from __future__ import annotations

import argparse
import faulthandler
import gc
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")      # .gitignore lists it
TEMPLATE = os.path.join(HERE, "configs", "power_run_tpu.template")
SF = "1"
PLATFORM = "tpu"        # what jax must report, and every summary record
# concurrent compiles: each is one busy core and 3-5 GB of host RAM, and
# the one-chip machine has 13 cores / 40 GiB, the four-chip host 30 / 140
WARM_THREADS = max(2, (os.cpu_count() or 4) // 2)

# (stream query name, TPU compile seconds rehearsed for a described v5e
# on an idle 8-core host, PR 21 — set-up cost of a COLD run; n sorts).
# Of the 22: q6 scan-only, q1 wide aggregate, q3 join+sort+limit, q18
# large join/aggregate, q13 outer join, q16 distinct/anti, q21
# semi/anti. The other 15 are left out for time, not for failing:
# CHANGES.md PR 21 has the rehearsal table and the reasons.
NDS_H_QUERIES = (
    ("query6", 1),       # 0 sorts
    ("query1", 198),     # 5 sorts
    ("query3", 224),     # 6 sorts
    ("query18", 401),    # 11 sorts
    ("query13", 127),    # 9 sorts + result compactor
    ("query16", 261),    # 7 sorts + result compactor (loaded host)
    ("query21", 85),     # 7 sorts
)
NDS_QUERIES = (
    ("query96", 60),     # 3 sorts
    ("query7", 255),     # 10 sorts
    ("query3", 341),     # 8 sorts
)
# --chips 4: q5/q18 force the hash exchange. (single-device, sharded)
# rehearsed compile seconds; the sharded ones for a described v5e:2x2
# mesh, four at once on a loaded 8-core host
MULTICHIP_QUERIES = (
    ("query1", 198, 765),
    ("query3", 224, 995),
    ("query5", 461, 815),
    ("query18", 401, 977),
)

_children: list = []
_t_start = time.monotonic()
_phases: dict = {}


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _t_start:6.1f}s] {msg}", flush=True)


class phase:
    """Wall-clock bracket: totals are printed with the verdict."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.monotonic()
        say(f"{self.name} ...")
        return self

    def __exit__(self, *exc):
        dt = time.monotonic() - self.t0
        _phases[self.name] = _phases.get(self.name, 0.0) + dt
        say(f"{self.name}: {dt:.1f}s")


# ------------------------------------------------------------ children

def host_child(args: list, log_name: str) -> subprocess.Popen:
    """A host-only step as a child pinned to JAX_PLATFORMS=cpu, in its
    own process group (datagen fans out into a worker pool)."""
    from nds_tpu.utils.power_core import subprocess_env
    log = open(os.path.join(WORK, log_name), "ab")
    proc = subprocess.Popen([sys.executable, "-m", *args],
                            env=subprocess_env("cpu"), stdout=log,
                            stderr=subprocess.STDOUT, cwd=HERE,
                            start_new_session=True)
    proc.smoke_log = log.name
    _children.append(proc)
    return proc


def wait_child(proc: subprocess.Popen, what: str) -> None:
    rc = proc.wait()
    if rc != 0:
        with open(proc.smoke_log, errors="replace") as f:
            tail = f.read()[-3000:]
        raise SmokeFailure(f"{what} exited {rc}; end of its log:\n{tail}")


def kill_children() -> None:
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()


def build_warehouse(suite: str) -> None:
    """gen_data -> transcode for one suite (two children in sequence;
    the two suites' chains run side by side)."""
    root = os.path.join(WORK, suite)
    t0 = time.monotonic()
    wait_child(host_child(
        [f"nds_tpu.{suite}.gen_data", SF, "8", os.path.join(root, "raw"),
         "--overwrite_output"], f"{suite}_gen.log"), f"{suite} gen_data")
    t1 = time.monotonic()
    wait_child(host_child(
        [f"nds_tpu.{suite}.transcode", os.path.join(root, "raw"),
         os.path.join(root, "wh"), os.path.join(root, "load_report.txt")],
        f"{suite}_transcode.log"), f"{suite} transcode")
    _phases[f"{suite} datagen"] = t1 - t0
    _phases[f"{suite} transcode"] = time.monotonic() - t1


def suite_parts(suite: str):
    """(driver module, validate module, Suite, stream path)."""
    import importlib
    power = importlib.import_module(f"nds_tpu.{suite}.power")
    validate = importlib.import_module(f"nds_tpu.{suite}.validate")
    streams = importlib.import_module(f"nds_tpu.{suite}.streams")
    sdir = os.path.join(WORK, suite, "streams")
    path = streams.generate_query_streams(sdir, 1)[0]
    return power, validate, power.SUITE, path


# ------------------------------------------------------- compile counts

class CompileCounter:
    """(compiler runs, persistent-cache hits) from jax.monitoring's
    events: once the cache is enabled every compile request consults it
    first, so requests minus hits IS the number of compiler runs."""

    def __init__(self):
        import jax.monitoring
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        with self._lock:
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

    def mark(self) -> tuple:
        return self.requests, self.hits

    def since(self, mark: tuple) -> tuple:
        requests, hits = self.requests - mark[0], self.hits - mark[1]
        return requests - hits, hits


# ------------------------------------------------------------- warm-up

def load_tables(suite, config, wh: str) -> dict:
    from nds_tpu.utils import power_core
    session = power_core.make_session(suite, config)
    power_core.load_warehouse(
        suite, session, wh, "parquet",
        schemas=power_core.suite_schemas(suite, config))
    return session.tables


def warm_up(jobs: list, kept: "dict | None" = None) -> dict:
    """Compile (and run once) every (label, suite, config, tables, sql,
    rehearsed seconds) concurrently, one session per statement, the
    longest compile first. Returns label -> seconds (None = failed: it
    is reported, and whoever runs the statement next decides). With
    ``kept``, label -> (session, result) stays alive for the caller."""
    from nds_tpu.utils import power_core
    jobs = sorted(jobs, key=lambda j: -j[5])
    sessions = []
    for _label, suite, config, tables, _sql, _secs in jobs:
        # built one after another: make_session resets jax's cache
        # object, which must not happen under a running compile
        s = power_core.make_session(suite, config)
        for t in tables.values():
            s.register_table(t)
        sessions.append(s)
    took: dict = {}

    def one(i: int) -> None:
        label, sql = jobs[i][0], jobs[i][4]
        t0 = time.monotonic()
        # faulthandler names threads by ident only
        say(f"warm-up {label} on thread {threading.get_ident():#x}")
        try:
            result = sessions[i].sql(sql)
            took[label] = round(time.monotonic() - t0, 1)
            say(f"warm-up {label}: {took[label]}s")
            if kept is not None:
                kept[label] = (sessions[i], result)
        except Exception as exc:  # noqa: BLE001 - reported, see above
            took[label] = None
            say(f"warm-up {label} FAILED after "
                f"{time.monotonic() - t0:.1f}s: {type(exc).__name__}: "
                f"{str(exc)[:2000]}")
        sessions[i] = None   # free this statement's device buffers

    with ThreadPoolExecutor(min(WARM_THREADS, len(jobs))) as pool:
        list(pool.map(one, range(len(jobs))))
    gc.collect()
    return took


# ------------------------------------------------------------- drivers

def run_driver(power, argv: list) -> int:
    """The driver's own CLI entry, in this process."""
    try:
        power.main(argv)
    except SystemExit as exc:
        return int(exc.code or 0)
    return 0


def device_pass(power, suite_name: str, tag: str, stream: str,
                subset: list, counter: CompileCounter) -> dict:
    """One ``--backend tpu`` power run; returns its facts."""
    root = os.path.join(WORK, suite_name)
    jdir = os.path.join(root, f"json_{tag}")
    out = os.path.join(root, f"out_{tag}")
    mark = counter.mark()
    rc = run_driver(power, [
        os.path.join(root, "wh"), stream,
        os.path.join(root, f"time_{tag}.csv"),
        "--backend", "tpu", "--template", TEMPLATE,
        "--json_summary_folder", jdir, "--output_prefix", out,
        "--query_subset", *subset])
    misses, hits = counter.since(mark)
    if rc != 0:
        raise SmokeFailure(f"{suite_name} power driver ({tag}) exited "
                           f"{rc}: a query failed (ERROR BEGIN above)")
    return {"json": jdir, "out": out, "xla_compiles": misses,
            "xla_cache_hits": hits}


def read_summaries(jdir: str, subset: list) -> dict:
    found = {}
    for path in glob.glob(os.path.join(jdir, "*.json")):
        with open(path) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and doc.get("query") in subset:
            found[doc["query"]] = doc
    missing = [q for q in subset if q not in found]
    if missing:
        raise SmokeFailure(f"no summary for {missing} in {jdir}")
    return found


def check_summaries(suite_name: str, summaries: dict) -> list:
    """Step 5: nothing may have ended on the CPU. Returns problems."""
    bad = []
    for q, doc in summaries.items():
        conf = (doc.get("env") or {}).get("engineConf") or {}
        if doc.get("queryStatus") != ["Completed"]:
            bad.append(f"{suite_name} {q}: status {doc.get('queryStatus')}")
        if doc.get("placement") not in ("device", "chunked"):
            bad.append(f"{suite_name} {q}: placement "
                       f"{doc.get('placement')!r}")
        if "cpu" in (doc.get("ladder") or []):
            bad.append(f"{suite_name} {q}: ladder {doc['ladder']}")
        if doc.get("degradations"):
            bad.append(f"{suite_name} {q}: degradations "
                       f"{doc['degradations']}")
        if conf.get("platform") != PLATFORM:
            bad.append(f"{suite_name} {q}: recorded platform "
                       f"{conf.get('platform')!r}")
    return bad


def validate_outputs(validate, suite_name: str, cpu_out: str,
                     tpu_out: str, stream: str, subset: list) -> set:
    for q in subset:
        for side in (cpu_out, tpu_out):
            if not os.path.isdir(os.path.join(side, q)):
                raise SmokeFailure(f"{suite_name} {q}: no output under "
                                   f"{side} — nothing to compare")
    return set(validate.iterate_queries(cpu_out, tpu_out, stream,
                                        ignore_ordering=True))


def print_table(suite_name: str, subset: list, summaries: dict,
                warm: dict, unmatched: set, out: str) -> None:
    import pyarrow.parquet as pq
    for q in subset:
        doc = summaries[q]
        tm = doc.get("engineTimings") or {}
        w = warm.get(f"{suite_name}:{q}")
        rows = pq.read_metadata(
            os.path.join(out, q, "part-0.parquet")).num_rows
        print(f"{suite_name:5s} {q:14s} placement={doc.get('placement')} "
              f"ladder={doc.get('ladder') or [doc.get('placement')]} "
              f"warmup_s={'-' if w is None else w} "
              f"driver_compile_s={tm.get('compile_ms', 0) / 1000:.1f} "
              f"execute_ms={tm.get('execute_ms', 0):.1f} "
              f"wall_ms={doc['queryTimes'][-1]} rows={rows} "
              f"{'MISMATCH' if q in unmatched else 'MATCH'}", flush=True)


# ------------------------------------------------------- default phase

def default_phase() -> None:
    from nds_tpu.utils import xla_cache
    from nds_tpu.utils.config import EngineConfig
    with phase("datagen+transcode (host-only children)"):
        with ThreadPoolExecutor(2) as pool:   # the two chains side by side
            list(pool.map(build_warehouse, ("nds_h", "nds")))
    say(f"xla cache: {xla_cache.enable()}")
    counter = CompileCounter()
    config = EngineConfig(TEMPLATE, None, {"engine.backend": "tpu"})
    plans = []
    for name, queries in (("nds_h", NDS_H_QUERIES), ("nds", NDS_QUERIES)):
        power, validate, suite, stream = suite_parts(name)
        root = os.path.join(WORK, name)
        subset = [q for q, _secs in queries]
        # the oracle runs beside the device work, never on the chip
        oracle = host_child(
            [f"nds_tpu.{name}.power", os.path.join(root, "wh"), stream,
             os.path.join(root, "time_cpu.csv"), "--backend", "cpu",
             "--output_prefix", os.path.join(root, "out_cpu"),
             "--query_subset", *subset], f"{name}_cpu_power.log")
        plans.append(SimpleNamespace(
            name=name, queries=queries, subset=subset, root=root,
            power=power, validate=validate, suite=suite, stream=stream,
            oracle=oracle))

    with phase("warm-up: load"):
        jobs = []
        for p in plans:
            tables = load_tables(p.suite, config,
                                 os.path.join(p.root, "wh"))
            sqls = p.suite.parse_query_stream(p.stream)
            jobs += [(f"{p.name}:{q}", p.suite, config, tables, sqls[q],
                      secs) for q, secs in p.queries]
    with phase("warm-up: concurrent compile"):
        mark = counter.mark()
        warm = warm_up(jobs)
        runs, hits = counter.since(mark)
        say(f"warm-up: {runs} compiler runs, {hits} persistent-cache "
            f"hits")
    del jobs, tables

    problems = []
    for p in plans:
        with phase(f"{p.name} power (driver)"):
            p.result = device_pass(p.power, p.name, "tpu", p.stream,
                                   p.subset, counter)
        if p.name == "nds_h":
            with phase("nds_h power, second pass"):
                second = device_pass(p.power, p.name, "tpu2", p.stream,
                                     p.subset, counter)
            say(f"second pass: {second['xla_compiles']} compiler runs, "
                f"{second['xla_cache_hits']} persistent-cache hits")
            if second["xla_compiles"]:
                problems.append(f"second pass ran the compiler "
                                f"{second['xla_compiles']} time(s)")
            problems += check_summaries(
                "nds_h(2)", read_summaries(second["json"], p.subset))
    for p in plans:
        with phase(f"{p.name} oracle wait + validate"):
            wait_child(p.oracle, f"{p.name} --backend cpu power run")
            unmatched = validate_outputs(
                p.validate, p.name, os.path.join(p.root, "out_cpu"),
                p.result["out"], p.stream, p.subset)
        summaries = read_summaries(p.result["json"], p.subset)
        print_table(p.name, p.subset, summaries, warm, unmatched,
                    p.result["out"])
        problems += check_summaries(p.name, summaries)
        problems += [f"{p.name} {q}: MISMATCH vs the CPU oracle"
                     for q in sorted(unmatched)]
        say(f"{p.name} driver pass: {p.result['xla_compiles']} compiler "
            f"runs, {p.result['xla_cache_hits']} persistent-cache hits")
    if problems:
        raise SmokeFailure("; ".join(problems))


# ----------------------------------------------------- four-chip phase

def multichip_phase(devices) -> None:
    """backend=distributed over a 4-device mesh against the
    single-device executor, NDS-H SF1, nothing else."""
    from nds_tpu.io.result_io import write_result
    from nds_tpu.nds_h import validate
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.utils import xla_cache
    from nds_tpu.utils.config import EngineConfig
    with phase("datagen+transcode (host-only children)"):
        build_warehouse("nds_h")
    say(f"xla cache: {xla_cache.enable()}")
    _power, _validate, suite, stream = suite_parts("nds_h")
    sqls = suite.parse_query_stream(stream)
    single_cfg = EngineConfig(TEMPLATE, None, {"engine.backend": "tpu"})
    dist_cfg = EngineConfig(
        os.path.join(HERE, "configs", "power_run_distributed.template"),
        None, {"engine.backend": "distributed",
               # a sharded query must END sharded: no ladder below it
               "engine.placement.floor": "sharded"})
    with phase("load"):
        tables = load_tables(suite, single_cfg,
                             os.path.join(WORK, "nds_h", "wh"))
    # each executor's four programs compile concurrently, one session
    # each; the sharded ones DISPATCH one at a time (dist_exec's lock).
    # The two executors take turns: with all eight in flight at once the
    # TPU runtime segfaulted on the four-chip host (CHANGES.md PR 21)
    before = obs_metrics.snapshot()
    kept: dict = {}
    took: dict = {}
    for i, (name, cfg) in enumerate((("sharded", dist_cfg),
                                     ("single", single_cfg))):
        with phase(f"{name}: concurrent compile + run"):
            took.update(warm_up(
                [(f"{name}:{q}", suite, cfg, tables, sqls[q], secs[1 - i])
                 for q, *secs in MULTICHIP_QUERIES], kept))
    failed = sorted(label for label, secs in took.items() if secs is None)
    if failed:
        raise SmokeFailure(f"failed: {failed} (messages above)")
    problems = []
    a2a_total, widths = 0, {}
    for q, *_secs in MULTICHIP_QUERIES:
        for name in ("single", "sharded"):
            write_result(kept[f"{name}:{q}"][1],
                         os.path.join(WORK, f"out_{name}", q))
        ok = validate.compare_results(
            os.path.join(WORK, "out_single"),
            os.path.join(WORK, "out_sharded"), q, ignore_ordering=True)
        # the sharded run ended sharded, its exchange is in the program
        # the chips ran, and its inputs really live on four chips
        session = kept[f"sharded:{q}"][0]
        pipe = session._executor_factory(session.tables)
        placement = (pipe.last_schedule or {}).get("placement")
        ex = pipe._executor("sharded")
        a2a = sum(entry[1]["jitted"].as_text().count(" all-to-all(")
                  for entry in ex._compiled.values()
                  if "jitted" in entry[1])
        sizes = {k: len(b.sharding.device_set)
                 for k, b in ex._buffers.items()}
        a2a_total += a2a
        widths.update({f"{q}:{k}": n for k, n in sizes.items()})
        print(f"nds_h {q:8s} sharded-vs-single rows="
              f"{kept['sharded:' + q][1].nrows} "
              f"{'MATCH' if ok else 'MISMATCH'} placement={placement} "
              f"all_to_all_ops={a2a} buffers={len(sizes)} "
              f"device_set_sizes={sorted(set(sizes.values()))} "
              f"compile+run s: single {took['single:' + q]}, sharded "
              f"{took['sharded:' + q]}", flush=True)
        if not ok:
            problems.append(f"{q}: sharded rows differ from single-device")
        if placement != "sharded":
            problems.append(f"{q}: sharded run ended on {placement!r}")
    delta = obs_metrics.delta(before, obs_metrics.snapshot())
    traced = delta.get("counters", {}).get("exchanges_traced_total", 0)
    say(f"exchanges traced: {traced}; all-to-all ops in the compiled "
        f"sharded programs: {a2a_total}")
    if not traced or not a2a_total:
        problems.append("no all-to-all in the sharded programs")
    narrow = [k for k, n in widths.items() if n != len(devices)]
    if narrow:
        problems.append(f"buffers not laid out on all {len(devices)} "
                        f"devices: {narrow[:5]}")
    if problems:
        raise SmokeFailure("; ".join(problems))


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the multi-chip phase and what it "
                         "is compared with")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    # after the TPU runtime installed its own handlers: a crash inside
    # it then also names the Python frames of every thread
    faulthandler.enable()
    if dev.platform != PLATFORM or len(devices) < args.chips:
        print(f"chip_smoke: jax reports {len(devices)} x {dev.platform} "
              f"({dev.device_kind}); this needs {args.chips} TPU "
              f"chip(s). No CPU continuation.", file=sys.stderr)
        return 1
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    # first import on the MAIN thread: it pulls in pyarrow, and a
    # pyarrow first imported by a helper thread (build_warehouse runs in
    # two) segfaults at the main thread's first parquet read
    import nds_tpu.utils.power_core  # noqa: F401
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        if args.chips == 4:
            multichip_phase(devices[:4])
        else:
            default_phase()
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        kill_children()
    for name, secs in _phases.items():
        print(f"total {name}: {secs:.1f}s")
    print(f"total wall: {time.monotonic() - _t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
