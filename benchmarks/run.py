"""run.py -- run ONE cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The window drives the shipped path: ``power_core.make_session`` with the
configuration's engine template as shipped, ``power_core.load_warehouse``,
then ``session.sql(text)`` per statement, one closed loop of whole passes
in the one process that holds the chip.  A pass runs its mix's writes
(refresh functions, through the engine's DML) before its reads, and
the tables it wrote are restored outside the clock.  Datagen and
transcode are host-only children.  Everything that belongs to one cell
is data: the cell's configuration file (benchmarks/configs/), its traffic mix
(benchmarks/traffic/), the plain references (benchmarks/reference/) and
the per-layer metric readers (benchmarks/layers/), all found by the names
in BENCHMARK.json.  This file names no cell, statement or scale.

Last line of standard output: one JSON object (README.md).  No TPU, or
fewer chips than the cell asks for: exit code 3 and no line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()        # process start, for setup_s

import argparse                   # noqa: E402
import gc                         # noqa: E402
import hashlib                    # noqa: E402
import importlib                  # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import shutil                     # noqa: E402
import signal                     # noqa: E402
import statistics                 # noqa: E402
from contextlib import nullcontext  # noqa: E402
import subprocess                 # noqa: E402
import sys                        # noqa: E402
import threading                  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")          # benchmarks/.gitignore lists it
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_PROGRAM = 2
EXIT_NO_CHIP = 3
ON_DEVICE = ("device", "chunked", "sharded")


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Spans:
    """The benchmark's own spans round its calls into each layer, kept
    in memory: (name, start, end, attributes), seconds on one clock."""

    def __init__(self):
        self.items: list = []

    def add(self, name: str, start: float, end: float, **attrs) -> None:
        self.items.append({"name": name, "start": start, "end": end,
                           **attrs})

    def total(self, name: str) -> float:
        return sum(s["end"] - s["start"] for s in self.items
                   if s["name"] == name)


class CompileCounter:
    """(compiler runs, persistent-cache hits) from jax.monitoring: once
    the persistent cache is on, every compile request consults it, so
    requests minus hits is the number of compiler runs."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == self.REQUEST or name == self.HIT:
            with self._lock:
                if name == self.REQUEST:
                    self.requests += 1
                else:
                    self.hits += 1

    def mark(self) -> tuple:
        return self.requests, self.hits

    def since(self, mark: tuple) -> tuple:
        requests, hits = self.requests - mark[0], self.hits - mark[1]
        return requests - hits, hits


# ------------------------------------------------------------- the cell

def load_cell(bench_path: str, workload: str) -> dict:
    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {bench_path}; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    # a per-layer metric with no `workloads` key is read in every cell
    # that reports the end-to-end metric it moves
    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    moved = {m["name"] for m in end_to_end}
    return {"cell": cell, "config": config, "bench": bench,
            "end_to_end": end_to_end,
            "per_layer": [m for m in bench["per_layer"]
                          if reported(m) and m["moves"] in moved]}


# ------------------------------------------------------ host-only children

_children: list = []


def host_env() -> dict:
    """Environment of a host-only child: pinned to the CPU so that it can
    never ask for the chip this process holds."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def host_child(args: list, log_path: str) -> None:
    with open(log_path, "ab") as log:
        proc = subprocess.Popen([sys.executable, "-m", *args],
                                env=host_env(), stdout=log,
                                stderr=subprocess.STDOUT, cwd=ROOT,
                                start_new_session=True)
        _children.append(proc)
        rc = proc.wait()
    if rc != 0:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-3000:]
        raise RuntimeError(f"{' '.join(args[:1])} exited {rc}:\n{tail}")


def kill_children() -> None:
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()


def refresh_dir(root: str, config: dict) -> "str | None":
    """Where a configuration's refresh set (``"refresh": {"update":
    N}``) is generated: ``<root>/refresh<N>``; None without one."""
    refresh = config.get("refresh")
    if not refresh:
        return None
    return os.path.join(root, f"refresh{int(refresh['update'])}")


def build_warehouse(config: dict) -> str:
    """The configuration's population, generated and transcoded once per
    checkout by host-only children; later runs reuse it.  It is a
    function of the scale factor alone, as with dbgen/dsdgen, and of
    the update number for a refresh set."""
    root = os.path.join(WORK, config["name"])
    ready = os.path.join(root, "ready.json")
    if os.path.exists(ready):
        return root
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    suite = config["suite"]
    t0 = time.monotonic()
    host_child([f"nds_tpu.{suite}.gen_data", str(config["scale"]),
                str(config["gen_parallel"]), os.path.join(root, "raw"),
                "--overwrite_output"], os.path.join(root, "gen.log"))
    refresh = refresh_dir(root, config)
    if refresh is not None:
        host_child([f"nds_tpu.{suite}.gen_data", str(config["scale"]), "1",
                    refresh, "--update", str(config["refresh"]["update"]),
                    "--overwrite_output"], os.path.join(root, "gen.log"))
    t1 = time.monotonic()
    host_child([f"nds_tpu.{suite}.transcode", os.path.join(root, "raw"),
                os.path.join(root, "wh"),
                os.path.join(root, "load_report.txt")],
               os.path.join(root, "transcode.log"))
    with open(ready, "w") as f:
        json.dump({"datagen_s": t1 - t0,
                   "transcode_s": time.monotonic() - t1}, f)
    return root


# ----------------------------------------------------------- the session

def engine_config(config: dict):
    from nds_tpu.utils.config import EngineConfig
    return EngineConfig(os.path.join(ROOT, config["template"]))


def statement_facts(session) -> dict:
    """What the pipeline recorded about the statement that just ran."""
    return schedule_facts(session._executor_factory(session.tables))


def schedule_facts(pipe) -> dict:
    """What the pipeline recorded about the query it ran last."""
    sched = getattr(pipe, "last_schedule", None) or {}
    timings = getattr(pipe, "last_timings", None) or {}
    return {"placement": sched.get("placement"),
            "reschedules": int(sched.get("reschedules", 0) or 0),
            "ladder": list(sched.get("ladder", []) or []),
            "execute_ms": timings.get("execute_ms")}


def on_device(facts: dict) -> bool:
    return (facts.get("placement") in ON_DEVICE
            and not facts.get("reschedules")
            and "cpu" not in facts.get("ladder", []))


def placed(rec: dict) -> bool:
    """Every query of the statement ended on the device: a read's one,
    a write's engine-run SELECTs (an INSERT's, a DELETE's subqueries).
    A DELETE's predicate is evaluated on the host by design and is no
    query."""
    return all(on_device(f) for f in rec.get("selects", [rec]))


def warm_marker(cache_dir: str, config_name: str, sql: str) -> str:
    """Where a run notes that a statement's programs went into the
    persistent cache at ``cache_dir`` from THIS checkout: that keys
    carry over between checkouts rests on one reading (PERF.md section
    7), and a statement wrongly taken for warm would compile alone, in
    sequence, in the warm pass."""
    key = hashlib.sha256(
        f"{ROOT}\n{config_name}\n{sql}".encode()).hexdigest()[:24]
    return os.path.join(cache_dir, f"benchmarks-warm-{key}")


def concurrent_warm_up(suite, econf, tables: dict, todo: list,
                       threads: int) -> dict:
    """Compile (and run once) each statement of ``todo`` in a session of
    its own, several at a time: the TPU compiler is single-threaded and
    XLA releases the GIL, so the set-up is bounded by the slowest program
    and not by their sum.  The programs land in jax's persistent cache,
    where the timed session finds them.  label -> seconds or None."""
    from nds_tpu.utils import power_core
    sessions = []
    for _stmt in todo:
        # one after another: make_session resets jax's cache object,
        # which must not happen under a running compile
        s = power_core.make_session(suite, econf)
        for t in tables.values():
            s.register_table(t)
        sessions.append(s)
    took: dict = {}

    def one(i: int) -> None:
        stmt = todo[i]
        t0 = time.monotonic()
        try:
            sessions[i].sql(stmt.sql)
            took[stmt.label] = time.monotonic() - t0
            say(f"warm-up {stmt.label}: {took[stmt.label]:.1f}s")
        except Exception as exc:  # noqa: BLE001 - the window reports it
            took[stmt.label] = None
            say(f"warm-up {stmt.label} FAILED after "
                f"{time.monotonic() - t0:.1f}s: {type(exc).__name__}: "
                f"{str(exc)[:1500]}")
        sessions[i] = None            # free this statement's buffers

    with ThreadPoolExecutor(max(1, min(threads, len(todo)))) as pool:
        list(pool.map(one, range(len(todo))))
    gc.collect()
    return took


def run_write(session, stmt, keep: bool = False) -> dict:
    """One write (a refresh function): each of its statements through
    ``session.sql``, the engine's DML path.  Its record holds the
    schedule of every query the pipeline ran inside it and the row
    count of each table it writes, as they stand after it; with
    ``keep``, those tables' rows (``kept_rows``), for the comparison of
    their content after the window."""
    from nds_tpu.columnar import delta
    pipe = session._executor_factory(session.tables)
    inner, selects = pipe.execute, []

    def execute(*args, **kwargs):
        try:
            return inner(*args, **kwargs)
        finally:
            selects.append(schedule_facts(pipe))

    t0 = time.perf_counter()
    error = None
    pipe.execute = execute
    try:
        for part in stmt.parts:
            session.sql(part)
    except Exception as exc:  # noqa: BLE001 - counted in `failed`
        error = f"{type(exc).__name__}: {str(exc)[:500]}"
    finally:
        del pipe.execute
    t1 = time.perf_counter()
    rows = ({t: int(delta.visible_rows(session.tables[t]))
             for t in stmt.writes} if error is None else {})
    rec = {"stmt": stmt, "start": t0, "end": t1, "result": None,
           "error": error, "selects": selects, "rows": rows}
    if keep and error is None:
        rec["tables"] = {t: kept_rows(session.tables[t])
                         for t in stmt.writes}
    return rec


def kept_rows(table) -> tuple:
    """(live-row mask or None, columns): what a table holds for the
    program, kept past its pass without keeping the table.  Each column
    is a new object over the same arrays: a column's device copy lives
    as long as the column object does, and a kept table would hold its
    pass's uploads on the chip."""
    import dataclasses
    from nds_tpu.columnar import delta
    return delta.live_mask(table), {
        name: dataclasses.replace(col) for name, col in table.columns.items()}


def live_columns(kept: tuple, fields: list) -> dict:
    """The live rows of ``kept_rows``' reading, column by column as
    ``compare.table_digest`` takes them."""
    import numpy as np
    live, columns = kept
    out = {}
    for name, kind in fields:
        col = columns[name]
        values = col.decode() if col.is_string else np.asarray(col.values)
        valid = col.null_mask
        if live is not None:
            values = values[live]
            valid = None if valid is None else valid[live]
        if valid is not None and kind != "str":
            values = np.where(valid, values.astype(np.float64), np.nan)
        out[name] = (values, kind)
    return out


def restore(session, loaded: dict, written, spans: "Spans") -> None:
    """The tables a pass wrote back to their load-time selves, as the
    program's own compaction swaps a table (register, then the scoped
    invalidation): every pass then does the same work.  Outside the
    clock; a ``restore`` span."""
    t = time.monotonic()
    names = sorted(written)
    for name in names:
        session.register_table(loaded[name])
    session.invalidate(tables=names)
    spans.add("restore", t, time.monotonic(), tables=names)


def run_statement(session, stmt) -> dict:
    """One client-side statement: call to host rows."""
    t0 = time.perf_counter()
    result, error = None, None
    try:
        result = session.sql(stmt.sql)
    except Exception as exc:  # noqa: BLE001 - counted in `failed`
        error = f"{type(exc).__name__}: {str(exc)[:500]}"
    t1 = time.perf_counter()
    facts = statement_facts(session) if error is None else {}
    return {"stmt": stmt, "start": t0, "end": t1, "result": result,
            "error": error, **facts}


# ------------------------------------------------------------ the window

def run_pass(session, statements: list, records: list, i: int,
             annotate=None, kept: "set | None" = None) -> set:
    """Pass ``i``'s statements, their records appended to ``records``;
    returns the tables its writes mutated.  The first pass of each
    sequence of writes keeps the tables they leave (``kept`` holds the
    sequences that have)."""
    written: set = set()
    state = tuple(s.sql for s in statements if s.writes)
    keep = kept is not None and bool(state) and state not in kept
    if keep:
        kept.add(state)
    for stmt in statements:
        with (annotate(f"bench.stmt:{stmt.label}") if annotate
              else nullcontext()):
            rec = (run_write(session, stmt, keep) if stmt.writes
                   else run_statement(session, stmt))
        rec["pass"] = i
        written.update(stmt.writes)
        records.append(rec)
    return written


def measure(session, sets: dict, names: list, seconds: float,
            trace_dir: "str | None", slice_s: float, restore_fn) -> dict:
    """Whole passes back to back until ``seconds`` have elapsed; the
    window closes at the end of the pass then running.  With a trace
    directory, the first whole passes (``slice_s`` seconds of them, one
    pass at the least) run under the profiler, started on this, the
    main, thread.  After a pass that wrote, ``restore_fn(tables)`` puts
    the tables back and the window's start moves on by its time."""
    import jax
    from benchmarks import generator
    records, passes, kept = [], 0, set()
    sliced = None

    def restored(written: set) -> float:
        if not written:
            return 0.0
        t = time.perf_counter()
        restore_fn(written)
        return time.perf_counter() - t

    t0 = time.perf_counter()
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the annotations are enough;
        options.host_tracer_level = 1       # per-call Python events slow
        options.enable_hlo_proto = False    # the host and swell the file
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.slice"):
                while passes == 0 or time.perf_counter() - t0 < slice_s:
                    written = run_pass(
                        session, generator.pass_statements(sets, names,
                                                           passes),
                        records, passes, jax.profiler.TraceAnnotation, kept)
                    passes += 1
                    t0 += restored(written)
            sliced = (t0, time.perf_counter(), passes, len(records))
        finally:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            # writing the trace is not part of the window
            t0 += time.perf_counter() - t_stop
    while passes == 0 or time.perf_counter() - t0 < seconds:
        written = run_pass(session,
                           generator.pass_statements(sets, names, passes),
                           records, passes, kept=kept)
        passes += 1
        t0 += restored(written)
    return {"start": t0, "end": time.perf_counter(), "passes": passes,
            "records": records, "slice": sliced}


def quantile95(values: list) -> float:
    """95th percentile, nearest rank: a value that was observed."""
    ordered = sorted(values)
    return ordered[max(0, -(-95 * len(ordered) // 100) - 1)]


# ------------------------------------------------------------ correctness

def reference_tables(config: dict, raw_dir: str):
    """The plain reference's tables: the raw files, and the refresh
    set's staging tables where the configuration has one."""
    from benchmarks.reference import rawdata
    return rawdata.Tables(config["suite"], raw_dir, refresh_dir(
        os.path.dirname(os.path.abspath(raw_dir)), config))


def reference_columns(tables, table: str) -> dict:
    """A table as the reference holds it now, as
    ``compare.table_digest`` takes it."""
    frame = tables(table, tables.columns(table))
    return {name: (frame[name].to_numpy(), kind)
            for name, kind in tables.schema[table]}


def reference_module(stmt):
    return importlib.import_module(
        "benchmarks.reference." + stmt.template.replace("/", "."))


def check_rows(window: dict, config: dict, raw_dir: str) -> dict:
    """Every answer of the window against the plain reference: the first
    execution of each distinct statement in full, every repeat against
    that first one.  A read is held against the reference over the
    state its pass's writes left (the references' ``apply`` laid over
    the raw tables), a write by the row count of each table it writes
    and, in the first pass of its state, by those tables' rows.  Runs
    after the window, on the host, without jax."""
    from benchmarks import compare
    from benchmarks.reference import rawdata
    tables, real = reference_tables(config, raw_dir), rawdata.Real()
    # a pass's state: the writes it ran, in order; a read's answer
    # depends on it, and every pass of one state gives the same answers
    writes_of: dict = {}          # pass -> its writes, in order
    for rec in window["records"]:
        if rec["stmt"].writes:
            writes_of.setdefault(rec.get("pass"), []).append(rec["stmt"])
    states = {(): []}             # state -> its writes
    for writes in writes_of.values():
        states[tuple(w.sql for w in writes)] = writes
    first: dict = {}
    wrote: dict = {}              # (state, position) -> write records
    repeats_differ = failed = 0
    notes = []
    for rec in window["records"]:
        writes = writes_of.get(rec.get("pass"), [])
        state = tuple(w.sql for w in writes)
        if rec["stmt"].writes:
            wrote.setdefault((state, writes.index(rec["stmt"])), []
                             ).append(rec)
        if rec["error"] is not None or not placed(rec):
            failed += 1
            notes.append(f"{rec['stmt'].label}: " + (
                rec["error"] or "; ".join(
                    f"placement {f.get('placement')!r}, "
                    f"ladder {f.get('ladder')}, "
                    f"{f.get('reschedules')} reschedules"
                    for f in rec.get("selects", [rec]) if not on_device(f))))
            continue
        if rec["stmt"].writes:
            continue
        key = (state, rec["stmt"].sql)
        d = compare.digest(rec["result"])
        if key not in first:
            first[key] = (rec, d)
        elif first[key][1] != d:
            repeats_differ += 1
    rows_wrong, writes_wrong, gap, per_stmt = 0, 0, 0.0, {}
    for state in sorted(states, key=len):
        tables.overlay.clear()
        for j, stmt in enumerate(states[state]):
            for table, frame in reference_module(stmt).apply(
                    tables, stmt.params, real).items():
                tables.write(table, frame)
            want = {t: tables.rows(t) for t in stmt.writes}
            for rec in wrote.get((state, j), []):
                if rec["error"] is not None:
                    continue
                ok = rec["rows"] == want
                if not ok:
                    notes.append(f"{stmt.label}: rows after it "
                                 f"{rec['rows']} vs reference {want}")
                elif "tables" in rec:
                    differ = [t for t in stmt.writes if compare.table_digest(
                        live_columns(rec["tables"][t], tables.schema[t]))
                        != compare.table_digest(reference_columns(
                            tables, t))]
                    ok = not differ
                    if differ:
                        notes.append(f"{stmt.label}: rows of "
                                     f"{', '.join(differ)} after it differ "
                                     f"from the reference's (row counts "
                                     f"{rec['rows']} agree)")
                per_stmt[stmt.label] = {"rows": rec["rows"], "ok": ok}
                writes_wrong += 0 if ok else 1
        for (key_state, _sql), (rec, _d) in first.items():
            if key_state != state:
                continue
            stmt = rec["stmt"]
            ref = reference_module(stmt).reference(tables, stmt.params,
                                                   real)
            got, kinds = compare.result_frame(rec["result"])
            ok, g, note = compare.compare_statement(got, kinds, ref,
                                                     stmt.order_by)
            per_stmt[stmt.label] = {"rows": int(got.shape[0]), "ok": ok,
                                    "rel_gap": g}
            if not ok:
                rows_wrong += 1
                notes.append(f"{stmt.label}: {note}")
            gap = max(gap, g)
    tables.overlay.clear()
    numbers = {"failed_statements": failed, "rows_wrong": rows_wrong,
               "repeats_differ": repeats_differ, "max_rel_gap": gap}
    if "not_restored" in window:
        # a mix that writes: what its writes left, and the session's
        # tables after the window, which have to be the load-time ones
        for table in window["not_restored"]:
            notes.append(f"{table}: not the load-time table after the "
                         f"window")
        numbers["writes_wrong"] = writes_wrong
        numbers["tables_not_restored"] = len(window["not_restored"])
    ok, checks = compare.verdict(numbers, config["limits"])
    return {"correct": ok and bool(first), "checks": checks,
            "failed": failed, "notes": notes[:20], "per_stmt": per_stmt}


# ---------------------------------------------------------------- metrics

def end_to_end(window: dict, setup_s: float) -> dict:
    """Every end-to-end number a cell can list; BENCHMARK.json says which
    a cell reports.  ``pass_s`` and ``stmt_mean_ms`` are the whole window
    over the passes, or the statements, completed in it."""
    walls = [(r["end"] - r["start"]) * 1e3 for r in window["records"]]
    wall_s = window["end"] - window["start"]
    return {"pass_s": wall_s / window["passes"],
            "stmt_mean_ms": wall_s * 1e3 / len(window["records"]),
            "stmt_p95_ms": quantile95(walls),
            "setup_s": setup_s}


def per_layer(names: list, run: dict) -> dict:
    """Each per-layer metric has a reader of its own,
    benchmarks/layers/<name>.py, found by name (loaded from its path: a
    name may hold dots); a reader that finds nothing to read returns
    None and the metric is left out."""
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layers." + name.replace(".", "_"),
            os.path.join(HERE, "layers", f"{name}.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(run)
        if value is not None:
            out[name] = value
    return out


def find_xplane(trace_dir: str) -> str:
    hits = []
    for base, _dirs, files in os.walk(trace_dir):
        hits += [os.path.join(base, f) for f in files
                 if f.endswith(".xplane.pb")]
    if not hits:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    return max(hits, key=os.path.getmtime)


# ------------------------------------------------------------------- main

def find_devices(cell: dict, config: dict):
    """(devices, peaks of the device kind); exits with EXIT_NO_CHIP and
    no result line otherwise: a cell runs on TPU chips only.  The one
    CPU route is a configuration marked ``"rehearsal": true`` under the
    user's own ``JAX_PLATFORMS=cpu``; its line names ``cpu``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    rehearsal = (dev.platform == "cpu" and bool(config.get("rehearsal"))
                 and os.environ.get("JAX_PLATFORMS", "").split(",")[0]
                 == "cpu")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if rehearsal:
        return devices, None
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmarks/run.py: jax reports {len(devices)} x "
              f"{dev.platform} ({dev.device_kind}); {cell['name']} needs "
              f"{cell['chips']} TPU chip(s). No result.", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    if dev.device_kind not in peaks:
        print(f"benchmarks/run.py: no peaks for device kind "
              f"{dev.device_kind!r} in benchmarks/peaks.json",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return devices, peaks[dev.device_kind]


def warm_up(session, suite, econf, config: dict, todo: list,
            counter: CompileCounter, spans: Spans, restore_fn) -> tuple:
    """Make every program of the run ready: statements this checkout has
    not yet put into the persistent cache compile concurrently in
    sessions of their own; then one untimed pass of the run's own
    statements in the timed session (re-lower, cache load, upload).
    A mix that writes runs its writes first, so that the reads compile
    against the tables as the writes leave them, and the tables are
    restored (``restore_fn``) after that and after the untimed pass."""
    import jax
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or jax.config.jax_compilation_cache_dir)
    t = time.monotonic()
    mark = counter.mark()
    writes = [s for s in todo if s.writes]
    for stmt in writes:
        rec = run_write(session, stmt)
        if rec["error"]:
            say(f"warm-up {stmt.label} FAILED: {rec['error']}")
    written = {name for s in writes for name in s.writes}
    cold = [s for s in todo if not s.writes and not (
        cache_dir and os.path.exists(
            warm_marker(cache_dir, config["name"], s.sql)))]
    if cold:
        say(f"{len(cold)} of {len(todo)} statements not warmed in "
            f"{cache_dir}: compiling them concurrently")
        took = concurrent_warm_up(suite, econf, session.tables, cold,
                                  max(2, (os.cpu_count() or 4) // 2))
        for s in cold:
            if took.get(s.label) is not None and cache_dir:
                with open(warm_marker(cache_dir, config["name"], s.sql),
                          "w") as f:
                    f.write(s.label + "\n")
    if written:
        restore_fn(written)
    for stmt in todo:
        rec = (run_write(session, stmt) if stmt.writes
               else run_statement(session, stmt))
        if rec["error"]:
            say(f"warm pass {stmt.label} FAILED: {rec['error']}")
    if written:
        restore_fn(written)
    runs, hits = counter.since(mark)
    spans.add("compile", t, time.monotonic(), compiler_runs=runs,
              cache_hits=hits)
    say(f"compile span {spans.total('compile'):.1f}s: {runs} compiler "
        f"runs, {hits} persistent-cache hits")
    gc.collect()
    return runs, hits


def main(argv=None, tamper=None) -> int:
    """``tamper(session)``: the tests break the timed path with it."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the benchmark file (the tests bring their own)")
    args = ap.parse_args(argv)
    spec = load_cell(args.benchmark, args.workload)
    cell, config = spec["cell"], spec["config"]
    if not os.path.isdir(os.path.join(ROOT, "nds_tpu")):
        print("benchmarks/run.py: the program (nds_tpu/) is not in this "
              "directory; nothing to measure.", file=sys.stderr)
        return EXIT_NO_PROGRAM
    devices, peaks = find_devices(cell, config)
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; cell "
        f"{cell['name']} seed {args.seed}")
    # first import on the MAIN thread: it pulls in pyarrow, and a pyarrow
    # first imported by a helper thread segfaults at the main thread's
    # first parquet read (chip_smoke.py, PR 21)
    from nds_tpu.utils import power_core
    from benchmarks import generator
    spans = Spans()
    try:
        t = time.monotonic()
        root = build_warehouse(config)
        spans.add("datagen", t, time.monotonic())

        econf = engine_config(config)
        refresh = refresh_dir(root, config)
        # a refresh set's staging tables are in the catalog of the
        # program's own data-maintenance session, and nowhere else
        suite = (importlib.import_module(
            f"nds_tpu.{config['suite']}.power").SUITE if refresh is None
            else importlib.import_module(
                f"nds_tpu.{config['suite']}.maintenance"
            )._maintenance_suite(econf))
        t = time.monotonic()
        session = power_core.make_session(suite, econf)
        power_core.load_warehouse(
            suite, session, os.path.join(root, "wh"), "parquet",
            schemas=power_core.suite_schemas(suite, econf))
        if refresh is not None:
            schema = importlib.import_module(
                f"nds_tpu.{config['suite']}.schema")
            power_core.load_warehouse(
                suite, session, refresh, "raw",
                schemas=schema.get_maintenance_schemas(
                    **power_core.schema_kwargs_for(suite, econf)))
        spans.add("load", t, time.monotonic())
        loaded = dict(session.tables)
        if tamper is not None:
            tamper(session)

        mix = generator.load_mix(cell["traffic"])
        sets = generator.variants(mix, args.seed)
        names = generator.order(mix, args.seed)
        counter = CompileCounter()

        def restore_fn(written):
            restore(session, loaded, written, spans)

        runs, hits = warm_up(session, suite, econf, config,
                             generator.distinct(mix, sets), counter, spans,
                             restore_fn)

        trace_dir = (os.path.join(WORK, cell["name"], "trace")
                     if args.trace else None)
        mark = counter.mark()
        setup_s = time.monotonic() - T_START
        window = measure(session, sets, names, args.seconds, trace_dir,
                         float(mix.get("trace_slice_s", 10)), restore_fn)
        window_runs, _hits = counter.since(mark)
        if any(s.get("writes") for s in mix["statements"]):
            window["not_restored"] = sorted(
                t for t, table in loaded.items()
                if session.tables.get(t) is not table)
        say(f"window {window['end'] - window['start']:.2f}s, "
            f"{window['passes']} passes, {len(window['records'])} "
            f"statements, {window_runs} compiler runs")

        stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": int(max(
                      s.get("peak_bytes_in_use", 0) for s in stats))}
        del session, restore_fn, loaded
        gc.collect()
    finally:
        kill_children()

    run = {"cell": cell, "config": config, "mix": mix, "spans": spans,
           "window": window, "peaks": peaks, "trace": None,
           "counters": {"window_compiler_runs": window_runs,
                        "setup_compiler_runs": runs,
                        "setup_cache_hits": hits}}
    line = {}
    if args.trace:
        from benchmarks import trace_reduce
        t = time.monotonic()
        trace = run["trace"] = trace_reduce.reduce(find_xplane(trace_dir))
        say(f"trace reduced in {time.monotonic() - t:.1f}s")
        listed = spec["per_layer"]
        values = per_layer([m["name"] for m in listed], run)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    else:
        listed = spec["end_to_end"]
        values = end_to_end(window, setup_s)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in listed if m["name"] in values}

    t = time.monotonic()
    verdict = check_rows(window, config, os.path.join(root, "raw"))
    say(f"reference and comparison {time.monotonic() - t:.1f}s")
    write_details(cell["name"], args, window, verdict, spans)
    for note in verdict["notes"]:
        print(f"check: {note}", file=sys.stderr)
    for name, c in verdict["checks"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({
        "correct": verdict["correct"], "attempted": len(window["records"]),
        "failed": verdict["failed"], "metrics": metrics, "device": device,
        **line, "passes": window["passes"],
        "checks": verdict["checks"]}), flush=True)
    return 0


def write_details(cell_name: str, args, window: dict, verdict: dict,
                  spans: Spans) -> None:
    """Per-statement medians and the comparison's detail: too long for
    the result line, kept beside the cell's other files."""
    by_stmt: dict = {}
    for r in window["records"]:
        by_stmt.setdefault(r["stmt"].name, []).append(
            (r["end"] - r["start"]) * 1e3)
    doc = {"workload": cell_name, "seed": args.seed, "trace": args.trace,
           "passes": window["passes"],
           "statement_wall_ms": {k: {"n": len(v),
                                     "median": statistics.median(v),
                                     "max": max(v)}
                                 for k, v in by_stmt.items()},
           "spans": [s for s in spans.items],
           "per_statement": verdict["per_stmt"],
           "notes": verdict["notes"]}
    path = os.path.join(WORK, cell_name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "last_run.json"), "w") as f:
        json.dump(doc, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
