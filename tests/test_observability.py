"""Observability layer tests (nds_tpu/obs): span nesting + attributes,
disabled-mode no-ops, the Chrome trace-event JSONL schema (golden,
gated by tools/check_trace_schema.py), the TaskFailureCollector ->
metrics bridge, timings parity between the span-fed query_timings
accessor and legacy last_timings on single-chip and virtual-mesh
distributed executors, and the end-to-end power-run contract: a
3-query NDS power run with NDS_TPU_TRACE set emits schema-valid JSONL
whose per-query span totals agree with the TimeLog CSV within 5 ms on
both executors, staged sub-program spans included."""

import json
import os
import subprocess
import sys
import threading

import pytest

from nds_tpu import obs
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import (
    NOOP_SPAN, Span, Tracer, export_chrome, timings_from_span,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


# --------------------------------------------------------------- tracer

class TestTracer:
    def test_span_nesting_and_attrs(self):
        tr = Tracer(enabled=True)
        with tr.span("query", query="q1") as root:
            with tr.span("sql.parse", chars=42) as p:
                pass
            with tr.span("device.execute") as ex:
                with tr.span("device.compile") as c:
                    pass
        assert [c.name for c in root.children] == ["sql.parse",
                                                   "device.execute"]
        assert ex.children == [c]
        assert root.attrs["query"] == "q1"
        assert p.attrs["chars"] == 42
        assert root.t1 is not None
        assert root.dur_ms >= ex.dur_ms >= c.dur_ms >= 0
        assert [s.name for s in root.walk()] == [
            "query", "sql.parse", "device.execute", "device.compile"]
        assert root.find("device.compile") == [c]
        # the tracer keeps no tree, only the totals by name: one of
        # each, and every self time is the span minus its children
        totals = tr.totals()
        assert {n: t["count"] for n, t in totals.items()} == {
            "query": 1, "sql.parse": 1, "device.execute": 1,
            "device.compile": 1}
        assert totals["query"]["total_s"] == pytest.approx(
            root.dur_ms / 1e3)
        assert totals["query"]["self_s"] == pytest.approx(
            (root.dur_ms - p.dur_ms - ex.dur_ms) / 1e3)
        assert totals["device.compile"]["self_s"] == pytest.approx(
            totals["device.compile"]["total_s"])

    def test_exception_closes_span_and_records_error(self):
        tr = Tracer(enabled=True)
        with pytest.raises(ValueError):
            with tr.span("query") as root:
                raise ValueError("boom")
        assert root.t1 is not None
        assert "boom" in root.attrs["error"]

    def test_begin_attach_for_async_owners(self):
        """Async executors own their span explicitly: begin() does not
        touch the thread stack; attach() makes it current for nested
        phases without ending it."""
        tr = Tracer(enabled=True)
        q = tr.begin("device.execute", parent=None)
        assert tr.current() is None
        with tr.attach(q):
            assert tr.current() is q
            with tr.span("device.materialize"):
                pass
        assert tr.current() is None
        assert q.t1 is None  # attach never ends
        run = tr.begin("device.run", parent=q, t0=q.t0)
        run.end(t=q.t0 + 0.5)
        assert abs(run.dur_ms - 500.0) < 1e-6
        q.set(timings={"execute_ms": 500.0}).end()
        assert [c.name for c in q.children] == ["device.materialize",
                                                "device.run"]
        # an owned root lands in the totals when its owner ends it.
        # device.run was handed a bracket from the root's start to
        # half a second on: it overlaps device.materialize and outlasts
        # the root, so the root's self time takes the children's UNION
        # off, cut to the root: nothing is left, and never less
        totals = tr.totals()
        assert totals["device.execute"]["count"] == 1
        assert totals["device.run"]["total_s"] == pytest.approx(0.5)
        assert totals["device.execute"]["self_s"] == pytest.approx(
            0.0, abs=1e-9)

    def test_disabled_mode_is_noop(self):
        tr = Tracer(enabled=False)
        s = tr.span("query", big_attr="x")
        assert s is NOOP_SPAN and not s
        assert s.set(a=1) is s and s.end() is s
        with s:
            pass
        assert tr.begin("device.execute") is NOOP_SPAN
        with tr.attach(s):
            assert tr.current() is None
        assert tr.totals() == {}
        assert timings_from_span(s) == {}

    def test_threads_get_independent_stacks(self):
        tr = Tracer(enabled=True)
        seen = {}

        def worker():
            seen["current"] = tr.current()
            with tr.span("query", thread="t") as s:
                seen["span"] = s

        with tr.span("query", thread="main") as root:
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["current"] is None       # no cross-thread leakage
        assert seen["span"].parent is None   # its own root
        assert root.children == []

    def test_timings_from_span_prefers_attached_dict(self):
        tr = Tracer(enabled=True)
        with tr.span("device.execute") as q:
            with tr.span("device.compile"):
                pass
        q.set(timings={"compile_ms": 7.0, "bytes_scanned": 10.0})
        assert timings_from_span(q) == {"compile_ms": 7.0,
                                        "bytes_scanned": 10.0}

    def test_timings_from_span_sums_phases(self):
        tr = Tracer(enabled=True)
        q = tr.begin("device.execute", parent=None)
        tr.begin("device.run", parent=q, t0=1.0).end(t=1.25)
        tr.begin("device.run", parent=q, t0=2.0).end(t=2.25)
        tr.begin("device.compile", parent=q, t0=0.0).end(t=0.5)
        q.end()
        t = timings_from_span(q)
        assert abs(t["execute_ms"] - 500.0) < 1e-6
        assert abs(t["compile_ms"] - 500.0) < 1e-6


# ------------------------------------------------------- chrome export

class TestChromeExport:
    def _tree(self):
        tr = Tracer(enabled=True)
        with tr.span("query", query="q96") as root:
            with tr.span("device.execute", executor="DeviceExecutor"):
                pass
        return root

    def test_export_appends_jsonl(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        root = self._tree()
        export_chrome(root, path)
        export_chrome(root, path)  # append, not truncate
        lines = [json.loads(ln) for ln in open(path)]
        assert len(lines) == 4
        assert lines[0]["name"] == "query"
        assert lines[1]["name"] == "device.execute"

    def test_event_schema_golden(self, tmp_path):
        """The documented event schema, field by field — consumers
        (Perfetto after array-wrapping, check_trace_schema.py) parse
        exactly this."""
        path = str(tmp_path / "trace.jsonl")
        export_chrome(self._tree(), path)
        ev = json.loads(open(path).readline())
        assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid",
                           "tid", "args"}
        assert ev["ph"] == "X"
        assert ev["cat"] == "query"
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert isinstance(ev["dur"], float) and ev["dur"] >= 0
        assert ev["pid"] == os.getpid()
        assert isinstance(ev["tid"], int)
        assert ev["args"] == {"query": "q96"}

    def test_env_var_triggers_export_on_root_end(self, tmp_path,
                                                 monkeypatch):
        path = str(tmp_path / "auto.jsonl")
        monkeypatch.setenv("NDS_TPU_TRACE", path)
        tr = Tracer(enabled=True)
        with tr.span("query", query="auto"):
            with tr.span("sql.parse"):
                pass
        events = [json.loads(ln) for ln in open(path)]
        assert [e["name"] for e in events] == ["query", "sql.parse"]

    def test_check_trace_schema_validates(self, tmp_path):
        from tools.check_trace_schema import validate_file
        path = str(tmp_path / "trace.jsonl")
        export_chrome(self._tree(), path)
        assert validate_file(path) == []

    def test_check_trace_schema_rejects_bad_events(self, tmp_path):
        from tools.check_trace_schema import validate_event, validate_file
        assert validate_event([]) != []
        assert validate_event({"name": "x"}) != []
        good = {"name": "x", "cat": "x", "ph": "X", "ts": 0.0,
                "dur": 1.0, "pid": 1, "tid": 1, "args": {}}
        assert validate_event(good) == []
        assert validate_event({**good, "ph": "B"}) != []
        assert validate_event({**good, "dur": -1}) != []
        assert validate_event({**good, "args": 3}) != []
        bad = tmp_path / "bad.jsonl"
        bad.write_text(json.dumps(good) + "\nnot json\n")
        errs = validate_file(str(bad))
        assert len(errs) == 1 and "line 2" in errs[0]
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert validate_file(str(empty)) != []


# -------------------------------------------------------------- metrics

class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        reg.gauge("g").set(7)
        reg.histogram("h").observe(1.0)
        reg.histogram("h").observe(3.0)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3.5
        assert snap["gauges"]["g"] == 7
        assert snap["histograms"]["h"] == {
            "count": 2, "sum": 4.0, "min": 1.0, "max": 3.0,
            "p50": 1.0, "p95": 3.0, "p99": 3.0}

    def test_delta(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("a").inc(5)
        reg.histogram("h").observe(2.0)
        before = reg.snapshot()
        reg.counter("a").inc(3)
        reg.counter("b").inc()
        reg.gauge("g").set(1)
        reg.histogram("h").observe(4.0)
        d = obs_metrics.delta(before, reg.snapshot())
        assert d["counters"] == {"a": 3, "b": 1}
        assert d["gauges"] == {"g": 1}
        # count/sum are deltas; the quantiles are the AFTER snapshot's
        # distribution state
        assert d["histograms"]["h"] == {"count": 1, "sum": 4.0,
                                        "p50": 2.0, "p95": 4.0,
                                        "p99": 4.0}
        assert obs_metrics.delta(before, before) == {}

    def test_counter_thread_safety(self):
        reg = obs_metrics.MetricsRegistry()

        def hammer():
            c = reg.counter("n")
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("n").value == 8000

    def test_task_failure_collector_bridge(self):
        """Every TaskFailureCollector.notify lands in the
        task_failures_total counter — with and without a registered
        listener."""
        from nds_tpu.utils.report import TaskFailureCollector
        before = obs_metrics.counter("task_failures_total").value
        TaskFailureCollector.notify("anomaly with nobody listening")
        col = TaskFailureCollector()
        col.register()
        try:
            TaskFailureCollector.notify("anomaly with a listener")
        finally:
            col.unregister()
        assert obs_metrics.counter(
            "task_failures_total").value == before + 2
        assert col.failures == ["anomaly with a listener"]


# ------------------------------------------------------- timings parity

SF = 0.002


@pytest.fixture(scope="module")
def tpch_raw():
    from nds_tpu.datagen import tpch
    from nds_tpu.nds_h.schema import get_schemas
    return {t: tpch.gen_table(t, SF) for t in get_schemas()}


def _nds_h_session(raw, factory=None):
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h.schema import get_schemas
    schemas = get_schemas()
    sess = Session.for_nds_h(factory)
    for t in schemas:
        sess.register_table(from_arrays(t, schemas[t], raw[t]))
    return sess


TIMING_KEYS = {"compile_ms", "execute_ms", "materialize_ms",
               "bytes_scanned", "scan_gbps"}


class TestTimingsParity:
    def test_single_chip_query_timings_match_last_timings(self,
                                                          tpch_raw):
        from nds_tpu.engine.device_exec import make_device_factory
        from nds_tpu.nds_h import streams
        sess = _nds_h_session(tpch_raw, make_device_factory())
        sess.sql(streams.render_query(6))
        ex = sess._executor_factory(sess.tables)
        got = obs.query_timings(ex)
        assert got == ex.last_timings
        assert TIMING_KEYS <= set(got)
        root = ex.last_query_span
        assert root.name == "device.execute"
        # nothing reads this tree (no export, no profile, no owner), so
        # only the executor's own spans are in it; the phases under
        # them are in the totals (TestStatementSpans)
        assert [c.name for c in root.children] == ["device.run"]

    def test_distributed_query_timings_match_last_timings(self,
                                                          tpch_raw):
        """The multichip path reports the same timing schema as
        single-chip (round-5 advisor fix: DistributedExecutor.execute
        used to leave last_timings stale/empty)."""
        from nds_tpu.nds_h import streams
        from nds_tpu.parallel.dist_exec import make_distributed_factory
        sess = _nds_h_session(
            tpch_raw,
            make_distributed_factory(n_devices=8, shard_threshold=1000))
        sess.sql(streams.render_query(6))
        ex = sess._executor_factory(sess.tables)
        got = obs.query_timings(ex)
        assert got == ex.last_timings
        assert TIMING_KEYS <= set(got)
        assert got["execute_ms"] > 0

    def test_distributed_staged_bill_folds_into_timings(
            self, tpch_raw, monkeypatch, tmp_path):
        """Staged sub-programs on the multichip path must bill into
        the query's timings (the dropped-bill half of the advisor
        finding) and appear as spans."""
        # the tree is kept where something reads it: the Chrome export
        monkeypatch.setenv("NDS_TPU_TRACE", str(tmp_path / "t.jsonl"))
        from nds_tpu.engine import staging
        from nds_tpu.nds_h import streams
        from nds_tpu.parallel.dist_exec import (
            DistributedExecutor, make_distributed_factory,
        )
        monkeypatch.setattr(DistributedExecutor, "STAGE_WEIGHT", 4)
        monkeypatch.setattr(staging, "MIN_CUT_WEIGHT", 2)
        sess = _nds_h_session(
            tpch_raw,
            make_distributed_factory(n_devices=8, shard_threshold=1000))
        sess.sql(streams.render_query(3))
        ex = sess._executor_factory(sess.tables)
        tm = obs.query_timings(ex)
        assert tm.get("staged_programs", 0) >= 1
        # a statement's span carries the published vocabulary only, on
        # every executor (one skeleton sets it); the dunder keys
        # (``__kernels``, which report.attach_kernels reads) stay in
        # last_timings alone
        assert tm == {k: v for k, v in ex.last_timings.items()
                      if not k.startswith("__")}
        assert not ex._stage_timings  # bill consumed, no leak
        assert len(ex.last_query_span.find("stage.sub")) >= 1

    def test_stage_plan_reuse_requires_pinned_plan(self, tpch_raw,
                                                   monkeypatch):
        """_stage_plans entries pin the caller's plan object; an entry
        whose pin does not match the incoming plan (recycled id() /
        rebound key) is recomputed, never served stale (round-5
        advisor finding)."""
        from nds_tpu.engine import staging
        from nds_tpu.engine.device_exec import DeviceExecutor
        from nds_tpu.nds_h import streams
        monkeypatch.setattr(DeviceExecutor, "STAGE_WEIGHT", 4)
        monkeypatch.setattr(staging, "MIN_CUT_WEIGHT", 2)
        sess = _nds_h_session(tpch_raw)
        planned_a = sess.plan(streams.render_query(3))
        planned_b = sess.plan(streams.render_query(10))
        ex = DeviceExecutor(sess.tables)
        ex.execute(planned_a, key="k")
        entry_a = ex._stage_plans["k"]
        assert entry_a[0] is planned_a
        # pin matches: the cached split is reused, not recomputed
        ex.execute(planned_a, key="k")
        assert ex._stage_plans["k"] is entry_a
        # the overflow-retry path re-dispatches the staged MAIN plan
        # under the same key: that must reuse the split (temps are
        # registered, the bill is parked) — NOT evict the compile entry
        # whose slack the retry just doubled
        main = entry_a[2]
        assert main is not planned_a
        assert ex._staged_effective(main, "k") is main
        assert ex._stage_plans["k"] is entry_a
        assert "k" in ex._compiled
        # eviction dropped the program + pinning ref, then the key was
        # recycled by a DIFFERENT plan: the stale split must not serve
        ex._compiled.pop("k")
        ex.execute(planned_b, key="k")
        assert ex._stage_plans["k"][0] is planned_b

    def test_distributed_eviction_drops_stage_state(self, tpch_raw,
                                                    monkeypatch):
        """LRU eviction of a compiled program also drops its staging
        state (including recursive sub-program keys) so recycled id()s
        can never hit a stale split."""
        from nds_tpu.engine import staging
        from nds_tpu.nds_h import streams
        from nds_tpu.parallel.dist_exec import DistributedExecutor
        monkeypatch.setattr(DistributedExecutor, "STAGE_WEIGHT", 4)
        monkeypatch.setattr(DistributedExecutor, "MAX_COMPILED", 2)
        monkeypatch.setattr(staging, "MIN_CUT_WEIGHT", 2)
        holder = {}

        def factory(tables):
            ex = holder.get("ex")
            if ex is None or ex.tables is not tables:
                ex = DistributedExecutor(tables, n_devices=8,
                                         shard_threshold=1000)
                holder["ex"] = ex
            return ex

        sess = _nds_h_session(tpch_raw, factory)
        sess.sql(streams.render_query(3))   # stages: main + sub keys
        ex = holder["ex"]
        staged_keys = set(ex._stage_plans)
        assert staged_keys
        temps = [t for e in ex._stage_plans.values() for _s, t in e[1]]
        assert temps and all(t in ex.tables for t in temps)
        sess.sql(streams.render_query(6))
        sess.sql(streams.render_query(1))
        assert len(ex._compiled) <= 2
        # q3's main AND derived sub-program staging state evicted with it
        assert not (staged_keys & set(ex._stage_plans))
        # ...including its temp tables and their caches (eviction+rerun
        # cycles must not leak staged intermediates)
        for t in temps:
            assert t not in ex.tables and t not in ex._stage_fps
            assert not any(k.startswith(t + ".") for k in ex._buffers)


# ----------------------------------------------- power-run integration

NDS_SF = 0.002
NDS_QUERIES = [96, 7, 93]


@pytest.fixture(scope="module")
def nds_power_dirs(tmp_path_factory):
    """Tiny NDS warehouse (one parquet per table) + a 3-query stream."""
    from nds_tpu.datagen import tpcds
    from nds_tpu.io import csv_io
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds import streams
    from nds_tpu.nds.schema import get_schemas
    root = tmp_path_factory.mktemp("obs_power")
    wh = root / "wh"
    wh.mkdir()
    schemas = get_schemas()
    for t, schema in schemas.items():
        table = from_arrays(t, schema, tpcds.gen_table(t, NDS_SF))
        csv_io.write_parquet(table, str(wh / f"{t}.parquet"))
    sdir = root / "streams"
    streams.generate_query_streams(str(sdir), 1,
                                   templates=NDS_QUERIES)
    return {"wh": str(wh), "stream": str(sdir / "query_0.sql"),
            "root": str(root)}


def _run_power(dirs, backend, tag, monkeypatch, tmp_path):
    from nds_tpu.engine import staging
    from nds_tpu.engine.device_exec import DeviceExecutor
    from nds_tpu.nds.power import SUITE
    from nds_tpu.parallel.dist_exec import DistributedExecutor
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    # force plan splitting so staged sub-program spans appear
    monkeypatch.setattr(DeviceExecutor, "STAGE_WEIGHT", 8)
    monkeypatch.setattr(DistributedExecutor, "STAGE_WEIGHT", 8)
    monkeypatch.setattr(staging, "MIN_CUT_WEIGHT", 2)
    trace_path = str(tmp_path / f"trace_{tag}.jsonl")
    time_log = str(tmp_path / f"time_{tag}.csv")
    summaries = str(tmp_path / f"json_{tag}")
    monkeypatch.setenv("NDS_TPU_TRACE", trace_path)
    failures = power_core.run_query_stream(
        SUITE, dirs["wh"], dirs["stream"], time_log,
        config=EngineConfig(overrides={"engine.backend": backend}),
        json_summary_folder=summaries)
    return {"failures": failures, "trace": trace_path,
            "time_log": time_log, "summaries": summaries}


def _check_power_artifacts(res):
    """The acceptance contract, shared by both backends: schema-valid
    trace, span/CSV agreement within 5 ms, staged spans present,
    engineTimings + spans + metrics in the JSON summaries."""
    from nds_tpu.utils.timelog import TimeLog
    from tools.check_trace_schema import validate_file
    assert res["failures"] == 0
    assert validate_file(res["trace"]) == []
    events = [json.loads(ln) for ln in open(res["trace"])]
    csv_ms = {q: ms for _app, q, ms in TimeLog.read(res["time_log"])}
    roots = [e for e in events if e["name"] == "query"]
    assert {e["args"]["query"] for e in roots} == {
        f"query{n}" for n in NDS_QUERIES}
    for ev in roots:
        q = ev["args"]["query"]
        span_ms = ev["dur"] / 1000.0
        assert abs(span_ms - csv_ms[q]) <= 5.0, (
            f"{q}: span {span_ms:.2f} ms vs CSV {csv_ms[q]} ms")
    # staged sub-programs traced (STAGE_WEIGHT forced low)
    assert any(e["name"] == "stage.sub" for e in events)
    assert any(e["name"] == "device.compile" for e in events)
    # JSON summaries carry the new schema fields (the resume journal,
    # <unit>_queries.json, lives in the same dir but is not a report)
    from nds_tpu.obs import analyze
    files = [f for f in os.listdir(res["summaries"])
             if analyze.is_report_basename(f)]
    assert len(files) == len(NDS_QUERIES)
    for f in files:
        with open(os.path.join(res["summaries"], f)) as fh:
            s = json.load(fh)
        assert s["queryStatus"] == ["Completed"]
        et = s["engineTimings"]
        assert et["execute_ms"] > 0 and et["bytes_scanned"] > 0
        assert et.get("staged_programs", 0) >= 1
        assert s["spans"]["name"] == "query"
        # the catalogue's phases hang from the power loop's root, no
        # `stmt` of their own in between; the executor's span hangs
        # from the ladder walk
        kids = {c["name"]: c for c in s["spans"]["children"]}
        assert {"sched.place", "sched.run", "sched.note"} <= set(kids)
        assert "stmt" not in kids
        assert "device.execute" in [
            c["name"] for c in kids["sched.run"]["children"]]
        assert s["metrics"]["counters"]["queries_total"] == 1


class TestPowerRunTracing:
    def test_single_chip_power_run_trace(self, nds_power_dirs,
                                         monkeypatch, tmp_path):
        res = _run_power(nds_power_dirs, "tpu", "tpu", monkeypatch,
                         tmp_path)
        _check_power_artifacts(res)

    def test_distributed_power_run_trace(self, nds_power_dirs,
                                         monkeypatch, tmp_path):
        res = _run_power(nds_power_dirs, "distributed", "dist",
                         monkeypatch, tmp_path)
        _check_power_artifacts(res)


# ------------------------------------------------------------ CI gates

class TestToolGates:
    def test_check_headers_gate(self):
        """Every source file keeps its design-intent docstring (the
        repo's license-header-check analog) — run the real tool so a
        regression fails tier-1, not just CI."""
        proc = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "check_headers.py")],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout

    def test_check_trace_schema_cli(self, tmp_path):
        tr = Tracer(enabled=True)
        with tr.span("query", query="cli") as root:
            pass
        good = tmp_path / "good.jsonl"
        export_chrome(root, str(good))
        tool = os.path.join(TOOLS, "check_trace_schema.py")
        ok = subprocess.run([sys.executable, tool, str(good)],
                            capture_output=True, text=True)
        assert ok.returncode == 0, ok.stdout
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"name": "x"}\n')
        fail = subprocess.run([sys.executable, tool, str(bad)],
                              capture_output=True, text=True)
        assert fail.returncode == 1
        assert "missing key" in fail.stdout


# ------------------------------------------- one statement, one span tree

STMT_SF = 0.01
PROFILE_STATS = ("stmt_id", "plan_cache_hit", "syncs", "bytes", "first",
                 "uploads", "upload_bytes", "bytes_accessed",
                 # sched.place (PR 31; its `placement` is a string)
                 "est_bytes", "live_bytes", "projected_bytes",
                 "budget_bytes", "governed",
                 # device.launch: the executable's number (obs/costs)
                 "program")


def _totals_since(before: dict, after: dict, key: str = "count") -> dict:
    return {n: t[key] - before.get(n, {}).get(key, 0)
            for n, t in after.items()
            if t[key] != before.get(n, {}).get(key, 0)}


def _root_of(span):
    while span.parent is not None:
        span = span.parent
    return span


@pytest.fixture(scope="module")
def stmt_session(tmp_path_factory):
    """NDS-H at SF0.01 as the drivers build it: a parquet warehouse
    read by ``load_warehouse`` into a ``make_session`` session on the
    device executor (compiled by CPU XLA here).  Yields the session,
    the totals the load added, and the trees of q6's first (compiling)
    and second (warm) execution, taken with the Chrome export on: a
    tree is kept only where something reads it."""
    from nds_tpu.datagen import tpch
    from nds_tpu.io import csv_io
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h import streams
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.nds_h.schema import get_schemas
    from nds_tpu.obs.trace import get_tracer
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    wh = tmp_path_factory.mktemp("obs_stmt") / "wh"
    wh.mkdir()
    schemas = get_schemas()
    for t, schema in schemas.items():
        csv_io.write_parquet(
            from_arrays(t, schema, tpch.gen_table(t, STMT_SF)),
            str(wh / f"{t}.parquet"))
    tracer = get_tracer()
    before = tracer.totals()
    sess = power_core.make_session(
        SUITE, EngineConfig(overrides={"engine.backend": "tpu"}))
    power_core.load_warehouse(SUITE, sess, str(wh), "parquet",
                              schemas=schemas)
    loaded = _totals_since(before, tracer.totals())
    pipe = sess._executor_factory(sess.tables)
    q6 = streams.render_query(6)
    roots = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE", str(wh.parent / "stmt_trace.jsonl"))
        for _ in range(2):
            sess.sql(q6)
            roots.append(_root_of(pipe.last_query_span))
    return {"session": sess, "pipe": pipe, "q6": q6, "loaded": loaded,
            "first": roots[0], "warm": roots[1],
            "tables": len(schemas)}


def _names(spans) -> list:
    return [s.name for s in spans]


class TestStatementSpans:
    def test_one_stmt_root_with_the_catalogue_in_order(self, stmt_session):
        first, warm = stmt_session["first"], stmt_session["warm"]
        for root in (first, warm):
            assert root.name == "stmt" and root.parent is None
            assert root.t1 is not None
            assert len(root.find("stmt")) == 1
        # one id a statement, from one process-wide sequence
        assert warm.attrs["stmt_id"] == first.attrs["stmt_id"] + 1
        assert first.attrs["plan_cache_hit"] is False
        assert warm.attrs["plan_cache_hit"] is True
        assert _names(first.children) == [
            "sql.parse", "sql.plan", "sched.place", "sched.run",
            "sched.note"]
        assert _names(warm.children) == [
            "sched.place", "sched.run", "sched.note"]
        (run,) = warm.find("sched.run")
        (ex,) = run.children
        assert ex.name == "device.execute"
        assert _names(ex.children) == [
            "device.dispatch", "device.readback", "device.run",
            "device.materialize", "device.finish"]
        assert _names(ex.children[0].children) == [
            "device.bind", "device.launch"]
        (bind,) = warm.find("device.bind")
        assert bind.attrs == {"first": False, "uploads": 0,
                              "upload_bytes": 0}
        (rb,) = warm.find("device.readback")
        assert rb.attrs["syncs"] == 1 and rb.attrs["bytes"] > 0

    def test_first_execution_shows_the_compile_funnel(self, stmt_session):
        first = stmt_session["first"]
        (dispatch,) = first.find("device.dispatch")
        assert _names(dispatch.children) == [
            "device.compile", "device.bind", "device.launch"]
        (compiled,) = first.find("device.compile")
        assert _names(compiled.children) == [
            "device.bind", "compile.lower", "compile.xla"]
        # the bind under the compile built the scan view and uploaded;
        # the dispatch's own bind found everything in place
        under, own = first.find("device.bind")
        assert under.attrs["first"] is True and under.attrs["uploads"] > 0
        assert under.attrs["upload_bytes"] > 0
        assert own.attrs["first"] is False and own.attrs["uploads"] == 0
        (lower,) = first.find("compile.lower")
        assert lower.attrs == {"kind": "DeviceExecutor"}
        (xla,) = first.find("compile.xla")
        assert xla.attrs["persistent_cache_hit"] in (True, False)

    def test_self_times_add_up_to_the_root(self, stmt_session):
        for root in (stmt_session["first"], stmt_session["warm"]):
            (ex,) = root.find("device.execute")
            (run,) = root.find("device.run")
            # device.run is the launch-to-read-back bracket, handed to
            # the tree after the fact: it overlaps its siblings, so it
            # is left out and what it ALONE covers is added back
            others = [c for c in ex.children if c is not run]
            alone = (run.t1 - run.t0) - sum(
                max(0.0, min(c.t1, run.t1) - max(c.t0, run.t0))
                for c in others)
            own = sum(s.self_s() for s in root.walk() if s is not run)
            assert own + alone == pytest.approx(root.t1 - root.t0,
                                                rel=1e-9)

    def test_totals_count_the_statements_run(self, stmt_session):
        from nds_tpu.obs.trace import get_tracer
        sess, q6 = stmt_session["session"], stmt_session["q6"]
        tracer = get_tracer()
        before, m0 = tracer.totals(), obs_metrics.snapshot()["counters"]
        for _ in range(3):
            sess.sql(q6)
        counts = _totals_since(before, tracer.totals())
        assert counts == {name: 3 for name in (
            "stmt", "sched.place", "sched.run", "sched.note",
            "device.execute", "device.dispatch", "device.bind",
            "device.launch", "device.readback", "device.run",
            "device.materialize", "device.finish")}
        seconds = _totals_since(before, tracer.totals(), "total_s")
        own = _totals_since(before, tracer.totals(), "self_s")
        assert 0 < own["stmt"] < seconds["stmt"]
        assert own["device.launch"] == pytest.approx(
            seconds["device.launch"])
        m1 = obs_metrics.snapshot()["counters"]
        moved = {k: m1[k] - m0.get(k, 0) for k in m1
                 if m1[k] != m0.get(k, 0)}
        assert moved["plan_cache_hits_total"] == 3
        assert moved["device_readbacks_total"] == 3
        assert moved["readback_bytes_total"] > 0
        assert moved["scan_view_hits_total"] >= 3
        assert "device_uploads_total" not in moved
        assert "plan_cache_misses_total" not in moved

    def test_load_and_first_bind_totals(self, stmt_session):
        from nds_tpu.obs.trace import FIRST_SUFFIX, get_tracer
        loaded, n = stmt_session["loaded"], stmt_session["tables"]
        assert loaded["engine.init"] == 1
        assert (loaded["load.table"], loaded["load.read"],
                loaded["load.build"]) == (n, n, n)
        totals = get_tracer().totals()
        firsts = totals["device.bind" + FIRST_SUFFIX]
        assert 1 <= firsts["count"] < totals["device.bind"]["count"]
        assert 0 < firsts["total_s"] < totals["device.bind"]["total_s"]

    def test_trees_are_held_to_the_catalogue(self, stmt_session):
        sys.path.insert(0, TOOLS)
        from check_trace_schema import SPAN_PARENTS, _validate_span_tree
        for root in (stmt_session["first"], stmt_session["warm"]):
            assert _validate_span_tree(root.to_dict(), "spans") == []
            for span in root.walk():
                if span.parent is not None and span.name in SPAN_PARENTS:
                    assert span.parent.name in SPAN_PARENTS[span.name]
        stray = {"name": "query", "dur_ms": 1.0, "children": [
            {"name": "device.readback", "dur_ms": 0.5}]}
        (err,) = _validate_span_tree(stray, "spans")
        assert "device.readback" in err and "'query'" in err

    def test_untraced_statement_is_timed_and_nothing_is_kept(
            self, stmt_session):
        """No profile, no export, no owner: the statement's `with`
        spans are timed into the totals and leave no tree; the
        executor's owned span is a root of its own, as before PR 25."""
        from nds_tpu.obs.trace import get_tracer
        sess, pipe = stmt_session["session"], stmt_session["pipe"]
        tracer = get_tracer()
        before = tracer.totals()
        sess.sql(stmt_session["q6"])
        ex = pipe.last_query_span
        assert ex.name == "device.execute" and ex.parent is None
        assert not ex.kept and _names(ex.children) == ["device.run"]
        assert "execute_ms" in ex.attrs["timings"]
        assert tracer.current() is None
        after = tracer.totals()
        total = _totals_since(before, after, "total_s")
        own = _totals_since(before, after, "self_s")
        assert set(total) == {
            "stmt", "sched.place", "sched.run", "sched.note",
            "device.execute", "device.dispatch", "device.bind",
            "device.launch", "device.readback", "device.run",
            "device.materialize", "device.finish"}
        # self times still add up to the statement: device.run, the
        # bracket over the launch and the read-back, is left out
        assert sum(v for n, v in own.items() if n != "device.run") == \
            pytest.approx(total["stmt"], rel=0.01)
        assert 0 < own["sched.run"] < total["sched.run"] - \
            total["device.execute"] * 0.99

    def test_sql_async_has_one_root_too(self, stmt_session, tmp_path,
                                        monkeypatch):
        monkeypatch.setenv("NDS_TPU_TRACE", str(tmp_path / "t.jsonl"))
        sess, pipe = stmt_session["session"], stmt_session["pipe"]
        handle = sess.sql_async(stmt_session["q6"])
        out = handle.result()
        assert out is not None
        root = _root_of(pipe.last_query_span)
        assert root.name == "stmt" and root.t1 is not None
        assert _names(root.children) == ["sched.place", "sched.run",
                                         "sched.note"]
        (ex,) = root.find("device.execute")
        assert ex.parent.name == "sched.run"
        assert "device.readback" in _names(ex.children)


def _profile_events(tmp_path, body) -> list:
    """[(name, start, end, {stat: value})] of the ``nds.*`` annotations
    a CPU profile taken round ``body()`` holds, in start order."""
    import glob
    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("nds."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return sorted(events, key=lambda e: e[1])


class TestProfilerAnnotations:
    def test_profile_holds_the_nested_spans_and_their_attributes(
            self, stmt_session, tmp_path):
        sess, q6 = stmt_session["session"], stmt_session["q6"]
        events = _profile_events(tmp_path, lambda: sess.sql(q6))
        by_name = {}
        for ev in events:
            by_name.setdefault(ev[0], []).append(ev)
        # every `with` span of the warm tree, once; the owned
        # device.execute / device.run cannot be annotations
        assert sorted(by_name) == sorted("nds." + n for n in (
            "stmt", "sched.place", "sched.run", "sched.note",
            "device.dispatch", "device.bind", "device.launch",
            "device.readback", "device.materialize", "device.finish"))
        assert all(len(v) == 1 for v in by_name.values())

        def inside(child: str, parent: str) -> bool:
            (_n, c0, c1, _s), = by_name["nds." + child]
            (_n, p0, p1, _s), = by_name["nds." + parent]
            return p0 <= c0 and c1 <= p1

        for child, parent in (
                ("sched.place", "stmt"), ("sched.run", "stmt"),
                ("sched.note", "stmt"), ("device.dispatch", "sched.run"),
                ("device.bind", "device.dispatch"),
                ("device.launch", "device.dispatch"),
                ("device.readback", "sched.run"),
                ("device.materialize", "sched.run"),
                ("device.finish", "sched.run")):
            assert inside(child, parent), (child, parent)
        stats = {n: ev[0][3] for n, ev in by_name.items()}
        assert stats["nds.stmt"]["stmt_id"] > 0
        assert stats["nds.stmt"]["plan_cache_hit"] == 1
        assert stats["nds.device.readback"]["syncs"] == 1
        assert stats["nds.device.readback"]["bytes"] > 0
        assert stats["nds.device.bind"] == {"first": 0, "uploads": 0,
                                            "upload_bytes": 0}
        place = stats["nds.sched.place"]
        assert place["governed"] == 0 and place["est_bytes"] > 0
        assert place["projected_bytes"] <= place["budget_bytes"]
        # strings stay out of the annotation; numbers are all there is
        assert all(k in PROFILE_STATS or k == "flops"
                   for s in stats.values() for k in s)
        # the launch names its program, whose instructions the registry
        # reads back for the profile's op events
        from nds_tpu.obs import costs
        program = stats["nds.device.launch"]["program"]
        assert program > 0 and costs.sites(program)

    def test_obs_off_is_the_noop_and_opens_no_annotation(
            self, stmt_session, tmp_path, monkeypatch):
        from nds_tpu.obs import trace
        monkeypatch.setenv("NDS_TPU_OBS", "0")
        off = Tracer()                     # what a process started so is
        assert off.enabled is False
        assert off.span("stmt", stmt_id=1) is NOOP_SPAN
        assert off.totals() == {}
        sess, q6 = stmt_session["session"], stmt_session["q6"]
        tracer = trace.get_tracer()
        before = tracer.totals()
        trace.set_enabled(False)
        try:
            events = _profile_events(tmp_path, lambda: sess.sql(q6))
        finally:
            trace.set_enabled(True)
        assert events == []
        assert tracer.totals() == before
        assert stmt_session["pipe"].last_query_span is None


SITES_TEXT = """\
HloModule jit_fn, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %gather.9 = f32[8]{0} gather(%param_0), metadata={op_name="gather"}
}

ENTRY %main.5 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0:T(1024)} parameter(0)
  %fusion.1 = f32[8]{0:T(1024)S(1)} fusion(f32[8]{0:T(1024)} %p), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(fn)/op.limit/op.aggregate/gather/jit(_take)/gather" stack_frame_id=3}
  %copy.2 = f32[8]{0} copy(%fusion.1)
  %reduce-window.3 = (f32[8]{0}, f32[8]{0}) reduce-window(%copy.2, %copy.2), window={size=8}
  %sort.4 = (f32[8]{0:T(1024)}, s32[8]{0}) sort(%p, %p), dimensions={0}
  ROOT %tuple.5 = (f32[8]{0}) tuple(%copy.2)
}
"""


def test_sites_name_every_instruction_by_its_scopes():
    """``costs.parse_sites``: each instruction's ``op_name`` and opcode
    (a layout's ``T(..)`` is no opcode); an instruction the compiler
    made without metadata takes the operator scopes of its first
    operand that has any, and none where no operand has one."""
    from nds_tpu.obs import costs
    sites = costs.parse_sites(SITES_TEXT)
    assert sites["fusion.1"] == (
        "jit(fn)/op.limit/op.aggregate/gather/jit(_take)/gather", "fusion")
    assert sites["gather.9"] == ("gather", "gather")
    assert sites["copy.2"] == ("jit(fn)/op.limit/op.aggregate/copy", "copy")
    assert sites["reduce-window.3"] == (
        "jit(fn)/op.limit/op.aggregate/reduce-window", "reduce-window")
    assert sites["sort.4"] == ("", "sort")
    assert sites["p"] == ("", "parameter")
