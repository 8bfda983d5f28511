"""NDS-H at scale 5 on one chip (benchmark cell ``nds_h_sf5.power``),
as far as a CPU can hold it to account: the cell's files against
``nds_h_sf1``'s, a rehearsal of the cell through ``benchmarks/run.py``
at SF0.01 in the deployment's 40 chunks, the scheduler at the
deployment's size without its data (scale 5's row counts, the resident
tables counted live: every statement starts ``device`` under the shipped
budget and ``sched.place`` says what it saw; scale 30's still starts
``chunked``), one device copy of a column a process, and the generator
at 40 chunks.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from nds_tpu.analysis import plan_verify
from nds_tpu.engine import scheduler
from nds_tpu.engine.scheduler import CHUNKED, DEVICE, ExecutionPipeline
from nds_tpu.obs import memwatch
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import get_tracer
from nds_tpu.utils.config import EngineConfig

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
REHEARSAL = os.path.join(BENCH, "tests", "rehearsal_sf5", "BENCHMARK.json")
MIX = "power_nds_h_sf5"
PLACE_ATTRS = {"placement", "est_bytes", "live_bytes", "projected_bytes",
               "budget_bytes", "governed"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _statements():
    from benchmarks import generator
    mix = generator.load_mix(MIX)
    return generator.distinct(mix, generator.variants(mix, 7))


# ------------------------------------------------------ (a) the cell's files

def test_traffic_is_sf1s_but_for_the_row_counts():
    sf1 = _json(BENCH, "traffic", "power_nds_h.json")
    sf5 = _json(BENCH, "traffic", "power_nds_h_sf5.json")
    rows: dict = {}
    for mix, keep in ((sf1, None), (sf5, rows)):
        for stmt in mix["statements"]:
            for need in stmt.get("need", []):
                if keep is not None:
                    keep.setdefault(need[0], set()).add(need[1])
                need[1] = None
    # statements, templates, sets, order_by, loop, bytes a row
    assert sf5 == sf1
    # one row count a table: the generated population's
    from nds_tpu.datagen import tpch
    _d, nlines, _c, _h = tpch._order_attrs(
        0, 5.0, 0, tpch.table_rows("orders", 5.0))
    want = {t: {tpch.table_rows(t, 5.0)} for t in rows}
    want["lineitem"] = {int(nlines.sum())}
    assert rows == want and rows["lineitem"] == {29_998_959}


def test_configuration_keeps_sf1s_guarantees_and_limits():
    sf1 = _json(BENCH, "configs", "nds_h_sf1.json")
    sf5 = _json(BENCH, "configs", "nds_h_sf5.json")
    assert sf5["guarantees"] == sf1["guarantees"]
    assert sf5["limits"] == sf1["limits"]
    assert (sf5["suite"], sf5["template"]) == (sf1["suite"],
                                               sf1["template"])
    # 5, in the spelling that only a program able to stage it reads
    assert sf5["scale"] == "sf5" and sf5["gen_parallel"] == 40
    assert sf5["architecture"] is None
    assert sf5["reduced"] == ["scale", "statements"]
    assert set(sf5["reduced_why"]) == set(sf5["reduced"])
    assert sf5["assumed"]["chips"] == 1


@pytest.mark.parametrize("text,want", [
    ("5", 5.0), ("sf5", 5.0), ("SF0.01", 0.01), ("0.01", 0.01),
    ("sf", None), ("five", None)])
def test_gen_data_reads_the_scale_as_tpc_writes_it(text, want):
    """``sf5`` is how the configuration asks for the deployment: this
    program reads it, one from before PR 31, whose transcode and load
    cannot stage the population on the chip's host, refuses it at once
    (plain ``float``) and the harness ends in its first child."""
    from nds_tpu.nds_h import gen_data
    if want is None:
        with pytest.raises(ValueError):
            gen_data.scale_factor(text)
        with pytest.raises(SystemExit) as exc:
            gen_data.main([text, "40", os.devnull])
        assert exc.value.code == 2
        return
    assert gen_data.scale_factor(text) == want
    if text.lower().startswith("sf"):
        with pytest.raises(ValueError):
            float(text)             # what the parent's gen_data does


def test_benchmark_entries():
    bench = _json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}["nds_h_sf5.power"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nds_h_sf5", MIX, 1)
    entry = {c["name"]: c for c in bench["configs"]}["nds_h_sf5"]
    config = _json(ROOT, entry["file"])
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    reported = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if "nds_h_sf5.power" in m.get("workloads",
                                              ["nds_h_sf5.power"])}
    assert {"pass_s", "setup_s", "hbm_roofline_pct", "program_gb_per_pass",
            "idle_pct", "host_ms_per_stmt", "first_bind_s",
            "governed_stmts_per_pass", "place_projected_pct"} <= reported
    new = [m for m in bench["per_layer"]
           if m.get("workloads") == ["nds_h_sf5.power"]]
    assert [m["name"] for m in new] == ["governed_stmts_per_pass",
                                        "place_projected_pct"]
    for m in new:
        assert (m["moves"], m["layer"]) == ("pass_s", "host path")
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("spans,want", [
    ({"governed": 0, "projected_bytes": 30e9, "budget_bytes": 60e9},
     (0.0, 50.0)),
    ({"governed": 3, "projected_bytes": 66e9, "budget_bytes": 60e9},
     (1.5, 110.0)),
    ({"count": 16}, (None, None)),       # a program older than the attrs
    (None, (None, None)),                # no sched.place span at all
])
def test_readers_of_the_new_metrics(monkeypatch, spans, want):
    """The two readers over a reduced slice of two passes; what a
    program without the attributes leaves them is nothing, not 0."""
    import importlib.util
    from benchmarks import span_reduce
    table = {} if spans is None else {"sched.place": spans}
    monkeypatch.setattr(span_reduce, "for_run",
                        lambda run: {"spans": table})
    run = {"window": {"slice": (0.0, 1.0, 2, 16)}}
    got = []
    for name in ("governed_stmts_per_pass", "place_projected_pct"):
        spec = importlib.util.spec_from_file_location(
            "layer_" + name, os.path.join(BENCH, "layers", name + ".py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        got.append(reader.read(run))
    assert tuple(got) == pytest.approx(want)


# -------------------------------------------- (b) the cell, rehearsed whole

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """``benchmarks/run.py`` on the rehearsal twin of the cell, in a
    child of its own (run.py sets a process up for good), the program's
    Chrome export on so that the placements can be read."""
    trace = tmp_path_factory.mktemp("sf5") / "trace.jsonl"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("NDS_TPU_") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", NDS_TPU_TRACE=str(trace))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"),
         "--benchmark", REHEARSAL, "--workload", "rehearsal.sf5",
         "--seed", "3000000019", "--seconds", "0.3", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=1200)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return (proc.returncode, json.loads(lines[-1]) if lines else None,
            proc.stderr, str(trace))


def test_rehearsal_is_correct(rehearsal):
    rc, line, err, _trace = rehearsal
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 7
    assert set(line["metrics"]) == {"pass_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"        # never recorded
    assert line["checks"]["max_rel_gap"]["value"] <= 1e-9
    config = _json(BENCH, "tests", "rehearsal_sf5", "nds_h_sf001_p40.json")
    assert config["gen_parallel"] == _json(
        BENCH, "configs", "nds_h_sf5.json")["gen_parallel"]
    raw = os.path.join(BENCH, ".work", config["name"], "raw", "lineitem")
    assert len(os.listdir(raw)) == 40


def test_rehearsal_places_every_statement_on_the_device(rehearsal):
    _rc, line, _err, trace = rehearsal
    places, chunked = [], 0
    with open(trace) as f:
        for ln in f:
            event = json.loads(ln)
            if event["name"] == "sched.place":
                places.append(event["args"])
            chunked += event["name"].startswith("chunk.")
    # the warm-up's sessions, the untimed pass and the window
    assert len(places) >= line["attempted"] + 8
    for args in places:
        assert PLACE_ATTRS <= set(args)
        assert args["placement"] == "device" and args["governed"] == 0
        assert 0 < args["est_bytes"]
        assert args["projected_bytes"] <= args["budget_bytes"] == 8 << 30
    assert chunked == 0
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import check_trace_schema
    assert check_trace_schema.validate_file(trace) == []


# ------------------------- (c) the scheduler at the deployment's size

def _sized_tables(scale: float, lineitem: "int | None" = None) -> dict:
    """Row counts of the population at ``scale``, no data: the
    estimate then takes every column at its device width."""
    from nds_tpu.datagen import tpch
    from nds_tpu.engine.session import TPCH_SIZES
    rows = {t: tpch.table_rows(t, scale) for t in TPCH_SIZES}
    if lineitem:
        rows["lineitem"] = lineitem
    return {t: types.SimpleNamespace(nrows=n) for t, n in rows.items()}


class _Warehouse:
    """Stands in for the device executor of a session whose tables are
    on the device: whatever a plan scans is resident."""

    def __init__(self, tables):
        self.tables = tables
        self.last_timings = {}
        self.last_query_span = None

    def resident_bytes(self, planned) -> int:
        return plan_verify.estimate_plan(planned,
                                         tables=self.tables).held_bytes


def _place_all(scale, lineitem=None, warehouse_resident=True):
    """Every statement of the cell through ``ExecutionPipeline._place``
    on the shipped budget, the scanned tables resident and counted
    live (or, a warehouse no chip holds, not yet uploaded)
    -> {label: (placement, reason, sched.place attributes)}."""
    from nds_tpu.engine.session import Session
    tables = _sized_tables(scale, lineitem)
    pipe = ExecutionPipeline(backend="tpu", config=EngineConfig(
        overrides={"engine.backend": "tpu"}))
    pipe(tables)
    if warehouse_resident:
        pipe._executors[DEVICE] = _Warehouse(tables)
    assert pipe.governor.budget == scheduler.DEFAULT_DEVICE_BUDGET == 8 << 30
    session = Session.for_nds_h()
    resident = sum(
        plan_verify._scan_bytes(None, list(schema_cols), t.nrows)
        for t, schema_cols in (
            (tables[name], [(f.name, f.dtype) for f in schema])
            for name, schema in session.catalog.schemas.items()))
    if not warehouse_resident:
        resident = 0
    out = {}
    tracer = get_tracer()
    memwatch.add_live(resident)
    try:
        for stmt in _statements():
            planned = session.plan(stmt.sql)
            root = tracer.begin("stmt", parent=None, keep=True)
            with tracer.attach(root):
                placement, _stats, sched = pipe._place(planned)
            root.end()
            (span,) = root.find("sched.place")
            out[stmt.label] = (placement, sched["reason"],
                               dict(span.attrs))
    finally:
        memwatch.sub_live(resident)
    return out, resident


def test_scale_5_starts_every_statement_on_the_device():
    sf5 = _json(BENCH, "traffic", "power_nds_h_sf5.json")
    lineitem = sf5["statements"][1]["need"][0][1]
    before = obs_metrics.snapshot()
    placed, resident = _place_all(5.0, lineitem)
    assert set(placed) == {"q6#0", "q6#1", "q1#0", "q3#0", "q18#0",
                           "q13#0", "q16#0", "q21#0"}
    assert 3.2e9 < resident < 3.5e9          # all 8 tables, every column
    for label, (placement, reason, attrs) in placed.items():
        assert placement == DEVICE, (label, reason)
        assert reason.startswith("fits:"), (label, reason)
        assert set(attrs) == PLACE_ATTRS, label
        assert attrs["placement"] == "device" and attrs["governed"] == 0
        assert attrs["live_bytes"] >= resident
        # nothing left to upload: live + the intermediates alone
        assert attrs["live_bytes"] < attrs["projected_bytes"] \
            <= attrs["budget_bytes"] == 8 << 30, label
    d = obs_metrics.delta(before, obs_metrics.snapshot())["counters"]
    assert not d.get("governor_preemptive_demotions_total")
    assert not d.get("hwm_history_placements_total")


def test_why_the_parents_estimate_was_not_the_working_set():
    """q21 scans lineitem three times and reads 11 of its 16 columns:
    ``est.bytes`` (a table once a Scan node, every column) is 8.6 GB of
    estimate for 3.1 GB of buffers, and x3.0 it passed any chip."""
    from nds_tpu.engine.session import Session
    tables = _sized_tables(5.0, 29_998_959)
    session = Session.for_nds_h()
    q21 = next(s for s in _statements() if s.name == "q21")
    est = plan_verify.estimate_plan(session.plan(q21.sql), tables=tables)
    assert est.bytes > 8.5e9 and est.bytes * 3.0 > 16e9
    assert 3.0e9 < est.held_bytes < 3.2e9
    assert 1.9e9 < est.read_bytes < 2.1e9
    assert scheduler.working_set(est, 3.0) < 8 << 30
    # a table the plan never reads a column of inflates nothing ...
    assert scheduler.working_set(est, 3.0, resident=est.held_bytes) == \
        int(est.read_bytes * 2.0)
    # ... and an estimate made by hand is taken as before
    by_hand = types.SimpleNamespace(bytes=600)
    assert scheduler.working_set(by_hand, 2.0) == 1200


def test_scale_30_still_starts_out_of_core():
    placed, _resident = _place_all(30.0, warehouse_resident=False)
    placement, _reason, attrs = placed["q1#0"]
    assert placement == CHUNKED and attrs["placement"] == "chunked"
    # by the plan's own size (the governor then pre-shrinks its chunks)
    from nds_tpu.engine.session import Session
    q1 = next(s for s in _statements() if s.name == "q1")
    choice, why = scheduler.CostModel().choose(
        Session.for_nds_h().plan(q1.sql), scheduler.UNIVERSES["tpu"],
        tables=_sized_tables(30.0))
    assert choice == CHUNKED and why.startswith("working-set:")
    for label in ("q3#0", "q18#0", "q21#0", "q6#0"):
        assert placed[label][0] == CHUNKED, label


def test_governor_counts_resident_scans_once():
    est = types.SimpleNamespace(bytes=900, held_bytes=600, read_bytes=300)
    gov = scheduler.MemoryGovernor(budget=1000, expansion=2.0)
    # nothing resident: 600 to upload + 300 of intermediates
    assert gov.project(est, live=0) == 900
    # the scans resident and in the live bytes: only the intermediates
    assert gov.project(est, resident=600, live=600) == 900
    assert gov.decide(est, resident=600, live=600) is None
    assert gov.projected == 900
    # counted twice, as before, the same statement would be governed
    assert gov.decide(est, resident=0, live=600)
    assert gov.projected == 1500


def test_high_water_history_takes_the_statements_own():
    """The device's high-water is the process's; what other statements
    kept there comes off before the history hears of it, and a
    placement the history does take is counted and marked."""
    from nds_tpu.resilience import faults

    class Fake(_Warehouse):
        def execute(self, planned, key=None):
            memwatch.add_live(900)          # the statement's own peak
            memwatch.sub_live(900)
            return "ok"

        def resident_bytes(self, planned):
            return 0

    from nds_tpu.engine.session import Session
    session = Session.for_nds_h()
    planned = session.plan("select count(*) c from region")
    tables = {"region": types.SimpleNamespace(nrows=5)}
    pipe = ExecutionPipeline(backend="tpu", config=EngineConfig(
        overrides={"engine.backend": "tpu",
                   "engine.placement.device_budget_bytes": "1000"}))
    pipe(tables)
    pipe._executors[DEVICE] = Fake(tables)
    pipe._executors[CHUNKED] = Fake(tables)
    pipe.governor = None                    # the history alone
    memwatch.add_live(5000)                 # a warehouse, resident
    try:
        memwatch.reset_query()
        with faults.context(query="q_own"):
            pipe.execute(planned)
        # governor off: nothing read the live bytes, the whole
        # high-water is taken (the parent's behaviour, kept)
        assert pipe.cost_model.hwm_history["q_own"] == 5900
        pipe.cost_model.hwm_history.clear()
        pipe.governor = scheduler.MemoryGovernor(budget=1 << 40)
        memwatch.reset_query()
        with faults.context(query="q_own"):
            pipe.execute(planned)
        assert pipe.cost_model.hwm_history["q_own"] == 900
        assert pipe.last_schedule["placement"] == DEVICE
        # a statement whose OWN high-water passed the budget
        pipe.cost_model.observe("q_own", 1001)
        before = obs_metrics.snapshot()
        tracer = get_tracer()
        root = tracer.begin("stmt", parent=None, keep=True)
        with tracer.attach(root), faults.context(query="q_own"):
            placement, _stats, sched = pipe._place(planned)
        root.end()
    finally:
        memwatch.sub_live(5000)
    assert placement == CHUNKED
    assert sched["reason"].startswith("hwm-history:")
    (span,) = root.find("sched.place")
    assert span.attrs["governed"] == 1
    d = obs_metrics.delta(before, obs_metrics.snapshot())["counters"]
    assert d["hwm_history_placements_total"] == 1


# --------------------------------- one device copy of a column a process

def test_sessions_of_one_process_share_a_columns_device_copy():
    """Two device executors over the same host tables (the benchmark's
    concurrent warm-up) bind the SAME device arrays: the second uploads
    nothing, and counts what the first placed as resident."""
    from nds_tpu.engine.device_exec import DeviceExecutor
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h.schema import get_schemas
    from nds_tpu.datagen import tpch
    tables = {"region": from_arrays(
        "region", get_schemas()["region"], tpch.gen_table("region", 0.01))}
    planned = Session.for_nds_h().plan("select count(*) c from region")
    one, two = DeviceExecutor(tables), DeviceExecutor(tables)
    assert one.resident_bytes(planned) == 0
    bufs1 = one._collect_buffers(planned)
    assert one._uploads > 0
    assert two.resident_bytes(planned) == one.resident_bytes(planned) \
        == sum(b.nbytes for b in bufs1.values())
    bufs2 = two._collect_buffers(planned)
    assert two._uploads == 0
    assert set(bufs1) == set(bufs2)
    assert all(bufs1[k] is bufs2[k] for k in bufs1)
    # a new host table (DML makes new columns) is placed anew
    other = {"region": from_arrays(
        "region", get_schemas()["region"], tpch.gen_table("region", 0.01))}
    three = DeviceExecutor(other)
    assert three.resident_bytes(planned) == 0
    bufs3 = three._collect_buffers(planned)
    assert three._uploads > 0 and bufs3["region.r_regionkey"] is not \
        bufs1["region.r_regionkey"]


# ----------------------------------------------- (d) 40 chunks a table

@pytest.mark.parametrize("table,key", [
    ("orders", "o_orderkey"), ("customer", "c_custkey"),
    ("part", "p_partkey"), ("supplier", "s_suppkey"),
    ("partsupp", "ps_partkey"), ("lineitem", "l_orderkey"),
])
def test_forty_chunks_tile_each_table(table, key):
    from nds_tpu.datagen import tpch
    sf, parallel = 0.05, 40
    chunks = [tpch.gen_table(table, sf, parallel, step)[key]
              for step in range(1, parallel + 1)]
    keys = np.concatenate(chunks)
    parent = {"partsupp": "part", "lineitem": "orders"}.get(table, table)
    total = tpch.table_rows(parent, sf)
    # chunk i holds exactly the keys of its row range, in order
    for step, chunk in enumerate(chunks, 1):
        lo, hi = tpch._chunk_range(total, parallel, step)
        assert chunk.min() == lo + 1 and chunk.max() == hi
        assert np.all(np.diff(chunk) >= 0)
    assert np.array_equal(np.unique(keys), np.arange(1, total + 1))
    if table == "partsupp":
        assert len(keys) == 4 * total
    elif table != "lineitem":
        assert len(keys) == total
    else:
        # every foreign key lies in orders, and the chunking does not
        # change the population's size
        whole = tpch.gen_table("lineitem", sf, 1, 1)["l_orderkey"]
        assert np.array_equal(keys, whole)


# ------------------------------------ sched.place in the exported summaries

@pytest.mark.parametrize("attrs,errors", [
    ({}, 0),                                   # a tree older than them
    ({"placement": "device", "governed": 0, "est_bytes": 10,
      "live_bytes": 0, "projected_bytes": 10, "budget_bytes": 20}, 0),
    ({"placement": "device", "governed": 2, "est_bytes": 10,
      "live_bytes": 0, "projected_bytes": 10, "budget_bytes": 20}, 1),
    ({"placement": "hbm", "governed": 1, "est_bytes": -1,
      "live_bytes": 0, "projected_bytes": 10}, 3),
])
def test_schema_checker_knows_sched_places_attributes(attrs, errors):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import check_trace_schema
    tree = {"name": "stmt", "dur_ms": 1.0, "children": [
        {"name": "sched.place", "dur_ms": 0.1, "attrs": attrs}]}
    assert len(check_trace_schema._validate_span_tree(tree, "spans")) \
        == errors


# ------------------------------------- transcode and load, a chunk at a time

@pytest.mark.parametrize("table", ["orders", "lineitem", "nation"])
def test_streamed_transcode_loads_the_table_the_raw_files_hold(
        table, tmp_path):
    """``transcode`` writes a chunk at a time (at scale 5 lineitem held
    whole took 19 GiB of host memory); what loads back is, column for
    column, what reading every raw chunk at once gives: values, sorted
    dictionaries and codes, null masks."""
    import pyarrow.parquet as pq
    from nds_tpu.io import csv_io
    from nds_tpu.nds_h import gen_data, transcode
    from nds_tpu.nds_h.schema import get_schemas
    raw, wh = tmp_path / "raw", tmp_path / "wh"
    gen_data.generate_data_local(0.01, 40, str(raw), table=table,
                                 workers=2)
    schema = get_schemas()[table]
    transcode.transcode_table(table, schema, str(raw), str(wh))
    out = wh / table / "part-0.parquet"
    chunks = sorted(str(p) for p in (raw / table).iterdir())
    assert len(chunks) == (1 if table == "nation" else 40)
    assert pq.ParquetFile(out).metadata.num_row_groups == len(chunks)
    whole = csv_io.read_tbl(chunks, table, schema)
    loaded = csv_io.read_table_fmt([str(out)], table, schema, "parquet")
    assert loaded.nrows == whole.nrows > 0
    for name, want in whole.columns.items():
        got = loaded.columns[name]
        assert got.values.dtype == want.values.dtype
        assert np.array_equal(got.values, want.values), name
        assert (got.dictionary is None) == (want.dictionary is None)
        if want.dictionary is not None:
            assert np.array_equal(got.dictionary, want.dictionary), name
            # code order is the strings' order
            assert list(got.dictionary) == sorted(got.dictionary)
        assert (got.null_mask is None) == (want.null_mask is None)
