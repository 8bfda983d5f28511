"""CPU rehearsal of the write path at SF0.01: refresh functions run
through the engine's DML at the head of every pass, each write is held
to the plain reference by the row count and the rows of the tables it
writes, the reads after them against the reference over what the
writes left, and the written tables are back at their load-time selves
after every pass.

rehearsal.dm_nds (LF_SS, DF_SS, three reads) is not correct on the
program as it stands: LF_SS's LEFT OUTER JOINs lose their keys on the
device path (test_device_left_join_fault_is_caught).  The delete path
alone, rehearsal.dm_nds_delete, stands in for the sound runs.  Planted
faults in a write and in the restore have to come out as not
correct."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

from conftest import HERE, REHEARSAL, ROOT

CELL = "rehearsal.dm_nds_delete"
SECONDS = 6               # two passes or more on the CPU (~3 s each)


def _window_compiler_runs(err: str) -> int:
    found = re.findall(r"\] window .* (\d+) compiler runs", err)
    assert found, err[-2000:]
    return int(found[-1])


def _last_run(cell=CELL) -> dict:
    with open(os.path.join(ROOT, "benchmarks", ".work", cell,
                           "last_run.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [3, 2_500_000_123])
def test_writes_then_reads_are_correct(run_cell, seed):
    rc, line, err = run_cell(CELL, seed=seed, seconds=SECONDS)
    assert rc == 0
    assert line["correct"] is True, err[-3000:]
    assert line["passes"] >= 2 and line["failed"] == 0
    assert line["attempted"] == 4 * line["passes"]
    assert _window_compiler_runs(err) == 0
    assert set(line["metrics"]) == {"pass_s", "setup_s"}
    assert list(line)[-1] == "checks"
    checks = line["checks"]
    assert checks["writes_wrong"] == {"value": 0, "limit": 0}
    # after the window the session's tables are the load-time ones
    assert checks["tables_not_restored"] == {"value": 0, "limit": 0}
    for name in ("failed_statements", "rows_wrong", "repeats_differ",
                 "max_rel_gap", "writes_wrong", "tables_not_restored"):
        assert f"compared {name}:" in err
    doc = _last_run()
    df = doc["per_statement"]["DF_SS#0"]
    assert df["ok"]
    # DF_SS took a month of sales and their returns, and left the rest
    assert 0 < df["rows"]["store_sales"] < 28804
    assert 0 < df["rows"]["store_returns"]
    # one restore after every pass of the window, two in set-up, each
    # of the two tables the pass wrote
    restores = [s for s in doc["spans"] if s["name"] == "restore"]
    assert len(restores) == line["passes"] + 2
    assert all(s["tables"] == ["store_returns", "store_sales"]
               for s in restores)


def test_traced_line(run_cell):
    rc, line, err = run_cell(CELL, seed=11, trace=1)
    assert rc == 0 and line["correct"] is True, err[-3000:]
    # the device-trace readers find no device plane on the CPU
    assert set(line["metrics"]) == {"host_ms_per_stmt", "window_compiles",
                                    "compile_s", "load_s"}
    assert line["metrics"]["window_compiles"]["value"] == 0


def _keep_one_more(session):
    """Every DELETE keeps the first row it should have deleted."""
    from nds_tpu.engine import dml

    inner = session._run_dml

    def run_dml(action, name, payload):
        if action != "delete":
            return inner(action, name, payload)
        table = session.tables[name]
        keep = dml.delete_mask(session, table, payload)
        dead = np.flatnonzero(~keep)
        if len(dead):
            keep[dead[0]] = True
        session.tables[name] = dml.apply_delete(table, keep)
        session.invalidate(tables=[name])

    session._run_dml = run_dml


def test_delete_keeping_a_row_is_not_correct(run_cell):
    rc, line, err = run_cell(CELL, seed=5, seconds=0.3,
                             tamper=_keep_one_more)
    assert rc == 0
    assert line["correct"] is False, err[-1500:]
    assert line["checks"]["writes_wrong"]["value"] >= 1, line["checks"]
    assert re.search(r"^check: DF_SS#0: rows after it", err, re.M), \
        err[-1500:]


def test_table_left_written_is_not_correct(run_cell, monkeypatch):
    """A restore that leaves store_returns as the pass wrote it."""
    from benchmarks import run
    inner = run.restore
    monkeypatch.setattr(run, "restore", lambda session, loaded, written,
                        spans: inner(session, loaded,
                                     set(written) - {"store_returns"},
                                     spans))
    rc, line, err = run_cell(CELL, seed=5, seconds=0.3)
    assert rc == 0
    assert line["correct"] is False, err[-1500:]
    assert line["checks"]["tables_not_restored"]["value"] == 1
    assert re.search(r"^check: store_returns: not the load-time table",
                     err, re.M), err[-1500:]


def test_device_left_join_fault_is_caught(run_cell):
    """LF_SS through the device path inserts the right number of rows
    with NULL keys where the reference has them: a LEFT OUTER JOIN whose
    right side is not declared unique is run as if its left side were
    (DeviceExecutor._run_join), so of the left rows that share a key
    only one finds its match, and LF_SS joins every dimension by a
    business id that way.  The row counts agree; the rows do not, and
    the run is not correct.  The CPU oracle of the program, the second
    witness, gives the reference's rows.  When the program's join is
    repaired, this run reads correct and the test changes with it."""
    from benchmarks import compare, generator, run
    from benchmarks.reference import rawdata
    rc, line, err = run_cell("rehearsal.dm_nds", seed=9, seconds=0.3)
    assert rc == 0
    assert line["correct"] is False, err[-1500:]
    assert line["failed"] == 0
    assert line["checks"]["writes_wrong"]["value"] >= 1
    assert re.search(r"^check: LF_SS#0: rows of store_sales after it "
                     r"differ from the reference's \(row counts", err, re.M), \
        err[-1500:]
    # the witness: the program's CPU oracle leaves the reference's rows
    from nds_tpu.nds import maintenance
    from nds_tpu.nds.schema import get_maintenance_schemas
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    root = os.path.join(ROOT, "benchmarks", ".work", "rehearsal_nds_dm")
    econf = EngineConfig(os.path.join(ROOT, "configs",
                                      "power_run_tpu.template"),
                         overrides={"engine.backend": "cpu"})
    suite = maintenance._maintenance_suite(econf)
    session = power_core.make_session(suite, econf)
    power_core.load_warehouse(suite, session, os.path.join(root, "wh"),
                              schemas=power_core.suite_schemas(suite, econf))
    power_core.load_warehouse(
        suite, session, os.path.join(root, "refresh1"), "raw",
        schemas=get_maintenance_schemas(
            **power_core.schema_kwargs_for(suite, econf)))
    stmt = generator.variants(generator.load_mix("dm_nds"), 0)["LF_SS"][0]
    rec = run.run_write(session, stmt, keep=True)
    assert rec["error"] is None
    tables = rawdata.Tables("nds", os.path.join(root, "raw"),
                            os.path.join(root, "refresh1"))
    for table, frame in run.reference_module(stmt).apply(
            tables, stmt.params, rawdata.Real()).items():
        tables.write(table, frame)
    fields = tables.schema["store_sales"]
    assert compare.table_digest(run.live_columns(
        rec["tables"]["store_sales"], fields)) == compare.table_digest(
        run.reference_columns(tables, "store_sales"))


def test_load_time_tables_digest_as_the_reference_reads_them():
    """The content comparison's two readings agree where nothing was
    written: every table a refresh function writes, as loaded."""
    from benchmarks import compare, run
    from benchmarks.reference import rawdata
    from nds_tpu.nds import power
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    root = os.path.join(ROOT, "benchmarks", ".work", "rehearsal_nds_dm")
    if not os.path.exists(os.path.join(root, "ready.json")):
        with open(REHEARSAL) as f:
            entry = {c["name"]: c for c in json.load(f)["configs"]}[
                "rehearsal_nds_dm"]
        with open(os.path.join(ROOT, entry["file"])) as f:
            try:
                run.build_warehouse(json.load(f))
            finally:
                run.kill_children()
    econf = EngineConfig(os.path.join(ROOT, "configs",
                                      "power_run_tpu.template"))
    session = power_core.make_session(power.SUITE, econf)
    power_core.load_warehouse(power.SUITE, session, os.path.join(root, "wh"),
                              schemas=power_core.suite_schemas(power.SUITE,
                                                               econf))
    tables = rawdata.Tables("nds", os.path.join(root, "raw"))
    for name in ("store_sales", "store_returns", "catalog_sales",
                 "web_sales", "inventory"):
        assert compare.table_digest(run.live_columns(run.kept_rows(
            session.tables[name]), tables.schema[name])) == \
            compare.table_digest(run.reference_columns(tables, name)), name


def test_a_pass_runs_its_writes_first():
    from benchmarks import generator
    mix = generator.load_mix("dm_nds")
    for seed in range(10):
        sets = generator.variants(mix, seed)
        names = generator.order(mix, seed)
        assert names[:2] == ["LF_SS", "DF_SS"]
        assert sorted(names[2:]) == ["query3", "query7", "query96"]
        assert [s.name for s in generator.distinct(mix, sets)] == [
            "LF_SS", "DF_SS", "query7", "query3", "query96"]
    orders = {tuple(generator.order(mix, seed)) for seed in range(20)}
    assert len(orders) > 1
    lf, df = (generator.variants(mix, 0)[n][0] for n in ("LF_SS", "DF_SS"))
    assert lf.writes == ("store_sales",)
    assert [p.split()[0] for p in lf.parts] == ["DROP", "CREATE", "INSERT",
                                                "DROP"]
    assert [p.split()[:3] for p in df.parts] == [
        ["DELETE", "FROM", "store_returns"], ["DELETE", "FROM", "store_sales"]]
    assert "'1998-03-31'" in df.parts[1] and "{" not in df.sql
    read = generator.variants(mix, 0)["query7"][0]
    assert read.writes == () and read.parts == [read.sql]


def _sequence_digest(mix_name: str, seed: int, passes: int) -> str:
    from benchmarks import generator
    mix = generator.load_mix(mix_name)
    sets = generator.variants(mix, seed)
    names = generator.order(mix, seed)
    h = hashlib.sha256()
    for i in range(passes):
        for s in generator.pass_statements(sets, names, i):
            h.update(f"{i}|{s.label}|{s.sql}\n".encode())
    for s in generator.distinct(mix, sets):
        h.update(f"d|{s.label}|{s.sql}\n".encode())
    return h.hexdigest()


def test_read_only_mixes_give_the_statements_they_gave():
    """Every traffic file without a write, seeds 0-9: the statements of
    the first passes and of the warm-up, label and SQL, in order, are
    those the generator gave before it knew writes."""
    from benchmarks import generator
    with open(os.path.join(HERE, "fixtures",
                           "generator_sequences.json")) as f:
        doc = json.load(f)
    traffic = os.path.join(ROOT, "benchmarks", "traffic")
    read_only = sorted(
        name[:-5] for name in os.listdir(traffic) if name.endswith(".json")
        and not any(s.get("writes") for s in
                    generator.load_mix(name[:-5])["statements"]))
    assert read_only == sorted(doc["digests"])
    for mix_name, digests in doc["digests"].items():
        for seed, want in enumerate(digests):
            assert _sequence_digest(mix_name, seed, doc["passes"]) == want, \
                (mix_name, seed)
