"""CPU rehearsal of the four-chip NDS cell (`nds_sf1.dist4`) through
run.py at SF0.01: a child process with four virtual CPU devices, the
shipped distributed template, no ``NDS_TPU_*`` variable set; one planted
fault a new reference (each has to come out not correct); and the
reader of ``replicate_mb_per_pass`` over the launch spans recorded from
the cell's traced run on the chip."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from conftest import HERE, ROOT

from benchmarks import compare, generator
from benchmarks import run as bench_run
from benchmarks import span_reduce as sr
from benchmarks.reference import rawdata

BENCH = os.path.join(HERE, "rehearsal_nds_dist4", "BENCHMARK.json")
CELL = "rehearsal.nds_dist4"
MIX = "power_nds_dist4"
RECORDED = os.path.join(HERE, "fixtures", "nds_dist4_launch_spans.json")


def run_cell(seed: int, trace: int):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("NDS_TPU_")}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--benchmark", BENCH, "--workload", CELL, "--seed", str(seed),
         "--seconds", "0.3", "--trace", str(trace)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc.returncode, (json.loads(lines[-1]) if lines else None), \
        proc.stderr


@pytest.fixture(scope="module")
def untraced():
    return run_cell(seed=2_500_000_321, trace=0)


def test_untraced_line_is_correct(untraced):
    rc, line, err = untraced
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 5
    assert set(line["metrics"]) == {"pass_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"       # never recorded
    assert line["device"]["count"] == 4
    with open(os.path.join(ROOT, "benchmarks", ".work", CELL,
                           "last_run.json")) as f:
        detail = json.load(f)
    assert set(detail["statement_wall_ms"]) == {
        "query7", "query27", "query98", "query38", "query21"}
    assert all(v["ok"] and v["rows"] > 0
               for v in detail["per_statement"].values())


def test_traced_line_leaves_the_device_metrics_out():
    rc, line, err = run_cell(seed=11, trace=1)
    assert rc == 0 and line["correct"] is True, err[-3000:]
    # no device plane on the CPU: the readers of the device trace and of
    # spans laid over it (replicate_mb_per_pass among them) find nothing.
    # Set-up's spans are all there, a first bind among them: the sharded
    # path binds its scans under device.bind since the operator scopes
    # came in, so first_bind_s, listed in the rehearsal's file, is read
    assert set(line["metrics"]) == {
        "host_ms_per_stmt", "window_compiles", "compile_s", "load_s",
        "engine_init_s", "load_read_s", "load_build_s", "lower_s",
        "cache_read_s", "first_bind_s"}
    assert line["metrics"]["window_compiles"]["value"] == 0


# ------------------------------------------ a planted fault a reference

@pytest.fixture(scope="module")
def references(untraced):
    """Each statement's reference over the rehearsal's population (the
    run above built it): (statement, frame)."""
    raw = os.path.join(ROOT, "benchmarks", ".work", "rehearsal_nds_dist4",
                       "raw")
    tables, real = rawdata.Tables("nds", raw), rawdata.Real()
    mix = generator.load_mix(MIX)
    out = {}
    for stmt in generator.distinct(mix, generator.variants(mix, 7)):
        fn = importlib.import_module(
            "benchmarks.reference." + stmt.template.replace("/", ".")
        ).reference
        out[stmt.name] = (stmt, fn(tables, stmt.params, real), tables)
    return out


def _numbers(stmt, got, ref):
    got = got.reset_index(drop=True)
    got.columns = range(got.shape[1])
    ok, gap, note = compare.compare_statement(
        got, compare.frame_kinds(ref), ref, stmt.order_by)
    return compare.verdict(
        {"failed_statements": 0, "rows_wrong": 0 if ok else 1,
         "repeats_differ": 0, "max_rel_gap": gap},
        {"failed_statements": 0, "rows_wrong": 0, "repeats_differ": 0,
         "max_rel_gap": 1e-9}), note


def _rollup_key_given_a_value(stmt, ref, _tables):
    """query27: a subtotal row's NULL s_state reads as a state."""
    got = ref.copy()
    row = got.index[got.s_state.isna() & got.i_item_id.notna()][0]
    got.loc[row, "s_state"] = "SD"
    return got, "rows_wrong"


def _window_share_off_by_a_row(stmt, ref, _tables):
    """query98: every item is given its neighbour's share of the class."""
    got = ref.copy()
    got["revenueratio"] = np.roll(got.revenueratio.to_numpy(), 1)
    return got, "max_rel_gap"


def _intersect_read_as_union(stmt, ref, tables):
    """query38: customers who bought in ANY channel are counted."""
    q38 = importlib.import_module("benchmarks.reference.nds.query38")
    union = pd.concat(q38.sides(tables, stmt.params)).drop_duplicates()
    assert len(union) > int(ref.cnt.iloc[0])
    return pd.DataFrame({"cnt": [len(union)]}), "rows_wrong"


def _ratio_filter_left_out(stmt, ref, _tables):
    """query21: a group outside the ratio's bounds comes back too."""
    got = ref.copy()
    extra = got.iloc[[0]].copy()
    extra["i_item_id"] = extra.i_item_id + "Z"
    extra["inv_after"] = extra.inv_before * 10
    got = pd.concat([got, extra]).sort_values(
        ["w_warehouse_name", "i_item_id"], na_position="first")
    return got, "rows_wrong"


FAULTS = {"query27": _rollup_key_given_a_value,
          "query98": _window_share_off_by_a_row,
          "query38": _intersect_read_as_union,
          "query21": _ratio_filter_left_out}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_planted_fault_is_not_correct(references, name):
    stmt, ref, tables = references[name]
    assert len(ref) > 0
    (sound, checks), note = _numbers(stmt, ref.copy(), ref)
    assert sound, note                      # the reference against itself
    got, number = FAULTS[name](stmt, ref, tables)
    (ok, checks), note = _numbers(stmt, got, ref)
    assert ok is False
    assert checks[number]["value"] > checks[number]["limit"], (checks, note)


def test_rollup_reference_has_its_three_levels(references):
    _stmt, ref, _tables = references["query27"]
    # NULL lowest: the grand total first, each item's subtotal before
    # its states; grouping(s_state) 1 on both
    assert ref.iloc[0].isna()[["i_item_id", "s_state"]].all()
    assert int(ref.iloc[0].g_state) == 1
    sub = ref[ref.s_state.isna() & ref.i_item_id.notna()]
    assert len(sub) > 0 and set(sub.g_state) == {1}
    assert set(ref[ref.s_state.notna()].g_state) == {0}
    for item, rows in ref[ref.i_item_id.notna()].groupby("i_item_id"):
        assert rows.iloc[0].s_state is None or pd.isna(rows.iloc[0].s_state)


# ----------------------------------------- the reader, recorded spans

def _read(monkeypatch, spans, passes):
    monkeypatch.setattr(sr, "for_run", lambda run: spans)
    run = {"trace": {"busy_s": 1.0, "window_s": 1.0},
           "cell": {"name": "none", "chips": 4}, "peaks": None,
           "window": {"slice": (0.0, 1.0, passes, 5), "records": []}}
    return bench_run.per_layer(["replicate_mb_per_pass"], run).get(
        "replicate_mb_per_pass")


def test_replicate_mb_per_pass_reads_the_recorded_launch_spans(monkeypatch):
    """``spans.json`` of the cell's traced run on the chip, cut to its
    launch spans: the slice's passes, and what the six programs of a
    pass say they gather (static, so the CPU mesh's trace says the
    same: 265.7 MB a pass beside 5,994.8 MB exchanged)."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    launch = recorded["spans"]["device.launch"]
    passes = recorded["passes"]
    assert launch["count"] == 6 * passes         # query38 is two programs
    got = _read(monkeypatch, recorded, passes)
    assert got == pytest.approx(launch["replicate_bytes"] / passes / 1e6)
    assert got == pytest.approx(265.700016)
    assert launch["exchange_bytes"] / passes / 1e6 == pytest.approx(
        5994.77884)
    # a program without the attribute (the parent): left out, never 0
    bare = {"spans": {"device.launch": {"count": 6, "exchange_bytes": 1}}}
    assert _read(monkeypatch, bare, 1) is None
    assert _read(monkeypatch, None, 1) is None
