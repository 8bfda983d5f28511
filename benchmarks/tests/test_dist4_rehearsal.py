"""CPU rehearsal of the four-chip cell (`nds_h_sf1.dist4`) through
run.py at SF0.01: a child process with four virtual CPU devices (the
count is fixed when jax starts, so not this process), the shipped
distributed template, no ``NDS_TPU_*`` variable set."""

import json
import os
import subprocess
import sys

import pytest

from conftest import HERE, ROOT

BENCH = os.path.join(HERE, "rehearsal_dist4", "BENCHMARK.json")
CELL = "rehearsal.dist4"


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


@pytest.fixture(scope="module")
def traced():
    return run_cell(seed=11, trace=1)


def test_untraced_line(untraced):
    rc, line, err = untraced
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["passes"] >= 1
    assert set(line["metrics"]) == {"pass_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"       # never recorded
    assert line["device"]["count"] == 4
    assert set(line["checks"]) == {"failed_statements", "rows_wrong",
                                   "repeats_differ", "max_rel_gap"}


def test_every_statement_was_placed_sharded(untraced):
    """`failed` 0 already says no statement ended on `cpu` or was
    rescheduled; the details say where each did end."""
    _rc, line, _err = untraced
    with open(os.path.join(ROOT, "benchmarks", ".work", CELL,
                           "last_run.json")) as f:
        detail = json.load(f)
    assert set(detail["statement_wall_ms"]) == {"q1", "q3", "q5", "q18"}
    assert all(v["ok"] for v in detail["per_statement"].values())
    assert line["checks"]["failed_statements"]["value"] == 0


def test_traced_line(traced):
    rc, line, err = traced
    assert rc == 0 and line["correct"] is True, err[-3000:]
    # no device plane on the CPU: the device-trace readers and the
    # readers of spans laid over it find nothing, and their metrics are
    # left out, never reported as 0
    assert set(line["metrics"]) == {"host_ms_per_stmt", "window_compiles",
                                    "compile_s", "load_s"}
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_real_cell_has_its_files_and_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["nds_h_sf1.dist4"]
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"] and config["scale"] == "1"
    assert config["reduced"] == entry["reduced"] == ["statements"]
    assert config["assumed"]["chips"] == 4
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nds_h_sf1.json")) as f:
        one_chip = json.load(f)
    assert config["limits"] == one_chip["limits"]
    assert config["guarantees"]["decimals"] == \
        one_chip["guarantees"]["decimals"]
    pass_s = {m["name"]: m for m in bench["end_to_end"]}["pass_s"]
    assert "nds_h_sf1.dist4" in pass_s["workloads"]
    # the cell's own metrics, which later four-chip cells share
    new = [m for m in bench["per_layer"]
           if m["name"] in ("collective_pct", "ici_roofline_pct",
                            "exchange_mb_per_pass", "hbm_roofline_pct.x4")]
    assert len(new) == 4
    for m in new:
        assert "nds_h_sf1.dist4" in m["workloads"]
        assert m["moves"] == "pass_s"
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "layers",
                                           m["name"] + ".py"))
