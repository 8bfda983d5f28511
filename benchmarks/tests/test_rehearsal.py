"""CPU rehearsal of run.py at SF0.01: the result line's keys, and the
plain references against the engine's rows for qualification and for
seeded parameters (a run is `correct` only if every statement of its
mix matched its reference)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import CELLS, REHEARSAL, ROOT

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2_500_000_123])
def test_untraced_line(run_cell, workload, seed):
    rc, line, err = run_cell(workload, seed=seed, trace=0)
    assert rc == 0
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "checks"          # compared numbers come last
    assert line["correct"] is True, err[-2000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    with open(REHEARSAL) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"     # never recorded
    # a read-only mix: its line and its compared numbers as they were,
    # every statement of every pass attempted once
    assert set(line) == LINE_KEYS | {"passes", "checks"}
    assert set(line["checks"]) == {"failed_statements", "rows_wrong",
                                   "repeats_differ", "max_rel_gap"}
    from benchmarks import generator
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    statements = generator.load_mix(cell["traffic"])["statements"]
    assert line["attempted"] == line["passes"] * len(statements)
    for name in ("failed_statements", "rows_wrong", "repeats_differ",
                 "max_rel_gap"):
        assert f"compared {name}:" in err


@pytest.mark.parametrize("workload", CELLS)
def test_traced_line(run_cell, workload):
    rc, line, err = run_cell(workload, trace=1)
    assert rc == 0 and line["correct"] is True, err[-2000:]
    # no device plane on the CPU: the device-trace readers find nothing
    # and their metrics are left out, never reported as 0; the others
    # are the cell's own (BENCHMARK.json splits them by the end-to-end
    # metric the cell reports)
    with open(REHEARSAL) as f:
        bench = json.load(f)
    moved = {m["name"] for m in bench["end_to_end"]
             if workload in m.get("workloads", [workload])}
    want = {m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in moved and m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    assert len(want) == 4
    compiles = [v["value"] for k, v in line["metrics"].items()
                if k.startswith("window_compiles")]
    assert compiles == [0]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_seed_orders_the_same_work():
    from benchmarks import generator
    for mix_name in ("short", "power_nds", "power_nds_h"):
        mix = generator.load_mix(mix_name)

        def run_of(seed, passes=8):
            sets = generator.variants(mix, seed)
            names = generator.order(mix, seed)
            return [[s.sql for s in generator.pass_statements(sets, names, i)]
                    for i in range(passes)]

        assert run_of(5) == run_of(5)             # same seed, same inputs
        # another seed: the same statements, so the same work ...
        flat = lambda run: sorted(sql for p in run for sql in p)  # noqa: E731
        assert flat(run_of(5)) == flat(run_of(6))
    # ... in another order, where there is anything to order
    mix = generator.load_mix("short")
    orders = {tuple(s.label for s in generator.pass_statements(
        generator.variants(mix, seed), generator.order(mix, seed), 0))
        for seed in range(20)}
    assert len(orders) > 5


def test_no_accelerator_no_line():
    """A real cell on a machine without a TPU: exit code other than 0
    and no result line."""
    if not os.path.exists(os.path.join(ROOT, "BENCHMARK.json")):
        pytest.skip("no BENCHMARK.json yet")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
