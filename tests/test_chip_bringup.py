"""PR 21 bring-up contracts: nothing may hide the device, and one chip
belongs to one process.

- a compile-time refusal is that query's failure, never a ladder walk
  to the CPU (runtime OOM still walks);
- ``backend=tpu`` without a TPU is an error unless the user pinned
  ``JAX_PLATFORMS=cpu``; every summary names the live platform;
- an unknown TPU kind has no peak row: an error, not a blank;
- the bench orchestrators never touch jax and run every device phase
  as one child at a time; subprocess-per-stream on one chip fails fast.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from nds_tpu.resilience.retry import (
    DETERMINISTIC, CompileRefused, classify, is_oom,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the v5e compiler says of a program that cannot fit (rehearsed
# against a described v5e: CHANGES.md PR 21)
TPU_COMPILE_OOM = (
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm. Used 55.88G of 15.75G hbm. Exceeded "
    "hbm capacity by 40.13G.")


# ------------------------------------------------- compile-time refusal

def test_compile_refusal_is_deterministic_and_not_an_oom():
    exc = CompileRefused(f"XLA refused to compile: {TPU_COMPILE_OOM}")
    assert classify(exc) == DETERMINISTIC
    assert not is_oom(exc)
    # the same words raised at DISPATCH are a runtime allocation
    # failure and keep walking the ladder
    assert is_oom(RuntimeError(TPU_COMPILE_OOM))


def test_funnel_surfaces_compiler_refusal_with_its_words():
    import jax

    from nds_tpu.cache import aot

    class Lowered:
        def compile(self):
            raise jax.errors.JaxRuntimeError(TPU_COMPILE_OOM)

    class Jitted:
        def lower(self, *args):
            return Lowered()

    with pytest.raises(CompileRefused, match="Ran out of memory in "
                                             "memory space hbm"):
        aot.lower_and_compile(Jitted(), kind="DeviceExecutor")


def test_refused_program_fails_the_query_without_walking_the_ladder():
    from test_scheduler import CHUNKED, CPU, DEVICE, FakeExec, _pipe, _plan
    dev = FakeExec([CompileRefused(TPU_COMPILE_OOM)])
    chk, cpu = FakeExec(), FakeExec()
    pipe = _pipe(execs={DEVICE: dev, CHUNKED: chk, CPU: cpu})
    planned, _cat = _plan("select count(*) c from reason")
    with pytest.raises(CompileRefused, match="compile permanent error"):
        pipe.execute(planned)
    assert (dev.calls, chk.calls, cpu.calls) == (1, 0, 0)
    assert pipe.last_schedule["ladder"] == [DEVICE]
    assert pipe.last_stats.gave_up_reason == DETERMINISTIC


# ------------------------------------------------ no hidden CPU fallback

def test_backend_tpu_without_a_tpu_is_an_error(monkeypatch):
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    cfg = EngineConfig(overrides={"engine.backend": "tpu"})
    # the live platform here is the CPU; without the user's own pin the
    # session must not be built
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        power_core.make_session(SUITE, cfg)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert power_core.make_session(SUITE, cfg) is not None
    # the CPU oracle backend never asks
    monkeypatch.delenv("JAX_PLATFORMS")
    assert power_core.make_session(
        SUITE, EngineConfig(overrides={"engine.backend": "cpu"}))


def test_every_summary_names_the_live_device():
    from nds_tpu.utils.report import BenchReport
    rep = BenchReport("q", {"engine.backend": "tpu"})
    rep.capture_env()
    conf = rep.summary["env"]["engineConf"]
    assert conf["platform"] == "cpu"
    assert conf["device_kind"] == "cpu"
    assert conf["device_count"] == "8"
    assert not any(str(v).startswith("configured:") for v in conf.values())


def test_unknown_tpu_kind_has_no_peak_row(monkeypatch):
    from nds_tpu.obs import costs
    monkeypatch.setenv(costs.PEAKS_ENV, "/nonexistent/peaks.json")
    assert costs.platform_peaks("TPU v5 lite")["mem_gbps"] == 819.0
    with pytest.raises(ValueError, match="no peak row for TPU"):
        costs.platform_peaks("TPU v99x")


def test_power_driver_names_queries_that_ended_on_cpu(tmp_path, capsys):
    """backend=tpu, every device rung fails with an injected OOM, the
    ladder's cpu floor answers: the exit code stays the reference's,
    but the closing lines say which queries are CPU wall-clocks."""
    from nds_tpu.nds_h import gen_data
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.nds_h.streams import generate_query_streams
    from nds_tpu.resilience import faults
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    raw = tmp_path / "raw"
    gen_data.generate_data_local(0.001, 1, str(raw), workers=1)
    stream = generate_query_streams(str(tmp_path / "streams"), 1)[0]
    faults.install("device.execute:oom*99@DeviceExecutor,"
                   "device.execute:oom*99@ChunkedExecutor,"
                   "device.execute:oom*99@_PhaseBExecutor,"
                   "device.execute:oom*99@_PartialAggExecutor")
    try:
        power_core.run_query_stream(
            SUITE, str(raw), stream, str(tmp_path / "t.csv"),
            config=EngineConfig(overrides={
                "engine.backend": "tpu",
                "engine.retry.base_delay_s": "0"}),
            input_format="raw",
            json_summary_folder=str(tmp_path / "json"),
            query_subset=["query6"])
    finally:
        faults.clear()
    out = capsys.readouterr().out
    assert "Time taken:" in out and "for query6" in out   # it DID answer
    assert ("WARNING: 1 query finished on the cpu placement under "
            "engine.backend=tpu") in out
    assert out.rstrip().endswith("query6")


# --------------------------------------------------- one process per chip

def test_subprocess_streams_refuse_one_chip(monkeypatch, tmp_path):
    from nds_tpu.nds import throughput as nds_tp
    from nds_tpu.nds_h import throughput as h_tp
    monkeypatch.delenv("JAX_PLATFORMS")
    for mod in (nds_tp, h_tp):
        with pytest.raises(RuntimeError, match="--in_process"):
            mod.run_streams("wh", ["s1.sql", "s2.sql"],
                            str(tmp_path / "tp"), backend="tpu")
    assert not (tmp_path / "tp").exists()    # refused before any spawn
    # one stream is one process; cpu streams share nothing; the user's
    # own cpu pin is the rehearsal
    nds_tp.refuse_chip_fanout("tpu", 1)
    nds_tp.refuse_chip_fanout("cpu", 4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    nds_tp.refuse_chip_fanout("tpu", 4)


_ORCHESTRATOR_CHILD = textwrap.dedent("""
    import json, os, subprocess, sys
    suite, work, real = sys.argv[1], sys.argv[2], sys.argv[3] == "real"
    spawns = []

    def record(cmd, env):
        spawns.append({"module": cmd[2], "argv": list(cmd[3:]),
                       "jax_loaded": "jax" in sys.modules,
                       "JAX_PLATFORMS": (env or {}).get("JAX_PLATFORMS")})

    real_run, real_popen = subprocess.run, subprocess.Popen

    def fake_outputs(cmd):
        from nds_tpu.nds.throughput import write_elapse
        from nds_tpu.utils.timelog import TimeLog
        mod, t = cmd[2], TimeLog("fake")
        if mod.endswith(".transcode"):
            with open(cmd[5], "w") as f:
                f.write("Total conversion time for 24 tables was 5.0s\\n"
                        "RNGSEED used: 123\\n")
        elif mod.endswith(".power"):
            t.add("Power Test Time", 2000)
            t.write(cmd[5])
        elif mod.endswith(".maintenance"):
            t.add("Data Maintenance Time", 1500)
            t.write(cmd[5])
        elif mod.endswith(".throughput"):
            out = cmd[cmd.index("--out_dir") + 1]
            os.makedirs(out, exist_ok=True)
            write_elapse(out, 3.0, [0])

    def run(cmd, *a, **kw):
        if real:        # subprocess.run spawns through Popen (below)
            return real_run(cmd, *a, **kw)
        record(cmd, kw.get("env"))
        fake_outputs(cmd)
        return subprocess.CompletedProcess(cmd, 0)

    class Popen(real_popen):
        def __init__(self, cmd, *a, **kw):
            record(cmd, kw.get("env"))
            super().__init__(cmd, *a, **kw)

    subprocess.run, subprocess.Popen = run, Popen
    import importlib
    bench = importlib.import_module(f"nds_tpu.{suite}.bench")
    tp = importlib.import_module("nds_tpu.nds.throughput")

    def never(*a, **kw):
        raise AssertionError("a device phase ran INSIDE the orchestrator")

    tp.run_streams_inprocess = never
    cfg = {"scale_factor": 0.01, "parallel": 2, "num_streams": 1,
           "backend": "tpu",
           "paths": {k: os.path.join(work, k) for k in
                     ("raw_data", "warehouse", "streams", "reports")},
           "skip": {}}
    metrics = bench.run_full_bench(cfg)
    print("RESULT " + json.dumps({
        "spawns": spawns, "metric": metrics["metric"],
        "jax_loaded_at_end": "jax" in sys.modules}))
""")


def _run_orchestrator(suite: str, work, mode: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, "-c", _ORCHESTRATOR_CHILD, suite, str(work),
         mode], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _check_spawns(res: dict, device_modules: list) -> None:
    spawns = res["spawns"]
    # the orchestrator has not touched jax at ANY spawn, nor at the end
    assert [s for s in spawns if s["jax_loaded"]] == []
    assert res["jax_loaded_at_end"] is False
    # host-only children are pinned off the chip
    for s in spawns:
        if s["module"].endswith((".gen_data", ".transcode")):
            assert s["JAX_PLATFORMS"] == "cpu", s
    # every device phase is ONE child, in order; throughput is a single
    # --in_process child, not a fan-out
    assert [s["module"] for s in spawns
            if not s["module"].endswith((".gen_data", ".transcode"))
            ] == device_modules
    for s in spawns:
        if s["module"].endswith(".throughput"):
            assert "--in_process" in s["argv"], s
            assert s["argv"][s["argv"].index("--backend") + 1] == "tpu"


def test_nds_h_bench_backend_tpu_runs_off_jax(tmp_path):
    """The REAL NDS-H orchestrator at SF0.01 with ``backend: tpu`` (the
    children run the device executor on the pinned CPU): it finishes
    with a composite metric, every device phase was one child, and the
    orchestrator never imported jax."""
    res = _run_orchestrator("nds_h", tmp_path, "real")
    _check_spawns(res, ["nds_tpu.nds_h.power", "nds_tpu.nds_h.throughput"])
    assert res["metric"] is not None and res["metric"] > 0


def test_nds_bench_backend_tpu_runs_off_jax(tmp_path):
    """The NDS orchestrator's phase sequence under ``backend: tpu`` with
    the children's outputs faked (103 statements x 3 device phases are
    beyond tier-1): power, throughput 1, maintenance 1, throughput 2,
    maintenance 2 — each ONE child, none started from a process that
    has touched jax, no device phase inside the orchestrator."""
    res = _run_orchestrator("nds", tmp_path, "fake")
    _check_spawns(res, [
        "nds_tpu.nds.power",
        "nds_tpu.nds.throughput", "nds_tpu.nds.maintenance",
        "nds_tpu.nds.throughput", "nds_tpu.nds.maintenance"])
    assert res["metric"] is not None and res["metric"] > 0
