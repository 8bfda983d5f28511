"""NDS whole-benchmark orchestrator.

Behavioral port of `nds/nds_bench.py:367-498`: run the TPC-DS phases as
subprocesses in spec order — data-gen (base + per-stream refresh sets)
-> load (transcode) -> stream-gen (RNGSEED = load end timestamp,
`nds/nds_bench.py:60-74`) -> power -> throughput 1 -> maintenance 1 ->
throughput 2 -> maintenance 2 -> validate (optional: post-maintenance
engine outputs diffed against a CPU-oracle round, nds/validate.py) —
with crash isolation via report-file state passing (SURVEY.md §3.4).
The orchestrator itself never touches jax: a chip belongs to one
process, so every device phase is ONE child at a time — with
``backend: tpu`` each throughput test is a single ``--in_process``
child time-sharing the chip, never a fan-out and never run inside the
orchestrator (which would then hold the chip against the maintenance
child that follows). Then compute the 4-term composite metric (`nds/nds_bench.py:334-357`):

    Q   = Sq * 99
    Tpt = Tpower * Sq / 3600 ;  Ttt = (Ttt1 + Ttt2) / 3600
    Tdm = (Tdm1 + Tdm2) / 3600 ; Tld = 0.01 * Sq * Tload / 3600
    metric = int(SF * Q / (Tpt * Ttt * Tdm * Tld) ** (1/4))

Config comes from a YAML like `configs/bench_nds.yml` (the reference's
`nds/bench.yml:18-59`).

Resumability (README "Resilience"): every completed phase journals its
timings to ``bench_state.json`` in the report dir; ``--resume`` replays
completed phases from the journal instead of re-running them, so a
crash in throughput round 2 costs only that round — the journal guards
against config drift via a digest, and the resumed run computes the
SAME composite metric an uninterrupted one would.

Observability (README "Observability"): power and throughput phases
leave ``analysis.json`` + ``report.html`` (per-query time attribution,
nds_tpu/obs/analyze.py) next to their BenchReport JSONs, and a
``metrics_snap: {dir, interval}`` YAML block threads
``NDS_TPU_METRICS_SNAP`` into every engine phase so long runs publish
live metrics snapshots while in flight.
"""

from __future__ import annotations

import argparse
import csv
import os
import subprocess
import sys
import time

import yaml

from nds_tpu.nds.transcode import get_load_time, get_rngseed
from nds_tpu.resilience.journal import PhaseJournal, config_digest
from nds_tpu.utils.timelog import TimeLog


def _run(cmd: list[str], backend: str | None = None,
         extra_env: dict | None = None) -> None:
    from nds_tpu.utils.power_core import subprocess_env
    print("+", " ".join(cmd))
    env = subprocess_env(backend)
    if extra_env:
        env.update(extra_env)
    subprocess.run(cmd, check=True, env=env)


def _run_rc(cmd: list[str], backend: str | None = None,
            extra_env: dict | None = None) -> int:
    """Like _run but returns the exit code instead of raising — the
    caller distinguishes RESUMABLE exits (75, a graceful preemption
    drain; resilience/drain.py) from real failures."""
    from nds_tpu.utils.power_core import subprocess_env
    print("+", " ".join(cmd))
    env = subprocess_env(backend)
    if extra_env:
        env.update(extra_env)
    return subprocess.run(cmd, env=env).returncode


# how many graceful-drain (exit 75) resumes the power phase tolerates
# before the bench gives up — the query journal makes each retry cost
# only the statements not yet journaled
MAX_PHASE_RESUMES = 5


def _analyze_phase(phase_name: str, run_dir: str) -> None:
    """Post-phase run analysis (nds_tpu/obs/analyze.py): write
    ``analysis.json`` + ``report.html`` next to the phase's BenchReport
    JSONs so every bench round leaves a per-query attribution
    breakdown, not just composite-metric inputs. Best-effort — a phase
    that wrote no summaries (skipped via cfg['skip']) is not an
    error."""
    try:
        from nds_tpu.obs import analyze
        paths = analyze.write_outputs(analyze.analyze_run(run_dir),
                                      run_dir)
        print(f"[{phase_name}] analysis: {paths['report']}")
    except Exception as exc:  # noqa: BLE001 - reporting only
        print(f"[{phase_name}] run analysis skipped: "
              f"{type(exc).__name__}: {exc}")


def get_power_time(time_log_path: str) -> float:
    for _app, query, ms in TimeLog.read(time_log_path):
        if query == "Power Test Time":
            return ms / 1000.0
    raise ValueError(f"no Power Test Time row in {time_log_path}")


def get_maintenance_time(time_log_path: str) -> float:
    """Tdm seconds from a maintenance CSV log
    (`nds/nds_bench.py:176-196` reads per-stream refresh times)."""
    for _app, query, ms in TimeLog.read(time_log_path):
        if query == "Data Maintenance Time":
            return ms / 1000.0
    raise ValueError(f"no Data Maintenance Time row in {time_log_path}")


def get_stream_range(num_streams: int, first_or_second: int) -> list[int]:
    """Stream numbers per throughput test (`nds/nds_bench.py:126-135`):
    9 streams -> test 1 runs [1..4], test 2 runs [5..8]."""
    if first_or_second == 1:
        return list(range(1, num_streams // 2 + 1))
    return list(range(num_streams // 2 + 1, num_streams))


def get_perf_metric(scale: float, num_streams: int, tload: float,
                    tpower: float, ttt1: float, ttt2: float,
                    tdm1: float, tdm2: float) -> int:
    """4-term composite (`nds/nds_bench.py:334-357`)."""
    sq = max(num_streams, 1)
    q = sq * 99
    tpt = (tpower * sq) / 3600.0
    ttt = (ttt1 + ttt2) / 3600.0
    tdm = (tdm1 + tdm2) / 3600.0
    tld = (0.01 * sq * tload) / 3600.0
    denom = (tpt * ttt * tdm * tld) ** (1.0 / 4.0)
    return int(scale * q / denom) if denom > 0 else 0


def run_full_bench(cfg: dict, resume: bool = False) -> dict:
    paths = cfg["paths"]
    scale = float(cfg.get("scale_factor", 1))
    parallel = int(cfg.get("parallel", 2))
    # total stream count is Sq*2+1 in the reference's bench.yml
    # convention: stream 0 powers, halves run the two throughput tests
    num_streams = int(cfg.get("num_streams", 2)) * 2 + 1
    backend = cfg.get("backend", "tpu")
    skip = cfg.get("skip", {})
    raw_dir = paths["raw_data"]
    refresh_base = paths.get("refresh_data",
                             os.path.join(raw_dir, "_refresh"))
    wh_dir = paths["warehouse"]
    stream_dir = paths["streams"]
    report_dir = paths.get("reports", "bench_reports")
    os.makedirs(report_dir, exist_ok=True)
    load_report = os.path.join(report_dir, "load_report.txt")
    metrics: dict = {"scale": scale, "streams": num_streams}

    # live metrics snapshots (README "Observability"): YAML
    # ``metrics_snap: {dir: ..., interval: 5}`` threads
    # NDS_TPU_METRICS_SNAP into every engine phase subprocess, one
    # snapshot file per phase
    snap_cfg = cfg.get("metrics_snap") or {}

    # YAML ``cache: {dir, readonly}`` (README "Plan cache"): one
    # persistent AOT plan cache shared by every phase subprocess, so
    # the throughput rounds replay the power round's compiles as hits
    from nds_tpu import cache as plan_cache
    plan_cache.export_env(cfg.get("cache"))

    def _snap_env(phase_name: str) -> dict | None:
        snap_dir = snap_cfg.get("dir")
        if not snap_dir:
            return None
        os.makedirs(snap_dir, exist_ok=True)
        interval = snap_cfg.get("interval", 5)
        return {"NDS_TPU_METRICS_SNAP":
                f"{os.path.join(snap_dir, phase_name)}.json:{interval}"}

    journal = PhaseJournal(os.path.join(report_dir, "bench_state.json"),
                           config_digest(cfg))
    if resume:
        if journal.load():
            done = sorted(journal.state["phases"])
            print(f"== resuming: journal has {', '.join(done)} ==")
    else:
        # a fresh run must not leave a stale journal a later --resume
        # could splice in
        journal.reset()

    def phase(name, body):
        """Run one phase unless the journal already has it; journal its
        result values (the numbers the composite metric needs) on
        completion. Phase bodies honor cfg['skip'] themselves."""
        if resume and journal.done(name):
            print(f"== skipping {name} (journaled) ==")
            return journal.timings(name)
        vals = body()
        journal.complete(name, **vals)
        return vals

    def _data_gen():
        if not skip.get("data_gen", False):
            _run([sys.executable, "-m", "nds_tpu.nds.gen_data",
                  str(scale), str(parallel), raw_dir,
                  "--overwrite_output"], backend="cpu")
            # one refresh set per maintenance run (2 per full bench)
            for update in (1, 2):
                _run([sys.executable, "-m", "nds_tpu.nds.gen_data",
                      str(scale), "1", f"{refresh_base}{update}",
                      "--update", str(update), "--overwrite_output"],
                     backend="cpu")
        return {}

    def _load_test():
        if not skip.get("load_test", False):
            cmd = [sys.executable, "-m", "nds_tpu.nds.transcode",
                   raw_dir, wh_dir, load_report]
            if resume:
                # an interrupted load resumes table-granular: tables
                # whose _manifest.json digests verify are not
                # re-transcoded (nds/transcode.py --resume)
                cmd.append("--resume")
            _run(cmd, backend="cpu")
        return {"load_time_s": get_load_time(load_report),
                "rngseed": get_rngseed(load_report)}

    phase("data_gen", _data_gen)
    load_vals = phase("load_test", _load_test)
    metrics["load_time_s"] = tld = load_vals["load_time_s"]
    rngseed = load_vals["rngseed"]

    def _stream_gen():
        if not skip.get("stream_gen", False):
            from nds_tpu.nds.streams import generate_query_streams
            # rngseed from the load report redraws every stream's
            # parameter bindings (dsqgen -rngseed,
            # `nds/nds_bench.py:415`): throughput streams must be
            # distinct workloads, not N copies
            generate_query_streams(stream_dir, num_streams,
                                   rng_seed=rngseed,
                                   qualification=False)
        return {}

    phase("stream_gen", _stream_gen)

    power_log = os.path.join(report_dir, "power_time.csv")

    def _power_test():
        if not skip.get("power_test", False):
            from nds_tpu.resilience.drain import EXIT_RESUMABLE
            base_cmd = [sys.executable, "-m", "nds_tpu.nds.power",
                        wh_dir,
                        os.path.join(stream_dir, "query_0.sql"),
                        power_log, "--backend", backend,
                        "--json_summary_folder",
                        os.path.join(report_dir, "json")]
            # a bench-level --resume also resumes mid-phase: the query
            # journal in the json dir replays finished statements
            cmd = base_cmd + (["--resume"] if resume else [])
            resumes = 0
            while True:
                rc = _run_rc(cmd, backend=backend,
                             extra_env=_snap_env("power"))
                if rc == 0:
                    break
                if rc == EXIT_RESUMABLE and resumes < MAX_PHASE_RESUMES:
                    # graceful preemption drain: re-run with --resume —
                    # only the statements not yet journaled execute,
                    # and the retry never counts as a failed phase
                    resumes += 1
                    print(f"== power phase drained (exit "
                          f"{EXIT_RESUMABLE}) — resuming "
                          f"({resumes}/{MAX_PHASE_RESUMES}) ==")
                    cmd = base_cmd + ["--resume"]
                    continue
                raise subprocess.CalledProcessError(rc, cmd)
            _analyze_phase("power", os.path.join(report_dir, "json"))
        return {"power_time_s": get_power_time(power_log)}

    metrics["power_time_s"] = tpt = phase(
        "power_test", _power_test)["power_time_s"]

    def _throughput(round_no):
        from nds_tpu.nds import throughput as tp
        streams_n = get_stream_range(num_streams, round_no)
        tstreams = [os.path.join(stream_dir, f"query_{i}.sql")
                    for i in streams_n]
        tdir = os.path.join(report_dir, f"throughput{round_no}")
        # one TPU chip cannot be opened by N subprocesses: there the
        # test is ONE child that time-shares it (--in_process);
        # cpu/distributed keep the reference's process fan-out, which
        # this process supervises without touching jax. Overridable
        # via YAML.
        mode = cfg.get("throughput_mode",
                       "inprocess" if backend == "tpu"
                       else "subprocess")
        snap_env = _snap_env(f"throughput{round_no}") or {}
        if mode == "inprocess":
            cmd = [sys.executable, "-m", "nds_tpu.nds.throughput",
                   wh_dir, *tstreams, "--out_dir", tdir,
                   "--backend", backend, "--in_process"]
            # the child's report file is the only way Ttt comes back:
            # a stale one must not stand in for a child that died
            elapse_path = os.path.join(tdir, tp.ELAPSE_FILE)
            if os.path.exists(elapse_path):
                os.remove(elapse_path)
            rc = _run_rc(cmd, backend=backend, extra_env=snap_env)
            if not os.path.exists(elapse_path):
                raise subprocess.CalledProcessError(rc or 1, cmd)
            ttt, codes = tp.read_elapse(tdir)
        else:
            # run_streams re-points the snapshot var per stream; it
            # reads it from THIS process's environment. Save/restore
            # so a user's own setting survives.
            saved = {k: os.environ.get(k) for k in snap_env}
            os.environ.update(snap_env)
            try:
                # YAML ``watchdog: {stall_s, max_restarts}`` arms
                # subprocess stream supervision (kill + bounded
                # restarts; README Resilience)
                wd_cfg = cfg.get("watchdog") or {}
                ttt, codes = tp.run_streams(
                    wh_dir, tstreams, tdir, backend=backend,
                    stall_s=wd_cfg.get("stall_s"),
                    max_restarts=wd_cfg.get("max_restarts"))
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        _analyze_phase(f"throughput{round_no}", tdir)
        if any(codes):
            raise SystemExit(
                f"throughput {round_no} streams failed: {codes}")
        return {"ttt": ttt}

    def _maintenance(round_no):
        from nds_tpu.resilience.drain import EXIT_RESUMABLE
        dm_log = os.path.join(report_dir,
                              f"maintenance{round_no}_time.csv")
        base_cmd = [sys.executable, "-m", "nds_tpu.nds.maintenance",
                    wh_dir, f"{refresh_base}{round_no}", dm_log,
                    "--backend", backend,
                    "--json_summary_folder",
                    os.path.join(report_dir,
                                 f"maintenance{round_no}_json")]
        # bench-level --resume also resumes mid-phase: the maintenance
        # commit journal in the warehouse replays the refresh functions
        # whose snapshot commits already landed (never double-applies)
        cmd = base_cmd + (["--resume"] if resume else [])
        resumes = 0
        while True:
            rc = _run_rc(cmd, backend=backend,
                         extra_env=_snap_env(f"maintenance{round_no}"))
            if rc == 0:
                break
            if rc == EXIT_RESUMABLE and resumes < MAX_PHASE_RESUMES:
                resumes += 1
                print(f"== maintenance {round_no} drained (exit "
                      f"{EXIT_RESUMABLE}) — resuming "
                      f"({resumes}/{MAX_PHASE_RESUMES}) ==")
                cmd = base_cmd + ["--resume"]
                continue
            raise subprocess.CalledProcessError(rc, cmd)
        return {"tdm": get_maintenance_time(dm_log)}

    ttts, tdms = [], []
    for round_no in (1, 2):
        if not skip.get("throughput_test", False):
            ttts.append(phase(f"throughput_{round_no}",
                              lambda r=round_no: _throughput(r))["ttt"])
        if not skip.get("maintenance_test", False):
            tdms.append(phase(f"maintenance_{round_no}",
                              lambda r=round_no: _maintenance(r))["tdm"])
    metrics["throughput_times_s"] = ttts
    metrics["maintenance_times_s"] = tdms

    def _validate():
        """Post-maintenance validation: run the power stream twice on
        the CURRENT (maintained) warehouse — once on the bench backend,
        once on the CPU oracle — and diff the saved outputs
        (nds/validate.py), patching ``queryValidationStatus`` into the
        engine round's JSON summaries."""
        vcfg = cfg.get("validate") or {}
        stream0 = os.path.join(stream_dir, "query_0.sql")
        vdir = os.path.join(report_dir, "validate")
        jdir = os.path.join(vdir, "json")
        subset = [str(q) for q in (vcfg.get("query_subset") or [])]
        out_engine = os.path.join(vdir, "output_engine")
        out_oracle = os.path.join(vdir, "output_oracle")
        for be, outp, tag in ((backend, out_engine, "engine"),
                              ("cpu", out_oracle, "oracle")):
            cmd = [sys.executable, "-m", "nds_tpu.nds.power",
                   wh_dir, stream0,
                   os.path.join(vdir, f"{tag}_time.csv"),
                   "--backend", be, "--output_prefix", outp]
            if tag == "engine":
                cmd += ["--json_summary_folder", jdir]
            if subset:
                cmd += ["--query_subset", *subset]
            _run(cmd, backend=be)
        vcmd = [sys.executable, "-m", "nds_tpu.nds.validate",
                out_engine, out_oracle, stream0, "--ignore_ordering",
                "--json_summary_folder", jdir]
        if vcfg.get("epsilon") is not None:
            vcmd += ["--epsilon", str(vcfg["epsilon"])]
        rc = _run_rc(vcmd, backend="cpu")
        if rc and not vcfg.get("allow_failure"):
            raise SystemExit(
                f"validate: engine outputs diverge from the CPU "
                f"oracle (exit {rc}; mismatches listed above)")
        return {"validation_ok": 0 if rc else 1}

    if cfg.get("validate") and not skip.get("validate", False):
        metrics["validation_ok"] = bool(
            phase("validate", _validate)["validation_ok"])

    # all four terms or no composite (a fabricated term would silently
    # skew the geometric mean)
    if len(ttts) == 2 and len(tdms) == 2:
        metrics["metric"] = get_perf_metric(
            scale, num_streams // 2, tld, tpt, ttts[0], ttts[1],
            tdms[0], tdms[1])
    else:
        metrics["metric"] = None
    out_csv = paths.get("metrics_csv",
                        os.path.join(report_dir, "metrics.csv"))
    with open(out_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["scale", "streams", "load_s", "power_s",
                    "throughput1_s", "throughput2_s", "maintenance1_s",
                    "maintenance2_s", "metric", "timestamp"])
        w.writerow([scale, num_streams, tld, tpt,
                    *(ttts or [None, None]), *(tdms or [None, None]),
                    metrics["metric"], int(time.time())])
    print(f"perf metric: {metrics['metric']} (details in {out_csv})")
    return metrics


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="full NDS benchmark")
    p.add_argument("config", help="bench YAML (like configs/bench_nds.yml)")
    p.add_argument("--resume", action="store_true",
                   help="replay completed phases from the report dir's "
                        "bench_state.json journal instead of re-running "
                        "them (crash recovery; README Resilience)")
    args = p.parse_args(argv)
    with open(args.config) as f:
        cfg = yaml.safe_load(f)
    run_full_bench(cfg, resume=args.resume)


if __name__ == "__main__":
    main()
