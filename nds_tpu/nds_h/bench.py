"""NDS-H whole-benchmark orchestrator.

The NDS-H analog of `nds/nds_bench.py:367-498`: run phases in TPC order
as subprocesses (crash isolation by design — state passes via report
files, SURVEY.md §3.4), then compute a composite metric. The
orchestrator itself never touches jax: a chip belongs to one process,
so every device phase is ONE child at a time (with ``backend: tpu`` the
throughput test is a single ``--in_process`` child time-sharing the
chip, not a fan-out).

Phases: data-gen -> load(transcode) -> stream-gen (RNGSEED = load end
timestamp, `nds/nds_bench.py:60-74`) -> power -> throughput. TPC-H has no
data-maintenance phase (refresh functions exist in TPC-H proper but the
reference's NDS-H suite omits them, `nds-h/` has no maintenance driver),
so the composite is the 3-term geometric form:

    metric = floor(SF * Sq * 22 / (Tpt * Ttt * Tld)^(1/3) / 3600)^-1-ish

mirroring `nds/nds_bench.py:334-357` with the maintenance term dropped.
Config comes from a YAML file like the reference's `nds/bench.yml`.
"""

from __future__ import annotations

import argparse
import csv
import os
import subprocess
import sys
import time

import yaml

from nds_tpu.nds_h.transcode import get_load_time, get_rngseed
from nds_tpu.utils.timelog import TimeLog


def _run(cmd: list[str], backend: str | None = None) -> None:
    from nds_tpu.utils.power_core import subprocess_env
    print("+", " ".join(cmd))
    subprocess.run(cmd, check=True, env=subprocess_env(backend))


def get_power_time(time_log_path: str) -> float:
    """Power Test Time seconds from a power-run CSV log."""
    for _app, query, ms in TimeLog.read(time_log_path):
        if query == "Power Test Time":
            return ms / 1000.0
    raise ValueError(f"no Power Test Time row in {time_log_path}")


def get_perf_metric(scale: float, num_streams: int, tld: float, tpt: float,
                    ttt: float) -> int:
    """3-term NDS-H composite (reference 4-term form:
    `nds/nds_bench.py:334-357`; maintenance term absent in NDS-H)."""
    sq = max(num_streams, 1)
    tld_h = sq * 22 * tld / 3600.0
    tpt_h = sq * 22 * tpt / 3600.0
    ttt_h = ttt / 3600.0
    denom = (tpt_h * ttt_h * tld_h) ** (1.0 / 3.0)
    return int(scale * sq * 22 / denom) if denom > 0 else 0


def run_full_bench(cfg: dict) -> dict:
    paths = cfg["paths"]
    scale = float(cfg.get("scale_factor", 1))
    parallel = int(cfg.get("parallel", 2))
    num_streams = int(cfg.get("num_streams", 2))
    backend = cfg.get("backend", "tpu")
    raw_dir = paths["raw_data"]
    wh_dir = paths["warehouse"]
    stream_dir = paths["streams"]
    report_dir = paths.get("reports", "bench_reports")
    os.makedirs(report_dir, exist_ok=True)
    load_report = os.path.join(report_dir, "load_report.txt")
    metrics = {}

    # YAML ``cache: {dir, readonly}`` (README "Plan cache"): one
    # persistent AOT plan cache shared by every phase subprocess
    from nds_tpu import cache as plan_cache
    plan_cache.export_env(cfg.get("cache"))

    if not cfg.get("skip", {}).get("data_gen", False):
        _run([sys.executable, "-m", "nds_tpu.nds_h.gen_data",
              str(scale), str(parallel), raw_dir, "--overwrite_output"],
             backend="cpu")
    if not cfg.get("skip", {}).get("load_test", False):
        _run([sys.executable, "-m", "nds_tpu.nds_h.transcode",
              raw_dir, wh_dir, load_report], backend="cpu")
    metrics["load_time_s"] = tld = get_load_time(load_report)
    rngseed = get_rngseed(load_report)

    if not cfg.get("skip", {}).get("stream_gen", False):
        from nds_tpu.nds_h.streams import generate_query_streams
        generate_query_streams(stream_dir, num_streams + 1,
                               rng_seed=rngseed, qualification=False)

    power_log = os.path.join(report_dir, "power_time.csv")
    if not cfg.get("skip", {}).get("power_test", False):
        _run([sys.executable, "-m", "nds_tpu.nds_h.power",
              wh_dir, os.path.join(stream_dir, "stream_0.sql"), power_log,
              "--backend", backend,
              "--json_summary_folder", os.path.join(report_dir, "json")],
             backend=backend)
    metrics["power_time_s"] = tpt = get_power_time(power_log)

    tstreams = [os.path.join(stream_dir, f"stream_{i}.sql")
                for i in range(1, num_streams + 1)]
    ttt = None
    if not cfg.get("skip", {}).get("throughput_test", False):
        from nds_tpu.nds.throughput import read_elapse
        tdir = os.path.join(report_dir, "throughput")
        cmd = [sys.executable, "-m", "nds_tpu.nds_h.throughput",
               wh_dir, *tstreams, "--out_dir", tdir,
               "--backend", backend]
        if backend == "tpu":
            cmd.append("--in_process")
        _run(cmd, backend=backend)
        ttt, _codes = read_elapse(tdir)
    metrics["throughput_time_s"] = ttt

    # no composite without a real throughput term (a fabricated Ttt would
    # silently skew the geometric mean)
    metrics["metric"] = (get_perf_metric(scale, num_streams, tld, tpt, ttt)
                         if ttt is not None else None)
    out_csv = paths.get("metrics_csv", os.path.join(report_dir,
                                                    "metrics.csv"))
    with open(out_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["scale", "streams", "load_s", "power_s",
                    "throughput_s", "metric", "timestamp"])
        w.writerow([scale, num_streams, tld, tpt, ttt, metrics["metric"],
                    int(time.time())])
    print(f"perf metric: {metrics['metric']} (details in {out_csv})")
    return metrics


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="full NDS-H benchmark")
    p.add_argument("config", help="bench YAML (like nds/bench.yml)")
    args = p.parse_args(argv)
    with open(args.config) as f:
        cfg = yaml.safe_load(f)
    run_full_bench(cfg)


if __name__ == "__main__":
    main()
