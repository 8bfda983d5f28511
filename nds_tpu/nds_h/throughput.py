"""NDS-H Throughput Run: N concurrent query streams.

The reference does this with xargs -P spawning one spark-submit per
stream (`nds/nds-throughput:23`). Here each stream is one subprocess
running the power driver (process isolation keeps per-stream XLA compile
caches and HBM pools independent — the analog of per-stream Spark apps),
and the throughput elapse is max(end) - min(start) rounded up to 0.1 s
(`nds/nds_bench.py:138-157,207-208`).

On ONE chip the deployable mode is ``--in_process``: a chip belongs to
one process, so the shared single-process loop
(nds_tpu.nds.throughput.run_streams_inprocess) time-shares it across
all streams; subprocess fan-out under ``--backend tpu`` fails fast.

Subprocess streams run SUPERVISED exactly like the NDS fleet
(resilience/supervise.py, spec plumbing shared via
nds_tpu.nds.throughput._stream_specs): heartbeat liveness through the
per-stream snapshot file, kill + restart-once on stall with
``--stall_s``, and a ``throughput_summary.json`` recording exit codes,
signals, stalls and restarts.
"""

from __future__ import annotations

import argparse
import math
import os
import sys


def run_streams(data_dir: str, stream_paths: list[str], out_dir: str,
                backend: str = "tpu",
                input_format: str = "parquet",
                stall_s: float | None = None,
                max_restarts: int | None = None
                ) -> tuple[float, list[int]]:
    """Launch one supervised power-run subprocess per stream; returns
    (throughput_elapse_seconds, per-stream final exit codes)."""
    from nds_tpu.nds.throughput import _stream_specs, refuse_chip_fanout
    from nds_tpu.nds_h.streams import parse_query_stream
    from nds_tpu.resilience.supervise import (
        StreamSupervisor, describe_summary,
    )
    refuse_chip_fanout(backend, len(stream_paths))
    os.makedirs(out_dir, exist_ok=True)
    specs = _stream_specs(data_dir, stream_paths, out_dir, backend,
                          input_format, False,
                          "nds_tpu.nds_h.power", parse_query_stream)
    # restarts only with the heartbeat plumbing stall_s arms (see
    # nds_tpu.nds.throughput.run_streams)
    if max_restarts is None:
        max_restarts = 1 if stall_s else 0
    sup = StreamSupervisor(specs, out_dir, stall_s=stall_s,
                           max_restarts=max_restarts)
    elapse, codes, summary = sup.run()
    print(describe_summary(summary))
    # round up to 0.1 s, the reference's Ttt granularity
    elapse = math.ceil(elapse * 10) / 10.0
    return elapse, codes


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="NDS-H throughput run")
    p.add_argument("data_dir")
    p.add_argument("streams", nargs="+", help="stream_N.sql files")
    p.add_argument("--out_dir", default="throughput_logs")
    p.add_argument("--backend", choices=["tpu", "cpu"], default="tpu")
    p.add_argument("--input_format", choices=["parquet", "raw"],
                   default="parquet")
    p.add_argument("--in_process", action="store_true",
                   help="time-share one device inside a single process "
                        "(required when all streams target one TPU chip)")
    p.add_argument("--stall_s", type=float, default=None,
                   help="supervise streams: kill on heartbeat stall "
                        "past this budget, restart once (README "
                        "Resilience)")
    p.add_argument("--max_restarts", type=int, default=None,
                   help="restart budget per supervised stream (default "
                        "1 when --stall_s is set; graceful-drain exits "
                        "75 resume without charging it)")
    args = p.parse_args(argv)
    from nds_tpu.nds.throughput import run_streams_inprocess, write_elapse
    if args.in_process:
        from nds_tpu.nds_h.power import SUITE
        elapse, codes = run_streams_inprocess(
            args.data_dir, args.streams, args.out_dir, args.backend,
            args.input_format, suite=SUITE)
    else:
        elapse, codes = run_streams(args.data_dir, args.streams,
                                    args.out_dir, args.backend,
                                    args.input_format,
                                    stall_s=args.stall_s,
                                    max_restarts=args.max_restarts)
    write_elapse(args.out_dir, elapse, codes)
    print(f"Throughput Time: {elapse} s over {len(args.streams)} streams")
    sys.exit(1 if any(codes) else 0)


if __name__ == "__main__":
    main()
