"""One engine replica of the serve fleet (launched by
resilience/supervise.ReplicaSupervisor, routed by serve/fleet.py).

    python -m nds_tpu.serve.replica --name r0 \
        --announce /fleet/announce/r0.json \
        --gen_scale 0.01 --gen_nds_tables store_sales,date_dim,... \
        --backend tpu --cache_dir /fleet/plancache \
        --summary_dir /fleet/serve_json

Wraps PR 11's QueryServer in the fleet contract:

- **Warehouse** either loaded from disk (``--nds_h_data``/``--nds_data``
  like ``python -m nds_tpu.serve``) or regenerated in-process from the
  seeded datagen (``--gen_scale``): datagen streams derive from
  ``(seed, table, step)``, so every replica — and the router's oracle —
  materializes bit-identical tables without sharing files.
- **Announce** — binds TCP on ``--port`` (0 = free port) and publishes
  ``{replica, host, port, pid, incarnation}`` atomically to
  ``--announce``; a resumed incarnation overwrites it with its NEW
  port, which is how the router discovers the comeback.
- **Liveness** — arms the metrics snapshotter and watchdog from the
  supervisor's env (``NDS_TPU_METRICS_SNAP`` / ``NDS_TPU_WATCHDOG``)
  and beats ``serve`` only while the engine thread is alive, so a
  wedged engine reads as a stall (exit 86) while an idle-but-healthy
  replica does not.
- **Drain** — SIGTERM runs ``begin_drain()`` (new submits shed
  ``server-stopping`` — departure notices the router redelivers),
  waits for in-flight work to reach zero under ``engine.drain_s``
  (the boundary-pipelined overlapped request resolves here too: its
  future is in-flight until ``_finalize_prev`` answers it), then exits
  :data:`~nds_tpu.resilience.drain.EXIT_RESUMABLE` (75). The
  supervisor relaunches warm — 0 compiles by construction, the shared
  ``cache.dir`` AOT store was paid by the first owner of each plan.
  SIGINT drains the same way but exits 0 (operator stop, not resume).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

from nds_tpu.resilience.drain import EXIT_RESUMABLE


def parse_incarnation(stream_name: "str | None") -> int:
    """``r0#r2`` -> 2 (the supervisor's incarnation suffix); bare
    names are incarnation 0."""
    if stream_name and "#r" in stream_name:
        try:
            return int(stream_name.rsplit("#r", 1)[1])
        except ValueError:
            return 0
    return 0


def _gen_tables(server, scale: float, nds_tables: "list[str]",
                h_tables: "list[str] | None" = None) -> int:
    """Seeded in-process warehouse: every replica (and the router's
    oracle) generates identical arrays from the deterministic datagen
    streams — fleet digest parity needs no shared storage."""
    from nds_tpu.datagen import tpcds as gen_d
    from nds_tpu.datagen import tpch as gen_h
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds.schema import get_schemas as d_schemas
    from nds_tpu.nds_h.schema import get_schemas as h_schemas
    n = 0
    hs = h_schemas()
    for t in (h_tables if h_tables is not None else list(hs)):
        server.register_table(
            from_arrays(t, hs[t], gen_h.gen_table(t, scale)), "nds_h")
        n += 1
    ds = d_schemas()
    for t in nds_tables:
        server.register_table(
            from_arrays(t, ds[t], gen_d.gen_table(t, scale)), "nds")
        n += 1
    return n


def build_server(args):
    """QueryServer from replica CLI args (importable so tests build
    the same server in-process)."""
    from nds_tpu.serve import QueryServer
    from nds_tpu.utils.config import EngineConfig
    overrides = {"engine.backend": args.backend,
                 "serve.replica_id": args.name}
    if args.cache_dir:
        overrides["cache.dir"] = args.cache_dir
    if args.summary_dir:
        overrides["serve.summary_dir"] = args.summary_dir
    if args.max_queue is not None:
        overrides["serve.max_queue"] = str(args.max_queue)
    if args.deadline_ms is not None:
        overrides["serve.deadline_ms"] = str(args.deadline_ms)
    for kv in args.property or []:
        k, _, v = kv.partition("=")
        overrides[k.strip()] = v.strip()
    cfg = EngineConfig(args.template, args.property_file, overrides)
    srv = QueryServer(cfg)
    if args.gen_scale is not None:
        nds_tables = [t for t in
                      (args.gen_nds_tables or "").split(",") if t]
        h_tables = ([t for t in args.gen_nds_h_tables.split(",") if t]
                    if args.gen_nds_h_tables is not None else None)
        _gen_tables(srv, args.gen_scale, nds_tables, h_tables)
    from nds_tpu.serve.__main__ import _load_suite
    for suite, d in (("nds_h", args.nds_h_data), ("nds", args.nds_data)):
        if d:
            _load_suite(srv, suite, d, args.input_format)
    return srv, cfg


async def serve_replica(srv, host: str, port: int,
                        announce_path: "str | None",
                        drain_s: float) -> int:
    """Serve until signalled; returns the process exit code (75 on a
    SIGTERM drain, 0 on SIGINT)."""
    import signal

    from nds_tpu.io.integrity import write_json_atomic
    from nds_tpu.resilience import watchdog
    from nds_tpu.serve.net import start_tcp

    tcp = await start_tcp(srv, host, port)
    bound = tcp.sockets[0].getsockname()[1]
    inc = parse_incarnation(os.environ.get(watchdog.STREAM_ENV))
    if announce_path:
        write_json_atomic(announce_path, {
            "replica": srv.replica_id, "host": host, "port": bound,
            "pid": os.getpid(), "incarnation": inc,
            "ts": time.time()})
    print(f"[replica {srv.replica_id}] inc={inc} listening on "
          f"{host}:{bound}", flush=True)

    drain_sig: "dict[str, int | None]" = {"sig": None}
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()

    def _on_signal(sig):
        drain_sig["sig"] = sig
        stop.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        # loop-native handlers: the default KeyboardInterrupt path can
        # land mid-callback and skip the drain below
        loop.add_signal_handler(sig, _on_signal, sig)

    async def _beat_loop():
        # IDLE-only heartbeat: the watchdog alarms on the NEWEST beat
        # across all units, so beating while requests are in flight
        # would mask a wedged query (the executors beat per chunk
        # while real work progresses — that is the busy-path
        # liveness). An idle replica beats here so quiet is not
        # mistaken for a stall; a dead engine thread stops both
        # sources and the watchdog (then the supervisor backstop)
        # fires.
        while not stop.is_set():
            if srv._thread is not None and srv._thread.is_alive():
                with srv._lock:
                    inflight = srv._inflight
                if inflight == 0:
                    watchdog.beat("serve", phase="idle")
            # completed-count into the snapshot progress dict (the
            # supervisor's liveness/resume bookkeeping reads it)
            getattr(srv, "_progress_tick", lambda: None)()
            await asyncio.sleep(0.25)

    beater = asyncio.ensure_future(_beat_loop())
    await stop.wait()

    # drain: refuse new work, finish what's in flight (including a
    # boundary-overlapped request — it stays in-flight until its
    # handle resolves), then exit resumable
    print(f"[replica {srv.replica_id}] draining "
          f"(budget {drain_s:g}s)", flush=True)
    # the listener only: live connections keep serving while the
    # backlog drains. close() shuts the listening sockets at once; NOT
    # awaiting wait_closed() here — on Python 3.12 it also waits for
    # every live connection to end, and the router holds its connection
    # open for the replica's whole life, so the drain would sit out a
    # timeout instead of exiting 75
    tcp.close()
    srv.begin_drain()
    deadline = time.monotonic() + max(0.1, drain_s)
    while time.monotonic() < deadline:
        with srv._lock:
            inflight = srv._inflight
        if inflight == 0:
            break
        await asyncio.sleep(0.02)
    # settle: let connection handlers flush resolved responses to
    # their sockets before the process exits
    await asyncio.sleep(0.1)
    beater.cancel()
    rc = (EXIT_RESUMABLE
          if drain_sig["sig"] == signal.SIGTERM else 0)
    print(f"[replica {srv.replica_id}] drained: {srv.stats} "
          f"-> exit {rc}", flush=True)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--name", required=True,
                    help="replica id (stamped on responses/summaries)")
    ap.add_argument("--announce",
                    help="atomic JSON endpoint file the router watches")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (published via announce)")
    ap.add_argument("--nds_h_data", help="NDS-H warehouse dir")
    ap.add_argument("--nds_data", help="NDS warehouse dir")
    ap.add_argument("--input_format", default="parquet")
    ap.add_argument("--gen_scale", type=float, default=None,
                    help="regenerate the warehouse in-process from the "
                         "seeded datagen at this scale factor")
    ap.add_argument("--gen_nds_tables", default="",
                    help="comma list of NDS tables to generate")
    ap.add_argument("--gen_nds_h_tables", default=None,
                    help="comma list of NDS-H tables (default: all)")
    ap.add_argument("--backend", default="tpu")
    ap.add_argument("--cache_dir",
                    help="SHARED persistent AOT plan cache (cache.dir) "
                         "— warm restarts and late joiners compile 0")
    ap.add_argument("--summary_dir")
    ap.add_argument("--max_queue", type=int, default=None)
    ap.add_argument("--deadline_ms", type=int, default=None)
    ap.add_argument("--template", help="engine template file")
    ap.add_argument("--property_file", help="k=v property overrides")
    ap.add_argument("--property", action="append",
                    help="inline k=v override (repeatable)")
    args = ap.parse_args(argv)
    if (args.gen_scale is None and not args.nds_h_data
            and not args.nds_data):
        ap.error("need --gen_scale or --nds_h_data/--nds_data")

    from nds_tpu.obs.snapshot import MetricsSnapshotter
    from nds_tpu.resilience import drain as drain_mod
    from nds_tpu.resilience import watchdog

    srv, cfg = build_server(args)
    progress = {"replica": args.name, "queries_completed": 0}

    def _progress_tick():
        with srv._lock:
            progress["queries_completed"] = srv.stats["completed"]
    # the beat loop inside serve_replica() refreshes this each tick;
    # the snapshotter daemon publishes it at its own interval
    srv._progress_tick = _progress_tick

    snap = MetricsSnapshotter.from_env(progress)
    if snap:
        snap.start()
    run_dir = (args.summary_dir or
               (os.path.dirname(args.announce) if args.announce
                else "."))
    wd = watchdog.Watchdog.from_env(run_dir)
    if wd:
        wd.start()
    srv.start()
    try:
        rc = asyncio.run(serve_replica(
            srv, args.host, args.port, args.announce,
            drain_mod.drain_seconds(cfg)))
    finally:
        _progress_tick()
        srv.stop()
        if snap:
            snap.stop()  # final snapshot always lands
        print(f"[replica {args.name}] stopped: {srv.stats}",
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
