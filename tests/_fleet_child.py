"""Child process for the fleet observability integration tests.

Two ranks join a jax.distributed world (the tests/_multihost_child.py
launch contract), each with an ARTIFICIALLY skewed export clock
(obs/trace._shift_epoch_offset — simulating hosts whose wall clocks
disagree), and exercise the fleet layer end-to-end. Queries execute on
each rank's OWN devices — this jaxlib's CPU backend cannot compile
cross-process XLA programs, and the fleet layer (handshake, shards,
sidecars, merge) is deliberately backend-free: it rides the
coordination service, exactly what lets it span worlds the compiler
cannot. Per-query coordination barriers stand in for the implicit
pairing a real pod's collectives provide.

- ``session`` mode (tests/test_fleet.py): in-memory NDS-H tables, a
  rank-local distributed session, and a handful of queries under
  power-loop-style ``query`` root spans with a fleet barrier before
  each — the parent merges the shards and asserts the paired spans
  overlap only AFTER clock alignment.

- ``power`` mode (tools/fleet_check.py): a real NDS-H power run
  (``power_core.run_query_stream``) over a raw warehouse the parent
  generated, with a watchdog armed, a ``stream.query:hang`` injected
  via the environment (both ranks hang at the same query), and an
  explicit-query profile trigger — the parent asserts the stall
  reports point at flight dumps + XLA captures and that ``ndsreport
  analyze`` renders the clock-aligned fleet timeline with straggler
  attribution.

argv: port rank nproc ndev workdir skew_s mode
"""

import os
import sys


def setup(port: str, pid: int, nproc: int, ndev: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    os.environ.setdefault("JAX_ENABLE_X64", "true")
    os.environ["NDS_TPU_COORDINATOR"] = f"localhost:{port}"
    os.environ["NDS_TPU_NUM_PROCESSES"] = str(nproc)
    os.environ["NDS_TPU_PROCESS_ID"] = str(pid)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def run_session(workdir: str, pid: int, skew_s: float) -> None:
    """Rank-local distributed session + manual query root spans: the
    minimal surface the clock-alignment merge needs."""
    import jax

    from nds_tpu.datagen import tpch
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h import streams
    from nds_tpu.nds_h.schema import get_schemas
    from nds_tpu.obs import fleet as obs_fleet
    from nds_tpu.obs import trace as obs_trace
    from nds_tpu.parallel import multihost
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    from nds_tpu.parallel.mesh import make_mesh

    run_dir = os.path.join(workdir, "run")
    os.makedirs(run_dir, exist_ok=True)
    # artificial per-rank clock skew BEFORE the handshake: the
    # handshake must measure (and the merge must undo) exactly this
    obs_trace._shift_epoch_offset(pid * skew_s)
    os.environ[obs_trace.TRACE_ENV] = os.path.join(run_dir,
                                                   "trace.jsonl")
    assert multihost.maybe_initialize(), "distributed init did not run"
    meta = obs_fleet.init_fleet(run_dir, distributed=True)
    assert meta is not None and meta["world"] == 2, meta
    assert meta["aligned"], "clock handshake failed"

    # rank-LOCAL mesh: each rank executes on its own virtual devices
    # (see module docstring); the fleet layer is what spans the world
    mesh = make_mesh(devices=jax.local_devices())
    schemas = get_schemas()
    raw = {t: tpch.gen_table(t, 0.005) for t in schemas}
    s = Session.for_nds_h(make_distributed_factory(
        mesh=mesh, shard_threshold=500, multiprocess=False))
    for t in schemas:
        s.register_table(from_arrays(t, schemas[t], raw[t]))

    tracer = obs_trace.get_tracer()
    for qn in (1, 6, 3):
        # pair the ranks the way a pod's collectives would: both
        # enter the query together
        assert multihost.barrier(f"nds_tpu/test/q{qn}"), "barrier"
        got = None
        with tracer.span("query", query=f"q{qn}", suite="nds_h",
                         backend="distributed"):
            for stmt in streams.statements(qn):
                r = s.sql(stmt)
                got = r if r is not None else got
        assert got is not None and len(got.to_pandas()) >= 0
        print(f"rank {pid}: q{qn} OK", flush=True)
    tracer.flush_exports()
    print(f"FLEET_OK rank={pid}", flush=True)


def run_power(workdir: str, pid: int, skew_s: float) -> None:
    """Real NDS-H power run inside a 2-process world: the fleet
    wiring runs exactly where production runs it (power_core)."""
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.obs import trace as obs_trace
    from nds_tpu.parallel import multihost
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig

    run_dir = os.path.join(workdir, "run")
    os.makedirs(run_dir, exist_ok=True)
    obs_trace._shift_epoch_offset(pid * skew_s)
    os.environ[obs_trace.TRACE_ENV] = os.path.join(run_dir,
                                                   "trace.jsonl")
    assert multihost.maybe_initialize(), "distributed init did not run"
    cfg = EngineConfig(overrides={
        # device placement on this rank's own devices (the CPU
        # backend cannot compile cross-process programs; the fleet
        # layer is what spans the world)
        "engine.backend": "tpu",
        "engine.watchdog.stall_s": "2",
        "engine.retry.base_delay_s": "0.01",
        "engine.profile.dir": os.path.join(workdir, "prof"),
        "engine.profile.mode": "query1",
    })
    failures = power_core.run_query_stream(
        SUITE, os.path.join(workdir, "raw"),
        os.path.join(workdir, "streams", "stream_0.sql"),
        os.path.join(run_dir, f"time_r{pid}.csv"), config=cfg,
        input_format="raw", json_summary_folder=run_dir,
        query_subset=["query1", "query6", "query3"])
    assert failures == 0, f"rank {pid}: {failures} queries failed"
    print(f"FLEET_OK rank={pid}", flush=True)


def main() -> None:
    port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    ndev = int(sys.argv[4])
    workdir = sys.argv[5]
    skew_s = float(sys.argv[6])
    mode = sys.argv[7] if len(sys.argv) > 7 else "session"
    setup(port, pid, nproc, ndev)
    if mode == "power":
        run_power(workdir, pid, skew_s)
    else:
        run_session(workdir, pid, skew_s)


if __name__ == "__main__":
    main()
