"""Child process for the 2-process multi-host integration test.

Each process provisions 4 virtual CPU devices and joins a 2-process
jax.distributed world (8 global devices): the DCN axis crosses a REAL
process boundary, which single-process virtual meshes cannot exercise.
Launched by tests/test_distributed.py::test_two_process_multihost.
"""

import os
import sys


def main() -> None:
    port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    # local virtual devices per process (argv[4], default 4): the
    # 4-process tier runs 4x2, the 2-process tier 2x4
    ndev = int(sys.argv[4]) if len(sys.argv) > 4 else 4
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={ndev}").strip()
    os.environ.setdefault("JAX_ENABLE_X64", "true")
    # the power_core "distributed" backend reads the launch contract
    # from these (parallel/multihost.py)
    os.environ["NDS_TPU_COORDINATOR"] = f"localhost:{port}"
    os.environ["NDS_TPU_NUM_PROCESSES"] = str(nproc)
    os.environ["NDS_TPU_PROCESS_ID"] = str(pid)

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from nds_tpu.parallel import multihost

    assert multihost.maybe_initialize(), "distributed init did not run"
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.local_devices()) == ndev
    assert len(jax.devices()) == ndev * nproc

    import numpy as np

    from nds_tpu.datagen import tpch
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h.schema import get_schemas
    from nds_tpu.parallel.dist_exec import make_distributed_factory

    schemas = get_schemas()
    raw = {t: tpch.gen_table(t, 0.005) for t in schemas}

    def build(factory=None):
        s = Session.for_nds_h(factory)
        for t in schemas:
            s.register_table(from_arrays(t, schemas[t], raw[t]))
        return s

    cpu = build()
    mesh = multihost.global_mesh()
    dist = build(make_distributed_factory(mesh=mesh,
                                          shard_threshold=500))
    from nds_tpu.nds_h import streams
    for qn in (1, 3, 6):
        exp = cpu.sql(streams.render_query(qn)).to_pandas()
        got = None
        for stmt in streams.statements(qn):
            r = dist.sql(stmt)
            got = r if r is not None else got
        got = got.to_pandas()
        assert len(got) == len(exp), (qn, len(got), len(exp))
        for c in exp.columns:
            g, e = got[c].to_numpy(), exp[c].to_numpy()
            if g.dtype.kind == "f" or e.dtype.kind == "f":
                np.testing.assert_allclose(
                    g.astype(float), e.astype(float), rtol=1e-9)
            else:
                assert list(g) == list(e), (qn, c)
        print(f"rank {pid}: q{qn} OK ({len(got)} rows)", flush=True)
    # survivor-reduced scans across a REAL process world: reduced
    # buffers must build as replicated global jax.Arrays
    # (DistributedExecutor._reduced_to_device multiprocess branch)
    from nds_tpu.parallel.dist_exec import DistributedExecutor

    class SmallReduce(DistributedExecutor):
        REDUCE_MIN_ROWS = 1

    holder = {}

    def factory(tables):
        ex = holder.get("ex")
        if ex is None or ex.tables is not tables:
            ex = SmallReduce(tables, mesh=mesh, shard_threshold=500)
            holder["ex"] = ex
        return ex

    red = build(factory)
    exp = cpu.sql(streams.render_query(3)).to_pandas()
    got = red.sql(streams.render_query(3)).to_pandas()
    assert len(got) == len(exp), ("reduce", len(got), len(exp))
    for c in exp.columns:
        g, e = got[c].to_numpy(), exp[c].to_numpy()
        if g.dtype.kind == "f" or e.dtype.kind == "f":
            np.testing.assert_allclose(
                g.astype(float), e.astype(float), rtol=1e-9)
        else:
            assert list(g) == list(e), ("reduce-q3", c)
    # engagement proof: reduced buffers actually uploaded (global
    # replicated jax.Arrays in this 2-process world)
    n_red = sum(1 for k in holder["ex"]._buffers
                if "@" in k.split(".", 1)[0])
    assert n_red > 0, "no reduced buffer uploaded in multiprocess world"
    print(f"rank {pid}: reduced-scan q3 OK ({n_red} buffers)",
          flush=True)

    # rank-0-only recording contract
    assert multihost.is_primary() == (pid == 0)
    print(f"MULTIHOST_OK rank={pid}", flush=True)


if __name__ == "__main__":
    main()
