"""Compile for the chip, without the chip (on-chip-measurement guide §2).

The TPU's compiler is installed and compiles for a DESCRIBED v5e:2x2:
the hot kernels of ``engine/kernels.py``, the 4-device exchange and one
whole query program are lowered with ``ShapeDtypeStruct``s placed on
described devices, so whatever the chip's compiler refuses — a program
that does not fit, an op it cannot lower (PR 21: an s64 ``pmax``) —
fails HERE, at no chip time. Nothing runs: these say nothing about
results or speed.

Shapes are real NDS-H SF1 join shapes from the customer / orders /
partsupp tier, where each compile stays in seconds. The lineitem tier
(6M rows) is left to the hand rehearsal, as is every sort-bearing
program: the TPU compiler's time is not flat in the shapes (CHANGES.md
PR 21 has the table) — one 64-bit ``lax.sort`` costs it 85-120 s at any
size; ``direct_lookup_join`` 1.9 s at 150k x 1.5M but 14 s at 1.5M x 6M;
``seg_scan`` 6 s at 100k rows, 90 s at 800k and over ten minutes at 6M.

The topology is described inside a module-scoped fixture that skips
when it cannot be (never at import: only one process may load the
TPU's library, and every xdist worker imports every test file), and
everything compiles in this process. Keep these tests in this ONE file.
"""

import numpy as np
import pytest

LINEITEM = 6_001_215   # NDS-H SF1 row counts
ORDERS = 1_500_000
PARTSUPP = 800_000
CUSTOMER = 150_000
SUPPLIER = 10_000
SEG_ROWS = 100_000     # a customer/supplier-sized grouped min/max


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to jax's persistent
    cache but can never be read back without a chip (the next compile
    warns and compiles again): switch the cache off around this file."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _kernel_cases():
    """name -> (fn, [(shape, dtype), ...]) at NDS-H SF1 shapes. Built
    lazily (a parametrize argument must not import jax)."""
    import jax.numpy as jnp

    from nds_tpu.engine import kernels as KX
    c, o, ps, s, g = CUSTOMER, ORDERS, PARTSUPP, SUPPLIER, SEG_ROWS
    return {
        # customer (build, unique c_custkey) x orders (probe): q3/q13/q18
        "direct_lookup_join": (
            lambda bk, bo, pk, po: KX.direct_lookup_join(
                bk, bo, pk, po, lo=1, dom=c),
            [((c,), "int64"), ((c,), "bool"),
             ((o,), "int64"), ((o,), "bool")]),
        # nation (25 rows) probed by customer: q5/q7/q10
        "matmul_probe_join": (
            KX.matmul_probe_join,
            [((25,), "int64"), ((25,), "bool"),
             ((c,), "int64"), ((c,), "bool")]),
        # q16: ps_suppkey NOT IN (select s_suppkey from supplier ...)
        "bitmask_semi": (
            lambda bk, bo, pk, po: KX.bitmask_semi(
                bk, bo, pk, po, lo=1, dom=s),
            [((s,), "int64"), ((s,), "bool"),
             ((ps,), "int64"), ((ps,), "bool")]),
        # q21's EXISTS with the <> residual. Its real build is lineitem
        # (18 s to compile there); what this guards is the int64
        # scatter-min/max the chip emulates, at customer size
        "keyed_minmax_semi": (
            lambda bk, bo, bv, pk, po, pv: KX.keyed_minmax_semi(
                bk, bo, bv, pk, po, pv, lo=1, dom=c),
            [((c,), "int64"), ((c,), "bool"), ((c,), "int64"),
             ((c,), "int64"), ((c,), "bool"), ((c,), "int64")]),
        # grouped min/max over sorted group ids, scaled-int64 decimals
        "seg_scan": (
            lambda v, f: KX.seg_scan(jnp.minimum, v, f),
            [((g,), "int64"), ((g,), "bool")]),
        "seg_reduce_at_ends": (
            lambda d, gid, s: KX.seg_reduce_at_ends(jnp.maximum, d, gid,
                                                    s),
            [((g,), "int64"), ((g,), "int32"), ((g,), "int32")]),
    }


@pytest.mark.parametrize("name", [
    "direct_lookup_join", "matmul_probe_join", "bitmask_semi",
    "keyed_minmax_semi", "seg_scan", "seg_reduce_at_ends"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    import jax
    fn, shapes = _kernel_cases()[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    # one v5e chip holds 16 GB; a kernel alone must leave room for the
    # tables and the rest of its program
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30


def test_exchange_compiles_for_four_chips(topo, no_persistent_cache):
    """The hash-partition exchange over a 4-device mesh built from the
    described devices: orders split four ways (375k rows a device), an
    int64 key and the payload widths the joins ship. The compiler must
    keep the all-to-all (a program that silently became local would
    pass every virtual-device test and shuffle nothing on the chips)."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P_

    from nds_tpu.parallel.dist_exec import shard_map
    from nds_tpu.parallel.exchange import exchange
    from nds_tpu.parallel.mesh import DATA_AXIS
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    n_dev = len(topo.devices)
    assert n_dev == 4

    def body(key, ok, qty, price):
        outs, out_ok, over = exchange([key, qty, price], key, ok, n_dev)
        return outs[0], outs[1], outs[2], out_ok, over[None]

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P_(DATA_AXIS),) * 4,
                   out_specs=(P_(DATA_AXIS),) * 5)
    rows = NamedSharding(mesh, P_(DATA_AXIS))
    n = ORDERS
    args = [_sds((n,), "int64", rows), _sds((n,), "bool", rows),
            _sds((n,), "int64", rows), _sds((n,), "int64", rows)]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    # the send buffers are read into place (PR 30): a scatter of a
    # 64-bit column costs the chip 80 ns an update
    assert "scatter(" not in text and "gather(" in text
    for s in jax.tree.leaves(compiled.input_shardings):
        assert len(s.device_set) == 4
    mem = compiled.memory_analysis()   # bytes on EACH device
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30


def test_q6_whole_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """NDS-H q6 (scan -> filter -> sum; no sort) as DeviceExecutor
    builds it over an SF1-sized lineitem: the survivor-reduced scan the
    power run really dispatches, lowered for the described chip."""
    import jax

    from nds_tpu.engine.device_exec import DeviceExecutor
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import HostColumn, HostTable
    from nds_tpu.nds_h import streams
    from nds_tpu.nds_h.schema import get_schemas
    schema = get_schemas()["lineitem"]
    rng = np.random.default_rng(6)
    # dbgen's distributions for the four columns q6 reads: dates span
    # 1992-01-02..1998-12-01 (days since epoch), decimals scaled x100
    cols = {
        "l_shipdate": rng.integers(8036, 10562, LINEITEM, dtype=np.int32),
        "l_discount": rng.integers(0, 11, LINEITEM, dtype=np.int64),
        "l_quantity": rng.integers(1, 51, LINEITEM, dtype=np.int64) * 100,
        "l_extendedprice": rng.integers(90_000, 10_500_000, LINEITEM,
                                        dtype=np.int64),
    }
    dtypes = {f.name: f.dtype for f in schema}
    lineitem = HostTable("lineitem", schema, {
        c: HostColumn(dtypes[c], v) for c, v in cols.items()})
    sess = Session.for_nds_h()
    planned = sess.plan(streams.render_query(6))
    # the planner's scan lists all 16 lineitem columns (jit then drops
    # the 12 unused inputs from the program): keep the synthetic table
    # to the four q6 reads by narrowing the scan the same way
    from nds_tpu.sql import plan as P
    for node in P.walk_plan(planned.root):
        if isinstance(node, P.Scan):
            node.output = [(n, dt) for n, dt in node.output if n in cols]
    # decimal mode: float_dtype None, as make_session builds it from
    # configs/power_run_tpu.template
    ex = DeviceExecutor({"lineitem": lineitem})
    jitted, side = ex._compile(planned)
    bufs = ex._collect_buffers(planned)
    # ~2% of lineitem survives q6's filters: the scan was reduced on the
    # host to a power-of-two capacity, as at SF1 on the chip
    assert all(b.shape[0] < LINEITEM // 8 for b in bufs.values())
    avatars = {k: _sds(b.shape, b.dtype, one_chip)
               for k, b in bufs.items()}
    compiled = jitted.lower(avatars).compile()
    assert " sort(" not in compiled.as_text()
    assert side["ops_est"] > 0



@pytest.mark.parametrize("sliced_first", [True, False],
                         ids=["gather-kept-rows", "gather-then-slice"])
def test_topn_gather_shape_on_v5e(sliced_first, one_chip,
                                  no_persistent_cache):
    """What follows the sort of ORDER BY ... LIMIT 100 over an SF1-sized
    lineitem. As ``_Trace._run_limit`` emits it, every column is
    gathered at ``perm[:100]``: no 6M-row ``kCustom`` fusion (the name
    the TPU compiler gives a gather). The shape it replaced,
    ``take(x, perm)[:100]``, keeps them, two for an int64 column: the
    compiler does not push a slice through a gather, which is the rule
    the executor's top-N rests on. The sort is left out of both: it
    costs the compiler 70-90 s at any size."""
    import re
    import types

    import jax

    from nds_tpu.engine.device_exec import DCtx, DVal, _Trace

    def after_sort(perm, present_s, okey, price, date, date_ok):
        tr = _Trace(types.SimpleNamespace(), {})
        ctx = DCtx(LINEITEM, None)
        ctx.cols = {("l", "okey"): DVal(okey), ("l", "price"): DVal(price),
                    ("l", "date"): DVal(date, date_ok)}
        if sliced_first:
            out = tr._gather(ctx, perm[:100])
        else:
            out = tr._gather(ctx, perm)
        return present_s[:100], [
            (dv.arr[:100], None if dv.valid is None else dv.valid[:100])
            for dv in out.cols.values()]

    n = LINEITEM
    args = [_sds((n,), "int32", one_chip), _sds((n,), "bool", one_chip),
            _sds((n,), "int64", one_chip), _sds((n,), "int64", one_chip),
            _sds((n,), "int32", one_chip), _sds((n,), "bool", one_chip)]
    text = jax.jit(after_sort).lower(*args).compile().as_text()
    custom = [int(r) for r in re.findall(
        r"= \w+\[(\d+)\]\S* fusion\(.*kind=kCustom", text)]
    assert custom, "no kCustom fusion: the compiler's naming has moved"
    if sliced_first:
        assert max(custom) <= 100, custom
    else:
        # int64 okey and price: two 32-bit gathers each; date; its flags
        assert custom.count(n) >= 5, custom
