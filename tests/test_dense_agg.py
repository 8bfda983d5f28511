"""The dense grouped form (``_Trace._run_aggregate_dense``): a GROUP BY
whose key domains the host knows, at or under
``kernels.DENSE_AGG_MAX_GROUPS`` slots, is one masked reduction a slot
and aggregate, with no group sort, permutation, gather or cumsum.

Every case is held against the CPU oracle row for row in the order
returned, and says which form it expects in the ``kernels`` block; the
sorted form keeps a twin wherever a pinned case went dense
(``tests/test_device_engine.py::SORT_PERM_CASES``,
``tests/test_kernels.py``). One case each runs through the sharded
trace and through the chunked executor's partial-and-merge aggregate,
which take the form from ``_Trace`` through ``super()``.
"""

import re

import numpy as np
import pytest

from nds_tpu.engine import device_exec as dx
from nds_tpu.engine import kernels as KX
from nds_tpu.engine.device_exec import make_device_factory
from nds_tpu.engine.session import Session
from nds_tpu.engine.types import (FLOAT64, INT32, INT64, Schema, decimal,
                                  varchar)
from nds_tpu.io.host_table import from_arrays
from nds_tpu.sql.planner import CatalogInfo

from tests.test_device_engine import _kernels
from tests.test_device_engine import assert_frames_equal_in_order as in_order

BOUND = KX.DENSE_AGG_MAX_GROUPS
NR = max(600, 4 * (BOUND + 1))
BIG = 1 << 40

SCHEMA = Schema.of(
    ("d_id", INT32, False), ("d_k", INT32, True),
    ("d_name", varchar(8), True), ("d_y", INT32, False),
    ("d_at", INT32, False), ("d_over", INT32, False),
    ("d_i", INT32, True), ("d_big", INT64, False),
    ("d_dec", decimal(12, 2), True), ("d_f", FLOAT64, True))


def _data():
    rng = np.random.default_rng(20261004)
    names = np.array(["ash", "birch", "cedar"], dtype=object)
    data = {
        "d_id": np.arange(NR, dtype=np.int32),
        "d_k": rng.integers(0, 5, NR).astype(np.int32),
        "d_k#null": rng.random(NR) >= 0.15,
        "d_name": names[rng.integers(0, 3, NR)],
        "d_name#null": rng.random(NR) >= 0.1,
        # a bounded integer whose lowest value is not 0
        "d_y": rng.integers(1995, 1999, NR).astype(np.int32),
        # exactly BOUND slots, and one more
        "d_at": (np.arange(NR) % BOUND).astype(np.int32),
        "d_over": (np.arange(NR) % (BOUND + 1)).astype(np.int32),
        "d_i": rng.integers(-40, 60, NR).astype(np.int32),
        "d_i#null": rng.random(NR) >= 0.2,
        "d_big": BIG + rng.integers(-999, 999, NR).astype(np.int64),
        "d_dec": rng.integers(-50_000, 900_000, NR).astype(np.int64),
        "d_dec#null": rng.random(NR) >= 0.1,
        "d_f": rng.normal(10.0, 4.0, NR),
        "d_f#null": rng.random(NR) >= 0.1,
    }
    # rows 0-39 hold one key (d_k = 4, d_y = 1998) and no argument: a
    # slot of NULL arguments, and what a filter on d_id cuts down to
    data["d_k"][:40], data["d_k#null"][:40] = 4, True
    data["d_y"][:40] = 1998
    for col in ("d_i", "d_dec", "d_f"):
        data[col + "#null"][:40] = False
    for col in ("d_k", "d_name", "d_i", "d_dec", "d_f"):
        data[col] = np.where(data[col + "#null"], data[col], data[col][-1])
    return data


def _sessions(factory=None):
    cat = CatalogInfo({"d": SCHEMA}, {"d": ["d_id"]}, {"d": NR})
    table = from_arrays("d", SCHEMA, _data())

    def build(f=None):
        s = Session(cat, f)
        s.register_table(table)
        return s

    return build(), build(factory or make_device_factory())


@pytest.fixture(scope="module")
def both():
    return _sessions()


DENSE, SORTED = "agg.dense", "agg.sorted_keys"


def assert_frames_equal_in_order(got, exp, label):
    """Integers, decimals, strings and NULLs to the digit; a float (an
    avg, a float sum, a stddev) to the last few bits."""
    in_order(got, exp, label, float_rtol=1e-12)


# (label, sql, the form's count that has to show)
CASES = [
    ("sum-int", "select d_k, sum(d_i) s from d group by d_k order by d_k",
     DENSE),
    ("sum-int64", "select d_y, sum(d_big) s from d group by d_y "
     "order by d_y", DENSE),
    ("sum-decimal", "select d_k, sum(d_dec) s, sum(d_dec * (1 - d_dec)) t "
     "from d group by d_k order by d_k", DENSE),
    ("sum-float", "select d_y, sum(d_f) s from d group by d_y order by d_y",
     DENSE),
    ("count-star", "select d_k, count(*) c from d group by d_k "
     "order by d_k", DENSE),
    ("count-arg", "select d_y, count(d_i) c, count(d_f) f, count(*) n "
     "from d group by d_y order by d_y", DENSE),
    ("avg", "select d_k, avg(d_i) a, avg(d_dec) b, avg(d_f) c from d "
     "group by d_k order by d_k", DENSE),
    ("min-max", "select d_y, min(d_i) a, max(d_i) b, min(d_big) c, "
     "max(d_dec) e, min(d_f) f, max(d_name) g from d group by d_y "
     "order by d_y", DENSE),
    ("stddev", "select d_k, stddev_samp(d_i) a, stddev_samp(d_f) b from d "
     "group by d_k order by d_k", DENSE),
    ("q1-shape", "select d_name, d_y, sum(d_dec) a, sum(d_big) b, "
     "avg(d_dec) c, avg(d_i) e, count(*) n from d where d_id <= 550 "
     "group by d_name, d_y order by d_name, d_y", DENSE),
    # a NULL key is its own group, and the last slot of its digit
    ("null-key-last", "select d_k, count(*) c, sum(d_i) s from d "
     "group by d_k order by d_k nulls last", DENSE),
    ("null-key-first", "select d_name, count(*) c, min(d_i) m from d "
     "group by d_name order by d_name nulls first", DENSE),
    # a slot whose every argument is NULL: NULL sums, count 0
    ("null-arguments", "select d_k, d_y, sum(d_i) s, avg(d_dec) a, "
     "min(d_f) m, count(d_i) c, count(*) n from d where d_id < 40 "
     "group by d_k, d_y order by d_k, d_y", DENSE),
    # mixed radix: a string key (nullable) times an integer key
    ("string-x-int", "select d_name, d_y, count(*) c, sum(d_big) s from d "
     "group by d_name, d_y order by d_name, d_y", DENSE),
    ("int-x-string-x-int", "select d_y, d_name, d_k, count(*) c from d "
     "where d_k < 2 group by d_y, d_name, d_k order by d_y, d_name, d_k",
     DENSE),
    ("filter-empties-some", "select d_k, d_y, count(*) c, max(d_i) m "
     "from d where d_k in (1, 3) and d_y <> 1996 group by d_k, d_y "
     "order by d_k, d_y", DENSE),
    ("having", "select d_k, sum(d_i) s from d group by d_k "
     "having count(*) > 90 order by d_k", DENSE),
    ("limit-over-slots", "select d_y, d_k, sum(d_dec) s from d "
     "group by d_y, d_k order by s desc, d_y, d_k limit 3", DENSE),
    ("slots-at-the-bound", "select d_at, count(*) c, sum(d_i) s from d "
     "group by d_at order by d_at", DENSE),
    ("slots-one-over-the-bound", "select d_over, count(*) c, sum(d_i) s "
     "from d group by d_over order by d_over", SORTED),
    # count(distinct) keeps the node on the sorted form whole
    ("count-distinct", "select d_k, count(distinct d_y) c, sum(d_i) s "
     "from d group by d_k order by d_k", SORTED),
    # a key domain at the row count: nothing to gain, sorted
    ("unique-key", "select d_id, sum(d_i) s from d where d_id < 30 "
     "group by d_id order by d_id", SORTED),
]


@pytest.mark.parametrize("label,sql,form", CASES,
                         ids=[c[0] for c in CASES])
def test_grouped_form_matches_oracle(label, sql, form, both):
    cpu, dev = both
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert len(exp), f"{label}: the oracle returns no row to compare"
    assert_frames_equal_in_order(got, exp, label)
    kern = _kernels(dev)
    other = SORTED if form == DENSE else DENSE
    assert kern.get(form) and not kern.get(other), (label, kern)


def test_filter_that_empties_every_slot(both):
    cpu, dev = both
    sql = ("select d_k, count(*) c, sum(d_i) s from d where d_id < 0 "
           "group by d_k order by d_k")
    assert len(cpu.sql(sql).to_pandas()) == 0
    assert len(dev.sql(sql).to_pandas()) == 0
    assert _kernels(dev).get(DENSE) == 1


def test_dense_and_sorted_forms_agree_without_order_by(monkeypatch):
    """Slots stand in the order the group sort gives its groups (first
    key most significant, ascending, NULL last), so with no ORDER BY the
    two forms return the same rows in the same order."""
    sql = ("select d_name, d_k, d_y, count(*) c, sum(d_dec) s, min(d_i) m "
           "from d where d_id % 7 <> 0 group by d_name, d_k, d_y")
    monkeypatch.setattr(KX, "DENSE_AGG_MAX_GROUPS", 4 * 6 * 4)
    _cpu, dense = _sessions()
    got = dense.sql(sql).to_pandas()
    assert _kernels(dense).get(DENSE) == 1
    monkeypatch.setattr(KX, "DENSE_AGG_MAX_GROUPS", 0)
    _cpu, by_sort = _sessions()
    exp = by_sort.sql(sql).to_pandas()
    assert _kernels(by_sort).get(SORTED) and not _kernels(by_sort).get(DENSE)
    assert len(exp) > 40
    assert_frames_equal_in_order(got, exp, "dense against sorted")


# gather_words of q1's program on the parent commit (040a607) at SF0.01
PARENT_Q1_GATHER_WORDS = 841_154


def test_q1_program_has_no_sort_or_gather_before_the_order_by():
    """NDS-H q1 lowered on the CPU at SF0.01: the only sort is the
    final ORDER BY's over the six slots, nothing upstream of it sorts,
    gathers or scatters, and the program's static gather_words fell to
    the few words of that ORDER BY."""
    from nds_tpu.datagen import tpch
    from nds_tpu.nds_h import streams
    from nds_tpu.nds_h.schema import get_schemas
    schemas = get_schemas()
    tables = {"lineitem": from_arrays(
        "lineitem", schemas["lineitem"], tpch.gen_table("lineitem", 0.01))}
    planned = Session.for_nds_h().plan(streams.render_query(1))
    ex = dx.DeviceExecutor(tables)
    jitted, side = ex._compile(planned)
    text = jitted.lower(ex._collect_buffers(planned)).as_text()
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }\n") + 1]
    assert main.count('"stablehlo.sort"') == 1
    before = main[:main.index('"stablehlo.sort"')]
    assert not re.search(
        r"call @_take|stablehlo\.gather|stablehlo\.scatter|call @cumsum"
        r"|stablehlo\.reduce_window", before)
    # what is gathered after it is the aggregate's output: a few slots
    after = main[main.index('"stablehlo.sort"'):]
    gathers = [(int(m.group(1)), int(m.group(2))) for m in re.finditer(
        r"(?:call @_take\w*|stablehlo\.gather).*: \(tensor<(\d+)x.*"
        r"-> tensor<(\d+)x", after)]
    assert gathers and max(max(g) for g in gathers) <= BOUND, gathers
    kern = side["kernels"]
    assert kern.get(DENSE) == 1 and SORTED not in kern, kern
    assert kern["gather_words"] < PARENT_Q1_GATHER_WORDS // 1000, kern


def test_dense_form_through_the_sharded_trace():
    """``_DistTrace._run_aggregate`` exchanges the rows by group key and
    calls ``super()``: on a sharded relation the aggregate over the
    receive buffer goes dense with no line of ``parallel/`` changed."""
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    cpu, dist = _sessions(make_distributed_factory(
        n_devices=4, shard_threshold=100))
    sql = ("select d_name, d_y, count(*) c, sum(d_dec) s, avg(d_i) a, "
           "max(d_big) m from d where d_id >= 40 group by d_name, d_y "
           "order by d_name, d_y")
    exp = cpu.sql(sql).to_pandas()
    got = dist.sql(sql).to_pandas()
    assert len(exp) == 16
    assert_frames_equal_in_order(got, exp, "sharded")
    ex = dist._executor_factory(dist.tables)
    kern = ex.last_timings.get("__kernels") or {}
    assert kern.get(DENSE) == 1 and not kern.get(SORTED), kern
    assert ex._is_sharded("d")


def test_dense_form_through_the_chunked_partial_and_merge(monkeypatch):
    """The chunked executor's per-chunk partial Aggregate and the merge
    Aggregate of ``_MergeTrace`` both take the dense form."""
    from nds_tpu.engine.chunked_exec import (_PartialAggExecutor,
                                             make_chunked_factory)
    traced = []
    dense = dx._Trace._run_aggregate_dense

    def counting(self, node, ctx, keyvals, G):
        traced.append((type(self).__name__, ctx.n, G))
        return dense(self, node, ctx, keyvals, G)

    monkeypatch.setattr(dx._Trace, "_run_aggregate_dense", counting)
    cpu, chunked = _sessions(make_chunked_factory(stream_bytes=1,
                                                  chunk_rows=128))
    sql = ("select d_k, d_y, count(*) c, sum(d_dec) s, avg(d_i) a, "
           "min(d_big) m from d group by d_k, d_y order by d_k, d_y")
    exp = cpu.sql(sql).to_pandas()
    got = chunked.sql(sql).to_pandas()
    assert_frames_equal_in_order(got, exp, "chunked")
    ex = chunked._executor_factory(chunked.tables)
    assert any(isinstance(s, _PartialAggExecutor)
               for s in ex._reduced.values())
    kinds = {k for k, _n, _g in traced}
    assert "_MergeTrace" in kinds and len(kinds) >= 2, traced
    assert all(g == 24 for _k, _n, g in traced), traced
