"""Differential tests: JAX device engine vs CPU oracle on all 22 queries.

This is the engine-tier analog of the reference's CPU-vs-GPU validation
(`nds/nds_validate.py:48-114`): the CPU oracle (itself validated against
independent pandas reimplementations in test_cpu_oracle.py) is ground
truth; every query must match row-for-row with the reference's epsilon
rules for float/decimal columns. Runs on the virtual 8-device CPU backend
(conftest), exercising the exact trace the TPU sees.
"""

import numpy as np
import pandas as pd
import pytest

from nds_tpu.datagen import tpch
from nds_tpu.engine.device_exec import make_device_factory
from nds_tpu.engine.session import Session
from nds_tpu.io.host_table import from_arrays
from nds_tpu.nds_h import streams
from nds_tpu.nds_h.schema import get_schemas

SF = 0.01


@pytest.fixture(scope="module")
def raw():
    return {t: tpch.gen_table(t, SF) for t in get_schemas()}


def _make_session(raw, factory=None):
    schemas = get_schemas()
    sess = Session.for_nds_h(factory)
    for t in schemas:
        sess.register_table(from_arrays(t, schemas[t], raw[t]))
    return sess


@pytest.fixture(scope="module")
def cpu_session(raw):
    return _make_session(raw)


@pytest.fixture(scope="module")
def dev_session(raw):
    return _make_session(raw, make_device_factory())


def run_query(session, qn):
    result = None
    for s in streams.statements(qn):
        r = session.sql(s)
        if r is not None:
            result = r
    return result


def _canon(df: pd.DataFrame) -> pd.DataFrame:
    """Canonical row order: sort by every column, floats rounded — the
    reference validator's --ignore_ordering sort (`nds_validate.py:130-131`)
    so tie order differences between engines don't fail the diff."""
    if not len(df):
        return df
    keyed = {}
    for i, c in enumerate(df.columns):
        col = df.iloc[:, i]
        if col.dtype.kind == "f":
            keyed[f"k{i}"] = col.round(4)
        else:
            keyed[f"k{i}"] = col.astype(str)
    order = pd.DataFrame(keyed).sort_values(list(keyed)).index
    return df.loc[order].reset_index(drop=True)


def assert_frames_close(got: pd.DataFrame, exp: pd.DataFrame, qn: int):
    assert got.shape == exp.shape, (
        f"q{qn}: shape {got.shape} vs oracle {exp.shape}")
    got, exp = _canon(got), _canon(exp)
    for i in range(exp.shape[1]):
        g, e = got.iloc[:, i], exp.iloc[:, i]
        name = exp.columns[i]
        if e.dtype.kind in "fc" or g.dtype.kind in "fc":
            np.testing.assert_allclose(
                pd.to_numeric(g, errors="coerce").to_numpy(dtype=float),
                pd.to_numeric(e, errors="coerce").to_numpy(dtype=float),
                rtol=1e-6, atol=1e-6,
                err_msg=f"q{qn} col {i} ({name})")
        else:
            ge = g.isna()
            ee = e.isna()
            assert list(ge) == list(ee), f"q{qn} col {i} ({name}) null mask"
            assert list(g[~ge].astype(str)) == list(e[~ee].astype(str)), (
                f"q{qn} col {i} ({name})")


@pytest.mark.parametrize("qn", range(1, 23))
def test_query_matches_oracle(qn, cpu_session, dev_session):
    exp = run_query(cpu_session, qn).to_pandas()
    got = run_query(dev_session, qn).to_pandas()
    assert_frames_close(got, exp, qn)


# ------------------------------------------------------------------------
# A sort's permutation is applied at the rows that are kept, and to nothing
# the sort already returned: top-N gathers LIMIT's rows, group keys and
# presence come from the sort's own outputs.

NW = 300
BIG = 1 << 40      # int64 values _narrow_key cannot narrow


def _wide_sessions():
    from nds_tpu.engine.types import INT32, INT64, Schema, varchar
    from nds_tpu.sql.planner import CatalogInfo
    schema = Schema.of(
        ("w_id", INT32, False), ("w_key", INT32, True),
        ("w_small", INT64, False), ("w_big", INT64, False),
        ("w_bign", INT64, True), ("w_name", varchar(10), True),
        ("w_val", INT32, False))
    cat = CatalogInfo({"w": schema}, {"w": ["w_id"]}, {"w": NW})
    rng = np.random.default_rng(20261001)
    names = np.array(["ash", "birch", "cedar", "elm", "fir"], dtype=object)
    data = {
        "w_id": np.arange(NW, dtype=np.int32),
        "w_key": rng.integers(0, 10, NW).astype(np.int32),   # ties
        "w_key#null": rng.random(NW) >= 0.15,
        "w_small": rng.integers(-50, 50, NW).astype(np.int64),
        "w_big": BIG + rng.integers(0, 12, NW).astype(np.int64) * 7,
        "w_bign": -BIG + rng.integers(0, 9, NW).astype(np.int64),
        "w_bign#null": rng.random(NW) >= 0.2,
        "w_name": names[rng.integers(0, 5, NW)],
        "w_name#null": rng.random(NW) >= 0.1,
        "w_val": rng.integers(0, 1000, NW).astype(np.int32),
    }
    # one fill value under every NULL, as the loaders leave it: the
    # oracle orders NULL rows among themselves by what lies underneath
    for col in ("w_key", "w_bign", "w_name"):
        data[col] = np.where(data[col + "#null"], data[col], data[col][0])

    def build(factory=None):
        s = Session(cat, factory)
        s.register_table(from_arrays("w", schema, data))
        return s

    return build(), build(make_device_factory())


@pytest.fixture(scope="module")
def wide():
    return _wide_sessions()


def assert_frames_equal_in_order(got, exp, label, float_rtol=None):
    """Row for row, in the order returned: the ORDER BY of every
    statement below leaves no tie for the engines to break apart.
    ``float_rtol``: hold float columns to it and not to the digit (a
    float sum's order of addition is the engine's own)."""
    assert got.shape == exp.shape, (
        f"{label}: shape {got.shape} vs oracle {exp.shape}")
    for i in range(exp.shape[1]):
        g, e = got.iloc[:, i], exp.iloc[:, i]
        assert list(g.isna()) == list(e.isna()), f"{label} col {i} nulls"
        keep = ~e.isna()
        if float_rtol is not None and e.dtype.kind == "f":
            np.testing.assert_allclose(
                g[keep].to_numpy(dtype=float), e[keep].to_numpy(dtype=float),
                rtol=float_rtol, atol=0, err_msg=f"{label} col {i}")
            continue
        assert list(g[keep].astype(str)) == list(e[keep].astype(str)), (
            f"{label} col {i} ({exp.columns[i]})")


def _kernels(dev):
    ex = dev._executor_factory(dev.tables)
    return ex.last_timings.get("__kernels") or {}


# (label, sql, kernel count that has to show)
SORT_PERM_CASES = [
    # ORDER BY ... LIMIT: the stable sort keeps tied rows in row order in
    # both engines when the input is the table itself
    ("topn-ties", "select w_id, w_key, w_big from w order by w_big "
     "limit 17", "sort.topn"),
    ("topn-desc", "select w_id, w_small from w order by w_small desc, "
     "w_id limit 10", "sort.topn"),
    ("topn-nulls-first", "select w_id, w_key from w order by w_key "
     "nulls first, w_id limit 60", "sort.topn"),
    ("topn-desc-nulls-last", "select w_id, w_key from w order by w_key "
     "desc nulls last, w_id limit 280", "sort.topn"),
    ("topn-limit-over-rows", "select w_id, w_name from w order by w_id "
     "desc limit 1000", "sort.topn"),
    ("topn-string-key", "select w_id, w_name from w order by w_name desc "
     "nulls first, w_id limit 25", "sort.topn"),
    ("topn-nullable-int64", "select w_id, w_bign from w order by w_bign, "
     "w_id limit 90", "sort.topn"),
    ("topn-filtered", "select w_id, w_val from w where w_key > 4 "
     "order by w_val desc, w_id limit 12", "sort.topn"),
    ("limit-no-sort", "select w_id, w_key from w where w_key > 4 limit 7",
     "sort.topn"),
    ("limit-no-sort-over-rows", "select w_id from w where w_id < 5 "
     "limit 50", "sort.topn"),
    ("topn-over-groups", "select w_key, w_name, sum(w_val) s from w "
     "group by w_key, w_name order by s desc, w_key, w_name limit 5",
     "sort.topn"),
    # GROUP BY over a handful of slots (key domains known on the host,
    # at or under kernels.DENSE_AGG_MAX_GROUPS): the dense form, no sort
    ("group-null-keys", "select w_key, count(*) c, sum(w_val) s from w "
     "group by w_key order by w_key", "agg.dense"),
    ("group-int64-nullable-wide", "select w_bign, count(*) c, max(w_val) "
     "m from w group by w_bign order by w_bign", "agg.dense"),
    ("group-string-keys", "select w_name, w_key, count(*) c from w "
     "group by w_name, w_key order by w_name, w_key", "agg.dense"),
    # GROUP BY with as many slots as rows (each twin of a case above adds
    # w_small's 100 values): keys read from the group sort's sorted
    # operands
    ("group-null-keys-sorted", "select w_key, w_small, count(*) c, "
     "sum(w_val) s from w group by w_key, w_small order by w_key, w_small",
     "agg.sorted_keys"),
    ("group-int64-narrowed-and-not", "select w_small, w_big, count(*) c, "
     "min(w_id) m from w group by w_small, w_big order by w_small, w_big",
     "agg.sorted_keys"),
    ("group-int64-nullable-wide-sorted", "select w_bign, w_small, "
     "count(*) c, max(w_val) m from w group by w_bign, w_small "
     "order by w_bign, w_small", "agg.sorted_keys"),
    ("group-string-keys-sorted", "select w_name, w_key, w_small, count(*) c "
     "from w group by w_name, w_key, w_small "
     "order by w_name, w_key, w_small", "agg.sorted_keys"),
    ("group-filtered", "select w_key, w_big, avg(w_val) a from w "
     "where w_id >= 40 and w_small < 20 group by w_key, w_big "
     "order by w_key, w_big", "agg.sorted_keys"),
    ("group-count-distinct", "select w_key, count(distinct w_small) d "
     "from w where w_id < 250 group by w_key order by w_key",
     "agg.sorted_keys"),
    # DISTINCT and UNION share the helper
    ("distinct-null-and-string", "select distinct w_key, w_name from w "
     "order by w_key, w_name", "agg.sorted_keys"),
    ("distinct-int64-wide", "select distinct w_big from w order by w_big",
     "agg.sorted_keys"),
    ("union", "select w_key k, w_name n from w where w_id < 100 union "
     "select w_key, w_name from w where w_id >= 80 order by k, n",
     "agg.sorted_keys"),
    ("union-int64-wide-nulls", "select w_big b from w union select "
     "w_bign from w order by b", "agg.sorted_keys"),
    # window sort: presence from the sort's first operand
    ("window-rank-filtered", "select w_id, rank() over (partition by "
     "w_key order by w_val desc) r from w where w_id < 200 order by w_id",
     None),
    ("window-count-filtered", "select w_id, count(*) over (partition by "
     "w_name) c, sum(w_val) over (partition by w_name) s from w "
     "where w_small >= 0 order by w_id", None),
]


@pytest.mark.parametrize(
    "label,sql,kernel", SORT_PERM_CASES,
    ids=[c[0] for c in SORT_PERM_CASES])
def test_sort_permutation_sites_match_oracle(label, sql, kernel, wide):
    cpu, dev = wide
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert len(exp), f"{label}: the oracle returns no row to compare"
    assert_frames_equal_in_order(got, exp, label)
    if kernel is not None:
        assert _kernels(dev).get(kernel), (label, _kernels(dev))


def test_statements_without_sort_carry_no_topn_or_sorted_keys(wide):
    """A global aggregate over a filtered scan (the q6 shape) touches
    none of the three sites."""
    _cpu, dev = wide
    dev.sql("select sum(w_val) s, count(*) c from w where w_key < 5")
    kern = _kernels(dev)
    assert "sort.topn" not in kern and "agg.sorted_keys" not in kern


# gather_words of the parent commit (f275791) at SF0.01, counted at the
# same take sites before the three sites changed
PARENT_GATHER_WORDS = {3: 2_883_996, 18: 4_023_380}


@pytest.mark.parametrize("qn,limit", [(3, 10), (18, 100)])
def test_topn_program_has_no_capacity_gather_after_final_sort(
        qn, limit, raw):
    """q3 / q18 (Limit -> Sort -> Project -> Aggregate) lowered on the
    CPU: downstream of the final Sort every gather returns LIMIT's rows,
    never the capacity, and the program's static gather_words fell."""
    import re

    from nds_tpu.engine.device_exec import DeviceExecutor
    schemas = get_schemas()
    tables = {t: from_arrays(t, schemas[t], raw[t]) for t in schemas}
    planned = Session.for_nds_h().plan(streams.render_query(qn))
    ex = DeviceExecutor(tables)
    jitted, side = ex._compile(planned)
    text = jitted.lower(ex._collect_buffers(planned)).as_text()
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }\n") + 1]
    after = main[main.rindex('"stablehlo.sort"'):]
    # (rows of the gathered operand, rows of the result) of every gather
    gathers = [(int(m.group(1)), int(m.group(2))) for m in re.finditer(
        r"(?:call @_take\w*|stablehlo\.gather).*: \(tensor<(\d+)x.*"
        r"-> tensor<(\d+)x", after)]
    assert gathers, "the top-N gathers are gone from the lowered text"
    assert max(src for src, _out in gathers) > 10 * limit   # the capacity
    assert max(out for _src, out in gathers) <= limit, gathers
    kern = side["kernels"]
    assert kern["sort.topn"] == 1 and kern["agg.sorted_keys"] >= 1
    assert kern["gather_words"] < PARENT_GATHER_WORDS[qn]
