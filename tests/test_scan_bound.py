"""The sorted GROUP BY's scan bound (``_Trace._scan_bound``): where every
key is a bare column of a scan whose row count the trace bakes in, the
group capacity ``G`` is that count plus one (an outer join's all-NULL
tuple), not the row count of the relation grouped.

Each case is held against the CPU oracle row for row in the order
returned, and says which ``G`` the trace chose and whether the
statement's ``kernels`` block carries ``agg.scan_bound``. The dense
choice reads the key domains alone and is untouched; a bound that is
wrong fails the statement through the overflow path instead of merging
groups. One case each runs through the sharded trace and the chunked
executor, which take the rule from ``_Trace`` through ``super()``.
"""

import numpy as np
import pytest

from nds_tpu.engine import device_exec as dx
from nds_tpu.engine.device_exec import DeviceExecError, make_device_factory
from nds_tpu.engine.session import Session
from nds_tpu.engine.types import INT32, Schema, decimal, varchar
from nds_tpu.io.host_table import from_arrays
from nds_tpu.sql.planner import CatalogInfo

from tests.test_device_engine import _kernels
from tests.test_device_engine import assert_frames_equal_in_order as in_order

# the item table is past the scan-view threshold, so a filter on it
# leaves a reduced view whose survivors the trace bakes in
NS, NI, NC = 40_000, 20_000, 3_000
assert NI >= dx.DeviceExecutor.REDUCE_MIN_ROWS

SALES = Schema.of(("s_id", INT32, False), ("s_item", INT32, True),
                  ("s_cust", INT32, True), ("s_qty", INT32, False),
                  ("s_amt", decimal(12, 2), False))
ITEM = Schema.of(("i_sk", INT32, False), ("i_id", varchar(16), False),
                 ("i_cat", varchar(8), False), ("i_grp", INT32, False),
                 ("i_mfg", INT32, False), ("i_price", decimal(7, 2), True))
CUST = Schema.of(("c_sk", INT32, False), ("c_name", varchar(16), False),
                 ("c_grp", INT32, False))


def _data():
    rng = np.random.default_rng(35)
    cats = np.array([f"cat{i}" for i in range(6)], dtype=object)
    item = {"i_sk": np.arange(NI, dtype=np.int32),
            "i_id": np.array([f"item{i:05d}" for i in range(NI)],
                             dtype=object),
            "i_cat": cats[rng.integers(0, 6, NI)],
            "i_grp": rng.integers(0, 20, NI).astype(np.int32),
            "i_mfg": rng.integers(0, 1000, NI).astype(np.int32),
            "i_price": rng.integers(0, 99_999, NI).astype(np.int64),
            "i_price#null": rng.random(NI) >= 0.05}
    item_ok = rng.random(NS) >= 0.05
    # every customer is bought by someone, and some sales by no one: a
    # LEFT JOIN grouped by customer columns has NC groups plus the NULLs
    cust_ok = (np.arange(NS) < NC) | (np.arange(NS) % 17 != 16)
    sales = {"s_id": np.arange(NS, dtype=np.int32),
             "s_item": np.where(item_ok, rng.integers(0, NI, NS),
                                0).astype(np.int32),
             "s_item#null": item_ok,
             "s_cust": np.where(cust_ok, np.arange(NS) % NC,
                                0).astype(np.int32),
             "s_cust#null": cust_ok,
             "s_qty": rng.integers(1, 100, NS).astype(np.int32),
             "s_amt": rng.integers(0, 100_000, NS).astype(np.int64)}
    cust = {"c_sk": np.arange(NC, dtype=np.int32),
            "c_name": np.array([f"cust{i:05d}" for i in range(NC)],
                               dtype=object),
            "c_grp": rng.integers(0, 10, NC).astype(np.int32)}
    return {"sales": sales, "item": item, "cust": cust}


SCHEMAS = {"sales": SALES, "item": ITEM, "cust": CUST}


def _sessions(factory=None):
    data = _data()
    cat = CatalogInfo(SCHEMAS,
                      {"sales": ["s_id"], "item": ["i_sk"], "cust": ["c_sk"]},
                      {k: len(next(iter(v.values()))) for k, v in data.items()})
    tables = [from_arrays(k, SCHEMAS[k], v) for k, v in data.items()]

    def build(f=None):
        s = Session(cat, f)
        for t in tables:
            s.register_table(t)
        return s

    return build(), build(factory or make_device_factory())


@pytest.fixture(scope="module")
def both():
    return _sessions()


def _survivors(pred) -> int:
    return int(pred(_data()["item"]).sum())


def _record(monkeypatch):
    """Every sorted GROUP BY's (relation capacity, scan bound) and every
    aggregate's output capacity, by the trace class that made it."""
    calls = {"bound": [], "G": []}
    bound, agg = dx._Trace._scan_bound, dx._Trace._run_aggregate

    def spy_bound(n, group_keys, keyvals):
        got = bound(n, group_keys, keyvals)
        calls["bound"].append((n, got))
        return got

    def spy_agg(self, node):
        out = agg(self, node)
        if node.group_keys:
            calls["G"].append((type(self).__name__, out.n))
        return out

    monkeypatch.setattr(dx._Trace, "_scan_bound", staticmethod(spy_bound))
    monkeypatch.setattr(dx._Trace, "_run_aggregate", spy_agg)
    return calls


SCAN_BOUND, DENSE, SORTED = "agg.scan_bound", "agg.dense", "agg.sorted_keys"

# (label, sql, the G the sorted form has to take, or None where the
# domains already bound it below the scan: no agg.scan_bound)
CASES = [
    # a reduced view's survivors under a dictionary x decimal domain
    # that passes the row count
    ("view", "select i_id, i_price, sum(s_qty) q, sum(s_amt) a "
     "from sales, item where s_item = i_sk and i_cat = 'cat2' "
     "group by i_id, i_price order by i_id, i_price",
     _survivors(lambda t: t["i_cat"] == "cat2") + 1),
    # an unfiltered dimension: its rows bound the keys whose domain
    # product (NC x 10) sits under the fact's rows
    ("table", "select c_name, c_grp, count(*) n from sales, cust "
     "where s_cust = c_sk group by c_name, c_grp order by c_name",
     NC + 1),
    # LEFT JOIN: every customer matched, plus the null-extended tuple:
    # exactly G groups, so the + 1 is needed
    ("left-join-nulls", "select c_name, c_grp, count(*) n, sum(s_qty) q "
     "from sales left join cust on s_cust = c_sk "
     "group by c_name, c_grp order by c_name nulls first, c_grp",
     NC + 1),
    # two origins, each bounded by its keys' domains (6 x 20 and 10)
    # before its rows: the bound is the domain product, nothing to note
    ("two-origins", "select i_cat, c_grp, i_grp, count(*) n "
     "from sales, item, cust where s_item = i_sk and s_cust = c_sk "
     "and i_mfg < 40 group by i_cat, c_grp, i_grp "
     "order by i_cat, c_grp, i_grp", None),
    # the key is an expression, not a bare column: no bound from it
    ("expression-key", "select c_grp + 0 g, c_name, count(*) n "
     "from sales, cust where s_cust = c_sk group by c_grp + 0, c_name "
     "order by c_name", None),
]


@pytest.mark.parametrize("label,sql,G", CASES, ids=[c[0] for c in CASES])
def test_sorted_group_by_takes_the_scan_bound(label, sql, G, both,
                                              monkeypatch):
    cpu, dev = both
    calls = _record(monkeypatch)
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert len(exp), f"{label}: the oracle returns no row to compare"
    in_order(got, exp, label, float_rtol=1e-12)
    kern = _kernels(dev)
    (n, bound), = calls["bound"]
    (_cls, out_n), = calls["G"]
    if G is None:
        assert SCAN_BOUND not in kern, (label, kern)
        assert out_n < n or bound >= n
    else:
        assert kern.get(SCAN_BOUND) == 1 and kern.get(SORTED), (label, kern)
        assert bound == out_n == G < n, (label, calls)
        assert len(exp) <= G
    if label == "left-join-nulls":
        assert len(exp) == G and exp["c_name"].isna().sum() == 1


def test_self_join_of_a_cte_bounds_by_the_product(both, monkeypatch):
    """A CTE read twice is two origins: a self-join grouped by a key of
    each side has more groups than one side has rows, and its bound is
    the product of the two sides (here past the relation's capacity)."""
    cpu, dev = both
    calls = _record(monkeypatch)
    sql = ("with t as (select i_sk, i_id, i_grp from item where i_mfg < 2) "
           "select a.i_id x, b.i_id y, count(*) n from t a, t b "
           "where a.i_grp = b.i_grp group by a.i_id, b.i_id order by x, y")
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    in_order(got, exp, "cte self-join")
    rows = _survivors(lambda t: t["i_mfg"] < 2)
    assert len(exp) > rows + 1
    for n, bound in calls["bound"]:
        assert bound == n
    assert SCAN_BOUND not in _kernels(dev)


DENSE_CASES = [
    # the domain (6 categories) is under the scan bound
    ("domain-under-bound", "select i_cat, count(*) n, sum(s_qty) q "
     "from sales, item where s_item = i_sk and i_mfg < 500 "
     "group by i_cat order by i_cat"),
    # the scan bound (a few dozen items) is under the domain (6 x 20),
    # which is at or under DENSE_AGG_MAX_GROUPS: dense all the same
    ("bound-under-domain", "select i_cat, i_grp, count(*) n "
     "from sales, item where s_item = i_sk and i_mfg = 7 "
     "group by i_cat, i_grp order by i_cat, i_grp"),
]


@pytest.mark.parametrize("label,sql", DENSE_CASES,
                         ids=[c[0] for c in DENSE_CASES])
def test_dense_choice_reads_the_domains_alone(label, sql, both,
                                              monkeypatch):
    cpu, dev = both
    calls = _record(monkeypatch)
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert len(exp)
    in_order(got, exp, label)
    kern = _kernels(dev)
    assert kern.get(DENSE) == 1, (label, kern)
    assert not kern.get(SORTED) and SCAN_BOUND not in kern, (label, kern)
    assert not calls["bound"]


def _sharded():
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    return _sessions(make_distributed_factory(n_devices=4,
                                              shard_threshold=1000))


@pytest.mark.parametrize("label", ["view", "table"])
def test_scan_bound_through_the_sharded_trace(label, monkeypatch):
    """``_DistTrace._run_aggregate`` exchanges by the key and calls
    ``super()``. A sharded scan stamps the table's GLOBAL row count (a
    chip's groups after the exchange come from every shard); the item
    filter is a reduced replicated view, query98's shape."""
    label, sql, G = next(c for c in CASES if c[0] == label)
    cpu, dist = _sharded()
    calls = _record(monkeypatch)
    exp = cpu.sql(sql).to_pandas()
    got = dist.sql(sql).to_pandas()
    in_order(got, exp, f"sharded {label}", float_rtol=1e-12)
    ex = dist._executor_factory(dist.tables)
    assert ex._is_sharded("sales") and ex._is_sharded("cust")
    kern = ex.last_timings.get("__kernels") or {}
    assert kern.get(SCAN_BOUND) == 1, kern
    assert calls["G"] == [("_DistTrace", G)], calls


# over the item and customer tables' bytes, under the sales table's
STREAM_SALES_ONLY = 800_000


def test_scan_bound_through_the_chunked_partials(monkeypatch):
    """The chunked executor's per-chunk partial Aggregate over the
    chunked fact takes the bound from the dimension's scan; the merge
    over the partials table is bounded by the partials' rows."""
    from nds_tpu.engine.chunked_exec import (_PartialAggExecutor,
                                             make_chunked_factory)
    cpu, chunked = _sessions(make_chunked_factory(
        stream_bytes=STREAM_SALES_ONLY, chunk_rows=1 << 13))
    calls = _record(monkeypatch)
    sql = ("select c_name, c_grp, count(*) n, sum(s_amt) a from sales, cust "
           "where s_cust = c_sk group by c_name, c_grp order by c_name")
    exp = cpu.sql(sql).to_pandas()
    got = chunked.sql(sql).to_pandas()
    in_order(got, exp, "chunked")
    ex = chunked._executor_factory(chunked.tables)
    assert any(isinstance(s, _PartialAggExecutor)
               for s in ex._reduced.values())
    partial = [g for cls, g in calls["G"] if cls == "_Trace"]
    merged = [g for cls, g in calls["G"] if cls == "_MergeTrace"]
    assert partial and set(partial) == {NC + 1}, calls
    assert merged and merged[0] >= len(exp), calls


# (executor, the planted bound from the true group count): a chip of
# the sharded trace holds about a quarter of the groups, so there the
# bound is one that any chip with two groups passes
PLANTED = {"device": lambda groups: groups - 1, "sharded": lambda groups: 1}


@pytest.mark.parametrize("where", sorted(PLANTED))
def test_a_bound_under_the_groups_fails_loudly(where, monkeypatch):
    """Planted fault: a bound under the groups there are. The
    program's overflow count says so, the executor's retries do not cure
    it, and the statement raises: it never returns merged groups."""
    _label, sql, _G = next(c for c in CASES if c[0] == "table")
    cpu, dev = _sharded() if where == "sharded" else _sessions()
    bound = PLANTED[where](len(cpu.sql(sql).to_pandas()))
    monkeypatch.setattr(dx._Trace, "_scan_bound", staticmethod(
        lambda n, group_keys, keyvals: bound))
    with pytest.raises(DeviceExecError, match="overflow persisted"):
        dev.sql(sql).to_pandas()
