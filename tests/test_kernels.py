"""Parity tests for the tensorized relational kernels
(nds_tpu/engine/kernels.py).

Two tiers, mirroring the repo's differential contract:

- SQL tier: purpose-built tables whose catalog stats make the planner
  pick each kernel (direct / matmul / partitioned / bitmask / minmax /
  segscan), every query cross-checked against the CPU oracle over all
  join kinds (inner/left/full/semi/anti), null join keys, duplicate
  keys, and empty (all-rows-filtered) inputs. Each test also asserts
  the intended kernel actually ENGAGED via the executor's trace-time
  kernel counts — a silently demoted kernel would otherwise pass
  parity while benchmarking the wrong code.
- Unit tier: each kernel function against a numpy brute-force oracle,
  including the overflow accounting of the partitioned join, plus one
  fixed-seed fuzz case per kernel.
"""

import numpy as np
import pandas as pd
import pytest

from nds_tpu.engine import kernels as KX
from nds_tpu.engine.device_exec import make_device_factory
from nds_tpu.engine.session import Session
from nds_tpu.engine.types import INT32, INT64, Schema, varchar
from nds_tpu.io.host_table import from_arrays
from nds_tpu.sql.planner import CatalogInfo

from tests.test_device_engine import assert_frames_close

NF = 400     # fact rows
ND = 120     # dim rows (> MATMUL_MAX_BUILD -> direct)
NT = 8       # tiny dim rows (<= MATMUL_MAX_BUILD -> matmul)


def _catalog():
    fact = Schema.of(
        ("f_id", INT32, False), ("f_dim", INT32, True),
        ("f_tiny", INT32, False), ("f_key", INT32, False),
        ("f_val", INT32, True), ("f_qty", INT32, False))
    fact2 = Schema.of(
        ("g_key", INT32, False), ("g_val", INT32, True),
        ("g_qty", INT32, False))
    dim = Schema.of(("d_id", INT32, False),
                    ("d_name", varchar(10), False))
    tiny = Schema.of(("t_id", INT32, False),
                     ("t_name", varchar(10), False))
    return CatalogInfo(
        {"fact": fact, "fact2": fact2, "dim": dim, "tiny": tiny},
        {"dim": ["d_id"], "tiny": ["t_id"], "fact": ["f_id"]},
        {"fact": NF, "fact2": NF, "dim": ND, "tiny": NT})


def _data():
    rng = np.random.default_rng(20260803)
    dim_valid = rng.random(NF) >= 0.1      # ~10% NULL join keys
    names = np.array(["alpha", "beta", "gamma", "delta"], dtype=object)
    fact = {
        "f_id": np.arange(NF, dtype=np.int32),
        # duplicate keys by construction; some keys miss the dim
        # domain entirely (d_id stops at ND-1, f_dim reaches ND+4)
        "f_dim": rng.integers(0, ND + 5, NF).astype(np.int32),
        "f_dim#null": dim_valid,
        "f_tiny": rng.integers(0, NT, NF).astype(np.int32),
        "f_key": rng.integers(0, NF // 4, NF).astype(np.int32),
        "f_val": rng.integers(0, 10, NF).astype(np.int32),
        "f_val#null": rng.random(NF) >= 0.1,
        "f_qty": rng.integers(1, 100, NF).astype(np.int32),
    }
    fact2 = {
        "g_key": rng.integers(0, NF // 4, NF).astype(np.int32),
        "g_val": rng.integers(0, 10, NF).astype(np.int32),
        "g_val#null": rng.random(NF) >= 0.1,
        "g_qty": rng.integers(1, 100, NF).astype(np.int32),
    }
    dim = {
        "d_id": np.arange(ND, dtype=np.int32),
        "d_name": names[rng.integers(0, 4, ND)],
    }
    tiny = {
        "t_id": np.arange(NT, dtype=np.int32),
        "t_name": names[rng.integers(0, 4, NT)],
    }
    return {"fact": fact, "fact2": fact2, "dim": dim, "tiny": tiny}


def _build_sessions():
    cat = _catalog()
    data = _data()

    def build(factory=None):
        s = Session(cat, factory)
        for t in cat.schemas:
            s.register_table(from_arrays(t, cat.schemas[t], data[t]))
        return s

    return build(), build(make_device_factory())


@pytest.fixture(scope="module")
def sessions():
    return _build_sessions()


def both(sessions, sql, want_kernel=None):
    """CPU-oracle vs device differential + kernel-engagement check."""
    cpu, dev = sessions
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert_frames_close(got, exp, sql[:48])
    if want_kernel is not None:
        ex = dev._executor_factory(dev.tables)
        kern = ex.last_timings.get("__kernels") or {}
        assert kern.get(want_kernel), (
            f"expected kernel {want_kernel!r} to engage, trace counted "
            f"{kern!r} for {sql[:60]!r}")
    return exp


# ------------------------------------------------------- SQL tier: joins

def test_inner_join_direct(sessions):
    both(sessions,
         "select f_id, d_name from fact join dim on f_dim = d_id "
         "order by f_id",
         want_kernel="join.direct")


def test_left_join_direct_keeps_unmatched(sessions):
    # rows with NULL f_dim or f_dim >= ND survive with NULL d_name
    exp = both(sessions,
               "select f_id, d_name from fact left join dim "
               "on f_dim = d_id order by f_id",
               want_kernel="join.direct")
    assert exp["d_name"].isna().any()


def test_inner_join_matmul_tiny_build(sessions):
    both(sessions,
         "select f_id, t_name from fact join tiny on f_tiny = t_id "
         "order by f_id",
         want_kernel="join.matmul")


def test_full_outer_join(sessions):
    # FULL OUTER needs unique keys both sides: join grouped CTEs
    both(sessions,
         "with a as (select f_dim k, count(*) ca from fact group by "
         "f_dim), b as (select d_id k, count(*) cb from dim group by "
         "d_id) select a.k ak, b.k bk, ca, cb from a full outer join "
         "b on a.k = b.k order by ak, bk")


def test_semi_join_bitmask(sessions):
    both(sessions,
         "select f_id from fact where exists (select 1 from dim "
         "where d_id = f_dim) order by f_id",
         want_kernel="semi.bitmask")


def test_anti_join_bitmask(sessions):
    both(sessions,
         "select f_id from fact where not exists (select 1 from dim "
         "where d_id = f_dim) order by f_id",
         want_kernel="semi.bitmask")


def test_exists_residual_minmax(sessions):
    # the q21 shape: exists a row with the same key and a DIFFERENT
    # value -> dense per-key min/max tables
    both(sessions,
         "select f_id from fact where exists (select 1 from fact2 "
         "where g_key = f_key and g_val <> f_val) order by f_id",
         want_kernel="semi.minmax")


def test_not_exists_residual_minmax(sessions):
    both(sessions,
         "select f_id from fact where not exists (select 1 from fact2 "
         "where g_key = f_key and g_val <> f_val) order by f_id",
         want_kernel="semi.minmax")


def test_mn_join_partitioned(monkeypatch):
    # the radix-partitioned path only engages for large estimates:
    # shrink the threshold and plan fresh sessions so annotate() sees it
    monkeypatch.setattr(KX, "PARTITION_MIN_ROWS", 64)
    cpu, dev = _build_sessions()
    sql = ("select f_id, g_qty from fact join fact2 on f_key = g_key "
           "order by f_id, g_qty")
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert_frames_close(got, exp, "mn-partitioned")
    ex = dev._executor_factory(dev.tables)
    kern = ex.last_timings.get("__kernels") or {}
    assert kern.get("join.partitioned"), kern


def test_empty_probe_side(sessions):
    # all probe rows filtered out: every kernel must survive a fully
    # masked input (static shapes keep the capacity, validity is 0)
    for sql in (
            "select f_id, d_name from fact join dim on f_dim = d_id "
            "where f_id < 0",
            "select f_id from fact where f_id < 0 and exists "
            "(select 1 from dim where d_id = f_dim)"):
        cpu, dev = sessions
        exp = cpu.sql(sql).to_pandas()
        got = dev.sql(sql).to_pandas()
        assert len(got) == 0 and len(exp) == 0


def test_empty_build_side(sessions):
    both(sessions,
         "with d as (select d_id from dim where d_id < 0) "
         "select f_id from fact where exists (select 1 from d "
         "where d_id = f_dim) order by f_id")


# ------------------------------------------- SQL tier: aggregation/window

def test_grouped_minmax_segscan(sessions):
    # f_key x f_tiny: 800 slots for 400 rows, so the group sort
    both(sessions,
         "select f_key, f_tiny, min(f_val) mn, max(f_val) mx, "
         "sum(f_qty) s, count(*) c from fact group by f_key, f_tiny "
         "order by f_key, f_tiny",
         want_kernel="agg.segscan")


def test_grouped_minmax_null_groups(sessions):
    # NULL group key forms its own group; NULL values are skipped
    both(sessions,
         "select f_dim, f_tiny, min(f_val) mn, max(f_val) mx from fact "
         "group by f_dim, f_tiny order by f_dim, f_tiny",
         want_kernel="agg.segscan")


def test_grouped_minmax_dense(sessions):
    # 100 slots (f_key alone): masked reductions, no sort, no scan
    both(sessions,
         "select f_key, min(f_val) mn, max(f_val) mx, sum(f_qty) s, "
         "count(*) c from fact group by f_key order by f_key",
         want_kernel="agg.dense")


def test_grouped_minmax_null_groups_dense(sessions):
    both(sessions,
         "select f_dim, min(f_val) mn, max(f_val) mx from fact "
         "group by f_dim order by f_dim",
         want_kernel="agg.dense")


def test_window_partition_minmax(sessions):
    both(sessions,
         "select f_id, min(f_qty) over (partition by f_key) pmn, "
         "max(f_qty) over (partition by f_key) pmx from fact "
         "order by f_id")


def test_kernels_env_kill_switch(monkeypatch):
    # NDS_TPU_KERNELS=0 plans everything unannotated: the legacy sort
    # paths serve the same rows
    monkeypatch.setenv("NDS_TPU_KERNELS", "0")
    cpu, dev = _build_sessions()
    sql = ("select f_id, d_name from fact join dim on f_dim = d_id "
           "order by f_id")
    exp = cpu.sql(sql).to_pandas()
    got = dev.sql(sql).to_pandas()
    assert_frames_close(got, exp, "kill-switch")
    ex = dev._executor_factory(dev.tables)
    kern = ex.last_timings.get("__kernels") or {}
    assert not kern.get("join.direct"), kern
    assert kern.get("join.sortmerge") or kern.get("join.presorted"), kern


# ------------------------------------------------- unit tier: primitives

def _jnp():
    import jax.numpy as jnp
    return jnp


def test_direct_lookup_join_unit():
    jnp = _jnp()
    rng = np.random.default_rng(7)
    dom = 32
    bkey = np.array([3, 9, 11, 4, 0, 31], dtype=np.int32)
    bok = np.array([True, True, False, True, True, True])
    pkey = rng.integers(-2, dom + 2, 64).astype(np.int32)
    pok = rng.random(64) >= 0.2
    ridx, hit = KX.direct_lookup_join(
        jnp.asarray(bkey), jnp.asarray(bok),
        jnp.asarray(pkey), jnp.asarray(pok), 0, dom)
    ridx, hit = np.asarray(ridx), np.asarray(hit)
    valid = {int(k): i for i, k in enumerate(bkey) if bok[i]}
    for j in range(64):
        exp_hit = bool(pok[j]) and int(pkey[j]) in valid
        assert bool(hit[j]) == exp_hit, j
        if exp_hit:
            assert int(ridx[j]) == valid[int(pkey[j])]
        assert 0 <= int(ridx[j]) < len(bkey)  # clamped even on miss


def test_matmul_probe_join_unit():
    jnp = _jnp()
    rng = np.random.default_rng(8)
    bkey = np.array([5, 2, 19, 7], dtype=np.int32)
    bok = np.array([True, False, True, True])
    pkey = rng.integers(0, 24, 50).astype(np.int32)
    pok = rng.random(50) >= 0.1
    ridx, hit = KX.matmul_probe_join(
        jnp.asarray(bkey), jnp.asarray(bok),
        jnp.asarray(pkey), jnp.asarray(pok))
    ridx, hit = np.asarray(ridx), np.asarray(hit)
    valid = {int(k): i for i, k in enumerate(bkey) if bok[i]}
    for j in range(50):
        exp_hit = bool(pok[j]) and int(pkey[j]) in valid
        assert bool(hit[j]) == exp_hit, j
        if exp_hit:
            assert int(ridx[j]) == valid[int(pkey[j])]


def test_bitmask_semi_unit():
    jnp = _jnp()
    rng = np.random.default_rng(9)
    dom = 40
    bkey = rng.integers(0, dom, 30).astype(np.int32)
    bok = rng.random(30) >= 0.3
    pkey = rng.integers(-3, dom + 3, 80).astype(np.int32)
    pok = rng.random(80) >= 0.2
    member = np.asarray(KX.bitmask_semi(
        jnp.asarray(bkey), jnp.asarray(bok),
        jnp.asarray(pkey), jnp.asarray(pok), 0, dom))
    present = set(int(k) for i, k in enumerate(bkey) if bok[i])
    for j in range(80):
        assert bool(member[j]) == (bool(pok[j])
                                   and int(pkey[j]) in present), j


def test_keyed_minmax_semi_unit():
    jnp = _jnp()
    rng = np.random.default_rng(10)
    dom = 16
    bkey = rng.integers(0, dom, 60).astype(np.int32)
    bok = rng.random(60) >= 0.2
    bval = rng.integers(0, 4, 60).astype(np.int32)
    pkey = rng.integers(0, dom, 60).astype(np.int32)
    pok = rng.random(60) >= 0.2
    pval = rng.integers(0, 4, 60).astype(np.int32)
    got = np.asarray(KX.keyed_minmax_semi(
        jnp.asarray(bkey), jnp.asarray(bok), jnp.asarray(bval),
        jnp.asarray(pkey), jnp.asarray(pok), jnp.asarray(pval),
        0, dom))
    for j in range(60):
        exp = bool(pok[j]) and any(
            bok[i] and int(bkey[i]) == int(pkey[j])
            and int(bval[i]) != int(pval[j]) for i in range(60))
        assert bool(got[j]) == exp, j


def _pairs(lidx, ridx, present, lkey, rkey):
    li, ri = np.asarray(lidx)[np.asarray(present)], \
        np.asarray(ridx)[np.asarray(present)]
    assert (np.asarray(lkey)[li] == np.asarray(rkey)[ri]).all()
    return sorted(zip(li.tolist(), ri.tolist()))


def test_partitioned_mn_join_unit():
    jnp = _jnp()
    rng = np.random.default_rng(11)
    n = 200
    lkey = rng.integers(0, 40, n).astype(np.int32)
    rkey = rng.integers(0, 40, n).astype(np.int32)
    lok = rng.random(n) >= 0.1
    rok = rng.random(n) >= 0.1
    exp = sorted(
        (i, j) for i in range(n) for j in range(n)
        if lok[i] and rok[j] and lkey[i] == rkey[j])
    K = 4 * len(exp) + 16
    lidx, ridx, present, over = KX.partitioned_mn_join(
        jnp.asarray(lkey), jnp.asarray(lok),
        jnp.asarray(rkey), jnp.asarray(rok), K, 2.0)
    assert int(over) == 0
    assert _pairs(lidx, ridx, present, lkey, rkey) == exp


def test_partitioned_mn_join_overflow_counted():
    jnp = _jnp()
    n = 64
    lkey = np.zeros(n, dtype=np.int32)   # one key, n*n pairs
    rkey = np.zeros(n, dtype=np.int32)
    ok = np.ones(n, dtype=bool)
    K = 16  # far below n*n
    _l, _r, present, over = KX.partitioned_mn_join(
        jnp.asarray(lkey), jnp.asarray(ok),
        jnp.asarray(rkey), jnp.asarray(ok), K, 2.0)
    # capacity misses must be COUNTED, not silently dropped (the
    # executor's doubled-slack retry keys off this)
    assert int(over) > 0
    assert int(np.asarray(present).sum()) <= K


def test_partitioned_mn_join_empty_sides():
    jnp = _jnp()
    n = 32
    key = np.arange(n, dtype=np.int32)
    none = np.zeros(n, dtype=bool)
    ok = np.ones(n, dtype=bool)
    _l, _r, present, over = KX.partitioned_mn_join(
        jnp.asarray(key), jnp.asarray(none),
        jnp.asarray(key), jnp.asarray(ok), 64, 2.0)
    assert int(over) == 0
    assert int(np.asarray(present).sum()) == 0


def test_seg_reduce_at_ends_unit():
    jnp = _jnp()
    rng = np.random.default_rng(12)
    n, G = 100, 12
    gid = np.sort(rng.integers(0, G, n)).astype(np.int32)
    data = rng.integers(0, 1000, n).astype(np.int32)
    starts = np.searchsorted(gid, np.arange(G)).astype(np.int32)
    got = np.asarray(KX.seg_reduce_at_ends(
        jnp.minimum, jnp.asarray(data), jnp.asarray(gid),
        jnp.asarray(starts)))
    for g in range(G):
        rows = data[gid == g]
        if len(rows):
            assert got[g] == rows.min(), g


def test_last_of_group_unit():
    jnp = _jnp()
    change = np.array([True, False, False, True, True, False])
    got = np.asarray(KX.last_of_group(jnp.asarray(change), 6))
    np.testing.assert_array_equal(got, [2, 2, 2, 3, 5, 5])


def test_domain_and_feasibility_rules():
    assert KX.domain_of(0, 99) == 100
    assert KX.domain_of(None, 5) is None
    assert KX.domain_of(5, 4) is None                  # empty range
    assert KX.domain_of(0, KX.DIRECT_MAX_DOMAIN) is None  # too wide
    assert KX.direct_feasible(100, 10)                 # 100 <= 10*16
    assert not KX.direct_feasible(1000, 10)
    assert not KX.direct_feasible(None, 10)


def test_select_join_kernel_rules():
    assert KX.select_join_kernel(1e6, 10, True, "inner") == KX.JOIN_MATMUL
    assert KX.select_join_kernel(1e6, 1e4, True, "inner") == KX.JOIN_DIRECT
    assert KX.select_join_kernel(1e6, 1e6, False, "inner") \
        == KX.JOIN_PARTITIONED
    assert KX.select_join_kernel(100, 100, False, "inner") == KX.JOIN_SORT
    assert KX.select_join_kernel(1e6, 1e6, False, "left") == KX.JOIN_SORT


# --------------------------------------------------- unit tier: fuzzing

@pytest.mark.parametrize("seed", [101, 202, 303])
def test_fuzz_direct_vs_sortmerge_semantics(seed):
    """Fixed-seed fuzz: direct lookup == brute-force dict join on
    random domains, null patterns, and duplicate probe keys."""
    jnp = _jnp()
    rng = np.random.default_rng(seed)
    dom = int(rng.integers(4, 200))
    nb = int(rng.integers(1, dom + 1))
    n = int(rng.integers(1, 500))
    bkey = rng.permutation(dom)[:nb].astype(np.int32)
    bok = rng.random(nb) >= 0.2
    pkey = rng.integers(-2, dom + 2, n).astype(np.int32)
    pok = rng.random(n) >= 0.2
    ridx, hit = KX.direct_lookup_join(
        jnp.asarray(bkey), jnp.asarray(bok),
        jnp.asarray(pkey), jnp.asarray(pok), 0, dom)
    valid = {int(k): i for i, k in enumerate(bkey) if bok[i]}
    hit = np.asarray(hit)
    ridx = np.asarray(ridx)
    for j in range(n):
        exp = bool(pok[j]) and int(pkey[j]) in valid
        assert bool(hit[j]) == exp
        if exp:
            assert int(ridx[j]) == valid[int(pkey[j])]


@pytest.mark.parametrize("seed", [404, 505])
def test_fuzz_partitioned_pairs(seed):
    jnp = _jnp()
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 150))
    nk = int(rng.integers(2, 30))
    lkey = rng.integers(0, nk, n).astype(np.int32)
    rkey = rng.integers(0, nk, n).astype(np.int32)
    lok = rng.random(n) >= 0.15
    rok = rng.random(n) >= 0.15
    exp = sorted(
        (i, j) for i in range(n) for j in range(n)
        if lok[i] and rok[j] and lkey[i] == rkey[j])
    K = 4 * max(len(exp), 1) + 32
    lidx, ridx, present, over = KX.partitioned_mn_join(
        jnp.asarray(lkey), jnp.asarray(lok),
        jnp.asarray(rkey), jnp.asarray(rok), K, 3.0)
    assert int(over) == 0
    assert _pairs(lidx, ridx, present, lkey, rkey) == exp


@pytest.mark.parametrize("seed", [606, 707])
def test_fuzz_sql_join_agg(seed):
    """Fixed-seed SQL fuzz across the kernel set: random tables,
    CPU-oracle differential on a join+agg+semi query battery."""
    rng = np.random.default_rng(seed)
    nf, nd = int(rng.integers(50, 300)), int(rng.integers(3, 60))
    fact = Schema.of(("a_id", INT32, False), ("a_k", INT32, True),
                     ("a_v", INT32, False))
    dim = Schema.of(("b_k", INT32, False), ("b_w", INT32, False))
    cat = CatalogInfo({"a": fact, "b": dim}, {"b": ["b_k"]},
                      {"a": nf, "b": nd})
    a = {"a_id": np.arange(nf, dtype=np.int32),
         "a_k": rng.integers(0, nd + 2, nf).astype(np.int32),
         "a_k#null": rng.random(nf) >= 0.15,
         "a_v": rng.integers(0, 1000, nf).astype(np.int32)}
    b = {"b_k": np.arange(nd, dtype=np.int32),
         "b_w": rng.integers(0, 100, nd).astype(np.int32)}

    def build(factory=None):
        s = Session(cat, factory)
        s.register_table(from_arrays("a", fact, a))
        s.register_table(from_arrays("b", dim, b))
        return s

    cpu, dev = build(), build(make_device_factory())
    for sql in (
            "select a_id, b_w from a join b on a_k = b_k order by a_id",
            "select a_id, b_w from a left join b on a_k = b_k "
            "order by a_id",
            "select a_k, min(a_v) mn, max(a_v) mx, count(*) c from a "
            "group by a_k order by a_k",
            "select a_id from a where exists (select 1 from b where "
            "b_k = a_k) order by a_id",
            "select a_id from a where not exists (select 1 from b "
            "where b_k = a_k) order by a_id"):
        exp = cpu.sql(sql).to_pandas()
        got = dev.sql(sql).to_pandas()
        assert_frames_close(got, exp, f"fuzz{seed}:{sql[:40]}")
