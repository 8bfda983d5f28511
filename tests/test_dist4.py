"""The four-chip NDS-H deployment (benchmark cell ``nds_h_sf1.dist4``)
on the virtual CPU mesh, at SF0.01: the shipped distributed template
through ``make_session``, the cell's own four statements held against
the benchmark's plain references by the benchmark's own comparison, a
planted exchange fault, the sharded statement's span tree, PR 26's
top-N path under sharding, and PR 28's rule that a sharded relation's
capacity follows the rows it can hold (``slack x rows``) through any
number of exchanges, a wide group key hashed instead of replicated,
and PR 30's send buffer, read into place by one gather a column.
"""

import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TEMPLATE = os.path.join(ROOT, "configs", "power_run_distributed.template")
SF = 0.01
MIX = "power_dist4"
LIMITS = {"failed_statements": 0, "rows_wrong": 0, "repeats_differ": 0,
          "max_rel_gap": 1e-9}
CONFIG = {"suite": "nds_h", "limits": LIMITS}


def _session(raw, shards=None, cache_dir=None):
    """A session as ``benchmarks/run.py`` makes it: the distributed
    template as shipped (``shards``: the ``engine.mesh.shards``
    override, as ``NDS_TPU_SHARDS`` would set it)."""
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    overrides = {}
    if shards:
        overrides["engine.mesh.shards"] = str(shards)
    if cache_dir:
        overrides["cache.dir"] = str(cache_dir)
    econf = EngineConfig(TEMPLATE, None, overrides)
    session = power_core.make_session(SUITE, econf)
    power_core.load_warehouse(
        SUITE, session, raw, "raw",
        schemas=power_core.suite_schemas(SUITE, econf))
    return session


@pytest.fixture(autouse=True)
def _no_plan_cache_left_behind():
    """A session made with ``cache.dir`` configures the PROCESS's plan
    cache; the next session (the planted fault's, another file's) must
    not load from it."""
    from nds_tpu import cache as plan_cache
    yield
    plan_cache.reset()


def _statements():
    from benchmarks import generator
    mix = generator.load_mix(MIX)
    return generator.distinct(mix, generator.variants(mix, 7))


def _verdict(records, raw):
    from benchmarks import run
    return run.check_rows({"records": records}, CONFIG, raw)


def _sharded_executor(session):
    return session._executor_factory(session.tables)._executor("sharded")


def _root_of(span):
    while span.parent is not None:
        span = span.parent
    return span


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    from nds_tpu.nds_h import gen_data
    out = tmp_path_factory.mktemp("dist4") / "raw"
    gen_data.generate_data_local(SF, 2, str(out), workers=2)
    return str(out)


@pytest.fixture(scope="module")
def dist4(raw, tmp_path_factory):
    """The four statements run twice on a 4-device mesh, span trees
    kept (the Chrome export is on), with a plan cache a second executor
    can load from."""
    from benchmarks import run
    cache = tmp_path_factory.mktemp("dist4_plans")
    session = _session(raw, shards=4, cache_dir=cache)
    pipe = session._executor_factory(session.tables)
    out = {"session": session, "cache": str(cache), "records": {},
           "first": {}, "warm": {}, "lowered": []}
    from nds_tpu.cache import aot
    compile_ = aot.lower_and_compile

    def keep_text(jitted, *args, **kw):
        out["lowered"].append(jitted.lower(*args).as_text(debug_info=True))
        return compile_(jitted, *args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE",
                  str(tmp_path_factory.mktemp("dist4_trace") / "t.jsonl"))
        mp.setattr(aot, "lower_and_compile", keep_text)
        for stmt in _statements():
            for which in ("first", "warm"):
                rec = run.run_statement(session, stmt)
                assert rec["error"] is None, rec["error"]
                out[which][stmt.name] = _root_of(pipe.last_query_span)
                out["records"].setdefault(stmt.name, []).append(rec)
    from nds_tpu import cache as plan_cache
    plan_cache.reset()          # module-scoped: set up before the autouse
    return out


def test_template_as_shipped_spans_every_visible_device(monkeypatch):
    import jax
    from nds_tpu.engine import scheduler
    from nds_tpu.utils.config import EngineConfig
    monkeypatch.delenv("NDS_TPU_SHARDS", raising=False)
    pipe = scheduler.make_pipeline(EngineConfig(TEMPLATE))
    assert pipe.backend == "distributed"
    assert pipe.mesh.devices.size == len(jax.devices()) == 8
    # the variable stays the override it was
    monkeypatch.setenv("NDS_TPU_SHARDS", "4")
    assert scheduler.make_pipeline(
        EngineConfig(TEMPLATE)).mesh.devices.size == 4


@pytest.mark.parametrize("name", ["q1", "q3", "q5", "q18"])
def test_statement_matches_the_plain_reference(dist4, raw, name):
    """``correct`` as the cell decides it: the first execution in full
    against the pandas reference, ORDER BY held, the repeat against the
    first, placed ``sharded`` and never rescheduled."""
    records = dist4["records"][name]
    for rec in records:
        assert rec["placement"] == "sharded" and rec["reschedules"] == 0
        assert rec["ladder"] == ["sharded"]
    verdict = _verdict(records, raw)
    assert verdict["correct"] is True, verdict["notes"]
    assert verdict["checks"]["rows_wrong"]["value"] == 0
    assert verdict["checks"]["repeats_differ"]["value"] == 0
    assert verdict["per_stmt"][f"{name}#0"]["rows"] > 0


def test_rows_dropped_after_the_exchange_read_rows_wrong(raw, monkeypatch):
    """Planted fault: what the exchange delivers to chip 0 is lost, as
    an overflow that nobody retried would lose it. The answer differs,
    so the comparison reads ``rows_wrong``."""
    from jax import lax
    from benchmarks import run
    from nds_tpu.parallel import dist_exec
    from nds_tpu.parallel.mesh import DATA_AXIS
    inner = dist_exec.exchange

    def lossy(arrays, key, ok, n_dev, slack=2.0, axis=DATA_AXIS, **kw):
        outs, out_ok, n_over = inner(arrays, key, ok, n_dev, slack, axis,
                                     **kw)
        return outs, out_ok & (lax.axis_index(axis) != 0), n_over

    monkeypatch.setattr(dist_exec, "exchange", lossy)
    session = _session(raw, shards=4)
    q3 = next(s for s in _statements() if s.name == "q3")
    verdict = _verdict([run.run_statement(session, q3)], raw)
    assert verdict["correct"] is False
    assert verdict["checks"]["failed_statements"]["value"] == 0
    assert verdict["checks"]["rows_wrong"]["value"] == 1


def _names(spans):
    return [s.name for s in spans]


@pytest.mark.parametrize("name", ["q1", "q3", "q5", "q18"])
def test_sharded_statement_has_the_single_device_span_tree(dist4, name):
    first, warm = dist4["first"][name], dist4["warm"][name]
    assert warm.name == "stmt"
    (run,) = warm.find("sched.run")
    assert run.attrs["placement"] == "sharded"
    (ex,) = run.children
    assert ex.name == "device.execute"
    assert _names(ex.children) == [
        "device.dispatch", "device.readback", "device.run",
        "device.materialize", "device.finish"]
    assert _names(ex.children[0].children) == ["device.bind",
                                               "device.launch"]
    (bind,) = warm.find("device.bind")
    assert bind.attrs == {"first": False, "uploads": 0, "upload_bytes": 0}
    (launch,) = warm.find("device.launch")
    assert launch.attrs["exchanges"] > 0
    assert launch.attrs["exchange_rows"] > 0
    # a chip sends each of four peers a bucket of every payload array
    # and the one-byte ok mask: more bytes than rows
    assert launch.attrs["exchange_bytes"] > launch.attrs["exchange_rows"]
    (rb,) = warm.find("device.readback")
    assert rb.attrs["syncs"] == 1 and rb.attrs["bytes"] > 0
    assert rb.attrs["overflow_rows"] == 0 and rb.attrs["skew"] >= 1.0
    # the first execution compiled (or loaded) under its dispatch
    (dispatch,) = first.find("device.dispatch")
    assert _names(dispatch.children)[-2:] == ["device.bind",
                                              "device.launch"]
    # (q1 ran first: its bind placed lineitem's columns on the mesh)
    assert any(b.attrs["uploads"] > 0 and b.attrs["first"]
               for b in dist4["first"]["q1"].find("device.bind"))
    assert first.find("device.launch")[0].attrs["exchange_bytes"] == \
        launch.attrs["exchange_bytes"]


def test_a_few_keys_group_by_is_sized_so_that_it_cannot_overflow(
        dist4, raw, monkeypatch):
    """q1 groups 60,000 rows into four keys: hashed over four chips one
    destination gets most of a chip's rows, and a slack-2 bucket
    overflows by construction (a retry, a second compile). The
    exchange of a key with fewer values than a few a device is sized at
    the local row count instead: one dispatch, one program."""
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.parallel import dist_exec
    first = dist4["first"]["q1"]
    assert len(first.find("device.dispatch")) == 1
    (launch,) = first.find("device.launch")
    lineitem = dist4["session"].tables["lineitem"].nrows
    # one exchange, every chip able to send all its rows to one peer
    assert launch.attrs["exchanges"] == 1
    assert launch.attrs["exchange_rows"] == 4 * -(-lineitem // 4)
    (rb,) = first.find("device.readback")
    assert rb.attrs["overflow_rows"] == 0 and rb.attrs["skew"] > 2.0
    # without the rule the same statement overflows and goes round again
    monkeypatch.setattr(dist_exec, "FEW_KEYS_A_DEVICE", 0)
    q1 = next(s for s in _statements() if s.name == "q1")
    name = "exchange_overflow_retries_total"
    before = obs_metrics.snapshot()["counters"].get(name, 0)
    rec = run.run_statement(_session(raw, shards=4), q1)
    assert rec["error"] is None
    assert obs_metrics.snapshot()["counters"][name] - before == 1
    assert _verdict([rec], raw)["correct"] is True      # retried to the end


def test_exchange_counters_move_with_every_launch(dist4):
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    q3 = next(s for s in _statements() if s.name == "q3")
    (launch,) = dist4["warm"]["q3"].find("device.launch")
    before = obs_metrics.snapshot()["counters"]
    rec = run.run_statement(dist4["session"], q3)
    assert rec["error"] is None
    after = obs_metrics.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["exchange_bytes_total"] == launch.attrs["exchange_bytes"]
    assert moved["exchange_rows_total"] == launch.attrs["exchange_rows"]
    assert moved.get("exchanges_traced_total", 0) == 0    # nothing traced
    assert moved["device_readbacks_total"] == 1


def test_attributes_survive_a_plan_cache_load(dist4, raw):
    """A second executor finds the programs in the plan cache, compiles
    nothing, and says the same of them."""
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    session = _session(raw, shards=4, cache_dir=dist4["cache"])
    pipe = session._executor_factory(session.tables)
    q3 = next(s for s in _statements() if s.name == "q3")
    before = obs_metrics.snapshot()["counters"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE", os.path.join(dist4["cache"], "t.jsonl"))
        rec = run.run_statement(session, q3)
    assert rec["error"] is None and rec["placement"] == "sharded"
    after = obs_metrics.snapshot()["counters"]
    assert after.get("compiles_total", 0) == before.get("compiles_total", 0)
    assert after["compile_cache_hits_total"] > before.get(
        "compile_cache_hits_total", 0)
    root = _root_of(pipe.last_query_span)
    assert "cache.load" in _names(root.find("device.dispatch")[0].children)
    (loaded,) = root.find("device.launch")
    (traced,) = dist4["warm"]["q3"].find("device.launch")
    for key in ("exchanges", "exchange_rows", "exchange_bytes",
                "send_words"):
        assert loaded.attrs[key] == traced.attrs[key] > 0
    assert _verdict([rec], raw)["correct"] is True


def test_limit_over_a_sort_with_ties_matches_single_device(raw):
    """PR 26's path under sharding: ``_DistTrace._run_limit`` replicates
    the Sort's INPUT and applies the permutation at the rows LIMIT
    keeps. Ties on the sort key must break as on one device."""
    from nds_tpu.nds_h.power import SUITE
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    sql = ("select l_orderkey, l_linenumber, l_quantity from lineitem "
           "order by l_quantity desc, l_orderkey, l_linenumber limit 40")
    tied = ("select l_quantity, count(*) as n from lineitem "
            "group by l_quantity order by n desc, l_quantity limit 7")
    single = power_core.make_session(
        SUITE, EngineConfig(overrides={"engine.backend": "tpu"}))
    power_core.load_warehouse(SUITE, single, raw, "raw")
    sharded = _session(raw, shards=4)
    for text in (sql, tied):
        want, got = single.sql(text), sharded.sql(text)
        assert got.nrows == want.nrows > 0
        for a, b in zip(got.cols, want.cols):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pipe = sharded._executor_factory(sharded.tables)
    assert pipe.last_schedule["placement"] == "sharded"
    kernels = _sharded_executor(sharded).last_timings["__kernels"]
    assert kernels.get("sort.topn") == 1


# ---- PR 28: capacity follows rows, a wide group key is hash-routed ----

SLACK = 2.0
EVERY_TABLE_BUT_TWO = 100       # rows: customer and supplier shard too


def _operator_log(monkeypatch):
    """Every relation the sharded trace makes from here on, in order:
    (plan node or '_replicate', capacity, rows, sharded, columns)."""
    from nds_tpu.parallel import dist_exec
    log = []
    run, replicate = dist_exec._DistTrace.run, dist_exec._DistTrace._replicate

    def spy_run(self, node):
        fresh = id(node) not in self._cache
        ctx = run(self, node)
        if fresh:
            log.append((type(node).__name__, ctx.n,
                        getattr(ctx, "rows", None),
                        getattr(ctx, "sharded", False), len(ctx.cols)))
        return ctx

    def spy_replicate(self, ctx, who):
        out = replicate(self, ctx, who)
        if out is not ctx:
            log.append(("_replicate", out.n, out.rows, False,
                        len(out.cols)))
        return out

    monkeypatch.setattr(dist_exec._DistTrace, "run", spy_run)
    monkeypatch.setattr(dist_exec._DistTrace, "_replicate", spy_replicate)
    return log


@pytest.fixture(scope="module")
def all_sharded(raw, tmp_path_factory):
    """q3, q5 and q18 with customer and supplier sharded as well (no
    reduced view, an exchange at every join of two tables): what each
    operator was traced at, the statement's span tree, its `kernels`."""
    from benchmarks import run
    cache = tmp_path_factory.mktemp("dist4_rows_plans")
    session = _session(raw, shards=4, cache_dir=cache)
    pipe = session._executor_factory(session.tables)
    ex = _sharded_executor(session)
    ex.shard_threshold = EVERY_TABLE_BUT_TWO
    out = {"session": session, "cache": str(cache), "ops": {},
           "records": {}, "root": {}, "kernels": {}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE",
                  str(tmp_path_factory.mktemp("dist4_rows") / "t.jsonl"))
        log = _operator_log(mp)
        for stmt in _statements():
            if stmt.name == "q1":
                continue
            del log[:]
            rec = run.run_statement(session, stmt)
            assert rec["error"] is None, rec["error"]
            out["ops"][stmt.name] = list(log)
            out["records"][stmt.name] = rec
            out["root"][stmt.name] = _root_of(pipe.last_query_span)
            out["kernels"][stmt.name] = dict(ex.last_timings["__kernels"])
    from nds_tpu import cache as plan_cache
    plan_cache.reset()
    return out


@pytest.mark.parametrize("name", ["q3", "q5", "q18"])
def test_no_operator_is_traced_above_slack_times_its_rows(
        all_sharded, raw, name):
    """Two, three and two exchanges in a row: each sized from the rows a
    chip holds, so nothing sharded is traced above ``slack x`` the
    largest shard (before: x4 / x8 of it, and x16 / x32 replicated)."""
    session, ops = all_sharded["session"], all_sharded["ops"][name]
    shard = -(-session.tables["lineitem"].nrows // 4)
    bound = 4 * -(-int(shard * SLACK) // 4)
    sharded = [op for op in ops if op[3]]
    assert len(sharded) >= 5
    for op, n, rows, _sharded, _cols in sharded:
        assert n <= bound and rows <= shard, (op, n, rows, bound)
    assert max(n for _op, n, *_ in sharded) == bound    # it did exchange
    # what is replicated is a chip's slots four times, no more
    assert all(n <= 4 * bound and rows <= 4 * shard
               for op, n, rows, *_ in ops if op == "_replicate")
    kernels = all_sharded["kernels"][name]
    assert kernels["exchange.by_rows"] >= 1
    (launch,) = all_sharded["root"][name].find("device.launch")
    assert launch.attrs["resized"] == kernels["exchange.by_rows"]
    assert launch.attrs["resized"] < launch.attrs["exchanges"]
    (rb,) = all_sharded["root"][name].find("device.readback")
    assert rb.attrs["overflow_rows"] == 0           # compiled once
    assert len(all_sharded["root"][name].find("device.dispatch")) == 1
    verdict = _verdict([all_sharded["records"][name]], raw)
    assert verdict["correct"] is True, verdict["notes"]


def test_a_wide_group_key_is_hash_routed_not_replicated(all_sharded):
    """q18 groups by (c_name, c_custkey, o_orderkey, o_orderdate,
    o_totalprice): more than 62 bits. The relation is exchanged by a
    hash of the five and aggregated sharded; only the aggregate's six
    output columns are gathered, for the top-100."""
    ops = all_sharded["ops"]["q18"]
    assert all_sharded["kernels"]["q18"]["agg.hash_routed"] == 1
    aggregates = [op for op in ops if op[0] == "Aggregate"]
    assert len(aggregates) == 2 and all(op[3] for op in aggregates)
    widest_scan = max(cols for op, _n, _r, _s, cols in ops if op == "Scan")
    replicated = [cols for op, _n, _r, _s, cols in ops
                  if op == "_replicate"]
    assert replicated and max(replicated) == 6 < widest_scan
    # the keys of q3 and q5 pack: nothing to hash
    for name in ("q3", "q5"):
        assert "agg.hash_routed" not in all_sharded["kernels"][name]


def test_row_bound_counters_survive_a_plan_cache_load(all_sharded, raw):
    from benchmarks import run
    session = _session(raw, shards=4, cache_dir=all_sharded["cache"])
    pipe = session._executor_factory(session.tables)
    ex = _sharded_executor(session)
    ex.shard_threshold = EVERY_TABLE_BUT_TWO
    q18 = next(s for s in _statements() if s.name == "q18")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE",
                  os.path.join(all_sharded["cache"], "t.jsonl"))
        rec = run.run_statement(session, q18)
    assert rec["error"] is None and rec["placement"] == "sharded"
    root = _root_of(pipe.last_query_span)
    assert "cache.load" in _names(root.find("device.dispatch")[0].children)
    (loaded,) = root.find("device.launch")
    (traced,) = all_sharded["root"]["q18"].find("device.launch")
    assert loaded.attrs["resized"] == traced.attrs["resized"] >= 1
    assert loaded.attrs["exchange_bytes"] == traced.attrs["exchange_bytes"]
    kernels = ex.last_timings["__kernels"]
    assert kernels["agg.hash_routed"] == 1
    assert kernels["exchange.by_rows"] == loaded.attrs["resized"]
    assert _verdict([rec], raw)["correct"] is True


def _two_table_sessions(fact_arrays, fact_schema, dim_arrays, dim_schema):
    """(single-device session, four-chip session with both tables
    sharded) over one fact and one dimension table."""
    from nds_tpu.engine.device_exec import make_device_factory
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    from nds_tpu.sql.planner import CatalogInfo
    cat = CatalogInfo({"fact": fact_schema, "dim": dim_schema},
                      {"dim": ["d_sk"], "fact": ["f_id"]},
                      {"fact": len(fact_arrays["f_id"]),
                       "dim": len(dim_arrays["d_sk"])})

    def build(factory):
        s = Session(cat, factory)
        s.register_table(from_arrays("fact", fact_schema, fact_arrays))
        s.register_table(from_arrays("dim", dim_schema, dim_arrays))
        return s

    return (build(make_device_factory()),
            build(make_distributed_factory(n_devices=4,
                                           shard_threshold=1000)))


def _same_rows(got, want):
    import pandas as pd
    assert got.nrows == want.nrows > 0
    pd.testing.assert_frame_equal(got.to_pandas(), want.to_pandas(),
                                  check_exact=True)


def test_wide_key_with_null_keys_on_every_shard_is_one_null_group():
    """Three 40-bit key columns do not pack into 62 bits. NULLs of the
    second sit on every shard over whatever the slot holds: they must
    meet on one chip and come out as ONE group, as on one device."""
    from nds_tpu.engine.types import INT32, INT64, Schema
    n, n_dim = 8192, 1024
    rng = np.random.default_rng(28)
    schema = Schema.of(("f_id", INT32, False), ("k1", INT64, False),
                       ("k2", INT64, True), ("k3", INT64, False),
                       ("f_val", INT32, False))
    dim_schema = Schema.of(("d_sk", INT32, False), ("d_val", INT32, False))
    k2_valid = rng.random(n) >= 0.25
    fact = {"f_id": np.arange(n, dtype=np.int32),
            "k1": rng.integers(0, 3, n) * (1 << 40),
            # garbage under the NULLs: no two alike
            "k2": np.where(k2_valid, rng.integers(0, 5, n) * (1 << 39),
                           rng.integers(1, 1 << 40, n)),
            "k2#null": k2_valid,
            "k3": rng.integers(0, 2, n) * (1 << 40) + 7,
            "f_val": rng.integers(0, 100, n).astype(np.int32)}
    dim = {"d_sk": np.arange(n_dim, dtype=np.int32),
           "d_val": np.arange(n_dim, dtype=np.int32)}
    single, sharded = _two_table_sessions(fact, schema, dim, dim_schema)
    sql = ("select k1, k2, k3, count(*) as n, sum(f_val) as s, "
           "min(f_id) as first_id from fact group by k1, k2, k3 "
           "order by k1, k2, k3")
    want, got = single.sql(sql), sharded.sql(sql)
    _same_rows(got, want)
    frame = got.to_pandas()
    nulls = frame[frame["k2"].isna()]
    assert len(nulls) == 3 * 2                  # one a (k1, k3) pair
    assert int(nulls["n"].sum()) == int((~k2_valid).sum())
    # and the NULLs were on every shard
    assert all((~k2_valid[i * n // 4:(i + 1) * n // 4]).any()
               for i in range(4))
    ex = sharded._executor_factory(sharded.tables)
    assert ex.last_timings["__kernels"]["agg.hash_routed"] == 1


def test_overflow_at_a_second_exchange_is_counted_and_retried():
    """A join of two sharded tables spreads the rows evenly (the first
    exchange); the group-by then sends nine rows in ten to ONE chip (the
    second). Its bucket holds half a chip's rows, so rows overflow: they
    are counted, the statement goes round again at doubled slack, and
    the answer is exact."""
    from nds_tpu.engine.types import INT32, Schema
    from nds_tpu.obs import metrics as obs_metrics
    n, n_dim = 8192, 2048
    rng = np.random.default_rng(5)
    schema = Schema.of(("f_id", INT32, False), ("f_dim_sk", INT32, False),
                       ("f_grp", INT32, False), ("f_val", INT32, False))
    dim_schema = Schema.of(("d_sk", INT32, False), ("d_val", INT32, False))
    # 64 group keys (too many for the few-keys rule), one of them hot
    grp = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 64, n))
    fact = {"f_id": np.arange(n, dtype=np.int32),
            "f_dim_sk": rng.integers(0, n_dim, n).astype(np.int32),
            "f_grp": grp.astype(np.int32),
            "f_val": rng.integers(0, 100, n).astype(np.int32)}
    dim = {"d_sk": np.arange(n_dim, dtype=np.int32),
           "d_val": (np.arange(n_dim) * 3).astype(np.int32)}
    single, sharded = _two_table_sessions(fact, schema, dim, dim_schema)
    sql = ("select f_grp, count(*) as n, sum(f_val + d_val) as s "
           "from fact join dim on f_dim_sk = d_sk "
           "group by f_grp order by f_grp")
    counters = ("exchange_overflow_retries_total",
                "exchange_overflow_rows_total", "recompiles_total")
    before = obs_metrics.snapshot()["counters"]
    got = sharded.sql(sql)
    after = obs_metrics.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in counters}
    assert moved["exchange_overflow_retries_total"] == 1
    assert moved["exchange_overflow_rows_total"] > 0
    assert moved["recompiles_total"] == 1
    _same_rows(got, single.sql(sql))
    assert int(got.to_pandas()["n"].sum()) == n         # never short
    ex = sharded._executor_factory(sharded.tables)
    # the exchange that overflowed is the one sized from rows
    assert ex.last_timings["__kernels"]["exchange.by_rows"] == 1


@pytest.mark.parametrize("bound", [None, "rows"])
def test_hierarchical_exchange_takes_the_row_bound(bound):
    """The 2x2 (host, lane) mesh obeys the same rule: a buffer that is
    half dead slots (what an exchange leaves) comes out at ``slack x
    rows`` with the bound passed, at ``slack x capacity`` without, and
    holds the same rows, each key on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from nds_tpu.parallel.dist_exec import shard_map
    from nds_tpu.parallel.exchange import (
        exchange_hierarchical, exchange_trace,
    )
    from nds_tpu.parallel.mesh import (
        DATA_AXIS, HOST_AXIS, make_multihost_mesh,
    )
    H, D, per = 2, 2, 512
    n = H * D * per
    rows = per // 2
    rng = np.random.default_rng(3)
    keys = rng.integers(1, 400, n).astype(np.int64)
    vals = np.arange(n, dtype=np.int64)
    ok = np.zeros(n, dtype=bool)
    for dev in range(H * D):        # `rows` live rows a device, scattered
        ok[dev * per + rng.choice(per, rows, replace=False)] = True
    both = P((HOST_AXIS, DATA_AXIS))

    def fn(k, v, o):
        k, v, o = k.reshape(-1), v.reshape(-1), o.reshape(-1)
        outs, out_ok, over = exchange_hierarchical(
            [v, k], k, o, H, D, slack=SLACK, rows=rows if bound else None)
        return (outs[0].reshape(1, -1), outs[1].reshape(1, -1),
                out_ok.reshape(1, -1), jnp.reshape(over, (1, 1)))

    f = shard_map(fn, mesh=make_multihost_mesh(H, D),
                  in_specs=(both,) * 3, out_specs=(both,) * 4)
    with exchange_trace() as xt:
        vo, ko, oko, over = (np.asarray(x) for x in jax.jit(f)(
            *(jnp.asarray(a).reshape(H * D, per) for a in (keys, vals, ok))))
    assert int(over.sum()) == 0
    assert vo.shape[1] == int(SLACK * (rows if bound else per))
    assert sorted(vo[oko].tolist()) == sorted(vals[ok].tolist())
    for k in np.unique(ko[oko]):
        assert sum((ko[i][oko[i]] == k).any() for i in range(H * D)) == 1
    # both stages with the bound; the second alone without (it never
    # sized from the padded length the first stage hands it)
    assert (xt.count, xt.resized) == (2, 2 if bound else 1)
    assert xt.stats()["resized"] == xt.resized


@pytest.mark.parametrize("n,rows,slack,n_dev,want", [
    (1000, None, 2.0, 4, 500),      # no bound: the capacity
    (2000, 1000, 2.0, 4, 500),      # after one exchange: the rows
    (4000, 1000, 2.0, 4, 500),      # after two: still the rows
    (1000, 5000, 2.0, 4, 500),      # a bound is never above capacity
    (1001, 1001, 2.0, 4, 501),      # rounded up
    (1000, 1000, 4.0, 4, 1000),     # the few-keys bucket: all of it
    (0, 0, 2.0, 4, 1),
])
def test_bucket_follows_the_rows(n, rows, slack, n_dev, want):
    from nds_tpu.parallel.exchange import bucket_for
    assert bucket_for(n, rows, slack, n_dev) == want


def test_hash_of_key_columns_separates_null_from_zero():
    import jax.numpy as jnp
    from nds_tpu.parallel.exchange import hash_columns
    a = jnp.asarray([5, 5, 0, 0, 9], jnp.int64)
    b = jnp.asarray([1, 1, 0, 0, 1], jnp.int32)
    valid = jnp.asarray([True, True, True, False, True])
    h = np.asarray(hash_columns([(a, None), (b, valid)]))
    assert h.dtype == np.int64
    assert h[0] == h[1]                     # equal rows, equal keys
    assert h[2] != h[3]                     # (0, 0) is not (0, NULL)
    assert len({int(x) for x in h}) == 4
    # the order of the columns is part of the key
    swapped = np.asarray(hash_columns([(b.astype(jnp.int64), None),
                                       (a.astype(jnp.int32), None)]))
    assert swapped[0] != np.asarray(hash_columns(
        [(a, None), (b, None)]))[0]


# ---- PR 30: the send buffer is read into place, nothing is scattered ----

def _ops_from(text, op, source):
    """(result name, enclosing function's text) of every region-bearing
    ``stablehlo.<op>`` of a lowered program (``debug_info=True``) whose
    location chain leads to the file ``source``."""
    import re
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    seen = {}

    def leads_there(loc):
        if loc not in seen:
            seen[loc] = False       # a cycle is no way there
            body = locs.get(loc, "")
            seen[loc] = source in body or any(
                leads_there(ref) for ref in re.findall(r"#loc\d+", body))
        return seen[loc]

    found = []
    for body in re.split(r"^\s*func\.func ", text, flags=re.M)[1:]:
        for m in re.finditer(
                r'^( *)(%\w+)(?::\d+)? = "stablehlo\.' + op + r'"\(.*?'
                r"^\1\}\) : [^\n]* loc\((#loc\d+)\)$",
                body, re.M | re.S):
            if leads_there(m.group(3)):
                found.append((m.group(2), body))
    return found


def test_send_buffer_is_gathered_not_scattered(dist4):
    """The mechanism, from the programs as lowered: no scatter comes
    from ``parallel/exchange.py`` (the parent's q3 held fifteen), every
    exchange's one sort has its sorted key READ (the parent dropped it
    and gathered ``dest`` through the permutation again), and the launch
    span says how many words were gathered into send buffers."""
    source = "nds_tpu/parallel/exchange.py"
    texts = dist4["lowered"]
    assert len(texts) == 4                  # one program a statement
    exchanges = 0
    for text in texts:
        assert source in text and "stablehlo.all_to_all" in text
        assert _ops_from(text, "scatter", source) == []
        sorts = _ops_from(text, "sort", source)
        exchanges += len(sorts)
        for result, body in sorts:
            assert f"{result}#0" in body and f"{result}#1" in body
    launches = {name: root.find("device.launch")[0].attrs
                for name, root in dist4["warm"].items()}
    assert exchanges == sum(a["exchanges"] for a in launches.values())
    for attrs in launches.values():
        # the index array and at least one 32-bit payload word a slot
        assert attrs["send_words"] >= 2 * attrs["exchange_rows"]


def _partition_reference(arrays, dest, ok, n_dev, bucket):
    """What ``exchange_by_dest`` has to deliver, in plain numpy: sender
    ``s`` gives peer ``d`` its live rows bound for ``d`` in input order,
    cut at ``bucket`` and zero-filled; device ``d`` holds the buckets of
    senders 0..n_dev-1 side by side. Rows cut are the sender's
    overflow."""
    outs = [np.zeros((n_dev, n_dev, bucket), a.dtype) for a in arrays]
    out_ok = np.zeros((n_dev, n_dev, bucket), bool)
    overflow = np.zeros(n_dev, np.int64)
    for s in range(n_dev):
        for d in range(n_dev):
            rows = np.flatnonzero(ok[s] & (dest[s] == d))
            overflow[s] += max(0, len(rows) - bucket)
            rows = rows[:bucket]
            out_ok[d, s, :len(rows)] = True
            for out, a in zip(outs, arrays):
                out[d, s, :len(rows)] = a[s][rows]
    return ([o.reshape(n_dev, -1) for o in outs],
            out_ok.reshape(n_dev, -1), overflow)


# name -> (live share, destinations drawn from, bucket or None for the
# capacity's)
PARTITIONS = {
    "dead_rows_mixed_in": (0.6, (0, 1, 2, 3), None),
    "every_row_to_one_destination": (1.0, (2,), None),
    "bucket_below_capacity": (0.45, (0, 1, 2, 3), "rows"),
    "an_empty_destination": (0.8, (0, 2, 3), None),
    "all_rows_dead": (0.0, (0, 1, 2, 3), None),
    "bucket_of_one": (0.9, (0, 1, 2, 3), 1),
}


@pytest.mark.parametrize("case", sorted(PARTITIONS))
def test_exchange_by_dest_is_the_plain_partition(case):
    """Slot for slot against the numpy partition: every output array
    (int64, int32 and bool payloads together), ``out_ok`` and each
    sender's overflow count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from nds_tpu.parallel.dist_exec import shard_map
    from nds_tpu.parallel.exchange import bucket_for, exchange_by_dest
    from nds_tpu.parallel.mesh import DATA_AXIS, make_mesh
    live, dests, bucket = PARTITIONS[case]
    n_dev, per = 4, 203
    rng = np.random.default_rng(30)
    ok = rng.random((n_dev, per)) < live
    dest = rng.choice(dests, (n_dev, per)).astype(np.int32)
    if bucket == "rows":        # sized from a row bound under capacity
        bucket = bucket_for(per, int(ok.sum(axis=1).max()), SLACK, n_dev)
        assert bucket < bucket_for(per, None, SLACK, n_dev)
    elif bucket is None:
        bucket = bucket_for(per, None, SLACK, n_dev)
    arrays = [rng.integers(-2**62, 2**62, (n_dev, per)),
              rng.integers(-2**31, 2**31 - 1, (n_dev, per)).astype(np.int32),
              rng.random((n_dev, per)) < 0.5]

    def fn(dest, ok, *arrays):
        outs, out_ok, over = exchange_by_dest(
            [a.reshape(-1) for a in arrays], dest.reshape(-1),
            ok.reshape(-1), n_dev, SLACK, DATA_AXIS, bucket=bucket)
        return (*(o.reshape(1, -1) for o in outs), out_ok.reshape(1, -1),
                jnp.reshape(over, (1,)))

    rows = P(DATA_AXIS)
    f = shard_map(fn, mesh=make_mesh(n_dev), in_specs=(rows,) * 5,
                  out_specs=(rows,) * 5)
    *outs, out_ok, over = (np.asarray(x) for x in jax.jit(f)(
        dest, ok, *arrays))
    want, want_ok, want_over = _partition_reference(
        arrays, dest, ok, n_dev, bucket)
    for got, ref in zip(outs, want):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(out_ok, want_ok)
    np.testing.assert_array_equal(over, want_over)
    # the cases are what they say they are
    overflowed = case in ("every_row_to_one_destination", "bucket_of_one")
    assert (want_over.sum() > 0) == overflowed
    assert out_ok.sum() + want_over.sum() == ok.sum()   # counted, not lost
