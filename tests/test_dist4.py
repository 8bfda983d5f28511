"""The four-chip NDS-H deployment (benchmark cell ``nds_h_sf1.dist4``)
on the virtual CPU mesh, at SF0.01: the shipped distributed template
through ``make_session``, the cell's own four statements held against
the benchmark's plain references by the benchmark's own comparison, a
planted exchange fault, the sharded statement's span tree, and PR 26's
top-N path under sharding.
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
           "first": {}, "warm": {}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE",
                  str(tmp_path_factory.mktemp("dist4_trace") / "t.jsonl"))
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

    def lossy(arrays, key, ok, n_dev, slack=2.0, axis=DATA_AXIS):
        outs, out_ok, n_over = inner(arrays, key, ok, n_dev, slack, axis)
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
    for key in ("exchanges", "exchange_rows", "exchange_bytes"):
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
