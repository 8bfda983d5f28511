"""Serving layer (nds_tpu/serve/) + parameterized plans (sql/params.py):

- fingerprint identity across literal variants for EVERY NDS + NDS-H
  template (ISSUE 12 satellite; q66 is the documented exception — its
  variant literal lands in a string-constant output column whose
  dictionary bakes into the program);
- hoisted-literal execution parity against the inlined-literal plan on
  the CPU oracle and the device engine;
- QueryServer admission/brownout semantics (queue depth, deadline,
  stop-drain, error answers), template batching, per-tenant metrics on
  the OpenMetrics emitter, the TCP JSON-lines front, and the
  per-request summary schema;
- ndsreport: per-tenant quantiles from serve run dirs, and the
  stale-metric refusal (bench exit codes + diff gate).
"""

import json
import os
import random
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from nds_tpu.cache import fingerprint as fpm
from nds_tpu.engine.session import Session
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.sql import ir
from nds_tpu.sql import params as sqlparams

# templates whose generator-varied literal provably cannot hoist (the
# value becomes a string-constant OUTPUT column -> its dictionary is a
# trace constant); everything else must share fingerprints
FP_EXCEPTIONS_NDS = {66}


def _apply_view_actions(sess, planned):
    act, name, node = planned
    if act == "create_view":
        sess.views[name] = node
    elif act == "drop_view":
        sess.views.pop(name, None)


def _fps_for(sess, stmts):
    out = []
    for stmt in stmts:
        planned = sess.plan(stmt)
        if isinstance(planned, tuple):
            _apply_view_actions(sess, planned)
            continue
        # a literal-free statement (q76 renders none) hoists nothing —
        # identity across variants is then trivially required
        out.append(fpm.fingerprint(planned, {}, kind="t", parts={}))
    return out


class TestFingerprintIdentity:
    def test_nds_h_all_templates_share(self):
        from nds_tpu.nds_h import streams as hs
        sess = Session.for_nds_h(parameterize=True)
        for qn in range(1, 23):
            per_seed = []
            for seed in (1, 2):
                sql = hs.render_query(
                    qn, hs.random_params(qn, random.Random(seed), 0))
                per_seed.append(_fps_for(sess, hs.statements(qn, sql)))
            assert per_seed[0] == per_seed[1], \
                f"NDS-H q{qn}: literal variants changed the fingerprint"

    def test_nds_all_templates_share(self):
        from nds_tpu.nds import streams as ds
        sess = Session.for_nds(parameterize=True)
        differing = []
        for qn in ds.available_templates():
            per_seed = []
            for seed in (1, 2):
                sql = ds.render_query(
                    qn, ds.random_params(qn, random.Random(seed), 0))
                stmts = [s.strip() for s in sql.split(";")
                         if s.strip()]
                per_seed.append(_fps_for(sess, stmts))
            if per_seed[0] != per_seed[1]:
                differing.append(qn)
        assert set(differing) <= FP_EXCEPTIONS_NDS, \
            f"unexpected fingerprint drift: {sorted(differing)}"

    def test_param_values_do_not_reach_canonical(self):
        sess = Session.for_nds_h(parameterize=True)
        p = sess.plan("select count(*) from lineitem "
                      "where l_quantity < 24")
        assert sqlparams.has_params(p)
        assert "24" not in fpm.canonical(p)
        assert any(isinstance(x, ir.ParamRef)
                   for e in _all_plan_exprs(p) for x in ir.walk(e))


def _all_plan_exprs(planned):
    from nds_tpu.sql import plan as P
    for root in [planned.root, *planned.scalar_subplans]:
        for node in P.walk_plan(root):
            for e in P.all_exprs(node):
                if e is not None:
                    yield e


# ------------------------------------------------------------- parity

@pytest.fixture(scope="module")
def h_tables():
    from nds_tpu.datagen import tpch
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.nds_h.schema import get_schemas
    schemas = get_schemas()
    return {t: from_arrays(t, schemas[t], tpch.gen_table(t, 0.01))
            for t in schemas}


def _h_session(h_tables, factory=None, param=False):
    s = Session.for_nds_h(factory, parameterize=param)
    for t in h_tables.values():
        s.register_table(t)
    return s


class TestParity:
    # dictionary predicates (LIKE/cmp/inlist incl. the q22 substring
    # chain), numeric/date/decimal scalars, numeric in-lists
    TEMPLATES = (1, 3, 6, 12, 13, 16, 19, 22)

    def test_inline_roundtrip_equals_plain_cpu(self, h_tables):
        """parameterize -> inline must execute EXACTLY like the plain
        plan on the oracle (the executors' inline() path)."""
        from test_device_engine import assert_frames_close

        from nds_tpu.nds_h import streams as hs
        plain = _h_session(h_tables)
        param = _h_session(h_tables, param=True)
        for qn in self.TEMPLATES:
            sql = hs.render_query(
                qn, hs.random_params(qn, random.Random(5), 0))
            exp = plain.sql(sql)
            got = param.sql(sql)
            assert_frames_close(got.to_pandas(), exp.to_pandas(), qn)

    def test_device_params_equal_plain_cpu(self, h_tables):
        """The device engine's NATIVE parameter path (runtime scalar +
        dictionary-table inputs) returns the oracle's rows."""
        from test_device_engine import assert_frames_close

        from nds_tpu.engine.device_exec import make_device_factory
        from nds_tpu.nds_h import streams as hs
        plain = _h_session(h_tables)
        dev = _h_session(h_tables, make_device_factory(), param=True)
        for qn in self.TEMPLATES:
            sql = hs.render_query(
                qn, hs.random_params(qn, random.Random(6), 0))
            exp = plain.sql(sql)
            got = dev.sql(sql)
            assert_frames_close(got.to_pandas(), exp.to_pandas(), qn)

    def test_device_shares_program_across_variants(self, h_tables):
        from nds_tpu.engine.device_exec import make_device_factory
        from nds_tpu.nds_h import streams as hs
        dev = _h_session(h_tables, make_device_factory(), param=True)
        dev.sql(hs.render_query(
            6, hs.random_params(6, random.Random(1), 0)))
        before = obs_metrics.snapshot()
        dev.sql(hs.render_query(
            6, hs.random_params(6, random.Random(2), 0)))
        delta = obs_metrics.delta(
            before, obs_metrics.snapshot()).get("counters", {})
        assert not delta.get("compiles_total"), \
            "literal variant recompiled instead of rebinding params"

    def test_compiled_entry_bound(self, h_tables, monkeypatch):
        """A serving workload cycles unbounded plan objects through the
        executor: the compile cache must evict past MAX_COMPILED
        instead of pinning plans + programs forever."""
        from nds_tpu.engine.device_exec import (
            DeviceExecutor, make_device_factory,
        )
        monkeypatch.setattr(DeviceExecutor, "MAX_COMPILED", 3)
        dev = _h_session(h_tables, make_device_factory())
        for i in range(6):
            dev.sql(f"select count(*) from region where "
                    f"r_regionkey < {i}")
        ex = dev._executor_factory(dev.tables)
        assert len(ex._compiled) <= 3

    def test_dict_binder_matches_trace(self, h_tables):
        """derive_dictionary replays substr/upper chains exactly like
        the trace's np.unique rewrites."""
        import numpy as np
        d = sqlparams.derive_dictionary(
            (("substr", 1, 2),), {"customer": h_tables["customer"]},
            "customer", "c_phone")
        base = np.asarray(
            h_tables["customer"].columns["c_phone"].dictionary)
        exp = np.unique(np.array([str(s)[0:2] for s in base]))
        assert list(d.astype(str)) == list(exp)


# ------------------------------------------------------------- server

@pytest.fixture()
def server(h_tables, tmp_path):
    from nds_tpu.serve import QueryServer
    from nds_tpu.utils.config import EngineConfig
    cfg = EngineConfig(overrides={
        "engine.backend": "cpu",
        "serve.max_queue": "4",
        "serve.summary_dir": str(tmp_path / "serve_json"),
    })
    srv = QueryServer(cfg)
    for t in h_tables.values():
        srv.register_table(t, "nds_h")
    srv.start()
    yield srv
    srv.stop()


def _submit_q6(srv, tenant="t0", qname="q6"):
    from nds_tpu.nds_h import streams as hs
    return srv.submit(tenant, "nds_h", hs.render_query(6), qname)


class TestQueryServer:
    def test_ok_response_with_digest_and_summary(self, server,
                                                 tmp_path):
        import check_trace_schema as cts
        resp = _submit_q6(server).result(timeout=120)
        assert resp.status == "ok"
        assert resp.rows >= 1 and resp.digest
        sdir = str(tmp_path / "serve_json")
        files = os.listdir(sdir)
        assert files
        for f in files:
            assert cts.validate_summary_file(
                os.path.join(sdir, f)) == []
            doc = json.load(open(os.path.join(sdir, f)))
            assert doc["tenant"] == "t0"

    def test_unknown_suite_and_bad_sql_answer_error(self, server):
        r = server.submit("t0", "nope", "select 1").result(timeout=60)
        assert r.status == "error" and "suite" in r.error
        r = server.submit("t0", "nds_h",
                          "select frobnicate from lineitem"
                          ).result(timeout=120)
        assert r.status == "error"
        # the server keeps serving after an error answer
        assert _submit_q6(server).result(timeout=120).status == "ok"

    def test_queue_depth_brownout_and_recovery(self, server):
        import ndsload
        docs = ndsload.build_requests(24, 3, tenants=2,
                                      nds_h_templates=(1, 5, 6),
                                      nds_templates=())
        responses = ndsload.burst_inproc(server, docs)
        summary = ndsload.summarize(responses)
        assert summary["status"].get("shed", 0) > 0, summary
        assert summary["status"].get("error", 0) == 0, summary
        assert obs_metrics.snapshot()["counters"].get(
            "server_shed_total", 0) > 0
        assert summary.get("shed_reasons", {}).get("queue-depth") \
            == summary["status"]["shed"]
        # brownout, not collapse
        assert _submit_q6(server).result(timeout=120).status == "ok"

    def test_deadline_shed(self, h_tables, tmp_path):
        from nds_tpu.serve import QueryServer
        from nds_tpu.utils.config import EngineConfig
        srv = QueryServer(EngineConfig(overrides={
            "engine.backend": "cpu",
            "serve.deadline_ms": "1",
        }))
        for t in h_tables.values():
            srv.register_table(t, "nds_h")
        # enqueue BEFORE starting the engine thread: the queued request
        # ages past the deadline and must shed at dequeue
        fut = _submit_q6(srv, qname="late")
        time.sleep(0.05)
        srv.start()
        try:
            r = fut.result(timeout=60)
            assert r.status == "shed" and "deadline" in r.shed_reason
        finally:
            srv.stop()

    def test_stop_sheds_queued(self, h_tables):
        from nds_tpu.serve import QueryServer
        from nds_tpu.utils.config import EngineConfig
        srv = QueryServer(EngineConfig(overrides={
            "engine.backend": "cpu"}))
        for t in h_tables.values():
            srv.register_table(t, "nds_h")
        fut = _submit_q6(srv)  # engine thread never started
        srv.stop()
        assert fut.result(timeout=10).status == "shed"
        # post-stop submits answer immediately instead of stranding
        r = _submit_q6(srv).result(timeout=10)
        assert r.status == "shed" and "stopping" in r.shed_reason
        # and a RESTARTED server serves again (no zombie-shed flag)
        srv.start()
        try:
            assert _submit_q6(srv).result(timeout=120).status == "ok"
        finally:
            srv.stop()

    def test_tenant_labels_in_openmetrics(self, server):
        from nds_tpu.obs.snapshot import (
            to_openmetrics, validate_openmetrics,
        )
        _submit_q6(server, tenant="alice").result(timeout=120)
        _submit_q6(server, tenant="bob").result(timeout=120)
        om = to_openmetrics(obs_metrics.snapshot())
        assert validate_openmetrics(om) == []
        assert 'server_requests_total{tenant="alice"}' in om
        assert 'server_requests_total{tenant="bob"}' in om
        assert '{tenant="alice",quantile="0.99"}' in om

    def test_tcp_front_roundtrip(self, server):
        import asyncio

        from nds_tpu.nds_h import streams as hs
        from nds_tpu.serve.net import request_many, start_tcp

        async def _go():
            tcp = await start_tcp(server, "127.0.0.1", 0)
            port = tcp.sockets[0].getsockname()[1]
            docs = [{"tenant": "net", "suite": "nds_h",
                     "qname": f"net{i}", "sql": hs.render_query(6)}
                    for i in range(4)]
            docs.append({"tenant": "net", "bogus": True})  # no sql
            out = await request_many("127.0.0.1", port, docs, 2)
            tcp.close()
            await tcp.wait_closed()
            return out

        out = asyncio.run(_go())
        assert [r["status"] for r in out[:4]] == ["ok"] * 4
        assert out[4]["status"] == "error"


# --------------------------------------------------- metrics + analyze

class TestLabeledMetrics:
    def test_labeled_and_split(self):
        name = obs_metrics.labeled("x_total", tenant="a b",
                                   suite="nds")
        assert name == 'x_total{suite="nds",tenant="a b"}'
        base, labels = obs_metrics.split_labels(name)
        assert base == "x_total"
        assert labels == '{suite="nds",tenant="a b"}'
        assert obs_metrics.split_labels("plain") == ("plain", "")

    def test_label_values_escaped_stay_distinct(self):
        a = obs_metrics.labeled("x", t='acme')
        b = obs_metrics.labeled("x", t='acme"')
        c = obs_metrics.labeled("x", t="a\\b")
        d = obs_metrics.labeled("x", t="ab")
        assert len({a, b, c, d}) == 4
        assert b == 'x{t="acme\\""}'
        # and the OpenMetrics renderer/validator accept escaped values
        from nds_tpu.obs.snapshot import (
            to_openmetrics, validate_openmetrics,
        )
        snap = {"counters": {obs_metrics.labeled(
            "esc_total", t='q"v\\x'): 1}}
        assert validate_openmetrics(to_openmetrics(snap)) == []


class TestAnalyzeTenants:
    def _summary(self, qname, tenant, wall_ms, **extra):
        return {"query": qname, "queryStatus": ["Completed"],
                "queryTimes": [wall_ms], "startTime": 1,
                "env": {}, "tenant": tenant, **extra}

    def _write(self, d, docs):
        os.makedirs(d, exist_ok=True)
        for i, doc in enumerate(docs):
            with open(os.path.join(d, f"serve-q{i}-{i}.json"),
                      "w") as f:
                json.dump(doc, f)

    def test_tenant_quantiles(self, tmp_path):
        from nds_tpu.obs import analyze
        d = str(tmp_path / "run")
        self._write(d, [self._summary(f"q{i}", "t0", 10 * (i + 1))
                        for i in range(10)]
                    + [self._summary("qx", "t1", 5)])
        a = analyze.analyze_run(d, with_trace=False)
        assert a["tenants"]["t0"]["requests"] == 10
        assert a["tenants"]["t0"]["p50_ms"] == 50.0
        assert a["tenants"]["t0"]["p99_ms"] == 100.0
        assert a["tenants"]["t1"]["requests"] == 1

    def test_stale_marker_fails_diff(self, tmp_path):
        from nds_tpu.obs import analyze
        clean = str(tmp_path / "clean")
        stale = str(tmp_path / "stale")
        docs = [self._summary(f"q{i}", "t0", 10.0) for i in range(3)]
        self._write(clean, docs)
        self._write(stale, [dict(doc, stale_device_times=True)
                            for doc in docs])
        a_clean = analyze.analyze_run(clean, with_trace=False)
        a_stale = analyze.analyze_run(stale, with_trace=False)
        assert "stale_device_times" not in a_clean
        assert len(a_stale["stale_device_times"]) == 3
        d = analyze.diff_runs(a_clean, a_stale)
        assert d["passed"] is False
        assert "cur" in d["stale_device_times"]
        # identical CLEAN dirs still pass
        assert analyze.diff_runs(a_clean, a_clean)["passed"] is True


# ------------------------------------------------- cache placement

def test_xla_cache_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory IS the cache —
    enable() sets no dir in jax's config and appends nothing. Unset:
    the same in-checkout path on every call, whatever XLA_FLAGS says."""
    import jax

    from nds_tpu.utils import xla_cache
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    updates = []
    real_update = jax.config.update

    def spy(name, val):
        updates.append(name)
        real_update(name, val)

    monkeypatch.setattr(jax.config, "update", spy)
    try:
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert xla_cache.enable() == outside
        assert "jax_compilation_cache_dir" not in updates
        assert not os.path.exists(outside)  # jax creates it, not we

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        first = xla_cache.enable()
        monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_enable_fast_math=false")
        assert xla_cache.enable() == first == os.path.join(
            repo, ".xla_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        monkeypatch.undo()
        for k, v in saved.items():
            jax.config.update(k, v)
        xla_cache.reset()


# --------------------------------------------------------- NDS115 rule

class TestBlockingInAsyncRule:
    def _lint(self, src, path="nds_tpu/serve/mod.py"):
        from nds_tpu.analysis.lint_rules import lint_sources
        return lint_sources({path: src}, enabled={"NDS115"})

    def test_flags_sleep_open_result(self):
        src = ("import time\n"
               "async def h(reader, fut):\n"
               "    time.sleep(1)\n"
               "    f = open('/tmp/x')\n"
               "    v = fut.result()\n"
               "    return f, v\n")
        res = self._lint(src)
        assert len(res.violations) == 3
        assert {v.line for v in res.violations} == {3, 4, 5}

    def test_sync_function_and_nested_def_are_clean(self):
        src = ("import time\n"
               "def sync():\n"
               "    time.sleep(1)\n"
               "async def h():\n"
               "    def helper():\n"
               "        return open('/tmp/x')\n"
               "    return helper\n")
        res = self._lint(src)
        assert res.violations == []

    def test_scoped_to_serve_package(self):
        src = ("import time\n"
               "async def h():\n"
               "    time.sleep(1)\n")
        res = self._lint(src, path="nds_tpu/engine/x.py")
        assert res.violations == []

    def test_waiver_honored(self):
        src = ("import time\n"
               "async def h():\n"
               "    time.sleep(1)  "
               "# ndslint: waive[NDS115] -- test fixture\n")
        res = self._lint(src)
        assert res.violations == [] and len(res.waived) == 1

    def test_serve_tree_is_clean(self):
        from nds_tpu.analysis.lint_rules import lint_sources
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        srcs = {}
        sdir = os.path.join(root, "nds_tpu", "serve")
        for f in os.listdir(sdir):
            if f.endswith(".py"):
                rel = f"nds_tpu/serve/{f}"
                srcs[rel] = open(os.path.join(sdir, f)).read()
        res = lint_sources(srcs, enabled={"NDS115"})
        assert res.violations == []

    def test_in_default_rules(self):
        from nds_tpu.analysis.lint_rules import default_rules
        assert any(r.id == "NDS115" for r in default_rules())
