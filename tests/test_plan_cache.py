"""Persistent AOT plan cache (nds_tpu/cache/): fingerprints, the
sha256-stamped store, AOT (de)serialization, and the compile-once
contract end to end — including the ISSUE 7 acceptance test: a
subprocess populates the cache, the parent re-runs the same 3-query
NDS-H power stream against the same warehouse and performs ZERO
compiles with identical rows."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from nds_tpu import cache as plan_cache
from nds_tpu.cache import fingerprint as fpm
from nds_tpu.cache.store import MANIFEST_NAME, PlanCache
from nds_tpu.datagen import tpch
from nds_tpu.engine.session import Session
from nds_tpu.io.host_table import from_arrays
from nds_tpu.nds_h.schema import get_schemas
from nds_tpu.obs import metrics as obs_metrics

SF = 0.01


@pytest.fixture(autouse=True)
def _cache_isolation():
    """No test leaks a cache activation into the next (the resolver is
    process-global by design — one cache per engine process)."""
    plan_cache.reset()
    yield
    plan_cache.reset()


@pytest.fixture(scope="module")
def raw():
    return {t: tpch.gen_table(t, SF) for t in get_schemas()}


def _session(raw, factory=None):
    schemas = get_schemas()
    sess = Session.for_nds_h(factory)
    for t in schemas:
        sess.register_table(from_arrays(t, schemas[t], raw[t]))
    return sess


def _run(sess, qn):
    from nds_tpu.nds_h import streams
    result = None
    for s in streams.statements(qn):
        r = sess.sql(s)
        if r is not None:
            result = r
    return result


def _counters(before):
    return obs_metrics.delta(before,
                             obs_metrics.snapshot()).get("counters", {})


# ------------------------------------------------------------ fingerprint

class TestFingerprint:
    def test_canonical_deterministic(self, raw):
        sess = _session(raw)
        p1 = sess.plan("select l_returnflag, sum(l_quantity) from "
                       "lineitem group by l_returnflag")
        p2 = sess.plan("select l_returnflag, sum(l_quantity) from "
                       "lineitem group by l_returnflag")
        assert fpm.canonical(p1) == fpm.canonical(p2)
        p3 = sess.plan("select l_returnflag, sum(l_tax) from "
                       "lineitem group by l_returnflag")
        assert fpm.canonical(p1) != fpm.canonical(p3)

    def test_table_digest_memoized_and_content_sensitive(self, raw):
        schemas = get_schemas()
        t1 = from_arrays("region", schemas["region"], raw["region"])
        d1 = fpm.table_digest(t1)
        assert fpm.table_digest(t1) == d1  # memo
        # same shape, different content -> different digest
        changed = dict(raw["region"])
        changed["r_regionkey"] = np.ascontiguousarray(
            np.array(changed["r_regionkey"])[::-1])
        t2 = from_arrays("region", schemas["region"], changed)
        assert fpm.table_digest(t2) != d1

    def test_fingerprint_components(self, raw):
        sess = _session(raw)
        p = sess.plan("select count(*) from region")
        base = fpm.fingerprint(p, sess.tables, kind="DeviceExecutor",
                               parts={"slack": 1.0})
        assert base == fpm.fingerprint(p, sess.tables,
                                       kind="DeviceExecutor",
                                       parts={"slack": 1.0})
        assert base != fpm.fingerprint(p, sess.tables,
                                       kind="DeviceExecutor",
                                       parts={"slack": 2.0})
        assert base != fpm.fingerprint(p, sess.tables,
                                       kind="DistributedExecutor",
                                       parts={"slack": 1.0})
        # extra roots (the partial-agg merge plan) shape the key
        p2 = sess.plan("select count(*) from nation")
        assert base != fpm.fingerprint(p, sess.tables,
                                       kind="DeviceExecutor",
                                       parts={"slack": 1.0},
                                       extra_roots=[p2.root])

    def test_fingerprint_tracks_table_content(self, raw):
        sess = _session(raw)
        p = sess.plan("select count(*) from region where r_regionkey=1")
        base = fpm.fingerprint(p, sess.tables, kind="x", parts={})
        schemas = get_schemas()
        changed = dict(raw["region"])
        changed["r_regionkey"] = np.ascontiguousarray(
            np.array(changed["r_regionkey"]) + 1)
        tables2 = dict(sess.tables)
        tables2["region"] = from_arrays("region", schemas["region"],
                                        changed)
        assert fpm.fingerprint(p, tables2, kind="x", parts={}) != base

    def test_code_epoch_stable(self):
        assert fpm.code_epoch() == fpm.code_epoch()
        assert len(fpm.code_epoch()) == 64


# ------------------------------------------------------------------ store

class TestStore:
    FP = "ab" + "0" * 62

    def test_roundtrip(self, tmp_path):
        store = PlanCache(str(tmp_path / "c"))
        payload = {"exec": b"\x00" * 256, "extra": {"dicts": [1, 2]}}
        assert store.put(self.FP, payload, meta={"kind": "T"})
        assert store.get(self.FP, expect_kind="T") == payload
        # kind mismatch degrades to a miss, not an error
        assert store.get(self.FP, expect_kind="Other") is None

    def test_missing_is_quiet_miss(self, tmp_path):
        store = PlanCache(str(tmp_path / "c"))
        before = obs_metrics.snapshot()
        assert store.get(self.FP) is None
        d = _counters(before)
        assert d.get("compile_cache_misses_total") == 1
        assert not d.get("compile_cache_errors_total")

    def test_corruption_quarantines_and_warns(self, tmp_path, capsys):
        store = PlanCache(str(tmp_path / "c"))
        store.put(self.FP, {"exec": b"\x01" * 512})
        payload_path = store.payload_path(self.FP)
        with open(payload_path, "r+b") as f:
            f.seek(100)
            f.write(b"\xff")
        before = obs_metrics.snapshot()
        assert store.get(self.FP) is None
        d = _counters(before)
        assert d.get("compile_cache_errors_total") == 1
        assert "corrupt entry" in capsys.readouterr().out
        # quarantined: inventory is empty, nothing re-diagnoses it
        assert store.entries() == []
        assert not os.path.exists(store.entry_dir(self.FP))
        # prune --corrupt clears the husk
        removed = store.prune(corrupt=True)
        assert any(".corrupt-" in fp for fp in removed)

    def test_version_skew_degrades(self, tmp_path):
        store = PlanCache(str(tmp_path / "c"))
        store.put(self.FP, {"exec": b"\x02" * 64})
        mpath = os.path.join(store.entry_dir(self.FP), MANIFEST_NAME)
        with open(mpath) as f:
            m = json.load(f)
        m["store_version"] = 99
        with open(mpath, "w") as f:
            json.dump(m, f)
        before = obs_metrics.snapshot()
        assert store.get(self.FP) is None
        assert _counters(before).get("compile_cache_errors_total") == 1

    def test_readonly_never_writes(self, tmp_path):
        root = str(tmp_path / "ro")
        PlanCache(root).put(self.FP, {"exec": b"\x03"})
        store = PlanCache(root, readonly=True)
        assert not store.put("cd" + "0" * 62, {"exec": b"\x04"})
        assert [m["fingerprint"] for m in store.entries()] == [self.FP]
        # readonly quarantine is a no-op: the entry stays
        store._quarantine(self.FP)
        assert store.get(self.FP) is not None

    def test_prune_by_age_and_jax(self, tmp_path):
        store = PlanCache(str(tmp_path / "c"))
        store.put(self.FP, {"exec": b"\x05"}, meta={"jax": "0.0.1"})
        other = "ef" + "0" * 62
        store.put(other, {"exec": b"\x06"}, meta={"jax": "9.9.9"})
        assert store.prune(keep_days=1) == []
        removed = store.prune(jax_version="9.9.9")
        assert removed == [self.FP]
        assert [m["fingerprint"] for m in store.entries()] == [other]


# ----------------------------------------------------------- aot runtime

class TestAot:
    def test_cached_compile_roundtrip(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from nds_tpu.cache import aot
        store = PlanCache(str(tmp_path / "c"))
        fp = "12" + "0" * 62
        x = np.arange(64, dtype=np.float32)
        calls = []

        def build():
            calls.append(1)
            return jax.jit(lambda a: jnp.cumsum(a) * 2)

        c1, extra1, hit1 = aot.cached_compile(
            store, fp, "T", build, (x,),
            extra_fn=lambda: {"dicts": ["d"]})
        assert not hit1 and calls == [1]
        timings = {}
        c2, extra2, hit2 = aot.cached_compile(
            store, fp, "T", build, (x,), timings=timings)
        assert hit2 and calls == [1]          # build() never re-ran
        assert extra2 == {"dicts": ["d"]}
        assert timings["cache_load_ms"] > 0
        assert np.array_equal(np.asarray(c1(x)), np.asarray(c2(x)))

    def test_incompatible_signature_is_miss(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from nds_tpu.cache import aot
        store = PlanCache(str(tmp_path / "c"))
        fp = "34" + "0" * 62
        x = np.arange(64, dtype=np.float32)
        aot.cached_compile(store, fp, "T",
                           lambda: jax.jit(jnp.cumsum), (x,))
        y = np.arange(128, dtype=np.float64)
        hit = aot.load_cached(store, fp, "T", args=(y,))
        assert hit is None  # shape/dtype drift degrades to a miss

    def test_platform_parts_key_the_backend(self):
        from nds_tpu.cache import aot
        parts = aot.platform_parts()
        assert parts["platform"] == "cpu"
        assert "jax" in parts and "jaxlib" in parts

    def test_hit_dispatches_on_a_many_device_host(self, tmp_path):
        """A persisted single-device program must load onto ONE device
        of an 8-device host (or a four-chip one): left to jax, the
        executable loads across all local devices and its first
        dispatch dies with "Expected args to
        execute_sharded_on_local_devices to have 8 shards"."""
        import jax
        import jax.numpy as jnp

        from nds_tpu.cache import aot
        assert len(jax.devices()) == 8
        store = PlanCache(str(tmp_path / "c"))
        fp = "56" + "0" * 62
        x = jnp.arange(64, dtype=jnp.float32)   # a live device buffer
        compiled = aot.lower_and_compile(
            jax.jit(lambda a: jnp.cumsum(a) * 2), x, fresh=True)
        assert aot.persist(store, fp, "T", compiled)
        loaded, _extra = aot.load_cached(store, fp, "T", args=(x,))
        out = loaded(x)
        assert out.sharding.device_set == {jax.devices()[0]}
        assert np.array_equal(np.asarray(out), np.asarray(compiled(x)))

    def test_mesh_program_hit_dispatches_on_its_mesh(self, tmp_path):
        """...and a sharded program onto its mesh's devices, in mesh
        order: four of the eight here."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P_

        from nds_tpu.cache import aot
        from nds_tpu.parallel.dist_exec import shard_map
        from nds_tpu.parallel.mesh import DATA_AXIS, make_mesh
        mesh = make_mesh(4)
        fn = jax.jit(shard_map(
            lambda a: a + lax.psum(jnp.sum(a), DATA_AXIS), mesh=mesh,
            in_specs=P_(DATA_AXIS), out_specs=P_(DATA_AXIS)))
        x = jax.device_put(np.arange(64, dtype=np.float32),
                           NamedSharding(mesh, P_(DATA_AXIS)))
        compiled = aot.lower_and_compile(fn, x, fresh=True)
        store = PlanCache(str(tmp_path / "c"))
        fp = "78" + "0" * 62
        assert aot.persist(store, fp, "T", compiled,
                           devices=mesh.devices.flat)
        loaded, _extra = aot.load_cached(store, fp, "T", args=(x,),
                                         devices=mesh.devices.flat)
        out = loaded(x)
        assert out.sharding.device_set == set(mesh.devices.flat)
        assert np.array_equal(np.asarray(out), np.asarray(x) + 2016.0)


# ----------------------------------------- executor integration (device)

class TestDeviceWarm:
    def test_second_executor_serves_warm(self, raw, tmp_path):
        from nds_tpu.engine.device_exec import make_device_factory
        plan_cache.configure(str(tmp_path / "pc"))
        before = obs_metrics.snapshot()
        a = _run(_session(raw, make_device_factory()), 1)
        cold = _counters(before)
        assert cold.get("compiles_total", 0) >= 1
        assert cold.get("compile_cache_bytes_written_total", 0) > 0
        # a NEW executor (fresh in-memory caches) in the same process:
        # every program deserializes from disk, zero compiles
        before = obs_metrics.snapshot()
        b = _run(_session(raw, make_device_factory()), 1)
        warm = _counters(before)
        assert not warm.get("compiles_total")
        assert not warm.get("recompiles_total")
        assert warm.get("compile_cache_hits_total", 0) >= 1
        assert a.to_pandas().equals(b.to_pandas())

    def test_chunked_executor_serves_warm(self, raw, tmp_path):
        """The out-of-core engine's sub-programs (phase-A chunk scans +
        phase-B partials) consult the same store: a fresh chunked
        executor against a warm cache compiles nothing."""
        from nds_tpu.engine.chunked_exec import make_chunked_factory
        plan_cache.configure(str(tmp_path / "pc"))

        def factory():
            # tiny stream threshold: lineitem really streams in chunks
            return make_chunked_factory(stream_bytes=1 << 16,
                                        chunk_rows=4096)
        before = obs_metrics.snapshot()
        a = _run(_session(raw, factory()), 6)
        cold = _counters(before)
        assert cold.get("compiles_total", 0) >= 1
        before = obs_metrics.snapshot()
        b = _run(_session(raw, factory()), 6)
        warm = _counters(before)
        assert not warm.get("compiles_total"), warm
        assert warm.get("compile_cache_hits_total", 0) >= 1
        assert a.to_pandas().equals(b.to_pandas())

    def test_distributed_executor_serves_warm(self, raw, tmp_path):
        """Sharded programs round-trip too (single-process worlds; a
        multi-controller run falls back to jax's own XLA cache): a
        fresh executor on the same 8-device virtual mesh serves every
        program — including slack-grown recompiles — from disk."""
        from nds_tpu.parallel.dist_exec import make_distributed_factory
        plan_cache.configure(str(tmp_path / "pc"))
        before = obs_metrics.snapshot()
        a = _run(_session(raw, make_distributed_factory(n_devices=8)),
                 6)
        cold = _counters(before)
        assert (cold.get("compiles_total", 0)
                + cold.get("recompiles_total", 0)) >= 1
        before = obs_metrics.snapshot()
        b = _run(_session(raw, make_distributed_factory(n_devices=8)),
                 6)
        warm = _counters(before)
        assert not warm.get("compiles_total"), warm
        assert not warm.get("recompiles_total"), warm
        assert warm.get("compile_cache_hits_total", 0) >= 1
        assert a.to_pandas().equals(b.to_pandas())

    def test_cache_off_is_null_change(self, raw):
        from nds_tpu.engine.device_exec import make_device_factory
        plan_cache.configure(None)  # explicit off
        before = obs_metrics.snapshot()
        _run(_session(raw, make_device_factory()), 6)
        d = _counters(before)
        assert d.get("compiles_total", 0) >= 1
        assert not d.get("compile_cache_misses_total")
        assert not d.get("compile_cache_hits_total")


# -------------------------------------------- cross-process warm start

@pytest.fixture(scope="module")
def nds_h_warehouse(tmp_path_factory):
    """Tiny NDS-H warehouse + power stream shared by the warm-start
    test: the subprocess and the parent must load IDENTICAL table
    content or the fingerprints (content stamps) would not match."""
    from nds_tpu.nds_h import gen_data, streams, transcode
    root = tmp_path_factory.mktemp("nds_h_wh")
    raw_dir = str(root / "raw")
    wh = str(root / "wh")
    gen_data.generate_data_local(SF, 2, raw_dir, workers=2)
    transcode.transcode(raw_dir, wh, str(root / "load_report.txt"))
    sdir = str(root / "streams")
    streams.generate_query_streams(sdir, 1)
    return {"wh": wh, "stream": os.path.join(sdir, "stream_0.sql"),
            "root": str(root)}


WARM_SUBSET = ["query1", "query6", "query12"]

_CHILD = """
import sys
from nds_tpu.nds_h.power import SUITE
from nds_tpu.utils import power_core
from nds_tpu.utils.config import EngineConfig

wh, stream, tlog, jsons, out = sys.argv[1:6]
cfg = EngineConfig(overrides={
    "engine.backend": "tpu",
    "engine.placement.force": "device",
})
failures = power_core.run_query_stream(
    SUITE, wh, stream, tlog, config=cfg,
    json_summary_folder=jsons, output_prefix=out,
    query_subset="@SUBSET@".split(","))
sys.exit(failures)
"""


class TestCrossProcessWarmStart:
    def test_warm_start_zero_compiles(self, nds_h_warehouse, tmp_path):
        """ISSUE 7 acceptance: subprocess populates the cache; the
        parent re-runs the same 3-query power stream and performs 0
        compiles with identical rows."""
        from nds_tpu.io.result_io import read_result
        from nds_tpu.nds_h.power import SUITE
        from nds_tpu.utils import power_core
        from nds_tpu.utils.config import EngineConfig

        cache_dir = str(tmp_path / "pc")
        child_out = str(tmp_path / "child_rows")
        env = dict(os.environ)
        env["NDS_TPU_PLAN_CACHE"] = cache_dir  # the env activation path
        env.setdefault("JAX_PLATFORMS", "cpu")
        script = _CHILD.replace("@SUBSET@", ",".join(WARM_SUBSET))
        proc = subprocess.run(
            [sys.executable, "-c", script, nds_h_warehouse["wh"],
             nds_h_warehouse["stream"], str(tmp_path / "child.csv"),
             str(tmp_path / "child_json"), child_out],
            env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        store = PlanCache(cache_dir, readonly=True)
        assert store.entries(), "subprocess persisted nothing"
        assert store.verify() == []

        # parent rerun: config activation path, same warehouse
        jsons = str(tmp_path / "parent_json")
        parent_out = str(tmp_path / "parent_rows")
        cfg = EngineConfig(overrides={
            "engine.backend": "tpu",
            "engine.placement.force": "device",
            "cache.dir": cache_dir,
        })
        before = obs_metrics.snapshot()
        failures = power_core.run_query_stream(
            SUITE, nds_h_warehouse["wh"], nds_h_warehouse["stream"],
            str(tmp_path / "parent.csv"), config=cfg,
            json_summary_folder=jsons, output_prefix=parent_out,
            query_subset=WARM_SUBSET)
        d = _counters(before)
        assert failures == 0
        # THE acceptance numbers: zero compiles, hits for every query
        assert not d.get("compiles_total"), d
        assert not d.get("recompiles_total"), d
        assert d.get("compile_cache_hits_total", 0) >= len(WARM_SUBSET)
        assert not d.get("compile_cache_errors_total"), d

        summaries = {}
        for f in os.listdir(jsons):
            with open(os.path.join(jsons, f)) as fh:
                s = json.load(fh)
            # the run dir also holds the resume journal
            # (<unit>_queries.json) — only BenchReports count here
            if isinstance(s, dict) and "query" in s:
                summaries[s["query"]] = s
        for q in WARM_SUBSET:
            s = summaries[q]
            assert s["queryStatus"] == ["Completed"], s["queryStatus"]
            # BenchReport cache block: all hits, no misses
            assert s["cache"]["hits"] >= 1, s.get("cache")
            assert s["cache"]["misses"] == 0, s.get("cache")
            assert s["cache"]["load_ms"] > 0
            # compile_ms stays 0 on the hit path; the deserialize cost
            # is billed separately
            assert s["engineTimings"].get("compile_ms", 0) == 0, \
                s["engineTimings"]
            assert s["engineTimings"]["cache_load_ms"] > 0
        # identical rows, child vs parent
        for q in WARM_SUBSET:
            a = read_result(os.path.join(child_out, q))
            b = read_result(os.path.join(parent_out, q))
            assert a.equals(b), f"{q} rows diverged across processes"


# ------------------------------------------------------- ndscache CLI

class TestNdsCacheCli:
    def _tool(self):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import ndscache
        return ndscache

    def test_ls_verify_prune(self, tmp_path, capsys):
        ndscache = self._tool()
        root = str(tmp_path / "c")
        store = PlanCache(root)
        fp = "ab" + "1" * 62
        store.put(fp, {"exec": b"\x00" * 128}, meta={"kind": "T"})
        assert ndscache.main(["ls", "--dir", root]) == 0
        out = capsys.readouterr().out
        assert fp[:16] in out and "1 entry" in out
        assert ndscache.main(["verify", "--dir", root]) == 0
        # corrupt it -> verify exits 1, prune --corrupt removes it
        p = store.payload_path(fp)
        with open(p, "r+b") as f:
            f.write(b"\xee")
        assert ndscache.main(["verify", "--dir", root]) == 1
        assert ndscache.main(["prune", "--dir", root, "--corrupt"]) == 0
        assert ndscache.main(["verify", "--dir", root]) == 0
        assert "0 corrupt of 0" in capsys.readouterr().out

    def test_warm_subset_then_all_hits(self, tmp_path, capsys):
        """`ndscache warm` compiles a statement subset into a cold
        cache on bare CPU; warming again serves every program from the
        cache (the acceptance sweep runs all 125 — tier-1 proves the
        mechanism on two)."""
        ndscache = self._tool()
        root = str(tmp_path / "c")
        before = obs_metrics.snapshot()
        rc = ndscache.main(["warm", "--dir", root, "--suite", "nds_h",
                            "--sf", "0.002", "--queries", "q1", "q6"])
        assert rc == 0
        cold = _counters(before)
        assert cold.get("compiles_total", 0) >= 2
        assert "warmed 2 statement(s) (0 failed)" in \
            capsys.readouterr().out
        store = PlanCache(root, readonly=True)
        assert store.entries() and store.verify() == []
        plan_cache.reset()
        before = obs_metrics.snapshot()
        assert ndscache.main(["warm", "--dir", root, "--suite",
                              "nds_h", "--sf", "0.002", "--queries",
                              "q1", "q6"]) == 0
        warm = _counters(before)
        assert not warm.get("compiles_total")
        assert warm.get("compile_cache_hits_total", 0) >= 2


# ------------------------------------------------- summary schema gate

class TestSummarySchema:
    def _validate(self, cache_block):
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import check_trace_schema as cts
        obj = {"query": "q", "queryStatus": ["Completed"],
               "queryTimes": [1], "startTime": 1, "env": {},
               "cache": cache_block}
        return cts.validate_summary(obj)

    def test_cache_block_valid(self):
        assert self._validate({"hits": 2, "misses": 0}) == []
        assert self._validate({"hits": 0, "misses": 3, "errors": 1,
                               "bytes_read": 10, "bytes_written": 20,
                               "load_ms": 1.5}) == []

    def test_cache_block_invalid(self):
        assert self._validate({"hits": 2})            # misses missing
        assert self._validate({"hits": -1, "misses": 0})
        assert self._validate({"hits": 1, "misses": 0,
                               "load_ms": "fast"})
        assert self._validate({"hits": 1, "misses": 0,
                               "bytes_read": -5})


# ------------------------------------- parameterized-fingerprint sharing

class TestParameterizedSharing:
    """ISSUE 12: same-template literal variants must land on ONE cache
    entry and pay zero compiles after the first (sql/params.py)."""

    def _variant(self, seed: int) -> str:
        import random

        from nds_tpu.nds_h import streams as hs
        return hs.render_query(5, hs.random_params(
            5, random.Random(seed), 0))

    def test_two_literal_variants_one_entry_zero_miss(self, raw,
                                                      tmp_path):
        from nds_tpu.engine.device_exec import make_device_factory
        plan_cache.configure(str(tmp_path / "pc"))
        dev = _session(raw, make_device_factory())
        dev.parameterize = True
        oracle = _session(raw)
        a, b = self._variant(31), self._variant(32)
        assert a != b, "variants must differ in literals"

        before = obs_metrics.snapshot()
        ra = dev.sql(a)
        cold = _counters(before)
        assert cold.get("compiles_total", 0) >= 1
        store = PlanCache(str(tmp_path / "pc"), readonly=True)
        entries_cold = len(store.entries())

        before = obs_metrics.snapshot()
        rb = dev.sql(b)
        warm = _counters(before)
        # the literal variant shares the in-process compiled program:
        # no compile, no cache consult, no new entry
        assert not warm.get("compiles_total")
        assert not warm.get("compile_cache_misses_total")
        assert len(store.entries()) == entries_cold

        # parity: each variant's rows equal the CPU oracle's for the
        # SAME literals
        from test_device_engine import assert_frames_close
        assert_frames_close(ra.to_pandas(), oracle.sql(a).to_pandas(),
                            5)
        assert_frames_close(rb.to_pandas(), oracle.sql(b).to_pandas(),
                            5)

    def test_variant_hits_across_processes_via_store(self, raw,
                                                     tmp_path):
        """Variant B in a FRESH executor (new in-process caches) must
        be served by the store entry variant A persisted — the
        cross-process sharing the fingerprint identity buys."""
        from nds_tpu.engine.device_exec import make_device_factory
        plan_cache.configure(str(tmp_path / "pc"))
        dev_a = _session(raw, make_device_factory())
        dev_a.parameterize = True
        dev_a.sql(self._variant(41))

        dev_b = _session(raw, make_device_factory())
        dev_b.parameterize = True
        before = obs_metrics.snapshot()
        dev_b.sql(self._variant(42))
        warm = _counters(before)
        assert not warm.get("compiles_total")
        assert warm.get("compile_cache_hits_total", 0) >= 1
