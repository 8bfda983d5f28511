"""The four-chip NDS (TPC-DS) deployment (benchmark cell
``nds_sf1.dist4``) on the virtual CPU mesh, at SF0.01: the shipped
distributed template through ``make_session``, the cell's own five
statements held against the benchmark's plain references by the
benchmark's own comparison and against the single-device executor row
for row, a planted fault after a replicate, what the sharded trace says
of its replicates (``replicates`` / ``replicate_bytes`` on
``device.launch``, ``replicate.<who>`` in ``kernels``), query27's ROLLUP
as exchanges plus one key-less ``psum`` aggregate, NULL foreign keys
through the exchange, the ORDER BY default the comparison holds the
engine to (NULL lowest), and the cell's entries in ``BENCHMARK.json``.
The GROUP BY's scan bound (``agg.scan_bound``): query98 and query21
against the CPU oracle, and every program of this cell and of the
older cells lowered with the bound and without it, equal to the byte
wherever the statement's ``kernels`` do not carry it.  The operator and
mechanism scopes (``op.<kind>``, ``exchange``, ``replicate``,
``gather``): the same programs lowered with them and without them, equal
to the byte but for locations, the compiled programs equal but for
their metadata, and every instruction of them that the trace made under
an operator.  DISTINCT and INTERSECT / EXCEPT on each chip's share
after an exchange by the row hash: against the CPU oracle over
duplicates on every shard, NULLs, two dictionaries, a replicated side
and a floating-point column, on the flat and the 2-D mesh; query38's
``kernels``; and every program but query38's lowered to the same text
as with the whole relation on every device.
"""

import contextlib
import json
import os
import re

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = os.path.join(ROOT, "benchmarks")
TEMPLATE = os.path.join(ROOT, "configs", "power_run_distributed.template")
ONE_CHIP_TEMPLATE = os.path.join(ROOT, "configs", "power_run_tpu.template")
SF = 0.01
MIX = "power_nds_dist4"
CELL = "nds_sf1.dist4"
NAMES = ["query7", "query27", "query98", "query38", "query21"]
LIMITS = {"failed_statements": 0, "rows_wrong": 0, "repeats_differ": 0,
          "max_rel_gap": 1e-9}
CONFIG = {"suite": "nds", "limits": LIMITS}
N_DEV = 4


def _suite(name):
    import importlib
    return importlib.import_module(f"nds_tpu.{name}.power").SUITE


def _session(raw, suite="nds", shards=None, cache_dir=None, backend=None,
             template=None):
    """A session as ``benchmarks/run.py`` makes it: the distributed
    template as shipped (``shards``: the ``engine.mesh.shards``
    override), or another shipped ``template``; ``backend``: a
    single-device session of no template instead."""
    from nds_tpu.utils import power_core
    from nds_tpu.utils.config import EngineConfig
    if backend:
        econf = EngineConfig(overrides={"engine.backend": backend})
    else:
        overrides = {}
        if shards:
            overrides["engine.mesh.shards"] = str(shards)
        if cache_dir:
            overrides["cache.dir"] = str(cache_dir)
        econf = EngineConfig(template or TEMPLATE, None, overrides)
    session = power_core.make_session(_suite(suite), econf)
    power_core.load_warehouse(
        _suite(suite), session, raw, "raw",
        schemas=power_core.suite_schemas(_suite(suite), econf))
    return session


@pytest.fixture(autouse=True)
def _no_plan_cache_left_behind():
    from nds_tpu import cache as plan_cache
    yield
    plan_cache.reset()


def _statements(mix=MIX):
    from benchmarks import generator
    loaded = generator.load_mix(mix)
    return generator.distinct(loaded, generator.variants(loaded, 7))


def _statement(name):
    return next(s for s in _statements() if s.name == name)


def _verdict(records, raw):
    from benchmarks import run
    return run.check_rows({"records": records}, CONFIG, raw)


def _sharded_executor(session):
    return session._executor_factory(session.tables)._executor("sharded")


def _root_of(span):
    while span.parent is not None:
        span = span.parent
    return span


def _names(spans):
    return [s.name for s in spans]


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    from nds_tpu.nds import gen_data
    out = tmp_path_factory.mktemp("nds_dist4") / "raw"
    gen_data.generate_data_local(SF, 2, str(out), workers=2)
    return str(out)


def _replicate_log(mp, log):
    """Every relation ``_replicate`` gathers from here on: (who, slots a
    device, bytes a slot over the row mask, every column and every
    validity), and every call of the key-less sharded aggregate."""
    from nds_tpu.parallel import dist_exec
    replicate = dist_exec._DistTrace._replicate
    keyless = dist_exec._DistTrace._global_agg_sharded

    def spy_replicate(self, ctx, who):
        if getattr(ctx, "sharded", False):
            width = ctx.row.dtype.itemsize
            for dv in ctx.cols.values():
                width += dv.arr.dtype.itemsize * int(
                    np.prod(dv.arr.shape[1:]))
                if dv.valid is not None:
                    width += dv.valid.dtype.itemsize
            log.append(("replicate", who, ctx.n, width))
        return replicate(self, ctx, who)

    def spy_keyless(self, node, ctx):
        log.append(("keyless", len(node.aggs), ctx.n, 0))
        return keyless(self, node, ctx)

    mp.setattr(dist_exec._DistTrace, "_replicate", spy_replicate)
    mp.setattr(dist_exec._DistTrace, "_global_agg_sharded", spy_keyless)


@pytest.fixture(scope="module")
def dist4(raw, tmp_path_factory):
    """The five statements run twice on a 4-device mesh: span trees,
    records, ``kernels``, what the trace replicated, and a plan cache a
    second executor can load from."""
    from benchmarks import run
    cache = tmp_path_factory.mktemp("nds_dist4_plans")
    session = _session(raw, shards=N_DEV, cache_dir=cache)
    pipe = session._executor_factory(session.tables)
    ex = _sharded_executor(session)
    out = {"session": session, "cache": str(cache), "records": {},
           "first": {}, "warm": {}, "kernels": {}, "traced": {}}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE", str(
            tmp_path_factory.mktemp("nds_dist4_trace") / "t.jsonl"))
        log = []
        _replicate_log(mp, log)
        for stmt in _statements():
            del log[:]
            for which in ("first", "warm"):
                rec = run.run_statement(session, stmt)
                assert rec["error"] is None, rec["error"]
                out[which][stmt.name] = _root_of(pipe.last_query_span)
                out["records"].setdefault(stmt.name, []).append(rec)
            out["traced"][stmt.name] = list(log)
            out["kernels"][stmt.name] = dict(ex.last_timings["__kernels"])
    from nds_tpu import cache as plan_cache
    plan_cache.reset()          # module-scoped: set up before the autouse
    return out


@pytest.fixture(scope="module")
def single(raw):
    return _session(raw, backend="tpu")


# ------------------------------------------------- (a) the answers

@pytest.mark.parametrize("name", NAMES)
def test_statement_matches_the_plain_reference(dist4, raw, name):
    """``correct`` as the cell decides it: the first execution in full
    against the pandas reference, ORDER BY held (NULL lowest), the
    repeat against the first, placed ``sharded``, never rescheduled."""
    records = dist4["records"][name]
    for rec in records:
        assert rec["placement"] == "sharded" and rec["reschedules"] == 0
        assert rec["ladder"] == ["sharded"]
    verdict = _verdict(records, raw)
    assert verdict["correct"] is True, verdict["notes"]
    assert verdict["checks"]["rows_wrong"]["value"] == 0
    assert verdict["checks"]["repeats_differ"]["value"] == 0
    assert verdict["per_stmt"][f"{name}#0"]["rows"] > 0


@pytest.mark.parametrize("name", NAMES)
def test_statement_matches_the_single_device_executor(dist4, single, name):
    """Row for row, in the returned order: integers, strings, dates,
    NULLs and exact decimals to the digit, averages and ratios to the
    last bits a different summation order leaves."""
    stmt = _statement(name)
    want = single.sql(stmt.sql).to_pandas()
    got = dist4["records"][name][0]["result"].to_pandas()
    assert len(got) == len(want) > 0
    pd.testing.assert_frame_equal(got, want, check_exact=False,
                                  rtol=1e-12, atol=0)


@pytest.fixture(scope="module")
def oracle(raw):
    return _session(raw, backend="cpu")


@pytest.mark.parametrize("name", ["query98", "query21"])
def test_scan_bounded_group_by_matches_the_cpu_oracle(dist4, oracle, name):
    """query98's five item columns and query21's warehouse and item
    columns are keys of replicated scans: the GROUP BY after the
    exchange takes their rows as its capacity where that is less than
    the key domains (``agg.scan_bound``: query98 here, query21 at SF1),
    and the rows are the CPU oracle's."""
    stmt = _statement(name)
    want = oracle.sql(stmt.sql).to_pandas()
    got = dist4["records"][name][0]["result"].to_pandas()
    assert len(got) == len(want) > 0
    pd.testing.assert_frame_equal(got, want, check_exact=False,
                                  rtol=1e-12, atol=0)
    assert dist4["kernels"][name].get("agg.scan_bound") == (
        1 if f"{MIX}:{name}#0" in SCAN_BOUNDED else None)


def test_every_program_was_launched_by_the_sharded_executor(dist4):
    """query38 is two programs (the staged split): both go through the
    sharded executor, so no single-device executor is live beside it
    (ROADMAP R5's condition is not met)."""
    for name in NAMES:
        root = dist4["warm"][name]
        executors = {e.attrs.get("executor")
                     for e in root.find("device.execute")}
        assert executors == {"DistributedExecutor"}, (name, executors)
    launches = dist4["warm"]["query38"].find("device.launch")
    assert len(launches) == 2
    assert launches[0].attrs["exchanges"] > 0
    assert launches[1].attrs["exchanges"] == 0
    assert launches[1].attrs["replicates"] == 0


def test_rows_lost_after_a_replicate_read_rows_wrong(raw, monkeypatch):
    """Planted fault: what the replicate brings from chip 0 is lost (its
    slots of the gathered row mask read dead). The window then sees
    three quarters of the groups: the answer differs, and the comparison
    reads ``rows_wrong``."""
    import jax.numpy as jnp
    from benchmarks import run
    from nds_tpu.parallel import dist_exec
    inner = dist_exec._DistTrace._replicate

    def lossy(self, ctx, who):
        out = inner(self, ctx, who)
        if out is not ctx:
            out.row = out.row & (jnp.arange(out.n) >= ctx.n)
        return out

    monkeypatch.setattr(dist_exec._DistTrace, "_replicate", lossy)
    session = _session(raw, shards=N_DEV)
    q98 = _statement("query98")
    verdict = _verdict([run.run_statement(session, q98)], raw)
    assert verdict["correct"] is False
    assert verdict["checks"]["failed_statements"]["value"] == 0
    assert verdict["checks"]["rows_wrong"]["value"] == 1


# ------------------------------------- (b) what the trace says it gathered

# query38 at this scale: web_sales is under the shard threshold, so its
# join replicates customer's shard; the INTERSECT chain stays sharded to
# the root, which gathers it
WHO = {"query7": {"replicate.limit": 1},
       "query27": {"replicate.setop": 2},
       "query98": {"replicate.window": 1},
       "query38": {"replicate.join": 1, "replicate.root": 1},
       "query21": {"replicate.limit": 1}}


@pytest.mark.parametrize("name", NAMES)
def test_replicates_ride_the_launch_span_and_name_who_asked(dist4, name):
    """``replicate_bytes`` is the traced shapes' arithmetic: the other
    three chips' slots of the row mask, every column and every validity
    of each relation gathered; ``kernels`` says which operator asked."""
    traced = [t for t in dist4["traced"][name] if t[0] == "replicate"]
    want_bytes = sum((N_DEV - 1) * n * width for _r, _w, n, width in traced)
    launches = dist4["warm"][name].find("device.launch")
    assert sum(a.attrs["replicates"] for a in launches) == len(traced) > 0
    assert sum(a.attrs["replicate_bytes"] for a in launches) == want_bytes
    assert want_bytes > 0
    kernels = dist4["kernels"][name]
    noted = {k: v for k, v in kernels.items() if k.startswith("replicate.")}
    by_who: dict = {}
    for _r, who, _n, _width in traced:
        by_who[f"replicate.{who}"] = by_who.get(f"replicate.{who}", 0) + 1
    if len(launches) == 1:
        assert noted == by_who == WHO[name]
    else:       # `kernels` is the last program's; the log holds both
        assert by_who == WHO[name]
    # the first execution said the same
    first = dist4["first"][name].find("device.launch")
    assert [a.attrs["replicate_bytes"] for a in first] == \
        [a.attrs["replicate_bytes"] for a in launches]


def test_replicate_counter_moves_with_every_launch(dist4):
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    q98 = _statement("query98")
    (launch,) = dist4["warm"]["query98"].find("device.launch")
    before = obs_metrics.snapshot()["counters"]
    rec = run.run_statement(dist4["session"], q98)
    assert rec["error"] is None
    after = obs_metrics.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert moved["replicate_bytes_total"] == launch.attrs["replicate_bytes"]
    assert moved["exchange_bytes_total"] == launch.attrs["exchange_bytes"]


def test_replicate_attributes_survive_a_plan_cache_load(dist4, raw):
    """A second executor finds query98's program in the plan cache,
    compiles nothing, and says the same of its replicates."""
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    session = _session(raw, shards=N_DEV, cache_dir=dist4["cache"])
    pipe = session._executor_factory(session.tables)
    q98 = _statement("query98")
    before = obs_metrics.snapshot()["counters"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE", os.path.join(dist4["cache"], "t.jsonl"))
        rec = run.run_statement(session, q98)
    assert rec["error"] is None and rec["placement"] == "sharded"
    after = obs_metrics.snapshot()["counters"]
    assert after.get("compiles_total", 0) == before.get("compiles_total", 0)
    root = _root_of(pipe.last_query_span)
    assert "cache.load" in _names(root.find("device.dispatch")[0].children)
    (loaded,) = root.find("device.launch")
    (traced,) = dist4["warm"]["query98"].find("device.launch")
    for key in ("replicates", "replicate_bytes", "exchanges",
                "exchange_bytes"):
        assert loaded.attrs[key] == traced.attrs[key] > 0
    kernels = _sharded_executor(session).last_timings["__kernels"]
    assert kernels["replicate.window"] == 1
    assert _verdict([rec], raw)["correct"] is True


def test_rollup_is_two_exchanges_and_one_keyless_psum_aggregate(dist4):
    """ROLLUP(i_item_id, s_state) is three aggregates over one shared
    child: the two keyed ones repartition it by their keys (one exchange
    each: at this scale the dimensions are replicated and no join
    exchanges), the grand total is per-chip partials combined by
    ``psum``; each keyed branch is gathered for the UNION ALL."""
    traced = dist4["traced"]["query27"]
    keyless = [t for t in traced if t[0] == "keyless"]
    assert len(keyless) == 1 and keyless[0][1] == 4     # the four avgs
    (launch,) = dist4["warm"]["query27"].find("device.launch")
    assert launch.attrs["exchanges"] == 2
    assert launch.attrs["replicates"] == 2
    # the control has the one group-by and nothing key-less
    (q7,) = dist4["warm"]["query7"].find("device.launch")
    assert q7.attrs["exchanges"] == 1
    assert not [t for t in dist4["traced"]["query7"] if t[0] == "keyless"]
    got = dist4["records"]["query27"][0]["result"].to_pandas()
    total = got[got.iloc[:, 0].isna()]
    assert len(total) == 1 and int(total.iloc[0, 2]) == 1   # grouping()
    assert total.index[0] == 0                              # NULL lowest
    subtotals = got[got.iloc[:, 1].isna() & got.iloc[:, 0].notna()]
    assert len(subtotals) > 0 and set(subtotals.iloc[:, 2]) == {1}


# ------------- (c) the older cells' programs are the parent's, but one

def _parent_replicate(self, ctx, who=None):
    """``_DistTrace._replicate`` as the parent commit had it: no note,
    no tally."""
    from jax import lax
    from nds_tpu.engine.device_exec import DCtx
    from nds_tpu.parallel.dist_exec import _rows
    if not getattr(ctx, "sharded", False):
        return ctx
    n = ctx.n * self.n_dev
    out = DCtx(n, lax.all_gather(ctx.row, self.axes, tiled=True))
    for k, dv in ctx.cols.items():
        arr = lax.all_gather(dv.arr, self.axes, tiled=True)
        valid = (None if dv.valid is None
                 else lax.all_gather(dv.valid, self.axes, tiled=True))
        out.cols[k] = dv.with_arrays(arr, valid)
    return self._stamp(out, False, _rows(ctx) * self.n_dev)


def _parent_run_distinct(self, node):
    """``_DistTrace._run_distinct`` as the parent commit had it: a
    sharded input whole on every device."""
    from nds_tpu.engine import device_exec as dx
    child = self.run(node.child)
    if getattr(child, "sharded", False):
        self.stash(node.child, self._replicate(child, "distinct"))
        self._cache.pop(id(node), None)
    out = dx._Trace._run_distinct(self, node)
    out.sharded = False
    return out


def _parent_run_setop(self, node):
    """``_DistTrace._run_setop`` as the parent commit had it: both sides
    whole on every device, whatever the kind."""
    from nds_tpu.engine import device_exec as dx
    for side in (node.left, node.right):
        c = self.run(side)
        if getattr(c, "sharded", False):
            self.stash(side, self._replicate(c, "setop"))
    self._cache.pop(id(node), None)
    out = dx._Trace._run_setop(self, node)
    out.sharded = False
    return out


def _parent_nulls_first(item):
    """``planner._nulls_first`` as the parent commit had it: what the
    ORDER BY says or None, which every executor's sort reads as NULL
    last."""
    return item.nulls_first


# every distinct statement of the five older cells: (mix, suite, mesh);
# `power_nds_h_sf5` is `power_nds_h`'s SQL (the test below holds it to
# that), and `power_nds_h`'s two q6 are `short`'s first two
OLDER_MIXES = (("power_dist4", "nds_h", N_DEV), ("short", "nds_h", None),
               ("power_nds_h", "nds_h", None), ("power_nds", "nds", None))
OLDER = (["power_dist4:" + n for n in ("q1#0", "q3#0", "q5#0", "q18#0")]
         + [f"short:{q}#{v}" for q in ("q6", "q14", "q19")
            for v in range(4)]
         + ["power_nds_h:" + n for n in (
             "q1#0", "q3#0", "q18#0", "q13#0", "q16#0", "q21#0")]
         + ["power_nds:" + n for n in ("query96#0", "query7#0", "query3#0")])
# and this cell's
OWN = [f"{MIX}:{n}#0" for n in NAMES]
# the one program the ORDER BY default changes: query96 orders by its
# one count, a key-less aggregate's column, which carries a validity
PROGRAMS_CHANGED = {"power_nds:query96#0"}
# the statements whose GROUP BY takes the scan bound at SF0.01
# (`_Trace._scan_bound`): query98's five item columns, q16's three of
# part's reduced view; query21's item ids are bounded by their
# dictionary at this scale, and take the bound at SF1 only
SCAN_BOUNDED = {f"{MIX}:query98#0", "power_nds_h:q16#0"}


def _no_scan_bound(n, group_keys, keyvals):
    """``_Trace._scan_bound`` that never binds: the group capacity as
    the domains alone gave it, before the scan bound."""
    return n


def _no_scope(_name):
    """``jax.named_scope`` that names nothing."""
    return contextlib.nullcontext()


# (side, mixes lowered, the scan bound taken out, the replicate's notes
# and the ORDER BY default put back to the commit before them, the
# scopes taken out, DISTINCT and INTERSECT / EXCEPT put back to the
# whole relation on every device)
SIDES = (("change", OLDER_MIXES + ((MIX, "nds", N_DEV),), False, False,
          False, False),
         ("no_scan_bound", OLDER_MIXES + ((MIX, "nds", N_DEV),), True, False,
          False, False),
         ("parent", OLDER_MIXES, True, True, False, False),
         ("no_scopes", OLDER_MIXES + ((MIX, "nds", N_DEV),), False, False,
          True, False),
         ("sets_everywhere", OLDER_MIXES + ((MIX, "nds", N_DEV),), False,
          False, False, True))
# the sides whose programs are compiled afresh and kept as text: jax's
# persistent cache leaves metadata out of its key, so a program it served
# would carry the scopes of whichever tree compiled it first
COMPILED = ("change", "no_scopes")


@pytest.fixture(scope="module")
def older_lowered(raw, tmp_path_factory):
    """The lowered text and ``kernels`` of every program of the older
    cells' statements and of this cell's (SF0.01; the shipped
    templates), as this tree lowers them, with the scan bound taken
    out, with the two changes that came with this cell put back too
    (the replicate without its notes, the planner without its ORDER BY
    default), with the scopes taken out, and with DISTINCT and
    INTERSECT / EXCEPT replicating their sharded inputs again (a
    recorder round ``cache.aot.lower_and_compile``); the compiled text
    of this tree's programs with the scopes and without them."""
    from benchmarks import run
    from nds_tpu.cache import aot
    from nds_tpu.engine import device_exec as dx
    from nds_tpu.nds_h import gen_data
    from nds_tpu.parallel import dist_exec
    from nds_tpu.sql import planner
    raw_h = tmp_path_factory.mktemp("nds_dist4_h") / "raw"
    gen_data.generate_data_local(SF, 2, str(raw_h), workers=2)
    population = {"nds_h": str(raw_h), "nds": raw}
    compile_ = aot.lower_and_compile
    counts = dx._Trace.kernel_counts
    import jax
    texts, kernels, compiled = {}, {}, {}
    for side, mixes, unbound, before_cell, unscoped, everywhere in SIDES:
        kept = texts.setdefault(side, {})
        noted = kernels.setdefault(side, {})
        done = compiled.setdefault(side, {})
        with pytest.MonkeyPatch.context() as mp:
            if unscoped:
                mp.setattr(jax, "named_scope", _no_scope)
            if everywhere:
                mp.setattr(dist_exec._DistTrace, "_run_distinct",
                           _parent_run_distinct)
                mp.setattr(dist_exec._DistTrace, "_run_setop",
                           _parent_run_setop)
            if unbound:
                mp.setattr(dx._Trace, "_scan_bound",
                           staticmethod(_no_scan_bound))
            if before_cell:
                mp.setattr(dist_exec._DistTrace, "_replicate",
                           _parent_replicate)
                mp.setattr(planner, "_nulls_first", _parent_nulls_first)
            traced = []

            def kernel_counts(self):
                traced.append(counts(self))
                return traced[-1]

            mp.setattr(dx._Trace, "kernel_counts", kernel_counts)
            sessions, seen = {}, set()
            for mix, suite, shards in mixes:
                if (suite, shards) not in sessions:
                    sessions[suite, shards] = _session(
                        population[suite], suite=suite, shards=shards,
                        template=None if shards else ONE_CHIP_TEMPLATE)
                for stmt in _statements(mix):
                    if (suite, shards, stmt.sql) in seen:
                        continue
                    seen.add((suite, shards, stmt.sql))

                    def keep_text(jitted, *args,
                                  _key=f"{mix}:{stmt.label}", **kw):
                        kept.setdefault(_key, []).append(
                            jitted.lower(*args).as_text())
                        noted.setdefault(_key, []).append(traced[-1])
                        if side not in COMPILED:
                            return compile_(jitted, *args, **kw)
                        out = compile_(jitted, *args, **{**kw, "fresh": True})
                        done.setdefault(_key, []).append(out.as_text())
                        return out

                    mp.setattr(aot, "lower_and_compile", keep_text)
                    rec = run.run_statement(sessions[suite, shards], stmt)
                    assert rec["error"] is None, rec["error"]
    return {"texts": texts, "kernels": kernels, "compiled": compiled}


def test_the_older_cells_statements_are_the_ones_lowered(older_lowered):
    texts = older_lowered["texts"]
    assert sorted(texts["change"]) == sorted(texts["no_scan_bound"]) == \
        sorted(texts["sets_everywhere"]) == sorted(OLDER + OWN)
    assert sorted(texts["parent"]) == sorted(OLDER)
    sf1, sf5 = (_statements(m) for m in ("power_nds_h", "power_nds_h_sf5"))
    assert [s.sql for s in sf1] == [s.sql for s in sf5]
    sharded = [t for k, ts in texts["change"].items() for t in ts
               if k.startswith("power_dist4:")]
    assert len(sharded) == 4 and all("all_to_all" in t for t in sharded)


@pytest.mark.parametrize("key", OLDER)
def test_no_program_of_an_older_cell_changes_but_query96(older_lowered, key):
    """What came with this cell alone: the tree without the scan bound
    against the commit before the cell."""
    change, parent = (older_lowered["texts"][s][key]
                      for s in ("no_scan_bound", "parent"))
    assert len(change) == len(parent) >= 1
    if key in PROGRAMS_CHANGED:
        assert change != parent
    else:
        assert change == parent


@pytest.mark.parametrize("key", OLDER + OWN)
def test_only_a_program_that_notes_the_scan_bound_changes(older_lowered,
                                                          key):
    """The scan bound changes the program of a statement whose
    ``kernels`` carries ``agg.scan_bound``, and no other by a byte:
    every other statement of every cell lowers to the same text with
    the rule and without it."""
    texts, kernels = older_lowered["texts"], older_lowered["kernels"]
    change, unbound = texts["change"][key], texts["no_scan_bound"][key]
    assert len(change) == len(unbound) == len(kernels["change"][key]) >= 1
    for before, after, noted, plain in zip(
            unbound, change, kernels["change"][key],
            kernels["no_scan_bound"][key]):
        assert "agg.scan_bound" not in plain
        if "agg.scan_bound" in noted:
            assert after != before
            assert key in SCAN_BOUNDED
        else:
            assert after == before
            assert key not in SCAN_BOUNDED


# the one statement of the six cells whose program the row-hash
# colocation of DISTINCT and INTERSECT / EXCEPT changes
COLOCATED = {f"{MIX}:query38#0"}
COLOCATION_NOTES = ("distinct.colocated", "setop.colocated", "setop.local")


@pytest.mark.parametrize("key", OLDER + OWN)
def test_only_query38_changes_with_the_row_hash_colocation(older_lowered,
                                                           key):
    """Every program of `nds_h_sf1.dist4`, of this cell's query7,
    query27 (its ROLLUP's UNION ALL), query98 and query21, and every
    single-device program of `short`, `power_nds_h` and `power_nds`,
    lowers to the same text with the colocation and with DISTINCT and
    INTERSECT / EXCEPT replicating their sharded inputs as before it;
    query38's first program (the staged INTERSECT chain) differs, and
    it alone notes the colocation."""
    texts, kernels = older_lowered["texts"], older_lowered["kernels"]
    change, before = texts["change"][key], texts["sets_everywhere"][key]
    assert len(change) == len(before) == len(kernels["change"][key]) >= 1
    differs = []
    for after, prior, noted, plain in zip(
            change, before, kernels["change"][key],
            kernels["sets_everywhere"][key]):
        assert not set(COLOCATION_NOTES) & set(plain)
        colocated = bool(set(COLOCATION_NOTES) & set(noted))
        assert (after != prior) == colocated
        differs.append(colocated)
    assert any(differs) == (key in COLOCATED)


@pytest.mark.parametrize("key", OLDER + OWN)
def test_the_scopes_change_no_instruction(older_lowered, key):
    """Every program of the six cells' statements (`power_nds_h_sf5`'s
    SQL is `power_nds_h`'s), on one chip or on the four-device mesh as
    its cell runs it, lowers to the same text without debug info with
    the scopes and with ``jax.named_scope`` naming nothing."""
    texts = older_lowered["texts"]
    scoped, plain = texts["change"][key], texts["no_scopes"][key]
    assert len(scoped) == len(plain) >= 1
    assert scoped == plain


# one statement a cell; `nds_h_sf5.power` runs `power_nds_h`'s SQL
COMPILED_KEYS = ["short:q19#0", "power_nds:query3#0", "power_nds_h:q18#0",
                 "power_nds_h:q3#0", "power_dist4:q5#0", f"{MIX}:query38#0"]


def _no_metadata(text: str) -> str:
    """A compiled module's text without what the scopes can reach: each
    instruction's ``metadata`` and the header's tables of files,
    functions and stack frames they point into."""
    text = re.sub(r",? metadata=\{[^{}]*\}", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)$|^\d+ ", line))


@pytest.mark.parametrize("key", COMPILED_KEYS)
def test_the_compiled_program_differs_in_metadata_alone(older_lowered, key):
    """Compiled with the scopes and without them (each afresh), a
    program differs in its instructions' metadata and nothing else."""
    compiled = older_lowered["compiled"]
    scoped, plain = compiled["change"][key], compiled["no_scopes"][key]
    assert len(scoped) == len(plain) >= 1
    for a, b in zip(scoped, plain):
        assert a != b
        assert _no_metadata(a) == _no_metadata(b)


CHECKED = ("fusion", "sort", "gather", "scatter", "all-to-all",
           "all-gather", "all-reduce", "collective-permute",
           "reduce-scatter")


def _entry_sites(text: str) -> dict:
    """``costs.parse_sites`` of the entry computation: the instructions
    a profile's op events name."""
    from nds_tpu.obs import costs
    entry = text[text.index("\nENTRY "):]
    return costs.parse_sites(entry[:entry.index("\n}")])


@pytest.mark.parametrize("key", OLDER + OWN)
def test_every_compiled_instruction_has_an_operator(older_lowered, key):
    """In the compiled program every fusion, sort, gather, scatter and
    collective that the trace made has an ``op.*`` scope; every
    all-to-all is under ``exchange``, every all-gather under
    ``replicate``.  The CPU compiler makes some fusions of its own
    (constant broadcasts, its reduce-window tree), with no ``op_name``
    or one that ends in an instruction's name (``broadcast.66``)."""
    from benchmarks import op_reduce
    sharded = key.startswith(("power_dist4:", MIX))
    kinds = set()
    for text in older_lowered["compiled"]["change"][key]:
        for name, (op_name, opcode) in _entry_sites(text).items():
            if opcode not in CHECKED or re.search(r"(^|\.\d+)$", op_name):
                continue
            kinds.add(opcode)
            path, mechanisms = op_reduce.scopes(op_name)
            assert path, (key, name, op_name)
            if opcode == "all-to-all":
                assert "exchange" in mechanisms, (key, name, op_name)
            if opcode == "all-gather":
                assert "replicate" in mechanisms, (key, name, op_name)
    assert "fusion" in kinds
    if sharded:
        assert kinds & {"all-to-all", "all-gather"}, (key, kinds)


# --------------------------- (d) NULL foreign keys through the exchange

def _fact_and_dim(nulls_every_shard=True):
    from nds_tpu.engine.types import INT32, Schema
    n, n_dim = 8192, 2048
    rng = np.random.default_rng(34)
    schema = Schema.of(("f_id", INT32, False), ("f_fk", INT32, True),
                       ("f_val", INT32, False))
    dim_schema = Schema.of(("d_sk", INT32, False), ("d_grp", INT32, False))
    fk_valid = rng.random(n) >= 0.04            # TPC-DS leaves ~4 % NULL
    fact = {"f_id": np.arange(n, dtype=np.int32),
            # garbage under the NULLs: no two alike, none a real key
            "f_fk": np.where(fk_valid, rng.integers(0, n_dim, n),
                             n_dim + np.arange(n)).astype(np.int32),
            "f_fk#null": fk_valid,
            "f_val": rng.integers(0, 100, n).astype(np.int32)}
    dim = {"d_sk": np.arange(n_dim, dtype=np.int32),
           "d_grp": (np.arange(n_dim) % 37).astype(np.int32)}
    assert all((~fk_valid[i * n // 4:(i + 1) * n // 4]).any()
               for i in range(4))
    return fact, schema, dim, dim_schema, fk_valid


def _two_table_sessions(fact, schema, dim, dim_schema):
    from nds_tpu.engine.device_exec import make_device_factory
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    from nds_tpu.sql.planner import CatalogInfo
    cat = CatalogInfo({"fact": schema, "dim": dim_schema},
                      {"dim": ["d_sk"], "fact": ["f_id"]},
                      {"fact": len(fact["f_id"]), "dim": len(dim["d_sk"])})

    def build(factory):
        s = Session(cat, factory)
        s.register_table(from_arrays("fact", schema, fact))
        s.register_table(from_arrays("dim", dim_schema, dim))
        return s

    return (build(make_device_factory()),
            build(make_distributed_factory(n_devices=N_DEV,
                                           shard_threshold=1000)))


NULL_KEY_SQL = {
    # the NULLs route as the 0 `_key_of` reads them as, and are ONE group
    "group_by_the_key": (
        "select f_fk, count(*) as n, sum(f_val) as s from fact "
        "group by f_fk order by f_fk"),
    # both sides sharded: the join exchanges by the key, NULLs match
    # nothing; then a ROLLUP over the joined rows
    "join_then_rollup": (
        "select d_grp, count(*) as n, sum(f_val) as s from fact, dim "
        "where f_fk = d_sk group by rollup(d_grp) order by d_grp"),
    # a left join keeps the NULL-key rows, null-extended
    "left_join_keeps_them": (
        "select count(*) as n, count(d_sk) as matched, sum(f_val) as s "
        "from fact left join dim on f_fk = d_sk"),
}


@pytest.mark.parametrize("case", sorted(NULL_KEY_SQL))
def test_null_foreign_keys_on_every_shard(case):
    fact, schema, dim, dim_schema, fk_valid = _fact_and_dim()
    single_s, sharded_s = _two_table_sessions(fact, schema, dim, dim_schema)
    sql = NULL_KEY_SQL[case]
    want, got = single_s.sql(sql).to_pandas(), sharded_s.sql(sql).to_pandas()
    assert len(got) == len(want) > 0
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    n_null = int((~fk_valid).sum())
    if case == "group_by_the_key":
        nulls = got[got["f_fk"].isna()]
        assert len(nulls) == 1 and int(nulls["n"].iloc[0]) == n_null
        assert nulls.index[0] == 0              # NULL lowest
        assert int(got["n"].sum()) == len(fk_valid)
    elif case == "join_then_rollup":
        total = got[got["d_grp"].isna()]
        assert len(total) == 1
        assert int(total["n"].iloc[0]) == len(fk_valid) - n_null
    else:
        assert int(got["n"].iloc[0]) == len(fk_valid)
        assert int(got["matched"].iloc[0]) == len(fk_valid) - n_null


# -------------------------------- (e) ORDER BY: NULL lowest by default

ORDERINGS = {
    "asc": ("order by f_fk, f_id", "first"),
    "desc": ("order by f_fk desc, f_id", "last"),
    "asc_nulls_last": ("order by f_fk nulls last, f_id", "last"),
    "desc_nulls_first": ("order by f_fk desc nulls first, f_id", "first"),
}


@pytest.mark.parametrize("case", sorted(ORDERINGS))
def test_order_by_puts_null_lowest_unless_told(case):
    """Spark's default, which the reference harness runs on and the
    benchmark's comparison holds an ORDER BY to: NULL first ascending,
    last descending; an explicit NULLS FIRST / LAST wins. The CPU
    oracle, the device executor and the sharded one agree."""
    from nds_tpu.engine.session import Session
    fact, schema, dim, dim_schema, fk_valid = _fact_and_dim()
    single_s, sharded_s = _two_table_sessions(fact, schema, dim, dim_schema)
    cpu_s = Session(single_s.catalog)           # the CPU oracle
    for t in single_s.tables.values():
        cpu_s.register_table(t)
    clause, where = ORDERINGS[case]
    sql = f"select f_fk, f_id from fact {clause}"
    frames = [s.sql(sql).to_pandas() for s in (cpu_s, single_s, sharded_s)]
    n_null = int((~fk_valid).sum())
    for got in frames:
        null = got["f_fk"].isna().to_numpy()
        assert null.sum() == n_null
        block = null[:n_null] if where == "first" else null[-n_null:]
        assert block.all()
    for got in frames[1:]:
        pd.testing.assert_frame_equal(got, frames[0], check_exact=True)


# ------------------------------------------------ (f) the cell's files

def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_benchmark_entries():
    bench = _json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nds_sf1_dist4", MIX, 4)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in bench["configs"]}["nds_sf1_dist4"]
    config = _json(ROOT, entry["file"])
    for key in ("name", "source", "reduced"):
        assert config[key] == entry[key]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    nds, dist_h = (_json(BENCH, "configs", f) for f in (
        "nds_sf1.json", "nds_h_sf1_dist4.json"))
    assert config["reduced"] == ["scale", "statements"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    # SF1, in the spelling that only a program with the deployment's
    # ORDER BY reads (nds.gen_data; an older tree exits 2 on it)
    from nds_tpu.datagen import scale_factor
    assert (config["suite"], config["scale"]) == ("nds", "sf1")
    assert scale_factor(config["scale"]) == scale_factor(nds["scale"]) == 1.0
    assert config["template"] == "configs/power_run_distributed.template"
    assert config["assumed"]["chips"] == 4
    # guarantees and limits: the one-chip NDS cell's and NDS-H's on four
    assert config["limits"] == nds["limits"] == dist_h["limits"]
    assert config["guarantees"] == dist_h["guarantees"]
    assert config["guarantees"]["decimals"] == nds["guarantees"]["decimals"]
    assert config["gen_parallel"] == nds["gen_parallel"]
    # two four-chip cells of six: under half
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 <= len(bench["workloads"]) // 2
    # what the cell reports
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["pass_s"]["workloads"]
    layers = {m["name"]: m for m in bench["per_layer"]}
    for name in ("collective_pct", "ici_roofline_pct",
                 "exchange_mb_per_pass", "hbm_roofline_pct.x4",
                 "replicate_mb_per_pass"):
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "pass_s"
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    assert layers["replicate_mb_per_pass"]["workloads"] == [
        CELL, "nds_h_sf1.dist4"]
    assert layers["replicate_mb_per_pass"]["layer"] == "exchange"
    # and of its set-up what the sharded path has spans for: its uploads
    # are a first device.bind under device.compile (_split_keys binds
    # the program's buffers), which first_bind_s would read; listing the
    # cell there is an edit of that entry, a benchmark change of its own
    for name in ("engine_init_s", "load_read_s", "load_build_s",
                 "lower_s", "cache_read_s"):
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "setup_s"
    assert CELL not in layers["first_bind_s"]["workloads"]


def test_traffic_is_the_issues_list_with_its_files():
    mix = _json(BENCH, "traffic", MIX + ".json")
    names = [s["name"] for s in mix["statements"]]
    assert names[0] == "query7" and set(names) <= set(NAMES)
    assert len(names) >= 4
    one_chip = {s["name"]: s for s in _json(
        BENCH, "traffic", "power_nds.json")["statements"]}
    q7 = mix["statements"][0]
    assert q7 == one_chip["query7"]             # the control, unchanged
    from nds_tpu.nds import streams
    for stmt in mix["statements"]:
        number = int(stmt["name"][len("query"):])
        assert stmt["sets"] == [streams.QUALIFICATION[number]]
        assert stmt["need"] and "order_by" in stmt
        with open(os.path.join(BENCH, "traffic", "sql",
                               stmt["template"] + ".txt")) as f:
            text = f.read()
        with open(os.path.join(streams.TEMPLATE_DIR,
                               f"q{number}.sql")) as f:
            assert text == f.read()             # no statement rewritten
        with open(os.path.join(BENCH, "reference",
                               stmt["template"] + ".py")) as f:
            source = f.read()
        assert not re.search(r"^\s*(from|import)\s+nds_tpu", source, re.M)


# ------------- (g) DISTINCT and INTERSECT / EXCEPT by a hash of the row

def _set_tables():
    """Three tables on the four-device mesh (shard threshold 1000): ``a``
    (8192 rows) and ``b`` (6144) sharded, ``c`` (600) replicated. Their
    (k, s) rows repeat across every shard, a tenth of each column is
    NULL over whatever the slot holds, and each table's strings carry a
    dictionary of their own: ``b``'s and ``c``'s names overlap ``a``'s
    in part. ``f`` is a floating-point column."""
    from nds_tpu.engine.types import FLOAT64, INT32, STRING, Schema
    rng = np.random.default_rng(37)
    names = np.array(["ash", "birch", "cedar", "elm", "fir", "hazel",
                      "larch", "oak", "pine", "yew"], dtype=object)
    pools = {"a": names[:8], "b": names[3:], "c": names[5:]}
    sizes = {"a": 8192, "b": 6144, "c": 600}
    schemas, arrays = {}, {}
    for t, n in sizes.items():
        schemas[t] = Schema.of((f"{t}_id", INT32, False),
                               (f"{t}_k", INT32, True),
                               (f"{t}_s", STRING, True),
                               (f"{t}_f", FLOAT64, False))
        k_valid = rng.random(n) >= 0.1
        s_valid = rng.random(n) >= 0.1
        arrays[t] = {
            f"{t}_id": np.arange(n, dtype=np.int32),
            # garbage under the NULLs: no two alike
            f"{t}_k": np.where(k_valid, rng.integers(0, 200, n),
                               1000 + np.arange(n)).astype(np.int32),
            f"{t}_k#null": k_valid,
            f"{t}_s": rng.choice(pools[t], n),
            f"{t}_s#null": s_valid,
            f"{t}_f": rng.integers(0, 4, n) * 0.5}
    return schemas, arrays


def _set_sessions(mesh=None):
    """(the CPU oracle, the four-device session) over ``_set_tables``;
    ``mesh``: the four devices as a 2-D (host, lane) mesh instead."""
    from nds_tpu.engine.session import Session
    from nds_tpu.io.host_table import from_arrays
    from nds_tpu.parallel.dist_exec import make_distributed_factory
    from nds_tpu.sql.planner import CatalogInfo
    schemas, arrays = _set_tables()
    cat = CatalogInfo(schemas, {t: [f"{t}_id"] for t in schemas},
                      {t: len(a[f"{t}_id"]) for t, a in arrays.items()})
    out = []
    for factory in (None, make_distributed_factory(
            mesh=mesh, n_devices=None if mesh else N_DEV,
            shard_threshold=1000)):
        s = Session(cat, factory) if factory else Session(cat)
        for t in schemas:
            s.register_table(from_arrays(t, schemas[t], arrays[t]))
        out.append(s)
    return out


_A = "select a_k, a_s from a"
# case: (sql, the notes of its `kernels` that say where it ran, the
# exchanges its program holds: one a relation placed by its row hash)
SET_SQL = {
    # duplicates on every shard, NULLs equal to NULLs
    "distinct": ("select distinct a_k, a_s from a order by a_k, a_s",
                 {"distinct.colocated": 1}, 1),
    # nine values (eight names and NULL): sized as a few-keys group-by
    # is, so that one device can take them all
    "distinct_few_values": ("select distinct a_s from a order by a_s",
                            {"distinct.colocated": 1}, 1),
    # one dictionary a column: each side is exchanged by its raw row, and
    # the planner's DISTINCT above reads the mark and exchanges nothing
    "intersect": (f"{_A} where a_id < 5000 intersect {_A} where a_id >= 3000"
                  " order by a_k, a_s",
                  {"setop.colocated": 1, "distinct.colocated": 1}, 2),
    "except": (f"{_A} where a_id < 5000 except {_A} where a_id >= 3000"
               " and a_k < 100 order by a_k, a_s",
               {"setop.colocated": 1, "distinct.colocated": 1}, 2),
    # query38's shape: three DISTINCT branches, two INTERSECTs and the
    # planner's two DISTINCTs above them; only the branches exchange
    "distinct_chain": (
        "select distinct a_k, a_s from a where a_id < 6000 intersect "
        "select distinct a_k, a_s from a where a_id >= 2000 intersect "
        "select distinct a_k, a_s from a where a_id % 2 = 0 "
        "order by a_k, a_s",
        {"distinct.colocated": 5, "setop.colocated": 2}, 3),
    # different dictionaries: the right side's codes are read in the
    # left's dictionary (-1 for a name the left lacks) and hashed so; the
    # left keeps its mark, and the DISTINCT above exchanges nothing
    "intersect_two_dictionaries": (
        "select a_k, a_s from a intersect select b_k, b_s from b "
        "order by a_k, a_s",
        {"setop.colocated": 1, "distinct.colocated": 1}, 2),
    "except_two_dictionaries": (
        "select b_k, b_s from b except select a_k, a_s from a "
        "order by b_k, b_s",
        {"setop.colocated": 1, "distinct.colocated": 1}, 2),
    # the right side replicated: each device checks its own left rows
    # against the whole right, no collective
    "intersect_right_replicated": (
        "select a_k, a_s from a intersect select c_k, c_s from c "
        "order by a_k, a_s",
        {"setop.local": 1, "distinct.colocated": 1}, 1),
    "except_right_replicated": (
        "select a_k, a_s from a except select c_k, c_s from c "
        "order by a_k, a_s",
        {"setop.local": 1, "distinct.colocated": 1}, 1),
    # the left side replicated: the right is gathered, as before
    "except_left_replicated": (
        "select c_k, c_s from c except select a_k, a_s from a "
        "order by c_k, c_s",
        {"replicate.setop": 1}, 0),
    # a floating-point column does not hash exactly: whole relations on
    # every device, as before
    "distinct_float": ("select distinct a_k, a_f from a order by a_k, a_f",
                       {"replicate.distinct": 1}, 0),
}


@pytest.mark.parametrize("mesh", ["flat", "2x2"])
@pytest.mark.parametrize("case", sorted(SET_SQL))
def test_set_operations_over_sharded_inputs_match_the_cpu_oracle(case, mesh):
    """On the flat mesh and on the (host, lane) mesh, whose exchange is
    two shuffles (host, then lane) that place a key where the flat one
    does."""
    from nds_tpu.obs import metrics as obs_metrics
    from nds_tpu.parallel.mesh import make_multihost_mesh
    sql, notes, exchanges = SET_SQL[case]
    cpu_s, sharded_s = _set_sessions(
        make_multihost_mesh(2, 2) if mesh == "2x2" else None)
    if mesh == "2x2":
        exchanges *= 2
    want = cpu_s.sql(sql).to_pandas()
    before = obs_metrics.snapshot()["counters"]
    got = sharded_s.sql(sql).to_pandas()
    after = obs_metrics.snapshot()["counters"]
    assert len(got) == len(want) > 0
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert got.iloc[:, 0].isna().any()          # NULL rows came through
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("exchanges_traced_total",
                       "exchange_overflow_retries_total")}
    assert moved == {"exchanges_traced_total": exchanges,
                     "exchange_overflow_retries_total": 0}
    kernels = sharded_s._executor_factory(sharded_s.tables).last_timings[
        "__kernels"]
    where = {k: v for k, v in kernels.items()
             if k in COLOCATION_NOTES
             or k in ("replicate.distinct", "replicate.setop")}
    assert where == notes


def test_query38_runs_its_distincts_and_intersects_on_each_chips_share(
        raw, tmp_path):
    """With web_sales sharded as well (as at SF1), query38's five
    DISTINCTs and two INTERSECTs run on each chip's share: three
    exchanges by the row hash (one a branch), no replicate but the
    root's, no overflow, the reference's answer."""
    from benchmarks import run
    from nds_tpu.obs import metrics as obs_metrics
    session = _session(raw, shards=N_DEV)
    ex = _sharded_executor(session)
    ex.shard_threshold = 4096
    assert ex._is_sharded("web_sales")
    pipe = session._executor_factory(session.tables)
    before = obs_metrics.snapshot()["counters"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("NDS_TPU_TRACE", str(tmp_path / "t.jsonl"))
        log = []
        _replicate_log(mp, log)
        rec = run.run_statement(session, _statement("query38"))
    after = obs_metrics.snapshot()["counters"]
    assert rec["error"] is None and rec["placement"] == "sharded"
    assert _verdict([rec], raw)["correct"] is True
    kernels = ex.last_timings["__kernels"]
    assert kernels["distinct.colocated"] == 5
    assert kernels["setop.colocated"] == 2
    assert "setop.local" not in kernels
    assert {k: v for k, v in kernels.items()
            if k.startswith("replicate.")} == {"replicate.root": 1}
    assert [who for _r, who, _n, _w in log] == ["root"]
    launches = _root_of(pipe.last_query_span).find("device.launch")
    assert sum(a.attrs["replicates"] for a in launches) == 1
    assert after.get("exchange_overflow_retries_total", 0) == before.get(
        "exchange_overflow_retries_total", 0)
