"""Query session: the engine-side analog of a SparkSession.

Holds the table registry (reference: temp views created per table,
`nds/nds_power.py:79-106`), session views (q15), parses + plans + executes
SQL, and exposes the executor backend choice (CPU oracle vs device
engine) the way templates choose cpu/gpu in the reference.
"""

from __future__ import annotations

import itertools

from nds_tpu.engine.cpu_exec import CpuExecutor, ResultTable
from nds_tpu.io.host_table import HostTable
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import get_tracer
from nds_tpu.sql import plan as P
from nds_tpu.sql.parser import parse
from nds_tpu.sql.planner import CatalogInfo, Planner

# relative size weights for greedy join ordering (TPC-H row ratios)
TPCH_SIZES = {
    "lineitem": 6_000_000, "orders": 1_500_000, "partsupp": 800_000,
    "part": 200_000, "customer": 150_000, "supplier": 10_000,
    "nation": 25, "region": 5,
}


# process-wide statement sequence: the ``stmt_id`` of every ``stmt``
# root span, so a profile's statements can be told apart and counted
_STMT_IDS = itertools.count(1)


class Session:
    # bound on the (SQL text, views) -> plan cache: a serving workload
    # submits an unbounded population of literal-variant texts, and an
    # unbounded dict would leak plans for the process lifetime
    PLAN_CACHE_MAX = 512

    def __init__(self, catalog: CatalogInfo, executor_factory=None,
                 parameterize: "bool | None" = None):
        self.catalog = catalog
        self.tables: dict[str, HostTable] = {}
        self.views: dict[str, P.Node] = {}
        # literal hoisting (sql/params.py): default from
        # NDS_TPU_PARAM_PLANS; the serving layer turns it on explicitly
        from nds_tpu.sql import params as sqlparams
        self.parameterize = (sqlparams.enabled_by_env()
                             if parameterize is None else parameterize)
        self._executor_factory = executor_factory or (
            # ndslint: waive[NDS110] -- bare sessions default to the CPU oracle directly; the pipeline only schedules engine-backed placements (make_session routes every backend through it)
            lambda tables: CpuExecutor(tables))
        # plan cache keyed by (SQL text, view-definition signature):
        # repeated queries (warmup passes, throughput streams) reuse the
        # SAME plan object, which is also the device engine's compile-cache
        # key — the load-once/query-many lifecycle of
        # `nds/nds_power.py:184-322`. The signature is the set of
        # (view name, view source SQL) currently defined, so q15's
        # CREATE/DROP VIEW cycle maps every pass onto one cache entry
        # (identical view body => identical signature => no replan and no
        # XLA recompile), while a re-created view with a DIFFERENT body
        # changes the signature and correctly replans.
        self._plan_cache: dict[tuple, object] = {}
        self._view_sql: dict[str, str] = {}

    @classmethod
    def for_nds_h(cls, executor_factory=None,
                  parameterize: "bool | None" = None) -> "Session":
        from nds_tpu.nds_h.schema import PRIMARY_KEYS, get_schemas
        cat = CatalogInfo(get_schemas(), PRIMARY_KEYS, dict(TPCH_SIZES))
        return cls(cat, executor_factory, parameterize=parameterize)

    @classmethod
    def for_nds(cls, executor_factory=None,
                use_decimal: bool = True,
                include_maintenance: bool = False,
                parameterize: "bool | None" = None) -> "Session":
        from nds_tpu.nds.schema import (
            PRIMARY_KEYS, SIZES, get_maintenance_schemas, get_schemas,
        )
        schemas = get_schemas(use_decimal)
        keys = dict(PRIMARY_KEYS)
        sizes = dict(SIZES)
        if include_maintenance:
            # the 12 s_*/delete staging tables the LF_*/DF_* refresh
            # functions read (`nds/nds_maintenance.py:270-274` registers
            # them as temp views)
            schemas = {**schemas, **get_maintenance_schemas(use_decimal)}
            keys.update({"s_purchase": ("purc_purchase_id",),
                         "s_catalog_order": ("cord_order_id",),
                         "s_web_order": ("word_order_id",)})
            sizes.update({t: 100.0 for t in
                          get_maintenance_schemas(use_decimal)})
        cat = CatalogInfo(schemas, keys, sizes)
        return cls(cat, executor_factory, parameterize=parameterize)

    def register_table(self, table: HostTable) -> None:
        self.tables[table.name] = table

    def plan(self, sql_text: str):
        from nds_tpu.resilience import faults
        # chaos site: deterministic plan-time faults must fail fast
        # (the retry classifier never retries this class)
        faults.fault_point("plan")
        with get_tracer().span("sql.parse", chars=len(sql_text)):
            stmt = parse(sql_text)
        return self.plan_ast(stmt)

    def plan_ast(self, stmt):
        planner = Planner(self.catalog, self.views,
                          parameterize=self.parameterize)
        planned = planner.plan_statement(stmt)
        from nds_tpu.analysis import plan_verify
        if plan_verify.verify_enabled():
            # NDS_TPU_VERIFY_PLANS=1 (always on in tests): reject a
            # structurally invalid plan here, where the statement text
            # is known, instead of as a KeyError inside an executor
            target = planned[2] if isinstance(planned, tuple) else planned
            if isinstance(target, P.PlannedQuery):
                plan_verify.assert_valid(target, catalog=self.catalog,
                                         label=type(stmt).__name__)
        return planned

    def _views_signature(self) -> frozenset:
        return frozenset(self._view_sql.items())

    def invalidate(self, tables=None) -> None:
        """Drop content-derived caches after a table mutation. With
        ``tables=None`` everything goes (the pre-delta behavior, still
        right for wholesale warehouse swaps like rollback). With a
        table-name iterable, eviction is SCOPED: only plan-cache
        entries whose plans scan a mutated table are dropped, and the
        executor factory is asked for a scoped invalidate — segment-
        granular content digests guarantee unaffected programs stay
        correct, so unaffected queries re-run at 0 compiles."""
        if tables is None:
            self._plan_cache.clear()
            inv = getattr(self._executor_factory, "invalidate", None)
            if inv is not None:
                inv()
            return
        touched = set(tables)
        from nds_tpu.cache import fingerprint
        for key in [k for k, planned in self._plan_cache.items()
                    if not isinstance(planned, tuple)
                    and touched.intersection(
                        fingerprint.scan_tables(planned))]:
            self._plan_cache.pop(key, None)
        inv_scoped = getattr(self._executor_factory,
                             "invalidate_tables", None)
        if inv_scoped is not None:
            inv_scoped(touched)
        else:
            inv = getattr(self._executor_factory, "invalidate", None)
            if inv is not None:
                inv()

    def _run_dml(self, action: str, name: str, payload) -> None:
        from nds_tpu.engine import dml
        table = self.tables.get(name)
        if table is None:
            raise ValueError(f"DML target {name!r} is not registered")
        if action == "insert":
            executor = self._executor_factory(self.tables)
            result = executor.execute(payload)
            self.tables[name] = dml.append_rows(table, result)
        else:  # delete
            keep = dml.delete_mask(self, table, payload)
            self.tables[name] = dml.apply_delete(table, keep)
        self.invalidate(tables=[name])

    def _planned_for(self, key: tuple, sql_text: str, root=None):
        """Plan-cache lookup that keeps the 'plan' chaos site firing
        exactly once per query submission: a cache MISS fires inside
        plan(); a HIT fires here (warmup passes populate the cache —
        a scheduled plan fault must still reach the timed pass)."""
        planned = self._plan_cache.get(key)
        hit = planned is not None
        obs_metrics.counter("plan_cache_hits_total" if hit
                            else "plan_cache_misses_total").inc()
        if root is not None:      # the statement's own ``stmt`` root
            root.set(plan_cache_hit=hit)
        if not hit:
            planned = self.plan(sql_text)
            self._plan_cache[key] = planned
            while len(self._plan_cache) > self.PLAN_CACHE_MAX:
                # FIFO bound: a serving workload's literal-variant texts
                # must not grow the plan cache for the process lifetime
                # (the shared COMPILED program lives in the executor's
                # digest-keyed cache, not here)
                self._plan_cache.pop(next(iter(self._plan_cache)))
        else:
            from nds_tpu.resilience import faults
            faults.fault_point("plan")
        return planned

    def sql(self, sql_text: str) -> ResultTable | None:
        """One statement, call to host rows, under ONE root span: the
        caller's where it has one open on this thread (the power
        loop's ``query``, a statement nested in DML), else a ``stmt``
        root of its own that every phase below hangs from."""
        tracer = get_tracer()
        if tracer.current() is not None:
            return self._sql(sql_text)
        with tracer.span("stmt", stmt_id=next(_STMT_IDS)) as root:
            return self._sql(sql_text, root or None)

    def _sql(self, sql_text: str, root=None) -> ResultTable | None:
        key = (sql_text, self._views_signature())
        planned = self._planned_for(key, sql_text, root)
        return self._run_planned(key, sql_text, planned)

    def _run_planned(self, key: tuple, sql_text: str, planned):
        if isinstance(planned, tuple):
            action, name, node = planned
            if action == "create_view":
                if name in self.views:
                    raise ValueError(f"view {name!r} already exists")
                self.views[name] = node
                self._view_sql[name] = sql_text
                return None
            if action == "drop_view":
                if name not in self.views and node != "if_exists":
                    raise ValueError(f"view {name!r} does not exist")
                self.views.pop(name, None)
                self._view_sql.pop(name, None)
                return None
            if action in ("insert", "delete"):
                # never replay a stale DML plan against mutated tables
                self._plan_cache.pop(key, None)
                self._run_dml(action, name, node)
                return None
        executor = self._executor_factory(self.tables)
        return executor.execute(planned)

    def sql_async(self, sql_text: str):
        """Dispatch-without-wait variant of sql(): returns a handle with
        .result(). SELECTs on executors supporting execute_async (the
        device engine) overlap with the caller's other work
        (`engine.concurrent_tasks` pipelining); everything else runs
        synchronously and returns an already-completed handle."""
        tracer = get_tracer()
        if tracer.current() is not None or not tracer.enabled:
            return self._sql_async(sql_text)
        # no caller's root: the statement's own ``stmt`` root is owned
        # (it outlives this call, so it cannot be a ``with`` span nor a
        # profiler annotation) and ends when the handle resolves
        root = tracer.begin("stmt", parent=None, stmt_id=next(_STMT_IDS))
        try:
            with tracer.attach(root):
                return _Rooted(self._sql_async(sql_text, root), root)
        except BaseException as exc:
            root.set(error=f"{type(exc).__name__}: {exc}").end()
            raise

    def _sql_async(self, sql_text: str, root=None):
        key = (sql_text, self._views_signature())
        planned = self._planned_for(key, sql_text, root)
        if not isinstance(planned, tuple):
            executor = self._executor_factory(self.tables)
            dispatch = getattr(executor, "execute_async", None)
            if dispatch is not None:
                return dispatch(planned)
        return _Completed(self._run_planned(key, sql_text, planned))


class _Completed:
    """Already-finished async handle (CPU oracle, DML, view DDL)."""

    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value


class _Rooted:
    """An async handle under the ``stmt`` root ``sql_async`` opened for
    it: ``result()`` runs the blocking half under that root and ends
    it.  Everything else is the inner handle's."""

    def __init__(self, inner, root):
        self._inner = inner
        self._root = root

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def result(self):
        root = self._root
        try:
            with get_tracer().attach(root):
                return self._inner.result()
        except BaseException as exc:
            root.set(error=f"{type(exc).__name__}: {exc}")
            raise
        finally:
            root.end()
