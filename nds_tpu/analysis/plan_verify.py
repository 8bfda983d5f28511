"""Plan verifier: structural invariants every executor assumes.

The executors (cpu_exec / device_exec / dist_exec) walk planned trees
and address columns as ``(binding, name)`` pairs in a runtime context;
nothing re-checks at execution time that those addresses exist, that
dtypes propagated consistently, or that join keys agree across sides —
a planner bug surfaces as a KeyError deep inside a compiled program (or
worse, as silently wrong rows). This module proves those invariants
right after planning, the typed-plan validation discipline the tensor-
runtime lowering papers rely on (PAPERS.md: Query Processing on Tensor
Computation Runtimes; Flare's staged compilation checks).

Checked per node (namespaces mirror each ``_run_*``'s context
construction in cpu_exec / the DCtx construction in device_exec):

- every ``ColRef`` resolves in the namespace of the child it is
  evaluated against, with the dtype recorded there;
- expression dtypes are consistent: ``Arith`` matches
  ``ir.arith_type``, aggregate specs match ``ir.agg_type``, predicates
  are BOOL;
- join / set-op key dtypes agree across sides (joinable, not merely
  present);
- ``AggRef`` / ``WindowRef`` / ``GroupingRef`` never survive planning
  (the planner remaps them onto concrete columns; one escaping — or
  carrying an out-of-range index — would crash or misbind at runtime);
- ``ScalarRef.plan_id`` indexes a real scalar subplan;
- Sort / Limit / Distinct binding invariants (passthrough output stays
  addressable, limit count non-negative);
- ``StagedScan`` integrity: mangled columns bijective with the backing
  temp-table scan, and (when an executor's table registry is supplied)
  the temp is actually registered;
- exchange slack / partition-capacity consistency for the distributed
  path (``check_exchange_invariants``).

Gate: ``NDS_TPU_VERIFY_PLANS=1`` turns verification on inside
``Session.plan`` and the device executors; tests force it on
(tests/conftest.py). ``tools/ndsverify.py`` runs it over every NDS /
NDS-H statement with no accelerator.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from nds_tpu.engine.types import (
    BOOL, BoolType, DateType, DecimalType, DType, FloatType, IntType,
    StringType,
)
from nds_tpu.sql import ir
from nds_tpu.sql import plan as P

ENV_FLAG = "NDS_TPU_VERIFY_PLANS"


def verify_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "0") not in ("", "0")


@dataclass
class Violation:
    rule: str       # short stable id, e.g. "colref-unresolved"
    node: str       # plan-node type the violation anchors to
    detail: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.node}: {self.detail}"


class PlanVerifyError(ValueError):
    def __init__(self, violations: list[Violation], label: str = ""):
        self.violations = violations
        head = f"plan verification failed{' for ' + label if label else ''}"
        super().__init__(
            "\n  ".join([f"{head} ({len(violations)} violation(s)):"]
                        + [str(v) for v in violations]))


# --------------------------------------------------------------- dtypes

def _joinable(lt: DType, rt: DType) -> bool:
    """Key dtypes that compare correctly on both engines. Integer
    widths may differ (the executors widen); everything else must match
    exactly — a decimal-scale or string/int mismatch would compare raw
    representations and silently drop matches."""
    if lt is None or rt is None:
        return False
    if lt == rt:
        return True
    if isinstance(lt, IntType) and isinstance(rt, IntType):
        return True
    # epoch-day dates are int32 on device; planner may emit either side
    # as the raw int (EXTRACT output, d_date + N arithmetic)
    date_int = (DateType, IntType)
    if isinstance(lt, date_int) and isinstance(rt, date_int):
        return True
    return False


def _union_compatible(lt: DType, rt: DType) -> bool:
    """Branch output dtypes a SetOp may concatenate: exact match, any
    integer pair, any float pair, or string/string (the cpu engine
    concatenates decoded values; dictionary codes never cross a union).
    Decimals must agree on scale — concatenating scaled ints of
    different scales is a value corruption."""
    if lt is None or rt is None:
        return False
    if lt == rt:
        return True
    if isinstance(lt, IntType) and isinstance(rt, IntType):
        return True
    if isinstance(lt, FloatType) and isinstance(rt, FloatType):
        return True
    if isinstance(lt, StringType) and isinstance(rt, StringType):
        return True
    if isinstance(lt, DecimalType) and isinstance(rt, DecimalType):
        return lt.scale == rt.scale
    return False


# ----------------------------------------------------------- namespaces

def _namespace(node: P.Node, memo: dict) -> dict:
    """{(binding, name): dtype} the node's runtime context exposes to
    its parent — mirrors cpu_exec's Context keys per node type (and
    staging._exposed, which encodes the same contract for cuts)."""
    nid = id(node)
    if nid in memo:
        return memo[nid]
    # ndslint: waive[NDS101] -- memo lives for one verify() pass; the plan pins nodes
    memo[nid] = {}  # cycle guard; real value set below
    if isinstance(node, P.Scan):
        ns = {(node.binding, n): dt for n, dt in node.output}
    elif isinstance(node, P.DerivedScan):
        ns = {(node.binding, n): dt for n, dt in node.child.output}
    elif isinstance(node, P.StagedScan):
        ns = {(b, n): dt for b, n, _m, dt in node.cols}
    elif isinstance(node, P.Project):
        ns = {(node.binding, n): e.dtype for n, e in node.exprs}
    elif isinstance(node, P.Aggregate):
        ns = {(node.binding, n): dt for n, dt in node.output}
    elif isinstance(node, P.Join):
        ns = dict(_namespace(node.left, memo))
        ns.update(_namespace(node.right, memo))
    elif isinstance(node, P.SemiJoin):
        ns = dict(_namespace(node.left, memo))
    elif isinstance(node, P.Window):
        ns = dict(_namespace(node.child, memo))
        ns.update({(node.binding, n): s.dtype for n, s in node.specs})
    elif isinstance(node, P.SetOp):
        if node.kind.startswith("union"):
            # _run_setop materializes ONLY the left output columns
            # under the left binding; sibling columns do not survive
            lb = node.left.binding
            ns = {(lb, n): dt for n, dt in node.left.output}
        else:  # intersect/except keep the left context wholesale
            ns = dict(_namespace(node.left, memo))
    elif isinstance(node, (P.Filter, P.Sort, P.Limit, P.Distinct)):
        ns = dict(_namespace(node.child, memo))
    else:
        ns = {}
    # ndslint: waive[NDS101] -- memo lives for one verify() pass; the plan pins nodes
    memo[nid] = ns
    return ns


# ---------------------------------------------------------- expressions

_PREDICATE_IRS = (ir.Cmp, ir.BoolOp, ir.Not, ir.LikeIR, ir.InListIR,
                  ir.IsNullIR)


class _Verifier:
    def __init__(self, planned: P.PlannedQuery,
                 tables: "dict | None" = None,
                 catalog=None):
        self.planned = planned
        self.tables = tables
        self.catalog = catalog
        self.out: list[Violation] = []
        self.ns_memo: dict = {}

    def fail(self, rule: str, node, detail: str) -> None:
        name = type(node).__name__ if isinstance(node, (P.Node, ir.IR)) \
            else str(node)
        self.out.append(Violation(rule, name, detail))

    # ------------------------------------------------- expression checks

    def check_expr(self, e: ir.IR, ns: dict, node: P.Node) -> None:
        for x in ir.walk(e):
            if isinstance(x, ir.ColRef):
                key = (x.binding, x.name)
                if key not in ns:
                    self.fail("colref-unresolved", node,
                              f"{x!r} not in the evaluation namespace "
                              f"(bindings in scope: "
                              f"{sorted({b for b, _ in ns})})")
                elif x.dtype is None:
                    self.fail("colref-untyped", node, f"{x!r} has no dtype")
                elif x.dtype != ns[key]:
                    self.fail("colref-dtype", node,
                              f"{x!r} typed {x.dtype} but the child "
                              f"exposes {ns[key]}")
            elif isinstance(x, (ir.AggRef, ir.WindowRef, ir.GroupingRef)):
                # the planner remaps every one of these onto concrete
                # columns; any survivor (in-range or not) would misbind
                idx = getattr(x, "index", getattr(x, "key_index", None))
                self.fail("ref-unresolved", node,
                          f"unresolved {type(x).__name__}(#{idx}) "
                          f"escaped planning")
            elif isinstance(x, ir.ScalarRef):
                nsub = len(self.planned.scalar_subplans)
                if not (0 <= x.plan_id < nsub):
                    self.fail("scalarref-range", node,
                              f"scalar#{x.plan_id} out of range "
                              f"({nsub} subplan(s))")
            elif isinstance(x, (ir.ParamRef, ir.DictParamIR,
                                ir.InListParamIR)):
                # hoisted literals (sql/params.py): the slot must bind
                # against the plan's value list, and dict/inlist
                # predicates must stay boolean
                vals = getattr(self.planned, "param_values", None)
                idx = getattr(x, "index", 0)
                if vals is None or not (0 <= idx < len(vals)):
                    self.fail("paramref-range", node,
                              f"{x!r} has no value slot "
                              f"({0 if vals is None else len(vals)} "
                              f"value(s) attached)")
                if isinstance(x, ir.ParamRef):
                    if x.dtype is None:
                        self.fail("expr-untyped", node,
                                  f"{x!r} has no dtype")
                elif not isinstance(x.dtype, BoolType):
                    self.fail("predicate-dtype", node,
                              f"{type(x).__name__} typed {x.dtype}, "
                              f"not bool")
                if (isinstance(x, ir.InListParamIR) and vals is not None
                        and 0 <= idx < len(vals)
                        and len(vals[idx]) != x.width):
                    self.fail("paramref-width", node,
                              f"{x!r} declares width {x.width} but the "
                              f"slot holds {len(vals[idx])} value(s)")
            elif isinstance(x, ir.Arith):
                lt, rt = x.left.dtype, x.right.dtype
                if lt is None or rt is None:
                    self.fail("arith-untyped", node,
                              f"{x.op} operand missing dtype")
                else:
                    try:
                        want = ir.arith_type(x.op, lt, rt)
                    except TypeError as exc:
                        self.fail("arith-illegal", node, str(exc))
                        continue
                    if x.dtype != want:
                        self.fail("arith-dtype", node,
                                  f"{lt} {x.op} {rt} must produce "
                                  f"{want}, plan says {x.dtype}")
            elif isinstance(x, _PREDICATE_IRS):
                if not isinstance(x.dtype, BoolType):
                    self.fail("predicate-dtype", node,
                              f"{type(x).__name__} typed {x.dtype}, "
                              f"not bool")
            elif isinstance(x, (ir.Neg, ir.CastIR, ir.CaseIR, ir.Lit,
                                ir.SubstrIR, ir.StrMapIR, ir.ConcatIR,
                                ir.ExtractIR)):
                if x.dtype is None:
                    self.fail("expr-untyped", node,
                              f"{type(x).__name__} has no dtype")

    # ------------------------------------------------------- node checks

    def check_node(self, node: P.Node) -> None:
        m = getattr(self, "_check_" + type(node).__name__.lower(), None)
        if m is not None:
            m(node)

    def _check_scan(self, node: P.Scan) -> None:
        ns = _namespace(node, self.ns_memo)
        for f in node.filters:
            self.check_expr(f, ns, node)
            if f.dtype is not None and not isinstance(f.dtype, BoolType):
                self.fail("filter-dtype", node,
                          f"pushed-down filter typed {f.dtype}, not bool")
        schema = None
        if self.tables is not None:
            t = self.tables.get(node.table)
            if t is None:
                # at execution time EVERY scan must resolve in the
                # registry — this would otherwise die as a KeyError
                # inside buffer collection
                self.fail("scan-unregistered", node,
                          f"table {node.table!r} not in the executor "
                          f"registry")
                return
            schema = getattr(t, "schema", None)
        elif self.catalog is not None:
            if not self.catalog.has_table(node.table):
                self.fail("scan-unknown-table", node,
                          f"table {node.table!r} not in catalog")
                return
            schema = self.catalog.schemas[node.table]
        if schema is not None:
            for n, dt in node.output:
                if n not in schema:
                    self.fail("scan-unknown-column", node,
                              f"{node.table}.{n} not in schema")
                elif schema.field(n).dtype != dt:
                    self.fail("scan-column-dtype", node,
                              f"{node.table}.{n} is "
                              f"{schema.field(n).dtype} in the schema, "
                              f"{dt} in the plan")

    def _check_stagedscan(self, node: P.StagedScan) -> None:
        if not isinstance(node.child, P.Scan):
            self.fail("staged-child", node,
                      f"child is {type(node.child).__name__}, not a "
                      f"temp-table Scan")
            return
        child_cols = dict(node.child.output)
        mangled = [m for _b, _n, m, _dt in node.cols]
        if sorted(mangled) != sorted(child_cols):
            self.fail("staged-mangle", node,
                      f"cols mapping {sorted(mangled)} is not a "
                      f"bijection with the temp scan's "
                      f"{sorted(child_cols)}")
        else:
            for _b, n, m, dt in node.cols:
                if child_cols[m] != dt:
                    self.fail("staged-dtype", node,
                              f"{m} staged as {child_cols[m]} but "
                              f"re-exposed as {dt} ({n})")
        if self.tables is not None and node.child.table not in self.tables:
            self.fail("staged-unregistered", node,
                      f"temp table {node.child.table!r} is not "
                      f"registered with the executor")

    def _check_filter(self, node: P.Filter) -> None:
        ns = _namespace(node.child, self.ns_memo)
        self.check_expr(node.predicate, ns, node)
        if (node.predicate.dtype is not None
                and not isinstance(node.predicate.dtype, BoolType)):
            self.fail("filter-dtype", node,
                      f"predicate typed {node.predicate.dtype}, not bool")

    def _check_project(self, node: P.Project) -> None:
        ns = _namespace(node.child, self.ns_memo)
        seen = set()
        for n, e in node.exprs:
            if n in seen:
                self.fail("project-dup", node,
                          f"duplicate output column {n!r}")
            seen.add(n)
            self.check_expr(e, ns, node)

    def _check_join_like(self, node) -> None:
        lns = _namespace(node.left, self.ns_memo)
        rns = _namespace(node.right, self.ns_memo)
        if len(node.left_keys) != len(node.right_keys):
            self.fail("join-key-arity", node,
                      f"{len(node.left_keys)} left vs "
                      f"{len(node.right_keys)} right keys")
        for k in node.left_keys:
            self.check_expr(k, lns, node)
        for k in node.right_keys:
            self.check_expr(k, rns, node)
        for lk, rk in zip(node.left_keys, node.right_keys):
            if not _joinable(lk.dtype, rk.dtype):
                self.fail("join-key-dtype", node,
                          f"key pair {lk!r}:{lk.dtype} vs "
                          f"{rk!r}:{rk.dtype} is not joinable")
        if node.residual is not None:
            both = dict(lns)
            both.update(rns)
            self.check_expr(node.residual, both, node)
            if (node.residual.dtype is not None
                    and not isinstance(node.residual.dtype, BoolType)):
                self.fail("residual-dtype", node,
                          f"residual typed {node.residual.dtype}")

    def _check_join(self, node: P.Join) -> None:
        self._check_join_like(node)
        if node.kind not in ("inner", "left", "full"):
            self.fail("join-kind", node, f"unknown kind {node.kind!r}")
        # kernel-choice invariants (engine/kernels.py): the planner's
        # stamp must name a real kernel AND one the trace can lower for
        # this node shape — a direct/matmul probe needs the unique-
        # build gather path, radix partitioning only exists for the
        # M:N inner expansion
        from nds_tpu.engine import kernels as KX
        if node.kernel not in KX.JOIN_KERNELS:
            self.fail("kernel-unknown", node,
                      f"unknown join kernel {node.kernel!r} "
                      f"(known: {[k for k in KX.JOIN_KERNELS if k]})")
        elif (node.kernel in (KX.JOIN_DIRECT, KX.JOIN_MATMUL)
                and not node.right_unique):
            self.fail("kernel-shape", node,
                      f"{node.kernel!r} requires a unique build side "
                      f"(right_unique)")
        elif node.kernel == KX.JOIN_PARTITIONED and (
                node.right_unique or node.kind != "inner"):
            self.fail("kernel-shape", node,
                      f"{node.kernel!r} only lowers the M:N inner "
                      f"expansion (kind={node.kind!r}, "
                      f"right_unique={node.right_unique})")

    def _check_semijoin(self, node: P.SemiJoin) -> None:
        self._check_join_like(node)
        from nds_tpu.engine import kernels as KX
        if node.kernel not in KX.SEMI_KERNELS:
            self.fail("kernel-unknown", node,
                      f"unknown semi-join kernel {node.kernel!r} "
                      f"(known: {[k for k in KX.SEMI_KERNELS if k]})")

    def _check_aggregate(self, node: P.Aggregate) -> None:
        from nds_tpu.engine import kernels as KX
        if node.kernel not in KX.AGG_KERNELS:
            self.fail("kernel-unknown", node,
                      f"unknown aggregate kernel {node.kernel!r} "
                      f"(known: {[k for k in KX.AGG_KERNELS if k]})")
        ns = _namespace(node.child, self.ns_memo)
        for _n, e in node.group_keys:
            self.check_expr(e, ns, node)
        for n, spec in node.aggs:
            if spec.arg is not None:
                self.check_expr(spec.arg, ns, node)
            arg_t = spec.arg.dtype if spec.arg is not None else None
            try:
                want = ir.agg_type(spec.func, arg_t)
            except TypeError as exc:
                self.fail("agg-illegal", node, f"{n}: {exc}")
                continue
            if spec.dtype != want:
                self.fail("agg-dtype", node,
                          f"{spec.func}({arg_t}) must produce {want}, "
                          f"plan says {spec.dtype} for {n!r}")

    def _check_window(self, node: P.Window) -> None:
        ns = _namespace(node.child, self.ns_memo)
        for n, s in node.specs:
            if s.dtype is None:
                self.fail("window-untyped", node, f"{n} has no dtype")
            if s.arg is not None:
                self.check_expr(s.arg, ns, node)
            for p in s.partition:
                self.check_expr(p, ns, node)
            for e, _asc, _nf in s.order:
                self.check_expr(e, ns, node)

    def _check_sort(self, node: P.Sort) -> None:
        ns = _namespace(node.child, self.ns_memo)
        for e, asc, nf in node.keys:
            self.check_expr(e, ns, node)
            # the planner hands a bool (NULL lowest where the ORDER BY
            # does not say); a hand-built plan's None reads as nulls last
            if not isinstance(asc, bool) or not isinstance(nf,
                                                           (bool,
                                                            type(None))):
                self.fail("sort-flags", node,
                          f"non-bool sort flags ({asc!r}, {nf!r})")

    def _check_limit(self, node: P.Limit) -> None:
        if not isinstance(node.count, int) or node.count < 0:
            self.fail("limit-count", node,
                      f"count {node.count!r} is not a non-negative int")

    def _check_distinct(self, node: P.Distinct) -> None:
        ns = _namespace(node.child, self.ns_memo)
        for n, _dt in node.output:
            if (node.binding, n) not in ns:
                self.fail("distinct-binding", node,
                          f"output column ({node.binding!r}, {n!r}) not "
                          f"addressable in the child context")

    def _check_setop(self, node: P.SetOp) -> None:
        kinds = ("union", "union all", "intersect", "except")
        if node.kind not in kinds:
            self.fail("setop-kind", node, f"unknown kind {node.kind!r}")
        lo, ro = node.left.output, node.right.output
        if len(lo) != len(ro):
            self.fail("setop-arity", node,
                      f"{len(lo)} vs {len(ro)} output columns")
            return
        for (ln, lt), (rn, rt) in zip(lo, ro):
            if not _union_compatible(lt, rt):
                self.fail("setop-dtype", node,
                          f"column pair {ln!r}:{lt} vs {rn!r}:{rt} "
                          f"cannot combine")

    # ------------------------------------------------------------ driver

    def run(self) -> list[Violation]:
        planned = self.planned
        roots = [("root", planned.root)]
        for i, sub in enumerate(planned.scalar_subplans):
            roots.append((f"scalar#{i}", sub))
            if not isinstance(sub, P.Node):
                self.fail("subplan-type", sub,
                          f"scalar subplan #{i} is not a plan Node")
                continue
            if len(sub.output) != 1:
                self.fail("subplan-arity", sub,
                          f"scalar subplan #{i} produces "
                          f"{len(sub.output)} columns, not 1")
        if planned.column_names and len(planned.column_names) != len(
                planned.root.output):
            self.fail("result-arity", planned.root,
                      f"{len(planned.column_names)} result names for "
                      f"{len(planned.root.output)} output columns")
        # the session/driver reads the root's output through its binding
        root_ns = _namespace(planned.root, self.ns_memo)
        for n, _dt in planned.root.output:
            if (planned.root.binding, n) not in root_ns:
                self.fail("root-binding", planned.root,
                          f"result column ({planned.root.binding!r}, "
                          f"{n!r}) not addressable at the root")
        seen: set = set()
        for _label, root in roots:
            if not isinstance(root, P.Node):
                continue
            for node in P.walk_plan(root):
                if id(node) in seen:
                    continue
                seen.add(id(node))
                self.check_node(node)
        return self.out


# -------------------------------------------------------------- frontend

def verify(planned: P.PlannedQuery, tables: "dict | None" = None,
           catalog=None) -> list[Violation]:
    """All invariant violations in one planned statement ([] = valid).

    ``tables`` (an executor's name -> HostTable registry) additionally
    proves Scan columns against real schemas and StagedScan temps
    against registration; ``catalog`` (planner CatalogInfo) does the
    schema half when no executor exists yet."""
    if not isinstance(planned, P.PlannedQuery):
        return [Violation("not-a-plan", type(planned).__name__,
                          "verify() expects a PlannedQuery")]
    return _Verifier(planned, tables, catalog).run()


def assert_valid(planned: P.PlannedQuery, tables: "dict | None" = None,
                 catalog=None, label: str = "") -> P.PlannedQuery:
    """verify() that raises PlanVerifyError on any violation; returns
    the plan unchanged so call sites can wrap in-line."""
    violations = verify(planned, tables, catalog)
    if violations:
        raise PlanVerifyError(violations, label)
    return planned


def check_exchange_invariants(n_rows: int, n_dev: int,
                              slack: float) -> list[Violation]:
    """Distributed-path consistency: the static-shape exchange contract
    (parallel/exchange.py) only holds when every device's per-peer
    bucket of ceil(n * slack / n_dev) rows gives total capacity >= the
    rows actually present. slack < 1 breaks that bound even with a
    perfectly uniform hash; non-positive mesh sizes are configuration
    corruption."""
    out: list[Violation] = []
    if n_dev < 1:
        out.append(Violation("exchange-mesh", "exchange",
                             f"n_dev={n_dev} must be >= 1"))
    if slack < 1.0:
        out.append(Violation("exchange-slack", "exchange",
                             f"slack={slack} < 1.0 cannot cover even a "
                             f"uniform partition"))
    if n_rows < 0:
        out.append(Violation("exchange-rows", "exchange",
                             f"negative row count {n_rows}"))
    if out:
        return out
    bucket = max(1, -(-int(n_rows * slack) // n_dev))
    if n_rows and bucket * n_dev < n_rows:
        out.append(Violation(
            "exchange-capacity", "exchange",
            f"bucket {bucket} x {n_dev} devices = {bucket * n_dev} "
            f"slots < {n_rows} rows"))
    return out


# ------------------------------------------------------- size estimates

def _dtype_width(dt: DType) -> int:
    """Estimated bytes per value as the engine materializes it on
    device: ints by declared width, decimals as scaled int64, dates as
    epoch-day int32, strings as int32 dictionary codes (the dictionary
    itself stays on host and is small next to the column)."""
    if isinstance(dt, IntType):
        return dt.bits // 8
    if isinstance(dt, FloatType):
        return dt.bits // 8
    if isinstance(dt, DecimalType):
        return 8
    if isinstance(dt, DateType):
        return 4
    if isinstance(dt, StringType):
        return 4
    return 8


def _scan_bytes(table, output, nrows: int,
                encoded: "bool | None" = None) -> int:
    """Bytes a device scan of these columns moves. With a real
    HostTable and an active columnar mode (nds_tpu/columnar/) the
    per-column ENCODED widths apply — so the scheduler cost model and
    the MemoryGovernor's pre-admission budget both see the compressed
    working set (an SF that only fits encoded must not be demoted off
    device on raw arithmetic). ``encoded=False`` forces raw widths —
    the scheduler passes it when costing a placement that uploads raw
    (the sharded SPMD path opts out of columnar upload, so shrinking
    ITS working-set math by the compression ratio would under-admit).
    Catalog-only estimates (and mode off) keep the raw device-width
    formula."""
    return sum(b for _n, b in _column_bytes(table, output, nrows,
                                            encoded))


def _column_bytes(table, output, nrows: int,
                  encoded: "bool | None" = None) -> list:
    """``_scan_bytes`` a column: [(name, bytes)] in ``output``'s
    order."""
    cols = getattr(table, "columns", None)
    if cols is not None:
        from nds_tpu import columnar
        if columnar.enabled() and encoded is not False:
            return [(name, columnar.scan_nbytes(cols[name])
                     if cols.get(name) is not None
                     else _dtype_width(dt) * nrows)
                    for name, dt in output]
    return [(name, nrows * _dtype_width(dt)) for name, dt in output]


def check_encoding_spec(spec, values, mask, nrows=None) -> list:
    """Invariants for one column-encoding choice (nds_tpu/columnar/):
    violations mean the spec cannot faithfully reproduce the column.
    Run at encode time under the verify gate (always on in tests).
    ``nrows`` bounds the LIVE prefix — pad rows past it are gated by
    the row mask at trace time and may clip freely."""
    import numpy as np
    out = []
    kind = getattr(spec, "kind", None)
    if kind not in ("bitpack", "rle", "raw"):
        out.append(f"unknown encoding kind {kind!r}")
        return out
    if spec.rows != len(values):
        out.append(f"{kind}: spec rows {spec.rows} != column rows "
                   f"{len(values)}")
    if spec.dtype != values.dtype.name:
        out.append(f"{kind}: spec dtype {spec.dtype!r} != column "
                   f"dtype {values.dtype.name!r}")
    if kind == "bitpack":
        if spec.bits not in (1, 2, 4, 8, 16, 32):
            out.append(f"bitpack: unsupported width {spec.bits}")
        else:
            live = values if nrows is None else values[:nrows]
            lmask = mask if nrows is None or mask is None \
                else mask[:nrows]
            live = live if lmask is None else live[lmask]
            if len(live):
                lo, hi = int(live.min()), int(live.max())
                top = spec.lo + ((2**31 - 1) if spec.bits >= 32
                                 else (1 << spec.bits) - 1)
                if lo < spec.lo or hi > top:
                    out.append(
                        f"bitpack: values [{lo},{hi}] exceed packed "
                        f"range [{spec.lo},{top}] — decode would "
                        f"clip live data")
    elif kind == "rle":
        if mask is not None:
            out.append("rle: null-masked column cannot RLE (runs "
                       "would splice null and live values)")
        if np.issubdtype(values.dtype, np.floating):
            out.append("rle: float column cannot RLE (value-equality "
                       "runs splice -0.0/+0.0; decode would flip "
                       "signbits vs the raw upload)")
        live = values if nrows is None else values[:nrows]
        if len(live) >= 2:
            actual = int(np.count_nonzero(
                live[1:] != live[:-1])) + 1
        else:
            actual = len(live)
        if spec.runs != actual:
            out.append(f"rle: spec runs {spec.runs} != actual "
                       f"{actual}")
    if spec.mask_packed and mask is None:
        out.append(f"{kind}: mask_packed without a null mask")
    return out


@dataclass
class PlanEstimate:
    """Static size estimate for one planned statement — the cost-model
    input the scheduler (engine/scheduler.py) seeds placement from.
    ``tables`` maps each scanned base table to its (rows, bytes)
    estimate; bytes count only the columns the plan's scans actually
    read, at device materialization widths. Estimates come from real
    HostTables when an executor registry is supplied, else from the
    planner catalog's relative size statistics — both paths need no
    accelerator (tools/ndsverify.py assigns placements on bare CPU)."""
    rows: int = 0
    bytes: int = 0
    widest_table_bytes: int = 0
    tables: dict = None  # type: ignore[assignment]
    # what the scheduler's working set is made of (engine/scheduler.
    # working_set). ``held_bytes``: the scans' columns counted ONCE a
    # (table, column), which is what a device executor keeps of them
    # (its buffers are keyed by table and column, however many Scan
    # nodes read the table). ``read_bytes``: of every scan, only the
    # columns an expression of the plan refers to (or the statement
    # returns): the bytes its operators' intermediates grow from. None:
    # an estimate made by hand, taken as ``bytes`` for both.
    held_bytes: "int | None" = None
    read_bytes: "int | None" = None
    joins: int = 0
    aggregates: int = 0
    sorts: int = 0
    windows: int = 0


def _plan_shape(planned: P.PlannedQuery) -> tuple:
    """What of a plan the size estimate needs, walked ONCE (a plan
    does not change once planned; the walk is kept on it):
    (joins, aggregates, sorts, windows, scans), every node counted
    once however many roots reach it, and per Scan node
    ``(table, output, read)`` with ``read`` the names of its columns
    that some expression of the plan refers to under the scan's
    binding, or that the statement's roots return (a column handed
    through to the result with no expression over it is read too)."""
    shape = planned.__dict__.get("_plan_shape")
    if shape is not None:
        return shape
    roots = [r for r in [planned.root, *planned.scalar_subplans]
             if isinstance(r, P.Node)]
    nodes, seen = [], set()
    for root in roots:
        for node in P.walk_plan(root):
            if id(node) not in seen:
                seen.add(id(node))
                nodes.append(node)
    refs: dict = {}
    for node in nodes:
        for e in P.all_exprs(node):
            for x in ir.walk(e):
                if isinstance(x, ir.ColRef):
                    refs.setdefault(x.binding, set()).add(x.name)
    passed = {name for root in roots for name, _dt in root.output}
    ops = [sum(isinstance(n, kind) for n in nodes)
           for kind in (P.Join, P.Aggregate, P.Sort, P.Window)]
    scans = [(n.table, n.output,
              frozenset(c for c, _dt in n.output
                        if c in passed or c in refs.get(n.binding, ())))
             for n in nodes if isinstance(n, P.Scan)]
    shape = planned.__dict__["_plan_shape"] = (*ops, scans)
    return shape


def estimate_plan(planned: P.PlannedQuery, tables: "dict | None" = None,
                  catalog=None,
                  encoded: "bool | None" = None) -> PlanEstimate:
    """Scan-level size estimate over every root (scalar subplans
    included). Row counts prefer the executor's registered HostTables
    (exact); the catalog's ``sizes`` statistics (relative row weights)
    are the planning-time fallback. Unknown tables estimate as 0 rows —
    the scheduler treats an all-unknown plan as small, which is the
    conservative direction for placement (the ladder recovers from an
    underestimate; overestimating would pin small queries off-device).
    ``encoded=False`` forces raw scan widths even under an active
    columnar mode (see ``_scan_bytes``)."""
    est = PlanEstimate(tables={})
    if not isinstance(planned, P.PlannedQuery):
        return est
    (est.joins, est.aggregates, est.sorts, est.windows,
     scans) = _plan_shape(planned)
    est.read_bytes = 0
    held: dict = {}     # table -> {column: bytes}
    for table, output, read in scans:
        nrows = 0
        t = tables.get(table) if tables is not None else None
        if t is not None:
            nrows = t.nrows
        elif catalog is not None:
            nrows = int(catalog.sizes.get(table, 0))
        per_col = _column_bytes(t, output, nrows, encoded)
        nbytes = sum(b for _n, b in per_col)
        est.read_bytes += sum(b for n, b in per_col if n in read)
        held.setdefault(table, {}).update(per_col)
        rows0, bytes0 = est.tables.get(table, (0, 0))
        # one table scanned by several Scan nodes: rows count once,
        # bytes accumulate per scan (each scan uploads its columns)
        # ndslint: waive[NDS119] -- est.tables is a local cost-estimate accumulator, not a session catalog
        est.tables[table] = (max(rows0, nrows), bytes0 + nbytes)
    est.held_bytes = sum(sum(cols.values()) for cols in held.values())
    for nrows, nbytes in est.tables.values():
        est.rows += nrows
        est.bytes += nbytes
        est.widest_table_bytes = max(est.widest_table_bytes, nbytes)
    return est
