"""Chunked (out-of-core) execution: tables larger than HBM stream
through the device in fixed-size chunks.

SURVEY.md §7 hard part 4: at SF3K a fact table (and its shuffle) exceeds
HBM, and the reference gets spill for free from Spark's block shuffle
(SURVEY.md §2.6). The TPU-native answer here is HOST-STAGED execution:

- big tables live in host RAM only; the device never holds more than
  ``chunk_rows`` of them at once;
- phase A (streaming scan): one compiled chunk program per streamed
  table evaluates every pushed-down scan filter for that table
  (`plan.Scan.filters`) over each chunk and returns just a keep-bitmap
  — values never round-trip; the host gathers surviving rows into a
  reduced table. Filters are re-applied in phase B, so phase A may be
  conservative (any filter it cannot evaluate keeps all rows);
- phase B: the UNCHANGED plan executes against the reduced table with
  the normal static-shape engine — now sized by post-filter survivors,
  not raw rows.

This bounds device residency by max(chunk, survivors): the engine runs
any query whose post-filter working set fits HBM, regardless of raw
table size. (The follow-on stage for full-scan aggregations — partial
aggregation per chunk with host combine — composes with the same chunk
loop.)

The per-chunk program is compiled ONCE per (table, plan): every chunk
has the same static shape; the tail chunk passes its logical row count
as a traced scalar, not a new shape.

Both phase-A loops ride the double-buffered prefetcher
(``engine/pipeline_io.py``, README "Pipelined execution"): host-side
slicing + columnar encoding + the ``jax.device_put`` for chunk N+1 run
on a worker thread while the compiled program scans chunk N, so scan
never blocks compute. ``engine.prefetch.enabled=off`` /
``NDS_TPU_PREFETCH=0`` restores the byte-identical serial loops; the
prefetch shapes nothing the chunkscan fingerprint sees, so warm-cache
runs stay at zero compiles either way.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from nds_tpu.engine import device_exec as dx
from nds_tpu.engine import pipeline_io
from nds_tpu.engine.device_exec import DCtx, DVal
from nds_tpu.engine.types import (
    INT64, DecimalType, FloatType, Schema, StringType,
)
from nds_tpu.io.host_table import HostColumn, HostTable, encode_strings
from nds_tpu.obs import memwatch
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import get_tracer
from nds_tpu.resilience import watchdog
from nds_tpu.resilience.retry import (
    QueryDeadlineExceeded, check_deadline, is_oom,
)
from nds_tpu.sql import ir
from nds_tpu.sql import plan as P

# stream tables above this many bytes (column data, host-side estimate);
# the default targets a 16G-HBM chip with headroom for join expansion
DEFAULT_STREAM_BYTES = 2 << 30
DEFAULT_CHUNK_ROWS = 1 << 20


def _table_bytes(t: HostTable) -> int:
    """Raw host bytes of a table — the stream/upload decision input.
    Deliberately NOT the encoded size: streaming is about host->device
    transfer and residency headroom for the UNREDUCED table, and a
    table that only fits encoded should still take the chunked path's
    conservative route (the governor's budget math is where encoded
    widths apply — analysis/plan_verify._scan_bytes)."""
    return sum(c.nbytes for c in t.columns.values())


class _PhaseBExecutor(dx.DeviceExecutor):
    """Per-plan executor over {full tables, streamed->reduced}: device
    buffers for NON-streamed tables come from a pool shared across every
    phase-B executor (dimension columns upload once per session, the
    load-once/query-many lifecycle), while reduced-table buffers stay
    local — their contents differ per plan."""

    # tables here are already survivor-reduced by the union of the
    # plan's scan filters; a second per-scan shrink would desync
    # _PartialAggExecutor's buffer walk from the trace for marginal gain
    SCAN_REDUCE = False
    # its pools hold reduced and chunked stand-ins under a table's
    # keys: never the process's whole-column copies
    SHARE_COLUMNS = False

    def __init__(self, tables, float_dtype, shared_buffers: dict,
                 streamed: set):
        super().__init__(tables, float_dtype)
        self._shared = shared_buffers
        self._streamed = streamed

    def _upload(self, bufs: dict, table: str, name: str) -> None:
        pool = (self._buffers if table in self._streamed
                else self._shared)
        # the shared pool-placement helper also applies the columnar
        # encoding (nds_tpu/columnar/): dimension columns upload
        # encoded ONCE into the shared pool, reduced streamed tables
        # encode into the executor-local pool per plan
        self._pool_upload(pool, bufs, table, name)


def _walk_skip(node: P.Node, skip: set):
    """walk_plan that does not descend below replaced nodes."""
    yield node
    if id(node) in skip:
        return
    for c in P.children(node):
        yield from _walk_skip(c, skip)


class _MergeTrace(dx._Trace):
    """Trace that substitutes a chunk-partial MERGE for one Aggregate
    node: when execution reaches the original aggregate, it instead
    aggregates the concatenated per-chunk partials (already in the
    buffer set) and re-maps the merged columns onto the original
    binding — sum of sums, sum of counts, min of mins, and
    sum/count recomposition for avg."""

    def run(self, node: P.Node) -> DCtx:
        rep = getattr(self.ex, "_replace", None)
        if rep and id(node) in rep and id(node) not in self._cache:
            self.stash(node, self._merged_ctx(*rep[id(node)]))
        return super().run(node)

    def _merged_ctx(self, merge_node: P.Aggregate,
                    A: P.Aggregate, sum_dtypes: dict) -> DCtx:
        mctx = self.run(merge_node)
        mb = merge_node.binding
        out = DCtx(mctx.n, mctx.row)
        for n, _e in A.group_keys:
            out.cols[(A.binding, n)] = mctx.cols[(mb, n)]
        for n, spec in A.aggs:
            if spec.func == "avg":
                s = mctx.cols[(mb, n + "__s")]
                c = mctx.cols[(mb, n + "__c")]
                f = dx._to_float(s.arr, sum_dtypes[n], self.fdt)
                cnt = c.arr.astype(self.fdt)
                arr = f / jnp.maximum(cnt, 1)
                valid = c.arr > 0
                if s.valid is not None:
                    valid = valid & s.valid
                out.cols[(A.binding, n)] = DVal(arr, valid)
            else:
                out.cols[(A.binding, n)] = mctx.cols[(mb, n)]
        return out


class _PartialAggExecutor(_PhaseBExecutor):
    """Phase-B executor for the partial-aggregation path: executes the
    ORIGINAL plan, but the subtree under the split Aggregate never runs
    (its buffers are never uploaded) — the merge plan over the partials
    table stands in for it via _MergeTrace. Non-streamed buffers come
    from the shared pool (_PhaseBExecutor contract)."""

    def __init__(self, tables, float_dtype, shared_buffers, streamed,
                 replace: dict, extra_roots: list):
        super().__init__(tables, float_dtype, shared_buffers, streamed)
        self._replace = replace
        self._extra_roots = extra_roots

    def _collect_buffers(self, planned: P.PlannedQuery) -> dict:
        bufs = {}
        roots = ([planned.root] + list(planned.scalar_subplans)
                 + self._extra_roots)
        for root in roots:
            for node in _walk_skip(root, set(self._replace)):
                if isinstance(node, P.Scan):
                    for name, _dt in node.output:
                        self._upload(bufs, node.table, name)
        return bufs

    def _compile(self, planned: P.PlannedQuery,
                 slack: float = dx.DeviceExecutor.DEFAULT_SLACK):
        side = {}

        def fn(bufs):
            tr = _MergeTrace(self, bufs, slack)
            row, outs, dicts = tr.run_query(planned)
            side["dicts"] = dicts
            with jax.named_scope("op.root"):
                return row, outs, tr.total_overflow()

        # ndslint: waive[NDS111] -- builds the traced callable only; AOT lower+compile routes through cache.aot (_compile_or_load)
        return jax.jit(fn), side

    def _fingerprint_roots(self) -> list:
        """The merge substitution shapes the program but lives OUTSIDE
        the PlannedQuery (the trace swaps it in at id-matched nodes):
        fold the merge plans into the fingerprint or a plain phase-B
        program of the same plan would key-collide. The partials
        table's content stamp rides along via the merge plan's scan."""
        return list(self._extra_roots)


class _ForwardResult:
    """Async handle that forwards the phase-B sub-executor's finalized
    timings + query span back onto the outer ChunkedExecutor when the
    caller blocks on result(). Phase A's prefetch attribution
    (engine/pipeline_io.py) merges into the published timings here —
    the one place the sub-executor's bill and the outer executor's
    staging overlap meet."""

    __slots__ = ("outer", "sub", "inner", "pf")

    def __init__(self, outer, sub, inner, pf=None):
        self.outer = outer
        self.sub = sub
        self.inner = inner
        self.pf = dict(pf or {})

    def result(self):
        out = self.inner.result()
        timings = self.sub.last_timings
        span = getattr(self.sub, "last_query_span", None)
        if self.pf and isinstance(timings, dict):
            timings.update(self.pf)
            # the span carries a FILTERED copy of the timings as its
            # exported attr (device_exec._finish_traced): update it too
            # so span-fed consumers (obs.query_timings) see the
            # prefetch keys
            attr = span.attrs.get("timings") if span else None
            if isinstance(attr, dict):
                attr.update(self.pf)
        self.outer.last_timings = timings
        self.outer.last_query_span = span
        return out


class ChunkedExecutor(dx.DeviceExecutor):
    """DeviceExecutor that streams oversized tables through the chip."""

    # phase-B executors kept alive (compiled programs + reduced
    # buffers); older ones evict so reduced-row HBM doesn't accumulate
    # across a 99-query power run
    MAX_REDUCED = 16
    # the relief placement holds what IT uploads, nothing a process
    # keeps resident beside it
    SHARE_COLUMNS = False

    def __init__(self, tables: dict[str, HostTable],
                 stream_bytes: int = DEFAULT_STREAM_BYTES,
                 chunk_rows: int = DEFAULT_CHUNK_ROWS,
                 float_dtype=None,
                 prefetch_depth: "int | None" = None):
        super().__init__(tables, float_dtype)
        self.stream_bytes = stream_bytes
        self.chunk_rows = chunk_rows
        # double-buffered phase-A prefetch depth (engine/pipeline_io.py;
        # 0 = the byte-identical serial loops). The scheduler may lower
        # it per query (governor depth admission, ladder relief entry)
        # through the same _restore contract as chunk_rows
        self.prefetch_depth = (pipeline_io.resolve_depth()
                               if prefetch_depth is None
                               else max(0, int(prefetch_depth)))
        # per-query prefetch attribution (wait billed to wall-clock,
        # hidden overlapped under compute), merged into the published
        # timings at result() by _ForwardResult
        self._pf_stats: dict = {}
        # (plan key) -> phase-B executor
        self._reduced: dict[object, _PhaseBExecutor] = {}
        # (table, filter repr) -> reduced HostTable, shared across plans
        self._survivor_cache: dict[tuple, HostTable] = {}

    def _note_prefetch(self, stats: dict) -> None:
        """Fold one prefetcher's close() stats into the query's
        attribution (several phase-A loops can run per query — one per
        streamed table plus the partial-agg chunk loop)."""
        if not stats or stats.get("depth", 0) <= 0:
            return
        pf = self._pf_stats
        pf["prefetch_wait_ms"] = (pf.get("prefetch_wait_ms", 0.0)
                                  + stats["wait_s"] * 1000.0)
        pf["prefetch_hidden_s"] = (pf.get("prefetch_hidden_s", 0.0)
                                   + stats["hidden_s"])
        pf["prefetch_depth"] = max(pf.get("prefetch_depth", 0),
                                   stats["depth"])

    def _is_streamed(self, table: str) -> bool:
        return _table_bytes(self.tables[table]) > self.stream_bytes

    # ----------------------------------------------------------------- API

    # chunk halving floor: below this the per-chunk dispatch overhead
    # dominates and an OOM is no longer a chunk-size problem
    MIN_CHUNK_ROWS = 1 << 12

    def execute_async(self, planned: P.PlannedQuery, key: object = None):
        from nds_tpu.sql import params as sqlparams
        if sqlparams.has_params(planned) and self._streamed_scans(planned):
            # the out-of-core phase machinery evaluates literals as
            # trace constants (keep masks, chunk-scan fingerprints):
            # streamed parameterized plans run their inlined form
            planned = sqlparams.inline(planned)
        scans = self._streamed_scans(planned)
        if not scans:
            # unstreamed: the base device path runs (natively
            # parameterized when the plan carries params)
            return super().execute_async(planned, key)
        key = key if key is not None else id(planned)
        # a failed streamed query must never inherit the previous
        # query's span OR timings (same reset contract as the base
        # executor; last_timings rebinds only after phase A succeeds)
        self.last_query_span = None
        self.last_timings = {}
        # fresh prefetch attribution window: phase A below may run
        # several prefetchers; their stats accumulate here and publish
        # at result() (a plan-cache-warm query that skips phase A
        # publishes nothing)
        self._pf_stats = {}
        # graceful degradation: an OOM-classified failure halves the
        # chunk size and rebuilds phase A before giving up — the
        # out-of-core engine's whole premise is that residency, not
        # total size, is the limit (no-sleep policy from the pipeline
        # module, the one place engine retry wiring is instantiated)
        from nds_tpu.engine.scheduler import adaptive_policy
        policy = adaptive_policy(3)
        last_attempt = policy.max_attempts - 1
        for attempt in policy.attempts():
            try:
                if key not in self._reduced:
                    sub = self._build_phase_b(planned, scans)
                    while len(self._reduced) >= self.MAX_REDUCED:
                        self._reduced.pop(next(iter(self._reduced)))
                    self._reduced[key] = sub
                sub = self._reduced[key]
                res = sub.execute_async(planned, key)
                break
            except Exception as exc:  # noqa: BLE001 - classified below
                if (not is_oom(exc) or attempt >= last_attempt
                        or self.chunk_rows // 2 < self.MIN_CHUNK_ROWS):
                    raise
                self.chunk_rows //= 2
                # drop every artifact sized by the old chunking
                self._reduced.pop(key, None)
                self._survivor_cache.clear()
                obs_metrics.counter("chunk_shrink_total").inc()
                from nds_tpu.utils.report import TaskFailureCollector
                TaskFailureCollector.notify(
                    f"OOM-classified failure in chunked execution "
                    f"({type(exc).__name__}); halving chunk_rows to "
                    f"{self.chunk_rows}")
        self.last_timings = sub.last_timings
        # the sub-executor's span/timings finalize at result(): forward
        # them so obs.query_timings(chunked_executor) sees the query
        return _ForwardResult(self, sub, res, pf=self._pf_stats)

    def _build_phase_b(self, planned: P.PlannedQuery, scans: dict):
        """Phase A (reduce streamed tables) + phase-B executor choice
        for one plan."""
        reduced = {}
        for table, table_scans in scans.items():
            reduced[table] = self._reduce_table(table, table_scans)
        sub = None
        # filters didn't shrink some table under the budget: try
        # per-chunk PARTIAL AGGREGATION before resorting to a full
        # upload (the q1 full-scan-aggregate shape)
        big = [t for t, r in reduced.items()
               if _table_bytes(r) > self.stream_bytes]
        if len(big) == 1 and len(scans[big[0]]) == 1:
            try:
                sub = self._try_partial_agg(
                    planned, big[0], scans[big[0]][0], reduced)
            except Exception as exc:  # noqa: BLE001 - fall back
                if isinstance(exc, QueryDeadlineExceeded):
                    # a deadlined query must ABORT, not fall back to a
                    # full upload that takes even longer
                    raise
                if (is_oom(exc)
                        and self.chunk_rows // 2 >= self.MIN_CHUNK_ROWS):
                    # the chunk-halving loop can still shrink phase A;
                    # once the floor is reached, OOM falls through to
                    # the full-upload fallback below like any other
                    # partial-agg failure
                    raise
                from nds_tpu.utils.report import TaskFailureCollector
                TaskFailureCollector.notify(
                    f"partial-agg path failed for {big[0]}, falling "
                    f"back to full upload: "
                    f"{type(exc).__name__}: {exc}")
        if sub is None:
            # identity reductions (keep-all) are the session's own
            # table objects — those buffers can live in the shared
            # pool; genuinely reduced tables differ per plan and
            # stay executor-local
            local = {t for t, r in reduced.items()
                     if r is not self.tables[t]}
            sub = _PhaseBExecutor({**self.tables, **reduced},
                                  self.float_dtype, self._buffers,
                                  local)
        return sub

    def _streamed_scans(self, planned: P.PlannedQuery) -> dict:
        """{table: [Scan, ...]} for streamed tables in this plan."""
        out: dict[str, list] = {}
        for root in [planned.root] + list(planned.scalar_subplans):
            for node in P.walk_plan(root):
                if isinstance(node, P.Scan) and self._is_streamed(
                        node.table):
                    out.setdefault(node.table, []).append(node)
        return out

    # -------------------------------------------- phase A: partial agg

    MERGEABLE = ("sum", "count", "min", "max", "avg")

    def _try_partial_agg(self, planned: P.PlannedQuery, table: str,
                         scan: P.Scan, reduced: dict):
        """Split the plan at the first Aggregate above the streamed
        scan: per-chunk partial aggregation on device, host concat of
        the (small) partials, then the ORIGINAL plan runs with the
        aggregate's subtree replaced by a merge over the partials.
        Returns a phase-B executor, or None when the plan shape does
        not split."""
        for sub in planned.scalar_subplans:
            for node in P.walk_plan(sub):
                if isinstance(node, P.Scan) and node.table == table:
                    return None  # scalar subplan scans the big table
        path = self._path_to(planned.root, scan)
        if path is None:
            return None
        agg_i = None
        for i in range(len(path) - 2, -1, -1):
            node = path[i]
            if isinstance(node, P.Aggregate):
                agg_i = i
                break
            if not isinstance(node, (P.Filter, P.Project, P.Join,
                                     P.SemiJoin, P.DerivedScan)):
                return None  # blocking op (sort/window/...) below any agg
            # chunking only distributes over sides whose rows partition
            # the operator's OUTPUT: either side of an inner join, the
            # LEFT of a left-outer or semi/anti join. Chunking a semi
            # join's RIGHT (the EXISTS set) would evaluate membership
            # against one chunk at a time — q22's NOT EXISTS(orders)
            # counted a customer once per orders-chunk without this.
            child = path[i + 1]
            if isinstance(node, P.SemiJoin) and child is not node.left:
                return None
            if isinstance(node, P.Join):
                if node.kind == "full":
                    return None  # distributes over neither side
                if node.kind != "inner" and child is not node.left:
                    return None
        if agg_i is None:
            return None
        A = path[agg_i]
        if any(spec.distinct or spec.func not in self.MERGEABLE
               for _n, spec in A.aggs):
            return None
        agg2, sum_dtypes = self._decompose(A)
        base = {**self.tables,
                **{t: r for t, r in reduced.items() if t != table}}
        planned_a = P.PlannedQuery(
            root=agg2, scalar_subplans=list(planned.scalar_subplans),
            column_names=[])
        plan_local = {t for t, r in reduced.items()
                      if r is not self.tables[t]} | {table}
        with get_tracer().span("chunk.partial_agg", table=table):
            parts = self._run_partial_chunks(base, reduced[table],
                                             table, planned_a,
                                             plan_local)
        ptable = self._partials_host_table(agg2, parts)
        pb = "__pa_scan__"
        scan_p = P.Scan(table=ptable.name, binding=pb,
                        output=list(agg2.output), filters=[])
        mg_keys = [(n, ir.ColRef(pb, n, e.dtype))
                   for n, e in A.group_keys]
        mg_aggs = []
        for n, spec in A.aggs:
            if spec.func == "avg":
                sdt = sum_dtypes[n]
                mg_aggs.append((n + "__s", P.AggSpec(
                    "sum", ir.ColRef(pb, n + "__s", sdt), False, sdt)))
                mg_aggs.append((n + "__c", P.AggSpec(
                    "sum", ir.ColRef(pb, n + "__c", INT64), False,
                    INT64)))
            elif spec.func == "count":
                mg_aggs.append((n, P.AggSpec(
                    "sum", ir.ColRef(pb, n, INT64), False, INT64)))
            else:  # sum / min / max merge with themselves
                mg_aggs.append((n, P.AggSpec(
                    spec.func, ir.ColRef(pb, n, spec.dtype), False,
                    spec.dtype)))
        merge_node = P.Aggregate(child=scan_p, group_keys=mg_keys,
                                 aggs=mg_aggs, binding="__pa_merge__")
        sub = _PartialAggExecutor(
            {**base, ptable.name: ptable}, self.float_dtype,
            self._buffers, plan_local | {ptable.name},
            {id(A): (merge_node, A, sum_dtypes)}, [merge_node])
        return sub

    @staticmethod
    def _path_to(root: P.Node, target: P.Node):
        if root is target:
            return [root]
        for c in P.children(root):
            p = ChunkedExecutor._path_to(c, target)
            if p is not None:
                return [root] + p
        return None

    @staticmethod
    def _decompose(A: P.Aggregate):
        """avg -> (sum, count) pair so partials merge exactly; other
        mergeable funcs keep their own spec. Returns (agg2, {avg name:
        sum dtype})."""
        aggs2, sum_dtypes = [], {}
        for n, spec in A.aggs:
            if spec.func != "avg":
                aggs2.append((n, spec))
                continue
            arg_dt = spec.arg.dtype
            if isinstance(arg_dt, (FloatType, DecimalType)):
                sdt = arg_dt
            else:
                sdt = INT64
            sum_dtypes[n] = sdt
            aggs2.append((n + "__s",
                          P.AggSpec("sum", spec.arg, False, sdt)))
            aggs2.append((n + "__c",
                          P.AggSpec("count", spec.arg, False, INT64)))
        agg2 = P.Aggregate(child=A.child, group_keys=list(A.group_keys),
                           aggs=aggs2, binding=A.binding)
        return agg2, sum_dtypes

    @staticmethod
    def _slice_table(t: HostTable, start: int, stop: int) -> HostTable:
        cols = {}
        for name, c in t.columns.items():
            cols[name] = HostColumn(
                c.dtype, c.values[start:stop], c.dictionary,
                None if c.null_mask is None
                else c.null_mask[start:stop])
        return HostTable(t.name, t.schema, cols)

    def _run_partial_chunks(self, base: dict, big: HostTable,
                            table: str, planned_a: P.PlannedQuery,
                            plan_local: set):
        """Execute the partial aggregate once per chunk. All full-size
        chunks share ONE compiled program (same static shape, buffers
        swapped per chunk); the tail chunk compiles once more at its
        own size."""
        n = big.nrows
        C = min(self.chunk_rows, max(n, 1))
        spans = [(s, min(s + C, n)) for s in range(0, n, C)]
        obs_metrics.counter("chunk_scans_total").inc(len(spans))
        by_size: dict[int, list] = {}
        for span in spans:
            by_size.setdefault(span[1] - span[0], []).append(span)
        # bounds of the table being chunked must come from ALL its rows:
        # the chunk program compiles ONCE from chunk 0's executor, and
        # col_bounds feed key packing clips, group capacity, and int32
        # narrowing — chunk-0-local bounds would silently corrupt later
        # chunks (clustered layouts make this the common case, not the
        # edge case)
        # ndslint: waive[NDS110] -- bounds-probe helper over one host table (col_bounds/col_is_sorted only); no plan ever executes on it
        bx = dx.DeviceExecutor({table: big})
        full_bounds = {(table, name): bx.col_bounds(table, name)
                       for name in big.columns}
        # same hazard for the presorted-build fast path: a chunk-0-local
        # "sorted" verdict would bake a sort-skip into the program later
        # chunks reuse with swapped (possibly unsorted) buffers — seed
        # the WHOLE-table verdict instead (a slice of a globally sorted
        # column is still sorted, so chunk reuse stays valid)
        full_bounds.update(
            {(table, name, "sorted"): bx.col_is_sorted(table, name)
             for name in big.columns})
        parts = []
        for size, group in by_size.items():
            # between-chunk control point: the per-query deadline is
            # enforced INSIDE the attempt (a 200-chunk scan must stop
            # at the next boundary, not finish a doomed pass), and the
            # heartbeat shows per-chunk liveness to the hang watchdog
            check_deadline()
            watchdog.beat("engine", phase="chunk.partial_agg",
                          table=table)
            s0, e0 = group[0]
            # every per-plan table (reduced variants + the chunked one)
            # stays executor-local; only immutable full tables share
            # the session pool
            ex = _PhaseBExecutor(
                {**base, table: self._slice_table(big, s0, e0)},
                self.float_dtype, self._buffers, plan_local)
            ex._bounds.update(full_bounds)
            # the swap loop below rebuilds this table's buffers as
            # RAW slices each chunk; an encoded chunk-0 program would
            # misread them, so the chunked table uploads raw (the
            # phase-A keep-mask scan is where streamed chunks scan
            # encoded)
            ex._no_encode = {table}
            parts.append(ex.execute(planned_a))  # compiles + runs chunk 0
            entry = ex._compiled[id(planned_a)]
            compiled, side = entry["compiled"], entry["side"]
            slack = entry["slack"]
            # the swap key template: exactly the streamed table's
            # buffer keys the compiled program consumes (raw uploads —
            # see _no_encode above), fixed after chunk 0's compile
            tmpl = set(ex._collect_buffers(planned_a))

            def _stage_swap(span):
                """Host half of one chunk: slice the streamed columns
                and issue their async host->device transfer
                (jax.device_put). Runs on the prefetch worker when
                depth > 0 — while the compiled program is still
                executing the previous chunk."""
                s, e = span
                swap = {}
                for name in big.columns:
                    bkey = f"{table}.{name}"
                    if bkey not in tmpl:
                        continue
                    col = big.columns[name]
                    swap[bkey] = jax.device_put(col.values[s:e])
                    if bkey + "#v" in tmpl:
                        swap[bkey + "#v"] = jax.device_put(
                            col.null_mask[s:e])
                return swap, sum(b.nbytes for b in swap.values())

            pf = pipeline_io.ChunkPrefetcher(
                group[1:], _stage_swap, self.prefetch_depth,
                table=table)
            tracer = get_tracer()
            try:
                for staged in pf:
                    s, e = staged.item
                    check_deadline()
                    watchdog.beat("engine", phase="chunk.partial_agg",
                                  table=table)
                    bufs = ex._collect_buffers(planned_a)
                    bufs.update(staged.payload)
                    # per-chunk memory window (obs/memwatch): the
                    # staged swap bytes are accounted by the
                    # prefetcher from stage to release; the shared
                    # pool references bracket the compute only —
                    # together the live set the serial loop accounted
                    win = sum(getattr(b, "nbytes", 0)
                              for k, b in bufs.items()
                              if k not in staged.payload)
                    memwatch.add_live(win)
                    try:
                        # overflow-retry on the shared policy
                        # (slack-doubling shape, no backoff sleep —
                        # same as dist_exec)
                        from nds_tpu.engine.scheduler import (
                            adaptive_policy,
                        )
                        overflow_policy = adaptive_policy(4)
                        for attempt in overflow_policy.attempts():
                            # each chunk (and each overflow retry)
                            # bills its program's compiler cost once,
                            # under its own device.launch
                            _t, devs = ex._launch(
                                tracer, type(ex).__name__, compiled,
                                bufs)
                            # ndslint: waive[NDS117] -- sanctioned per-chunk sync point: the overflow verdict gates the slack-doubling retry, and the partials must land on host before the next chunk swaps buffers
                            row_h, outs_h, over_h = ex._readback(
                                tracer, devs)
                            if int(over_h) == 0:
                                break
                            if attempt == overflow_policy.max_attempts - 1:
                                raise dx.DeviceExecError(
                                    "partial-agg chunk overflow "
                                    "persisted")
                            # skewed chunk expands past the
                            # chunk-0-sized join capacity: double
                            # slack and recompile, same as the
                            # executor's own overflow-retry contract
                            from nds_tpu.utils.report import (
                                TaskFailureCollector,
                            )
                            slack *= 2
                            TaskFailureCollector.notify(
                                f"partial-agg chunk [{s}:{e}] "
                                f"overflow; recompiling with "
                                f"slack={slack}")
                            from nds_tpu.cache import aot as cache_aot
                            jitted, side = ex._compile(planned_a,
                                                       slack)
                            # ndsjit finding: this overflow recompile
                            # was invisible to the cost ledger — a
                            # warm run could recompile here and still
                            # report compiles == 0
                            obs_metrics.counter(
                                "recompiles_total").inc()
                            compiled = cache_aot.lower_and_compile(
                                jitted, bufs, kind="partial_agg_retry")
                    finally:
                        memwatch.sub_live(win)
                        staged.release()
                    parts.append(ex._materialize(planned_a, row_h,
                                                 outs_h, side))
            finally:
                self._note_prefetch(pf.close())
        return parts

    @staticmethod
    def _partials_host_table(agg2: P.Aggregate, parts) -> HostTable:
        names = [n for n, _dt in agg2.output]
        dtypes = [dt for _n, dt in agg2.output]
        fields = []
        cols = {}
        for i, (name, dt) in enumerate(zip(names, dtypes)):
            vals = np.concatenate([np.asarray(p.cols[i]) for p in parts])
            valid_parts = []
            any_valid = any(p.valids[i] is not None for p in parts)
            if any_valid:
                for p in parts:
                    v = p.valids[i]
                    valid_parts.append(
                        np.ones(len(p.cols[i]), dtype=bool)
                        if v is None else np.asarray(v))
                mask = np.concatenate(valid_parts)
            else:
                mask = None
            if isinstance(dt, StringType):
                codes, dictionary = encode_strings(vals.astype(str))
                cols[name] = HostColumn(dt, codes, dictionary, mask)
            else:
                cols[name] = HostColumn(dt, vals, None, mask)
            fields.append((name, dt, True))
        schema = Schema.of(*fields)
        return HostTable("__pa_partials__", schema, cols)

    # ------------------------------------------------- phase A: chunk scan

    def _reduce_table(self, table: str, scans: list) -> HostTable:
        t = self.tables[table]
        # one reduced table serves every scan of it in the plan: a row
        # survives if ANY scan's filter conjunction accepts it (each
        # scan re-applies its own filters in phase B)
        cache_key = (table, tuple(sorted(
            repr(s.filters) for s in scans)))
        hit = self._survivor_cache.get(cache_key)
        if hit is not None:
            return hit
        need_cols = sorted({name for s in scans for name, _ in s.output})
        with get_tracer().span("chunk.reduce", table=table,
                               rows=t.nrows):
            keep = self._chunk_keep_mask(table, scans, need_cols)
        if keep.all():
            # zero reduction (filterless scan / fallback): the original
            # table IS the result — no multi-GB host copy
            reduced = t
        else:
            idx = np.nonzero(keep)[0]
            cols = {}
            for name in t.columns:
                c = t.columns[name]
                cols[name] = HostColumn(
                    c.dtype, c.values[idx], c.dictionary,
                    None if c.null_mask is None else c.null_mask[idx])
            reduced = HostTable(table, t.schema, cols)
        # bounded like _reduced: host RAM for survivor copies must not
        # accumulate across a 99-query run (live phase-B executors keep
        # their own references; eviction only drops the shared entry)
        while len(self._survivor_cache) >= self.MAX_REDUCED:
            self._survivor_cache.pop(next(iter(self._survivor_cache)))
        self._survivor_cache[cache_key] = reduced
        return reduced

    def invalidate_tables(self, names) -> None:
        """Scoped DML invalidation for the out-of-core engine: beyond
        the base executor's buffers/bounds/scan-views, drop the mutated
        tables' survivor copies and every phase-B executor (they embed
        reduced snapshots; which tables each one streamed isn't
        recorded, so the conservative drop is the correct one — their
        compiled programs persist in the AOT cache and re-attach
        without recompiling)."""
        super().invalidate_tables(names)
        touched = set(names)
        for ck in [ck for ck in self._survivor_cache
                   if ck[0] in touched]:
            del self._survivor_cache[ck]
        self._reduced.clear()

    def _chunk_keep_mask(self, table: str, scans: list,
                         need_cols: list) -> np.ndarray:
        t = self.tables[table]
        n = t.nrows
        C = min(self.chunk_rows, max(n, 1))
        # delta deleted-row bitmask: DF_*-deleted rows never survive
        # phase A regardless of what the filters say
        from nds_tpu.columnar import delta
        live = delta.live_mask(t)
        # an EMPTY filter conjunction accepts every row: if any scan of
        # this table is filterless, no reduction is possible (the one
        # reduced table serves all scans of it in phase B) — beyond
        # excluding deleted rows
        if any(not s.filters for s in scans):
            return np.ones(n, dtype=bool) if live is None \
                else live.copy()

        # encoded chunk scans (nds_tpu/columnar/): bitpack-only, with
        # bounds from the WHOLE table, so every chunk of a column
        # shares one spec and the compiled chunk program is reused
        # unchanged across chunks (RLE would change shape per chunk)
        from nds_tpu import columnar
        chunk_specs: dict = {}
        if columnar.enabled() and self.COLUMNAR_UPLOAD:
            for cname in need_cols:
                spec = columnar.chunk_spec(
                    t.columns[cname], C, self.col_bounds(table, cname))
                if spec is not None:
                    chunk_specs[cname] = spec

        skipped: list = []

        # ndsjit: waive[NDSJ302] -- t is self.tables[table], content-stamped into the fingerprint via tables=; skipped is trace-time bookkeeping that never shapes the program (warm hits legitimately skip it, see _keep_mask_compiled)
        def fn(bufs, n_valid):
            from nds_tpu.columnar import device as columnar_dev
            base = jnp.arange(C, dtype=jnp.int32) < n_valid
            keep = jnp.zeros(C, dtype=bool)
            for scan in scans:
                tr = dx._Trace(self, bufs)
                ctx = DCtx(C, base)
                for name, _dt in scan.output:
                    col = t.columns[name]
                    lo, hi = self.col_bounds(table, name)
                    sdict = col.dictionary if col.is_string else None
                    spec = chunk_specs.get(name)
                    if spec is not None:
                        arr, valid = columnar_dev.decode(
                            spec, bufs, name)
                    else:
                        arr, valid = bufs[name], bufs.get(name + "#v")
                    ctx.cols[(scan.binding, name)] = DVal(
                        arr, valid, sdict, lo, hi)
                for pred in scan.filters:
                    # PER-PREDICATE fallback: a filter the chunk
                    # program cannot evaluate (e.g. it references a
                    # scalar-subquery result, q32/q92 shape) is simply
                    # skipped — the other predicates (date ranges!)
                    # still reduce, and phase B re-applies everything
                    try:
                        ctx = tr._apply_filter(ctx, pred)
                    except Exception as exc:  # noqa: BLE001
                        skipped.append((pred, exc))
                keep = keep | ctx.row
            return keep

        def _stage_chunk(span):
            """Host half of one scan chunk: slice, pad the tail to the
            static shape, columnar-encode (pure numpy), and issue the
            async host->device transfer. Runs on the prefetch worker
            when depth > 0, overlapping the compiled keep-mask program
            still scanning the previous chunk."""
            start, stop = span
            bufs = {}
            for name in need_cols:
                col = t.columns[name]
                sl = col.values[start:stop]
                m = (None if col.null_mask is None
                     else col.null_mask[start:stop])
                if stop - start < C:  # tail: pad to the chunk shape
                    pad = C - (stop - start)
                    sl = np.concatenate(
                        [sl, np.zeros(pad, dtype=sl.dtype)])
                    if m is not None:
                        m = np.concatenate(
                            [m, np.zeros(pad, dtype=bool)])
                spec = chunk_specs.get(name)
                if spec is not None:
                    # every chunk encodes with the shared full-bounds
                    # spec: shapes stay static, so the one compiled
                    # program serves all chunks (the padded tail past
                    # nrows clips freely)
                    for sfx, arr in columnar.encode_values(
                            spec, sl, m, nrows=stop - start).items():
                        bufs[name + sfx] = jax.device_put(arr)
                    continue
                bufs[name] = jax.device_put(sl)
                if m is not None:
                    bufs[name + "#v"] = jax.device_put(m)
            return bufs, sum(b.nbytes for b in bufs.values())

        chunk_spans = [(start, min(start + C, n))
                       for start in range(0, n, C)]
        pf = pipeline_io.ChunkPrefetcher(
            chunk_spans, _stage_chunk, self.prefetch_depth, table=table)
        tracer = get_tracer()
        try:
            compiled = None
            keep_np = np.empty(n, dtype=bool)
            for staged in pf:
                start, stop = staged.item
                # same between-chunk control point as the partial-agg
                # loop: deadline stops a doomed scan at the next chunk,
                # the beat keeps the watchdog fed during long scans
                check_deadline()
                watchdog.beat("engine", phase="chunk.scan", table=table)
                obs_metrics.counter("chunk_scans_total").inc()
                bufs = staged.payload
                try:
                    if compiled is None:
                        # every chunk shares one static shape (the tail
                        # pads): AOT-compile once on the first chunk's
                        # buffers, consulting the persistent plan cache
                        # so a warm process scans with zero compiles
                        compiled = self._keep_mask_compiled(
                            table, scans, need_cols, C, fn, bufs,
                            chunk_specs)
                    # the chunk-length scalar stages BEFORE the
                    # dispatch scope: its tiny h2d is control-plane,
                    # not a buffer leaking into the guarded hot path
                    nchunk = jnp.int32(stop - start)
                    _t, mask_d = self._launch(
                        tracer, "chunkscan", compiled, bufs, nchunk)
                    # ndslint: waive[NDS117] -- sanctioned per-chunk sync point: the keep mask IS phase A's product and must land on host before the survivor gather
                    keep_np[start:stop] = self._readback(
                        tracer, mask_d)[:stop - start]
                finally:
                    staged.release()
            if skipped:
                from nds_tpu.utils.report import TaskFailureCollector
                TaskFailureCollector.notify(
                    f"chunked scan of {table}: {len(skipped)} filter(s) "
                    f"not chunk-evaluable, re-applied in phase B only "
                    f"({type(skipped[0][1]).__name__})")
            return keep_np if live is None else keep_np & live
        except Exception as exc:  # noqa: BLE001 - conservative fallback
            if isinstance(exc, QueryDeadlineExceeded):
                # deadlined queries abort; "keep all rows" would turn a
                # timeout into an even slower full-table phase B
                raise
            from nds_tpu.resilience.retry import TRANSIENT, classify
            if classify(exc) == TRANSIENT:
                # classified transients (injected faults, OOM) PROPAGATE
                # instead of degrading: the executor's chunk-halving
                # loop handles the OOMs and the pipeline's retry policy
                # re-runs the rest — retry semantics identical whether
                # the staging ran inline or on the prefetch worker. The
                # keep-all fallback would silently trade a retryable
                # hiccup for a full-table phase B.
                raise
            from nds_tpu.utils.report import TaskFailureCollector
            obs_metrics.counter("chunk_fallbacks_total").inc()
            TaskFailureCollector.notify(
                f"chunked scan fell back to full rows for {table}: "
                f"{type(exc).__name__}: {exc}")
            return np.ones(n, dtype=bool) if live is None \
                else live.copy()
        finally:
            # cancel-at-chunk-boundary + unconsumed-buffer release on
            # every exit path (success, fallback, deadline abort, drain)
            self._note_prefetch(pf.close())

    def _keep_mask_compiled(self, table: str, scans: list,
                            need_cols: list, C: int, fn, bufs: dict,
                            chunk_specs: "dict | None" = None):
        """AOT form of the phase-A chunk-scan program, consulted
        against the persistent plan cache (kind ``chunkscan``): the
        fingerprint folds in the scans' filter trees (extra roots),
        the streamed table's content stamp, the chunk shape, and the
        compute dtype. A warm hit skips the trace entirely — which
        also skips the per-predicate ``skipped`` bookkeeping, matching
        the baked behavior of the program it restores."""
        from nds_tpu.cache import aot as cache_aot
        from nds_tpu.engine import kernels as KX
        pc, fp = cache_aot.try_fingerprint(
            "chunkscan",
            {"table": table, "chunk": C, "cols": tuple(need_cols),
             "float_dtype": str(self.float_dtype),
             "donate": KX.donate_enabled(),
             # per-column chunk encodings shape the program (packed
             # word shapes, fused decode); specs are deterministic
             # from content+mode but the explicit fold keeps the key
             # honest even if that ever changes
             "enc": tuple(sorted((n, repr(s)) for n, s in
                          (chunk_specs or {}).items()))},
            tables=self.tables, extra_roots=list(scans))
        # chunk buffers are rebuilt per chunk and used exactly once:
        # donating them halves the phase-A device residency (the keep
        # mask no longer double-buffers against the chunk it scans)
        KX.silence_donation_warnings()
        compiled, _extra, _hit = cache_aot.cached_compile(
            pc, fp, "chunkscan", lambda: KX.donate_jit(fn, (0,)),
            (bufs, jnp.int32(0)))
        return compiled


def make_chunked_factory(stream_bytes: int = DEFAULT_STREAM_BYTES,
                         chunk_rows: int = DEFAULT_CHUNK_ROWS,
                         precision: str = "f64",
                         prefetch_depth: "int | None" = None):
    """Session executor factory (make_device_factory analog) for the
    out-of-core engine."""
    if precision not in dx.PRECISIONS:
        raise ValueError(f"unknown engine.precision {precision!r}")
    name = dx.PRECISIONS[precision]
    float_dtype = None if name is None else getattr(jnp, name)
    holder: dict = {}

    def factory(tables):
        ex = holder.get("ex")
        if ex is None or ex.tables is not tables:
            ex = ChunkedExecutor(tables, stream_bytes, chunk_rows,
                                 float_dtype,
                                 prefetch_depth=prefetch_depth)
            holder["ex"] = ex
        return ex

    factory.invalidate = holder.clear

    def invalidate_tables(names):
        ex = holder.get("ex")
        if ex is not None:
            ex.invalidate_tables(names)

    factory.invalidate_tables = invalidate_tables
    return factory
