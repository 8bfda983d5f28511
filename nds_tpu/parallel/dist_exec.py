"""Distributed (multi-chip) plan executor: shard_map over a device mesh.

The reference scales by Spark data parallelism with shuffle exchanges
delegated to the engine (SURVEY.md §2.6). The TPU-native equivalent here:

- fact tables are ROW-SHARDED across the 1-D mesh axis; dimension tables
  replicate (classic OLAP DP — the Spark broadcast-join analog);
- probe-side joins run device-local when the build side is replicated;
  when BOTH sides are sharded, both repartition by join key through the
  `exchange` all_to_all so matching keys colocate — shuffle over ICI,
  the deliverable the survey calls out (§5 "distributed communication
  backend");
- grouped aggregation exchanges rows by group-key hash, then aggregates
  locally: every group lands wholly on one device, so distinct/avg need
  no merge logic; global aggregates use psum/pmin/pmax;
- the whole query still compiles to ONE XLA program (shard_map under
  jit): collectives are inside the program, not host-driven.

Exchange overflow (static bucket exceeded) is counted in-program and
surfaced; the executor's overflow loop (DeviceExecutor._finish) goes
round again with doubled slack — adaptive, never silent
(utils.report.TaskFailureCollector records the retry).
"""

from __future__ import annotations

import math
import os
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P_

from nds_tpu.analysis import locksan
from nds_tpu.engine import device_exec as dx
from nds_tpu.engine.device_exec import DCtx, DVal, DeviceExecError, _ok
from nds_tpu.io.host_table import HostTable
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import get_tracer
from nds_tpu.parallel.exchange import (
    exchange, exchange_hierarchical, exchange_trace, hash_columns,
)
from nds_tpu.parallel.mesh import (
    DATA_AXIS, HOST_AXIS, make_mesh, pad_to_multiple,
)
from nds_tpu.sql import plan as P
from nds_tpu.utils.report import TaskFailureCollector

_DISPATCH_LOCK = locksan.lock("parallel.dist_exec._DISPATCH_LOCK")


def shard_map(fn, **kw):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(fn, check_vma=False, **kw)


def _pextreme(op, x, axes):
    """Cross-device min/max of a per-device scalar (``op`` is jnp.min
    or jnp.max): gather the scalars and reduce locally. NOT
    lax.pmin/pmax — the TPU's 64-bit emulation lowers only SUM
    all-reduces, and an s64 pmax is refused by its compiler
    ("UNIMPLEMENTED: Supported lowering only of Sum all reduce"; the
    scaled-int64 decimals and the overflow count are all s64). Same
    value on every platform.  The gather is a replicate of one value a
    device, and is named so in the compiled program."""
    with jax.named_scope("replicate"):
        return op(lax.all_gather(x, axes))

# a group-by or row-hash exchange whose key can take fewer values than
# this many a device is sized at the local row count
# (`_DistTrace._run_aggregate`, `_DistTrace._exchange_by_row`)
FEW_KEYS_A_DEVICE = 4

# tables at or above this row count shard across the mesh; smaller ones
# replicate (the Spark broadcast threshold analog, but by rows)
DEFAULT_SHARD_THRESHOLD = 8192


class DistributedExecutor(dx.DeviceExecutor):
    """Session-compatible executor that runs plans SPMD over a mesh."""

    # survivor reduction applies to REPLICATED tables only (scan_view
    # below): filtered dimension scans shrink every device's copy and
    # all downstream gather-join capacities; sharded tables keep the
    # shard layout as their capacity story
    SCAN_REDUCE = True

    # columnar encoding (nds_tpu/columnar/) stays OFF on the sharded
    # path: packed words don't align with the shard/pad row layout
    # (a row's field may straddle a shard boundary word) and RLE run
    # ends are global offsets a per-shard trace can't interpret.
    # Sharded placements scan raw even when the mode is on — results
    # stay identical, only the bytes win is forfeit (ROADMAP item 3
    # owns making multi-host first-class)
    COLUMNAR_UPLOAD = False
    # its buffers are shards placed over the mesh, never the process's
    # single-device copies of whole columns
    SHARE_COLUMNS = False

    # a sharded program's result is read back as it is: no on-device
    # compaction of it
    COMPACT_MIN_ROWS = math.inf

    def __init__(self, tables: dict[str, HostTable], mesh=None,
                 n_devices: int | None = None,
                 shard_tables: set[str] | None = None,
                 shard_threshold: int = DEFAULT_SHARD_THRESHOLD,
                 slack: float = 2.0,
                 multiprocess: bool | None = None):
        super().__init__(tables)
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        # multi-controller SPMD (one process per host): buffers must be
        # GLOBAL jax.Arrays, each process materializing only the shards
        # its devices own (parallel.multihost). Auto-detected.
        self.multiprocess = (jax.process_count() > 1
                             if multiprocess is None else multiprocess)
        self.n_dev = int(np.prod(self.mesh.devices.shape))
        # 2-D (host, lane) mesh: collectives span BOTH axes; the
        # exchange runs its hierarchical DCN-then-ICI form
        self.mesh_2d = self.mesh.devices.ndim == 2
        if self.mesh_2d:
            if tuple(self.mesh.axis_names) != (HOST_AXIS, DATA_AXIS):
                raise ValueError(
                    f"2-D mesh axes must be ({HOST_AXIS!r}, "
                    f"{DATA_AXIS!r}), got {self.mesh.axis_names} — "
                    f"build it with make_multihost_mesh")
            self.n_hosts, self.n_lanes = self.mesh.devices.shape
            self.axes = (HOST_AXIS, DATA_AXIS)
        else:
            self.n_hosts, self.n_lanes = 1, self.n_dev
            self.axes = DATA_AXIS
        self._explicit_shard = shard_tables
        self.shard_threshold = shard_threshold
        self.slack = slack
        self._execute_attrs["devices"] = self.n_dev
        # the thread whose program holds _DISPATCH_LOCK, launched and
        # not yet read back (written under the lock, by its holder)
        self._in_flight = None
        from nds_tpu.analysis import plan_verify
        if plan_verify.verify_enabled():
            # exchange static-shape contract: a slack below 1.0 or a
            # degenerate mesh makes every all_to_all bucket undersized
            vs = plan_verify.check_exchange_invariants(
                max(t.nrows for t in tables.values()) if tables else 0,
                self.n_dev, self.slack)
            if vs:
                raise plan_verify.PlanVerifyError(vs, "DistributedExecutor")

    def _is_sharded(self, table: str) -> bool:
        if self._explicit_shard is not None:
            return table in self._explicit_shard
        return self.tables[table].nrows >= self.shard_threshold

    def grow_slack(self) -> None:
        """Scheduler ladder hook (engine/scheduler.py): an exchange
        overflow that persisted through the in-execute slack-doubling
        retries re-plans at a doubled BASE slack — every compiled
        program is invalidated (their exchange capacities baked in the
        old slack), and the next execute recompiles from the new
        floor. Collective-safe: the scheduler only calls this after a
        consensus round, so every rank re-plans together."""
        self.slack *= 2
        for key in list(self._compiled):
            self._evict_query_state(key)
        obs_metrics.counter("slack_replans_total").inc()

    def _dev(self, arr: np.ndarray, sharded: bool):
        """Host array -> device buffer laid out on the MESH: a sharded
        table's rows split across its devices, everything else
        replicated on each. (A plain ``jnp.asarray`` lands whole on
        device 0, and the compiled program then re-distributes it on
        EVERY dispatch — invisible on virtual CPU devices, a full-table
        copy per query on real chips.) Multi-process: a global
        jax.Array built shard-by-shard so each host only holds its own
        rows."""
        spec = P_(self.axes) if sharded else P_()
        if not self.multiprocess:
            from jax.sharding import NamedSharding
            return jax.device_put(arr, NamedSharding(self.mesh, spec))
        from nds_tpu.parallel.multihost import make_global_array
        return make_global_array(self.mesh, spec, np.asarray(arr))

    def _place(self, arr, sharded: bool):
        """One counted host->device placement on the mesh (the
        ``device.bind`` span's ``uploads`` / ``upload_bytes``)."""
        return self._to_device(arr, lambda a: self._dev(a, sharded))

    # beside the base's: the exchange and replicate totals of the
    # program's trace, and which of its buffer keys are sharded /
    # replicated
    SIDE_KEYS = dx.DeviceExecutor.SIDE_KEYS + ("exchange", "sk", "rk")

    def _devices(self):
        return self.mesh.devices.flat

    def _call_args(self, side, bufs, pvals) -> tuple:
        return ({k: bufs[k] for k in side["sk"]},
                {k: bufs[k] for k in side["rk"]})

    def _launch_program(self, tracer, entry, bufs, pvals):
        """``device.launch`` of a sharded program: its exchange and
        replicate totals ride the span, and count."""
        side = entry["side"]
        xc = side.get("exchange")
        if xc:
            obs_metrics.counter("exchange_rows_total").inc(
                xc["exchange_rows"])
            obs_metrics.counter("exchange_bytes_total").inc(
                xc["exchange_bytes"])
            obs_metrics.counter("replicate_bytes_total").inc(
                xc["replicate_bytes"])
        # one collective program in flight per process: two host
        # threads launching multi-device programs can enqueue them on
        # the devices in different orders, and the collectives then
        # wait on each other for ever. Held from the launch to the end
        # of the read-back, the one arrangement with a record on four
        # chips (PERF.md section 7); compiles, plan-cache loads and
        # binds stay outside it
        _DISPATCH_LOCK.acquire()
        self._in_flight = threading.get_ident()
        return self._launch(tracer, type(self).__name__,
                            entry["compiled"],
                            *self._call_args(side, bufs, pvals), attrs=xc)

    def _landed(self) -> None:
        """Give the dispatch lock back, if this thread's program still
        holds it."""
        if self._in_flight == threading.get_ident():
            self._in_flight = None
            _DISPATCH_LOCK.release()

    def _read_outputs(self, tracer, devs) -> tuple:
        """One batched device->host round trip of the program's four
        outputs; the overflow and the skew ride the span."""
        try:
            row_h, outs_h, overflow_h, skew_h = self._readback(
                tracer, devs, describe=lambda host: {
                    "overflow_rows": int(host[2]),
                    "skew": float(host[3])})
        finally:
            self._landed()
        if float(skew_h) > 0:
            # worst per-shuffle destination skew this program saw:
            # visible in live snapshots before it becomes a
            # straggler (README "Fleet & profiling")
            obs_metrics.gauge("exchange_skew_ratio").set(
                round(float(skew_h), 4))
        return row_h, outs_h, overflow_h

    def _dispatch_over(self, timings: dict) -> None:
        # a raise between the launch and the end of the read-back
        self._landed()
        super()._dispatch_over(timings)

    # buffers: sharded tables pad to a multiple of n_dev
    def _upload(self, bufs: dict, table: str, name: str) -> None:
        key = f"{table}.{name}"
        if key not in self._buffers:
            col = self.tables[table].columns[name]
            vals = col.values
            sharded = self._is_sharded(table)
            if sharded:
                cap = pad_to_multiple(max(len(vals), self.n_dev),
                                      self.n_dev)
                pad = cap - len(vals)
                if pad:
                    vals = np.concatenate(
                        [vals, np.zeros(pad, dtype=vals.dtype)])
                if col.null_mask is not None:
                    m = np.concatenate(
                        [col.null_mask, np.zeros(pad, dtype=bool)])
                    self._buffers[key + "#v"] = self._place(m, True)
            elif col.null_mask is not None:
                self._buffers[key + "#v"] = self._place(
                    col.null_mask, False)
            self._buffers[key] = self._place(vals, sharded)
        bufs[key] = self._buffers[key]
        if key + "#v" in self._buffers:
            bufs[key + "#v"] = self._buffers[key + "#v"]

    def _upload_live(self, bufs: dict, table: str) -> None:
        # delta deleted-row bitmask shards with the table's own pad
        # layout (False-padded, so padded slots stay dead) — the
        # sharded scan ANDs its local slice into the row gate exactly
        # like the single-chip path
        from nds_tpu.columnar import delta
        live = delta.live_mask(self.tables[table])
        if live is None:
            return
        key = f"{table}.__live"
        if key not in self._buffers:
            sharded = self._is_sharded(table)
            if sharded:
                cap = pad_to_multiple(max(len(live), self.n_dev),
                                      self.n_dev)
                pad = cap - len(live)
                if pad:
                    live = np.concatenate(
                        [live, np.zeros(pad, dtype=bool)])
            self._buffers[key] = self._place(live, sharded)
        bufs[key] = self._buffers[key]

    def _compile(self, planned: P.PlannedQuery,
                 slack: float = dx.DeviceExecutor.DEFAULT_SLACK):
        sharded_keys, repl_keys = self._split_keys(planned)
        side = {"sk": sharded_keys, "rk": repl_keys}

        def fn(shard_bufs, repl_bufs):
            tr = _DistTrace(self, {**shard_bufs, **repl_bufs}, slack)
            # what the program's shuffles are is collected at trace
            # time (parallel/exchange.exchange_trace; the replicates by
            # the trace itself): the static totals ride `side` to the
            # device.launch span, and the
            # program returns the worst destination skew so the
            # executor can publish the exchange_skew_ratio gauge
            # host-side — an output, not a debug callback, so the
            # executable still serializes into the AOT plan cache
            with exchange_trace() as xt:
                row, outs, dicts = tr.run_query(planned)
            side["dicts"] = dicts
            side["kernels"] = tr.kernel_counts()
            side["ops_est"] = int(tr.ops_est)
            side["exchange"] = {**xt.stats(),
                                "replicates": tr.replicates,
                                "replicate_bytes": tr.replicate_bytes}
            with jax.named_scope("op.root"):
                overflow = tr.total_overflow()
                if xt.skews:
                    skew = xt.skews[0]
                    for s in xt.skews[1:]:
                        skew = jnp.maximum(skew, s)
                    # every device sees every exchange; the fleet-wide
                    # worst is the gauge's value
                    skew = lax.pmax(skew, tr.axes)
                else:
                    skew = jnp.zeros((), jnp.float32)
            return row, outs, overflow, skew

        wrapped = shard_map(
            fn, mesh=self.mesh,
            in_specs=({k: P_(self.axes) for k in sharded_keys},
                      {k: P_() for k in repl_keys}),
            out_specs=P_())
        # ndslint: waive[NDS111] -- builds the traced callable only; AOT lower+compile routes through cache.aot (_compile_or_load)
        return jax.jit(wrapped), side

    # ------------------------------------------------- plan cache (AOT)

    def _fingerprint_parts(self) -> dict:
        parts = super()._fingerprint_parts()
        parts.update({
            "mesh_shape": tuple(self.mesh.devices.shape),
            "mesh_axes": tuple(self.mesh.axis_names),
            "n_dev": self.n_dev,
            "shard_threshold": self.shard_threshold,
            "explicit_shard": (tuple(sorted(self._explicit_shard))
                               if self._explicit_shard is not None
                               else None),
        })
        return parts

    def _plan_fingerprint(self, planned, slack: float):
        """Single-process worlds only: a multi-controller executable
        spans every rank's devices, and per-rank deserialization
        against a local client is not a supported jax path.
        Multi-process runs fall back to jax's own persistent XLA cache
        (utils/xla_cache.py)."""
        if self.multiprocess:
            return None, None
        return super()._plan_fingerprint(planned, slack)

    # survivor cap for turning a SHARDED filtered scan into a
    # replicated reduced build side (the broadcast-join move Spark AQE
    # makes under its broadcast threshold): survivors above this keep
    # the sharded layout — replicating them would cost more than the
    # exchange they avoid
    BROADCAST_ROWS = 1 << 18

    def scan_view(self, node):
        rv = super().scan_view(node)
        if rv is None or not self._is_sharded(node.table):
            return rv
        # sharded table: only take the reduced (replicated) form when
        # the survivor set is broadcast-sized
        if rv.nrows <= self.BROADCAST_ROWS:
            return rv
        # reject permanently: the decision is deterministic, and the
        # cached view's survivor idx is O(rows) host memory (multi-GB
        # for a half-surviving SF100 fact) that would otherwise be
        # retained without ever uploading a buffer
        for ck, v in self._scan_views.items():
            if v is rv:
                self._scan_views[ck] = "full"
                break
        return None

    def _reduced_to_device(self, arr):
        # multiprocess mode needs global (replicated) jax.Arrays
        return self._dev(arr, sharded=False)

    def _split_keys(self, planned):
        """(sharded, replicated) buffer keys of the plan's program.  The
        buffers are bound here, at the program's compile: where that
        uploads them it is the statement's first ``device.bind``."""
        bufs, _pvals = self._bind(planned, get_tracer())
        sharded, repl = [], []
        for k in bufs:
            table = k.split(".", 1)[0]
            if "@" in table:
                # reduced-scan buffers ("table@digest.col") are always
                # replicated — broadcast-sized by scan_view's cap even
                # when the base table is sharded
                repl.append(k)
            else:
                (sharded if self._is_sharded(table)
                 else repl).append(k)
        return sharded, repl

    # compiled shard_map programs are large (the 8-way virtual-CPU
    # forms of the big NDS plans run to GBs of executable + constant
    # memory each); a 99-query power run must not accumulate them
    # unboundedly — LRU-evict beyond this many entries
    MAX_COMPILED = 24

    # tighter than the single-chip default: 8-device shard_map compile
    # memory/time is the binding constraint (q64 traced to 54k jaxpr
    # eqns in ONE program and its 8-device compile exceeded 130 GB host
    # RAM before splitting — DIST99.json HOST_LIMIT)
    STAGE_WEIGHT = int(os.environ.get("NDS_TPU_STAGE_DIST", "24"))

    def _plan_for_dispatch(self, planned):
        """Parameterized plans run INLINED on the sharded path: sharded
        programs bake literals into their traced collectives, and the
        multi-rank story (rank-local binding would have to agree
        across ranks) is not built yet."""
        from nds_tpu.sql import params as sqlparams
        return sqlparams.inline(planned)

    def execute_async(self, planned: P.PlannedQuery, key: object = None):
        """Finished before it returns: the statement holds the
        dispatch lock from its launch to the end of its read-back, and
        a caller who dispatched twice before collecting would wait on
        itself."""
        handle = super().execute_async(planned, key)
        handle.result()
        return handle

    # three executions: the base slack, twice that, four times that
    OVERFLOW_RETRIES = 2
    OVERFLOW_WHAT = "exchange overflow"

    def _note_overflow(self, n_over: int, slack: float) -> None:
        import gc
        TaskFailureCollector.notify(
            f"exchange overflow ({n_over} rows) at slack="
            f"{slack}; retrying with slack={slack * 2}")
        obs_metrics.counter("exchange_overflow_retries_total").inc()
        obs_metrics.counter("exchange_overflow_rows_total").inc(n_over)
        # the previous slack's executable goes BEFORE the bigger one
        # compiles: the 8-way compiled forms of wide plans are GBs
        # each, and holding both was the difference between fitting
        # and OOM on the virtual mesh (q72's slack-2 -> slack-4 retry)
        gc.collect()


def _rows(ctx: DCtx) -> int:
    """The static bound a relation carries on the rows ONE device holds
    of it (``_DistTrace._stamp``); its capacity where nothing set one."""
    return getattr(ctx, "rows", ctx.n)


def _domain(v: DVal) -> tuple:
    """(lo, hi) a column's values lie in by its static bounds: a string's
    dictionary codes, or the host bounds (None: not known)."""
    if v.sdict is not None:
        return 0, max(len(v.sdict) - 1, 0)
    return v.lo, v.hi


class _DistTrace(dx._Trace):
    """The sharded trace. Beside ``sharded`` every relation it makes
    carries ``rows``: how many rows a device is expected to hold of it,
    whatever buffers it came through. A scan sets it to the shard's
    local row count; filters, projections, semi-joins and joins against
    a unique build side keep the probe's; an exchange keeps it too (a
    hash partition moves rows, it makes none) and sizes its buckets
    from it, so capacity stays ``slack x rows`` after any number of
    exchanges. Only this class reads the attribute."""

    def __init__(self, ex: DistributedExecutor, bufs: dict,
                 slack: float):
        super().__init__(ex, bufs, slack)
        self.n_dev = ex.n_dev
        self.axes = ex.axes
        # what `_replicate` gathered, static, from its shapes: how many
        # relations, and the bytes ONE device receives of them
        self.replicates = 0
        self.replicate_bytes = 0

    @staticmethod
    def _stamp(out: DCtx, sharded: bool, rows: int) -> DCtx:
        out.sharded = sharded
        out.rows = min(rows, out.n)
        return out

    def _carry(self, out: DCtx, src: DCtx) -> DCtx:
        """``out`` holds (a subset of) ``src``'s rows, laid out as they
        are."""
        return self._stamp(out, getattr(src, "sharded", False), _rows(src))

    def total_overflow(self):
        """Join-expansion + exchange overflow total (both append to
        _overflows; the executor's retry loop doubles whole-program
        slack and surfaces the event through the
        exchange_overflow_retries_total / exchange_overflow_rows_total
        metrics counters)."""
        if not self._overflows:
            return jnp.zeros((), jnp.int64)
        tot = self._overflows[0].astype(jnp.int64)
        for o in self._overflows[1:]:
            tot = tot + o.astype(jnp.int64)
        # every device sees every exchange; max across devices is enough
        return _pextreme(jnp.max, tot, self.axes)

    # ------------------------------------------------------------- helpers

    def _replicate(self, ctx: DCtx, who: str) -> DCtx:
        """Every device gets every device's slots of a sharded relation
        (one ``all_gather`` an array); ``who`` is the operator that asked
        (``replicate.<who>`` in the statement's ``kernels``). What one
        device receives, the other devices' slots of the row mask, every
        column and every validity, adds to ``replicate_bytes``."""
        if not getattr(ctx, "sharded", False):
            return ctx
        self._note(f"replicate.{who}")
        self.replicates += 1

        def gather(a):
            self.replicate_bytes += (
                (self.n_dev - 1) * a.dtype.itemsize * math.prod(a.shape))
            return lax.all_gather(a, self.axes, tiled=True)

        n = ctx.n * self.n_dev
        # the scope names the all_gathers in the compiled program
        with jax.named_scope("replicate"):
            out = DCtx(n, gather(ctx.row))
            for k, dv in ctx.cols.items():
                arr = gather(dv.arr)
                valid = None if dv.valid is None else gather(dv.valid)
                out.cols[k] = dv.with_arrays(arr, valid)
        return self._stamp(out, False, _rows(ctx) * self.n_dev)

    def _exchange_ctx(self, ctx: DCtx, key, kok,
                      few_keys: bool = False) -> tuple[DCtx, object]:
        """Repartition a sharded ctx by an int64 key; returns (ctx', key')
        both with capacity ``slack x rows`` of ctx (rows colocated by key
        hash): the buckets are sized from the rows a device holds, not
        from the buffer they sit in. ``few_keys``: an exchange by a key
        of a handful of values, which must not overflow, sized so that a
        device can send ALL its slots to one peer."""
        slack, rows = self.slack, _rows(ctx)
        if few_keys:
            slack, rows = max(self.slack, float(self.n_dev)), ctx.n
        elif rows < ctx.n:
            self._note("exchange.by_rows")
        names = list(ctx.cols)
        arrays = [ctx.cols[k].arr for k in names]
        valids = [ctx.cols[k].valid for k in names]
        vmask = [v is not None for v in valids]
        payload = arrays + [v for v in valids if v is not None] + [key]
        ok = ctx.row & kok
        if self.ex.mesh_2d:
            outs, out_ok, n_over = exchange_hierarchical(
                payload, key, ok, self.ex.n_hosts, self.ex.n_lanes,
                slack, HOST_AXIS, DATA_AXIS,
                key_index=len(payload) - 1, rows=rows)
        else:
            outs, out_ok, n_over = exchange(payload, key, ok,
                                            self.n_dev, slack, rows=rows)
        self._overflows.append(n_over)
        out_arrays = outs[:len(names)]
        vout = outs[len(names):-1]
        out_key = outs[-1]
        new = DCtx(out_ok.shape[0], out_ok)
        vi = 0
        for i, k in enumerate(names):
            dv = ctx.cols[k]
            valid = None
            if vmask[i]:
                valid = vout[vi]
                vi += 1
            new.cols[k] = dv.with_arrays(out_arrays[i], valid)
        # after a few-keys exchange one device may hold every row
        return self._stamp(new, True, new.n if few_keys else rows), out_key

    def _key_of(self, ctx: DCtx, exprs) -> tuple:
        """One int64 routing key per row from a list of key exprs, plus
        validity, plus how many distinct values the key can take by its
        static bounds (None: not known). Equal keys get equal routing
        keys, which is all colocation needs: the key columns packed
        into 62 bits where their bounds are known and fit, else a hash
        of them (``card`` None). A NULL reads 0 whatever its slot holds,
        so NULL keys colocate too. Raises for a key that does neither."""
        vals = [self.eval(e, ctx) for e in exprs]
        ok = ctx.row
        for v in vals:
            ok = _ok(v, ok)
        arrs = [v.arr if v.valid is None
                else jnp.where(v.valid, v.arr, jnp.zeros((), v.arr.dtype))
                for v in vals]
        bounds = [_domain(v) for v in vals]
        card = 1
        for lo, hi in bounds:
            card = (None if card is None or lo is None or hi is None
                    else card * (hi - lo + 1))
        if len(vals) == 1:
            return arrs[0].astype(jnp.int64), ok, card
        widths = None if card is None else [
            max((hi - lo).bit_length(), 1) for lo, hi in bounds]
        if widths is None or sum(widths) > 62:
            if any(jnp.issubdtype(a.dtype, jnp.floating) for a in arrs):
                raise DeviceExecError(
                    "a floating-point key column does not hash exactly")
            # a string column hashes by its dictionary code: the
            # dictionaries are the host table's, the same on every device
            self._note("agg.hash_routed")
            return hash_columns(
                [(a, v.valid) for a, v in zip(arrs, vals)]), ok, None
        acc = None
        for arr, (lo, hi), w in zip(arrs, bounds, widths):
            norm = jnp.clip(arr.astype(jnp.int64) - lo, 0, hi - lo)
            acc = norm if acc is None else ((acc << w) | norm)
        return acc, ok, card

    # ---------------------------------------------------------- plan nodes

    def _run_scan(self, node: P.Scan) -> DCtx:
        if (not self.ex._is_sharded(node.table)
                or self.ex.scan_view(node) is not None):
            # replicated table, or a sharded one whose filtered
            # survivors broadcast as a reduced replicated build side
            ctx = super()._run_scan(node)
            ctx.sharded = False
            return ctx
        t = self.ex.tables[node.table]
        cap = pad_to_multiple(max(t.nrows, self.n_dev), self.n_dev)
        local = cap // self.n_dev
        dev_i = lax.axis_index(DATA_AXIS)
        if self.ex.mesh_2d:
            dev_i = (lax.axis_index(HOST_AXIS) * self.ex.n_lanes
                     + dev_i)
        gidx = dev_i.astype(jnp.int64) * local + jnp.arange(local)
        row = gidx < t.nrows
        live = self.bufs.get(f"{node.table}.__live")
        if live is not None:
            # delta deleted-row bitmask (local shard slice, padded
            # False): deleted rows leave the shard's row population
            row = row & live
        ctx = self._stamp(DCtx(local, row), True, local)
        for name, _dt in node.output:
            col = t.columns[name]
            arr = self.bufs[f"{node.table}.{name}"]
            valid = self.bufs.get(f"{node.table}.{name}#v")
            lo, hi = self.ex.col_bounds(node.table, name)
            sdict = col.dictionary if col.is_string else None
            # the group bound is the table's, not the shard's: a
            # device's groups after an exchange come from every shard
            ctx.cols[(node.binding, name)] = DVal(
                arr, valid, sdict, lo, hi, scan=(id(node), t.nrows))
        for pred in node.filters:
            ctx = self._carry(self._apply_filter(ctx, pred), ctx)
        return ctx

    def _run_derivedscan(self, node: P.DerivedScan) -> DCtx:
        ctx = super()._run_derivedscan(node)
        return self._carry(ctx, self.run(node.child))

    def _slots_everywhere(self, ctx: DCtx) -> int:
        return ctx.n * (self.n_dev if getattr(ctx, "sharded", False) else 1)

    def _run_filter(self, node: P.Filter) -> DCtx:
        child = self.run(node.child)
        return self._carry(self._apply_filter(child, node.predicate),
                           child)

    def _run_project(self, node: P.Project) -> DCtx:
        child = self.run(node.child)
        return self._carry(super()._run_project(node), child)

    def _run_join(self, node: P.Join) -> DCtx:
        lctx, rctx = self.run(node.left), self.run(node.right)
        ls = getattr(lctx, "sharded", False)
        rs = getattr(rctx, "sharded", False)
        if not node.left_keys:
            out = self._cross_replicated(node, lctx, rctx, ls, rs)
            return out
        if node.right_unique:
            probe_sharded = ls
            if rs and ls:
                # both sharded: colocate by join key over ICI. Keys must
                # be packed with PAIR-aligned bounds/dictionaries (the
                # single-device _align_pair rules) or identical logical
                # keys would hash differently per side
                lkey, lok, rkey, rok, _span = self._join_key_arrays(
                    [self.eval(k, lctx) for k in node.left_keys],
                    [self.eval(k, rctx) for k in node.right_keys],
                    lctx, rctx)
                if node.kind == "left":
                    # NULL-key left rows must SURVIVE the exchange to be
                    # null-extended: route them by a sentinel key (can't
                    # match — local probe re-checks key validity)
                    lkey = jnp.where(lok, lkey,
                                     jnp.zeros((), lkey.dtype))
                    lctx, _lk = self._exchange_ctx(lctx, lkey, lctx.row)
                else:
                    lctx, _lk = self._exchange_ctx(lctx, lkey, lok)
                rctx, _rk = self._exchange_ctx(rctx, rkey, rok)
            elif rs:
                rctx = self._replicate(rctx, "join")
            # every probe row comes out once at most: the probe's bound
            out = self._join_cached(node, lctx, rctx)
            return self._stamp(out, probe_sharded, _rows(lctx))
        # probe side is the right: left must be visible in full
        if ls and rs:
            lkey, lok, rkey, rok, _span = self._join_key_arrays(
                [self.eval(k, lctx) for k in node.left_keys],
                [self.eval(k, rctx) for k in node.right_keys],
                lctx, rctx)
            if node.kind == "left":
                # block B emits unmatched LEFT rows: NULL-key left rows
                # must survive the exchange (see gather-join path above)
                lkey = jnp.where(lok, lkey, jnp.zeros((), lkey.dtype))
                lctx, _ = self._exchange_ctx(lctx, lkey, lctx.row)
            else:
                lctx, _ = self._exchange_ctx(lctx, lkey, lok)
            rctx, _ = self._exchange_ctx(rctx, rkey, rok)
            # after the exchange all matches are device-local, so the
            # base expanding join (incl. left-outer block B) is exact:
            # exchanged shards are disjoint across devices
            # an expanding join can fill its output: the bound is the
            # capacity it chose
            out = self._join_cached(node, lctx, rctx)
            return self._stamp(out, True, out.n)
        if ls:
            lctx = self._replicate(lctx, "join")
        if rs and node.kind == "left":
            # left outer with replicated left + sharded right: the base
            # join computes 'matched' per device, so a left row matched
            # only on another device would ALSO null-extend from every
            # device's block B (duplicates). Replicate the right side —
            # correctness over memory until a pmax-matched path lands.
            rctx = self._replicate(rctx, "join")
            rs = False
        out = self._join_cached(node, lctx, rctx)
        return self._stamp(out, rs, out.n)

    def _join_cached(self, node, lctx, rctx):
        """Run the single-device join logic on prepared child contexts."""
        self.stash(node.left, lctx)
        self.stash(node.right, rctx)
        self._cache.pop(id(node), None)
        return super()._run_join(node)

    def _cross_replicated(self, node, lctx, rctx, ls, rs):
        lctx = self._replicate(lctx, "join") if ls else lctx
        rctx = self._replicate(rctx, "join") if rs else rctx
        self.stash(node.left, lctx)
        self.stash(node.right, rctx)
        out = self._cross_join(node, lctx, rctx)
        out.sharded = False
        return out

    def _run_semijoin(self, node: P.SemiJoin) -> DCtx:
        lctx, rctx = self.run(node.left), self.run(node.right)
        ls = getattr(lctx, "sharded", False)
        if getattr(rctx, "sharded", False):
            rctx = self._replicate(rctx, "join")
        self.stash(node.left, lctx)
        self.stash(node.right, rctx)
        self._cache.pop(id(node), None)
        return self._carry(super()._run_semijoin(node), lctx)

    def _run_aggregate(self, node: P.Aggregate) -> DCtx:
        ctx = self.run(node.child)
        if not getattr(ctx, "sharded", False):
            out = super()._run_aggregate(node)
            out.sharded = False
            return out
        if not node.group_keys:
            return self._global_agg_sharded(node, ctx)
        # repartition by group key so each group is wholly local, then the
        # single-device aggregate is exact (distinct/avg included). A
        # key that neither packs nor hashes exactly (a floating-point
        # column) leaves only the whole relation on every device
        try:
            key, _kok, card = self._key_of(
                ctx, [e for _, e in node.group_keys])
        except DeviceExecError:
            self.stash(node.child, self._replicate(ctx, "agg"))
            self._cache.pop(id(node), None)
            out = super()._run_aggregate(node)
            out.sharded = False
            return out
        # hashing cannot balance a handful of keys: every row of a key
        # goes to one chip, and with fewer keys than a few a chip one
        # destination can be sent most of a chip's rows (NDS-H q1 at
        # SF1: four groups on four chips, 99 % of the rows to one). A
        # bucket of slack 2 then overflows by construction and the
        # program compiles twice; a bucket of the local capacity cannot
        # overflow, and only the capacity promises that
        few_keys = (card is not None
                    and card < FEW_KEYS_A_DEVICE * self.n_dev)
        # rows travel whatever their key's validity: NULL keys route by
        # the 0 `_key_of` reads them as and form their group where they
        # land
        new, _ = self._exchange_ctx(ctx, key, ctx.row, few_keys)
        self.stash(node.child, new)
        self._cache.pop(id(node), None)
        return self._carry(super()._run_aggregate(node), new)

    def _global_agg_sharded(self, node: P.Aggregate, ctx: DCtx) -> DCtx:
        b = node.binding
        if any(spec.distinct for _, spec in node.aggs):
            self.stash(node.child, self._replicate(ctx, "agg"))
            self._cache.pop(id(node), None)
            out = super()._run_aggregate(node)
            out.sharded = False
            return out
        out = DCtx(1, jnp.ones(1, dtype=bool))
        out.sharded = False
        for name, spec in node.aggs:
            arr, valid, sdict = self._psum_agg(spec, ctx)
            out.cols[(b, name)] = DVal(arr, valid, sdict)
        return out

    def _psum_agg(self, spec: P.AggSpec, ctx: DCtx):
        import jax.numpy as jnp
        from nds_tpu.engine.device_exec import I64_MAX, I64_MIN, _to_float
        from nds_tpu.engine.types import FloatType
        dv = self._agg_arg(spec, ctx)
        if spec.func == "count" and dv is None:
            cnt = lax.psum(jnp.sum(ctx.row), self.axes)
            return cnt.reshape(1).astype(jnp.int64), jnp.ones(1, bool), None
        w = _ok(dv, ctx.row)
        cnt = lax.psum(jnp.sum(w), self.axes)
        valid = (cnt > 0).reshape(1)
        if spec.func == "count":
            return cnt.reshape(1).astype(jnp.int64), jnp.ones(1, bool), None
        if spec.func == "sum":
            if isinstance(spec.dtype, FloatType):
                s = jnp.sum(jnp.where(w, dv.arr.astype(jnp.float64), 0.0))
            else:
                s = jnp.sum(jnp.where(w, dv.arr.astype(jnp.int64), 0))
            return lax.psum(s, self.axes).reshape(1), valid, None
        if spec.func == "avg":
            f = _to_float(dv.arr, spec.arg.dtype)
            s = lax.psum(jnp.sum(jnp.where(w, f, 0.0)), self.axes)
            return (s / jnp.maximum(cnt, 1)).reshape(1), valid, None
        if spec.func in ("min", "max"):
            isf = jnp.issubdtype(dv.arr.dtype, jnp.floating)
            if isf:
                fill = jnp.inf if spec.func == "min" else -jnp.inf
                masked = jnp.where(w, dv.arr, fill)
            else:
                fill = I64_MAX if spec.func == "min" else I64_MIN
                masked = jnp.where(w, dv.arr.astype(jnp.int64), fill)
            op = jnp.min if spec.func == "min" else jnp.max
            red = _pextreme(op, op(masked), self.axes)
            return red.reshape(1), valid, dv.sdict
        raise DeviceExecError(spec.func)

    def _run_sort(self, node: P.Sort) -> DCtx:
        child = self.run(node.child)
        if getattr(child, "sharded", False):
            self.stash(node.child, self._replicate(child, "sort"))
            self._cache.pop(id(node), None)
        out = super()._run_sort(node)
        out.sharded = False
        return out

    def _run_limit(self, node: P.Limit) -> DCtx:
        # over a Sort the base applies the Sort's permutation itself, at
        # the rows LIMIT keeps: what it reads is the Sort's input
        src = (node.child.child if isinstance(node.child, P.Sort)
               else node.child)
        child = self.run(src)
        if getattr(child, "sharded", False):
            self.stash(src, self._replicate(child, "limit"))
            self._cache.pop(id(node), None)
        out = super()._run_limit(node)
        out.sharded = False
        return out

    # ------------------------------------------ DISTINCT and INTERSECT / EXCEPT
    #
    # Equal rows meet on one device by an exchange on a hash of the whole
    # row, and each device runs the single-device operator on its share;
    # the output stays sharded. What the exchange hashed is marked on its
    # output (`by_row`: the ordered column keys) and kept by these two
    # operators alone, whose rows stay where they are: a DISTINCT or set
    # operation over a relation marked by its own columns exchanges
    # nothing. The hash reads each column's raw value (a string's
    # dictionary code, 0 under a NULL) and its validity, never bounds
    # that differ from relation to relation, so two relations whose
    # string columns share their dictionaries are placed alike.

    @staticmethod
    def _exact(vals) -> None:
        if any(jnp.issubdtype(v.arr.dtype, jnp.floating) for v in vals):
            raise DeviceExecError(
                "a floating-point column does not hash exactly")

    def _exchange_by_row(self, ctx: DCtx, cols) -> DCtx:
        """ctx exchanged by a hash of ``cols``, (values, validity or None,
        lo, hi) a column: a column without a validity hashes as one whose
        every row is valid, so that equal values hash alike whichever
        side may hold NULLs. Rows that can take fewer values than a few
        a device are sized as `_run_aggregate` sizes such keys."""
        hashed, card = [], 1
        for arr, valid, lo, hi in cols:
            card = (None if card is None or lo is None or hi is None
                    else card * (hi - lo + 1 + (valid is not None)))
            if valid is None:
                valid = jnp.ones(arr.shape, bool)
            hashed.append((jnp.where(valid, arr, jnp.zeros((), arr.dtype)),
                           valid))
        few_keys = card is not None and card < FEW_KEYS_A_DEVICE * self.n_dev
        new, _ = self._exchange_ctx(ctx, hash_columns(hashed), ctx.row,
                                    few_keys)
        return new

    def _by_row(self, ctx: DCtx, keys: tuple) -> DCtx:
        """``ctx`` with its rows equal on the columns ``keys`` on one
        device, marked so: as it is where it carries that mark already."""
        if getattr(ctx, "by_row", None) == keys:
            return ctx
        vals = [ctx.cols[k] for k in keys]
        self._exact(vals)
        new = self._exchange_by_row(
            ctx, [(v.arr, v.valid, *_domain(v)) for v in vals])
        new.by_row = keys
        return new

    def _local(self, out: DCtx, src: DCtx) -> DCtx:
        """``out``, made on each device from its share of ``src``: sharded
        at ``src``'s rows, with ``src``'s mark."""
        out = self._carry(out, src)
        out.by_row = getattr(src, "by_row", None)
        return out

    def _run_distinct(self, node: P.Distinct) -> DCtx:
        child = self.run(node.child)
        if not getattr(child, "sharded", False):
            out = super()._run_distinct(node)
            out.sharded = False
            return out
        try:
            placed = self._by_row(
                child, tuple((node.binding, name) for name, _ in node.output))
        except DeviceExecError:
            placed = self._replicate(child, "distinct")
        self.stash(node.child, placed)
        self._cache.pop(id(node), None)
        out = super()._run_distinct(node)
        if not placed.sharded:
            out.sharded = False
            return out
        self._note("distinct.colocated")
        return self._local(out, placed)

    def _run_setop(self, node: P.SetOp) -> DCtx:
        if node.kind not in ("intersect", "except") or not getattr(
                self.run(node.left), "sharded", False):
            return self._setop_everywhere(node)
        # a membership of each left row, which each device can decide
        # for its own where every right row equal to one is on its device
        lctx, rctx = self.run(node.left), self.run(node.right)
        if getattr(rctx, "sharded", False):
            try:
                lctx, rctx = self._colocate(node, lctx, rctx)
            except DeviceExecError:
                return self._setop_everywhere(node)
            self._note("setop.colocated")
        else:
            self._note("setop.local")
        self.stash(node.left, lctx)
        self.stash(node.right, rctx)
        self._cache.pop(id(node), None)
        return self._local(super()._run_setop(node), lctx)

    def _setop_everywhere(self, node: P.SetOp) -> DCtx:
        """The set operation over both sides whole on every device."""
        for side in (node.left, node.right):
            c = self.run(side)
            if getattr(c, "sharded", False):
                self.stash(side, self._replicate(c, "setop"))
        self._cache.pop(id(node), None)
        out = super()._run_setop(node)
        out.sharded = False
        return out

    def _colocate(self, node: P.SetOp, lctx: DCtx, rctx: DCtx) -> tuple:
        """Both sides of an INTERSECT / EXCEPT with their equal rows on
        one device: the left placed by its own row (and so marked), the
        right by its row read as the left's values. Where that reading
        changed a string column's codes the right is not marked."""
        lkeys = tuple((node.left.binding, n) for n, _ in node.left.output)
        rkeys = tuple((node.right.binding, n) for n, _ in node.right.output)
        lvals = [lctx.cols[k] for k in lkeys]
        rvals = [rctx.cols[k] for k in rkeys]
        self._exact(lvals + rvals)
        onto = [self._onto(lv, rv) for lv, rv in zip(lvals, rvals)]
        if all(a is rv.arr for a, rv in zip(onto, rvals)):
            rctx = self._by_row(rctx, rkeys)
        else:
            rctx = self._exchange_by_row(rctx, [
                (a, rv.valid, *_domain(rv)) for a, rv in zip(onto, rvals)])
        return self._by_row(lctx, lkeys), rctx

    def _onto(self, lv: DVal, rv: DVal):
        """The right column's values as the left's: a string's codes in
        the left's dictionary, -1 where the left has no such string (no
        left row can equal it); the values themselves where both sides
        share a dictionary or hold no strings."""
        if lv.sdict is None and rv.sdict is None:
            return rv.arr
        if lv.sdict is None or rv.sdict is None:
            raise DeviceExecError("set operation of string and non-string")
        if lv.sdict is rv.sdict or (len(lv.sdict) == len(rv.sdict)
                                    and np.array_equal(lv.sdict, rv.sdict)):
            return rv.arr
        left, right = lv.sdict.astype(str), rv.sdict.astype(str)
        codes = np.full(len(right), -1, np.int32)
        if len(left):
            order = np.argsort(left, kind="stable")
            at = np.clip(np.searchsorted(left[order], right), 0,
                         len(left) - 1)
            codes = np.where(left[order][at] == right, order[at],
                             -1).astype(np.int32)
        return self._take(jnp.asarray(codes), rv.arr)

    def _run_window(self, node: P.Window) -> DCtx:
        # windows run post-aggregation on small relations; replicate
        # (an exchange-by-partition-key path can land later)
        child = self.run(node.child)
        if getattr(child, "sharded", False):
            self.stash(node.child, self._replicate(child, "window"))
            self._cache.pop(id(node), None)
        out = super()._run_window(node)
        out.sharded = False
        return out

    def _everywhere(self, node: P.Node, ctx: DCtx, who: str) -> DCtx:
        ctx = self._replicate(ctx, who)
        self.stash(node, ctx)
        return ctx


def make_distributed_factory(mesh=None, n_devices=None,
                             shard_tables=None,
                             shard_threshold=DEFAULT_SHARD_THRESHOLD,
                             multiprocess=None):
    """Session executor factory for the distributed engine (one executor
    per table registry, like `device_exec.make_device_factory`)."""
    holder: dict = {}

    def factory(tables):
        ex = holder.get("ex")
        if ex is None or ex.tables is not tables:
            ex = DistributedExecutor(
                tables, mesh=mesh, n_devices=n_devices,
                shard_tables=shard_tables,
                shard_threshold=shard_threshold,
                multiprocess=multiprocess)
            holder["ex"] = ex
        return ex

    # DML invalidation hooks (Session.invalidate), as in
    # device_exec.make_device_factory — the scoped form keeps warm
    # buffers and compiled programs for every unmutated table
    factory.invalidate = holder.clear

    def invalidate_tables(names):
        ex = holder.get("ex")
        if ex is not None:
            ex.invalidate_tables(names)

    factory.invalidate_tables = invalidate_tables
    return factory
