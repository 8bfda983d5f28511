"""TPU device executor: one logical plan -> ONE static-shape XLA program.

This is the engine half the reference delegates to Spark + spark-rapids
(`nds/power_run_gpu.template:35` enables the plugin; all GPU execution is
external to the reference repo). Here the execution layer is ours and is
designed TPU-first (SURVEY.md §7):

- **Masked fixed-capacity dataflow.** XLA wants static shapes, SQL produces
  data-dependent cardinalities. Resolution: every relation is a set of
  fixed-capacity device arrays plus a boolean presence mask. Filters AND
  the mask instead of compacting; every operator's output capacity is a
  *compile-time* function of its inputs' capacities, so the entire query
  traces into a single jit-compiled XLA program — no host round-trips, no
  recompiles within a scale factor.
- **Joins are gather joins.** Every equi-join in the TPC workloads has a
  side that is unique on the join keys (star schema). The unique side is
  sorted once (`lax.sort`), probes are `searchsorted` + gather — O(n log n)
  vectorized, no dynamic hash tables. Multi-column keys are bit-packed into
  one int64 using value bounds computed on the host at trace time.
- **Grouping is sort-based.** Rows sort by (presence, keys...) via a
  stable multi-operand `lax.sort`; group boundaries come from adjacent-row
  comparison; sums, counts and averages are differences of an inclusive
  cumsum at the group's ends (`_seg_sum`), min/max a segmented scan
  (`kernels.seg_reduce_at_ends`). Output capacity = min(input capacity,
  product of the key domains); the unused tail is masked.
- **A sort's permutation goes to index vectors only.** On the TPU every
  `take` is an N-row gather per 32-bit word and the compiler pushes no
  slice through it, so nothing the sort already returned is gathered
  again: presence after a sort is its first operand, group keys are its
  sorted key operands read at the groups' first rows, and a LIMIT
  gathers each column once, at the rows it keeps (`perm[:cap]`).
- **Strings never reach the device.** Columns are dictionary-encoded
  (sorted dictionary => code order == lexicographic order,
  `nds_tpu/io/host_table.py`); LIKE / IN / comparisons against literals are
  evaluated once on the host dictionary producing boolean lookup tables the
  device gathers through. Cross-column string ops go through a union
  dictionary remap.
- **Decimals are scaled int64** end to end (+,-,*,compare exact; division
  and AVG via float64), mirroring the reference's use_decimal=True path
  (`nds/nds_schema.py:43-47`) with the `--floats` epsilon mode as the
  alternative.

The differential oracle for all of this is `cpu_exec.CpuExecutor`
(reference analog: CPU Spark as ground truth, `nds/nds_validate.py:48-114`).
"""

from __future__ import annotations

import math
import os

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from nds_tpu.analysis import jitsan  # noqa: E402
from nds_tpu.engine import kernels as KX  # noqa: E402
from nds_tpu.engine.cpu_exec import ResultTable, like_mask  # noqa: E402
from nds_tpu.engine.types import (  # noqa: E402
    BoolType, DateType, DecimalType, DType, FloatType, IntType, StringType,
)
from nds_tpu.io.host_table import HostTable  # noqa: E402
from nds_tpu.obs import costs as obs_costs  # noqa: E402
from nds_tpu.obs import memwatch  # noqa: E402
from nds_tpu.obs import metrics as obs_metrics  # noqa: E402
from nds_tpu.obs.trace import get_tracer  # noqa: E402
from nds_tpu.sql import ir  # noqa: E402
from nds_tpu.sql import plan as P  # noqa: E402

I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min




class DeviceExecError(RuntimeError):
    pass


class DVal:
    """One evaluated column on device: array + optional validity, plus
    host-side metadata (string dictionary; integer value bounds used for
    join-key bit packing; ``scan``: ``(origin, rows)`` where every slot
    holds the column of at most one of an origin's ``rows`` rows, or
    NULL — the group capacity's scan bound, ``_Trace._scan_bound``).

    ``with_arrays`` keeps ``scan``: it is how a column travels through
    filters, joins, gathers, exchanges and replicates, which move rows
    and make none. A DVal computed from other values leaves it None."""

    __slots__ = ("arr", "valid", "sdict", "lo", "hi", "scan")

    def __init__(self, arr, valid=None, sdict=None, lo=None, hi=None,
                 scan=None):
        self.arr = arr
        self.valid = valid
        self.sdict = sdict
        self.lo = lo
        self.hi = hi
        self.scan = scan

    def with_arrays(self, arr, valid):
        return DVal(arr, valid, self.sdict, self.lo, self.hi, self.scan)


def _pred_sig(e) -> str:
    """Canonical predicate signature with column bindings normalized out
    (scan filters are single-table by construction, so the alias carries
    no meaning — q-pairs filtering the same table identically under
    different aliases must share one reduced view)."""
    import dataclasses
    if isinstance(e, ir.ColRef):
        return f"col:{e.name}"
    if dataclasses.is_dataclass(e) and not isinstance(e, type):
        parts = [type(e).__name__]
        for f in dataclasses.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, (list, tuple)):
                parts.append(
                    "[" + ",".join(_pred_sig(x) for x in v) + "]")
            else:
                parts.append(_pred_sig(v))
        return "(" + " ".join(parts) + ")"
    return repr(e)


def _ir_children(e):
    """Every IR node reachable one step below e, descending through
    arbitrarily nested lists/tuples (CaseIR.whens is a list of (IR, IR)
    TUPLES — a flat isinstance walk silently skips everything inside a
    CASE arm)."""
    import dataclasses
    for f in dataclasses.fields(e):
        stack = [getattr(e, f.name)]
        while stack:
            v = stack.pop()
            if isinstance(v, (list, tuple)):
                stack.extend(v)
            elif isinstance(v, ir.IR):
                yield v


def _touches_float(e) -> bool:
    """True if evaluating e involves float compute anywhere (FloatType
    values or division, which routes decimals through floats)."""
    if isinstance(e, ir.IR):
        if isinstance(getattr(e, "dtype", None), FloatType):
            return True
        if isinstance(e, ir.Arith) and e.op == "/":
            return True
        return any(_touches_float(c) for c in _ir_children(e))
    return False


_EXACT_FLOAT_NODES = (
    ir.ColRef, ir.Lit, ir.Arith, ir.Cmp, ir.BoolOp, ir.Not, ir.Neg,
    ir.CaseIR, ir.LikeIR, ir.InListIR, ir.IsNullIR, ir.ExtractIR,
    ir.SubstrIR, ir.StrMapIR, ir.ConcatIR, ir.CastIR)


def _float_exact_safe(e) -> bool:
    """Host f64 reduction of a float-touching predicate is only sound
    when every node in it evaluates bit-identically between numpy and
    the device f64 path. IEEE +,-,*,/ comparisons and the
    string/date/case nodes above are exact on both; anything NOT in
    the whitelist (a future transcendental, say) must refuse host
    reduction rather than silently drop rows the device re-filter can
    never resurrect (advisor finding, round 4)."""
    if isinstance(e, ir.IR):
        if not isinstance(e, _EXACT_FLOAT_NODES):
            return False
        return all(_float_exact_safe(c) for c in _ir_children(e))
    return True


# peak memory bandwidth per backend kind, GB/s — the roofline ceiling
# for this engine's scan-dominated programs (published specs: TPU v4
# 1228 GB/s HBM2e, v5e 819, v5p 2765; the CPU figure is a typical
# single-socket DDR envelope and is overridable for a measured value)
_PEAK_MEM_GBPS = {"tpu v4": 1228.0, "tpu v5 lite": 819.0,
                  "tpu v5e": 819.0, "tpu v5": 2765.0, "tpu v6 lite": 1640.0,
                  "cpu": 25.0}


def _peak_mem_gbps() -> float | None:
    """Roofline peak for the ACTIVE backend: env override first
    (NDS_TPU_PEAK_GBPS, for measured numbers), then measured numbers
    from ``ndsperf --calibrate`` (configs/platform_peaks.json, via
    obs/costs), then the builtin device-kind lookup. A TPU whose
    ``device_kind`` hits no row is an error — a roofline share against
    a guessed (or the CPU's) peak would be a wrong number, not a
    missing one; other unknown platforms report no roofline."""
    env = os.environ.get("NDS_TPU_PEAK_GBPS")
    if env:
        try:
            return float(env)
        except ValueError:  # telemetry stays best-effort on a typo
            return None
    dev = jax.devices()[0]
    kind = dev.device_kind.lower()
    measured = obs_costs.calibrated_mem_gbps(kind)
    if measured is not None:
        return measured
    for prefix, gbps in sorted(_PEAK_MEM_GBPS.items(),
                               key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return gbps
    if dev.platform == "tpu":
        raise DeviceExecError(
            f"no peak-bandwidth row for TPU device_kind "
            f"{dev.device_kind!r} (engine/device_exec._PEAK_MEM_GBPS); "
            f"add its published figure")
    return None


class _ReducedScan:
    """A survivor-reduced view of one table for one scan-filter signature:
    host row indices of the survivors plus a power-of-two padded capacity
    (pow2 padding lets signatures with similar survivor counts share
    program shapes across slack retries and maintenance deltas)."""

    __slots__ = ("prefix", "table", "nrows", "capacity", "idx")

    def __init__(self, prefix: str, table: str, nrows: int, idx):
        self.prefix = prefix
        self.table = table
        self.nrows = nrows
        self.idx = idx
        c = 1
        while c < max(nrows, 1):
            c <<= 1
        self.capacity = c


class _ResidentColumns:
    """The process's device copies of WHOLE base-table columns: one a
    host column, however many executors scan it. Sessions of one
    process over one warehouse (the benchmark's concurrent warm-up, the
    in-process throughput streams) each kept a copy of every table they
    scanned; at 6M lineitem rows that doubled a compiling run's memory
    peak, at 30M four copies of lineitem are 11 GB of a 16 GB chip
    before any program runs (PERF.md section 6, PR 31). Keyed by the
    host column's identity: a HostColumn's content does not change
    (DML makes new columns), so its copy is good for as long as it
    lives, and goes with it. Reduced scan views, chunk windows and
    sharded placements stay their executor's own."""

    def __init__(self):
        from nds_tpu.analysis import locksan
        self._lock = locksan.lock("engine.device_exec._RESIDENT")
        # id(HostColumn) -> {its encoding (None: raw): {key suffix:
        # device array}}
        self._cols: dict = {}
        # goes up whenever a column comes or goes: what was counted
        # under an older version may no longer hold
        self.version = 0

    def place(self, col, spec, upload) -> dict:
        """The column's device arrays by key suffix under the encoding
        ``spec`` (None: raw), uploaded by ``upload()`` if no executor
        of the process has yet. One upload at a time: two sessions that
        want the same column must not both make it."""
        import weakref
        with self._lock:
            forms = self._cols.get(id(col))
            if forms is None:
                # ndslint: waive[NDS101] -- the entry is dropped by the column's own finalizer, before its address can be given to another object
                forms = self._cols[id(col)] = {}
                weakref.finalize(col, self._forget, id(col))
            if spec not in forms:
                forms[spec] = upload()
                self.version += 1
            return forms[spec]

    def _forget(self, key: int) -> None:
        with self._lock:
            self._cols.pop(key, None)
            self.version += 1

    def nbytes(self, cols) -> int:
        """Device bytes held of these host columns, in every form."""
        with self._lock:
            return sum(a.nbytes for col in cols
                       for arrays in (self._cols.get(id(col)) or {}
                                      ).values()
                       for a in arrays.values())


_RESIDENT = _ResidentColumns()


class DCtx:
    """One relation during trace: capacity (static), presence mask (traced),
    and columns keyed by (binding, name)."""

    def __init__(self, n: int, row):
        self.n = n
        self.row = row
        self.cols: dict[tuple, DVal] = {}

    def gather(self, idx, clear_valid=None, take=jnp.take) -> "DCtx":
        """New ctx with every column gathered at idx (same capacity as idx).
        clear_valid, if given, is ANDed into every column's validity
        (used to null out the build side of outer joins). ``take``: the
        trace passes its tallying ``_take``."""
        out = DCtx(idx.shape[0], None)
        for k, dv in self.cols.items():
            arr = take(dv.arr, idx, axis=0)
            valid = None if dv.valid is None else take(dv.valid, idx)
            if clear_valid is not None:
                valid = clear_valid if valid is None else (valid & clear_valid)
            out.cols[k] = dv.with_arrays(arr, valid)
        return out

    def merge(self, other: "DCtx") -> "DCtx":
        assert self.n == other.n
        out = DCtx(self.n, self.row)
        out.cols.update(self.cols)
        out.cols.update(other.cols)
        return out


def _ok(dv: DVal, row):
    """Row-presence AND value-validity for a column."""
    return row if dv.valid is None else (row & dv.valid)


def _scale_of(t: DType) -> int:
    return t.scale if isinstance(t, DecimalType) else 0


def _to_float(arr, t: DType, fdt=None):
    """Float compute dtype: f64 (default) is emulated on TPU but matches
    the CPU oracle exactly; `engine.precision` selects f32/bf16 in
    floats mode for native VPU arithmetic (the reference's
    variableFloatAgg tradeoff). fdt comes from the trace."""
    fdt = fdt or jnp.float64
    if isinstance(t, DecimalType):
        return arr.astype(fdt) / (10.0 ** t.scale)
    return arr.astype(fdt)


def _rescale(arr, from_s: int, to_s: int):
    if from_s == to_s:
        return arr
    if to_s > from_s:
        return arr.astype(jnp.int64) * (10 ** (to_s - from_s))
    return arr.astype(jnp.int64) // (10 ** (from_s - to_s))


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _ss(ks, q, side="left"):
    """searchsorted via the sort method. XLA lowers the default binary
    search as ~log2(n) serial gather passes over the full array — ~200ms
    (i32) to ~800ms (i64) per call at 1.8M rows on TPU, measured. One
    native sort of the concatenation is ~10ms, so every probe-scale
    searchsorted in the engine goes through here."""
    # ndslint: waive[NDS112] -- central chokepoint: operand width is the caller's (all hot callers narrow via _narrow_key/bounds), and method="sort" already sidesteps the emulated-bisection pathology
    return jnp.searchsorted(ks, q, side=side, method="sort")


# segmented inclusive scan: shared with every scan-based kernel
# (engine/kernels.py owns the implementation)
_seg_scan = KX.seg_scan


def _epoch_days_to_civil(days):
    """Hinnant's algorithm: epoch days -> (year, month, day), integer ops
    only so it vectorizes onto the VPU."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _words_per_row(arr) -> int:
    """32-bit words in one row of arr (a bool or int8 row still costs
    the gather one word)."""
    return max(arr.dtype.itemsize // 4, 1) * math.prod(arr.shape[1:])


def _narrow_key(dv: DVal):
    """int64 sort keys whose host bounds fit int32 narrow to int32 —
    TPU sorts i32 natively but emulates s64. Negation headroom (for
    descending keys) is why the bound check excludes INT32_MIN."""
    arr = dv.arr
    if (arr.dtype == jnp.int64 and dv.lo is not None
            and dv.hi is not None and -2**31 < dv.lo
            and dv.hi < 2**31 - 1):
        return arr.astype(jnp.int32)
    return arr


def _key_radix(kv: DVal):
    """(lowest value, domain size) of a group key whose domain the host
    knows: a dictionary's codes, or integer bounds; a nullable key has
    one more digit, its last, for NULL. None where it knows neither."""
    if kv.sdict is not None:
        lo, dom = 0, max(len(kv.sdict), 1)
    elif kv.lo is not None and kv.hi is not None:
        lo, dom = int(kv.lo), max(int(kv.hi) - int(kv.lo) + 1, 1)
    else:
        return None
    return lo, dom + (kv.valid is not None)


def _plan_bindings(node: P.Node) -> set:
    """All binding names produced anywhere inside a plan subtree."""
    out = set()
    for n in P.walk_plan(node):
        b = getattr(n, "binding", "")
        if b:
            out.add(b)
    return out


def _expr_bindings(e: ir.IR) -> set:
    return {x.binding for x in ir.walk(e) if isinstance(x, ir.ColRef)}


class DeviceExecutor:
    """Executes logical plans on jax devices. One instance should live for
    a whole session: it owns the device buffer pool (columns uploaded once,
    the transcode/load analog) and the per-query compile cache."""

    def __init__(self, tables: dict[str, HostTable],
                 float_dtype=None):
        self.tables = tables
        self.float_dtype = float_dtype  # None -> float64 (exact oracle)
        self._buffers: dict[str, jnp.ndarray] = {}
        self._bounds: dict[tuple, tuple] = {}
        self._compiled: dict[object, tuple] = {}
        # columnar encoding state (nds_tpu/columnar/): buffer key ->
        # EncSpec for every ENCODED upload (the trace's decode reads
        # it), and the raw host bytes that buffer set replaces (the
        # per-query compression_ratio numerator). Lives and dies with
        # the corresponding _buffers entries.
        self._enc_specs: dict[str, object] = {}
        self._raw_nbytes: dict[str, float] = {}
        # tables whose buffers are swapped in-place per chunk by the
        # partial-agg loop upload RAW (the swap rebuilds plain value
        # buffers; an encoded chunk-0 program would misread them)
        self._no_encode: set = set()
        # survivor-reduced scan views keyed by (table, filter signature);
        # values are _ReducedScan or the "full" no-reduction marker.
        # (NOT named _reduced: ChunkedExecutor already uses that name
        # for its phase-B executor cache)
        self._scan_views: dict[tuple, object] = {}
        # memoized string-dictionary unions keyed by the (left, right)
        # dictionary identities: every execution of every join over the
        # same two string columns otherwise recomputes np.union1d + two
        # searchsorteds on the host. Entries pin both dictionaries
        # (id-recycling cannot serve a stale union)
        self._union_cache: dict[tuple, tuple] = {}
        # perf accounting for the last execute(): compile/execute/
        # materialize wall-clock ms (the breakdown the reference leaves to
        # the Spark UI; here it feeds the JSON summaries directly).
        # last_query_span is the span-tree form of the same bill
        # (obs.query_timings reads it; last_timings stays as the legacy
        # scrape surface)
        self.last_timings: dict[str, float] = {}
        self.last_query_span = None
        # host-staged plan splitting (engine/staging.py): key -> the
        # once-computed (orig_planned, [(sub_planned, temp_name), ...],
        # main_planned). orig_planned pins the caller's plan object:
        # the key is its id(), and a recycled address must never serve
        # another query's staged split (advisor finding, round 5)
        self._stage_plans: dict[object, tuple] = {}
        self._stage_fps: dict[str, str] = {}  # temp -> content md5
        # pending sub-program bills keyed by query key (async
        # interleaving: another query's _finish must not consume
        # or clear this query's pending bill)
        self._stage_timings: dict[object, dict] = {}
        # host->device placements and scan views built so far, counted
        # where they are made (_to_device, scan_view): a device.bind
        # span reports the difference across itself
        self._uploads = 0
        self._upload_bytes = 0
        self._views_built = 0
        # the slack a statement's first program compiles at (the
        # sharded executor's grows: grow_slack)
        self.slack = self.DEFAULT_SLACK
        # the device.execute span's attributes (the sharded executor
        # adds its device count)
        self._execute_attrs = {"executor": type(self).__name__}

    # ------------------------------------------------------------------ API

    DEFAULT_SLACK = 2.0

    # plans whose deduplicated node count exceeds this split into
    # multiple programs with host-staged intermediates (None = off).
    # The single-chip default keeps the widest templates (q64) from
    # multi-hour cold compiles; DistributedExecutor tightens it —
    # 8-device shard_map compile memory is the binding constraint
    # (q64/q72 exceeded 130 GB host RAM on the virtual mesh; DIST99.json).
    STAGE_WEIGHT: int | None = int(os.environ.get("NDS_TPU_STAGE", "56"))

    def _register_staged(self, temp: str, table) -> None:
        """(Re-)register a staged temp table, invalidating this
        executor's per-table caches when the content changed (base-table
        DML between runs changes the sub-result; stale device buffers
        would silently serve the old rows). Content is fingerprinted so
        the steady-state bench path keeps its warmed buffers."""
        import hashlib
        h = hashlib.md5()
        for name in sorted(table.columns):
            col = table.columns[name]
            arr = np.ascontiguousarray(col.values)
            h.update(name.encode())
            h.update(str(arr.shape).encode())
            # FULL content, not a prefix: a same-shape sub-result whose
            # change lies past any prefix must invalidate the staged
            # buffers (ADVICE r5); hashing is linear and cheap next to
            # the sub-program that produced the rows. The contiguous
            # array feeds hashlib via the buffer protocol — no bytes
            # copy of a possibly-GB column
            h.update(arr)
        fp = h.hexdigest()
        if self._stage_fps.get(temp) == fp:
            return
        self._stage_fps[temp] = fp
        # ndslint: waive[NDS119] -- executor-internal staged temp table scoped to one plan step and torn down by _unregister_staged; never visible to the session catalog or the DML journal
        self.tables[temp] = table
        self._drop_col_buffers(temp + ".")
        for k in [k for k in self._bounds if k[0] == temp]:
            del self._bounds[k]
        for k in [k for k in self._scan_views if k[0] == temp]:
            del self._scan_views[k]

    def _drop_col_buffers(self, prefix: str) -> None:
        """Free every device buffer under a key prefix together with
        its encoding bookkeeping (a stale EncSpec surviving its buffer
        would mis-decode whatever re-uploads under the same key)."""
        for d in (self._buffers, self._enc_specs, self._raw_nbytes):
            for k in [k for k in d if k.startswith(prefix)]:
                del d[k]

    def invalidate_tables(self, names) -> None:
        """Scoped DML invalidation: drop ONLY the mutated tables'
        device buffers, host-cached bounds/sorted verdicts, and reduced
        scan views. Everything else — other tables' buffers, the whole
        compiled-program cache — survives: programs key on content
        fingerprints (segment-granular digests for delta tables), so a
        stale entry can never be SERVED for mutated content, and
        unaffected queries re-dispatch their warm programs at 0
        compiles."""
        for t in set(names):
            self._drop_col_buffers(f"{t}.")
            for k in [k for k in self._bounds if k[0] == t]:
                del self._bounds[k]
            for ck in [ck for ck in self._scan_views if ck[0] == t]:
                old = self._scan_views.pop(ck)
                if isinstance(old, _ReducedScan):
                    self._drop_col_buffers(old.prefix + ".")

    def _staged_effective(self, planned: P.PlannedQuery, key):
        """Resolve plan splitting for `planned`: execute + register any
        stage tables (every call — the timed run must pay for its
        sub-programs too, and DML may have changed their inputs), then
        return the plan the main program compiles from. Accumulates the
        sub-programs' timing bill under this key so last_timings can
        report the WHOLE query, not just the final program. No-op below
        STAGE_WEIGHT."""
        if not self.STAGE_WEIGHT:
            return planned
        from nds_tpu.engine import staging
        plans = self._stage_plans.get(key)
        if (plans is not None and plans[0] is not planned
                and not plans[1] and isinstance(key, tuple)
                and len(key) == 2 and key[0] == "param"):
            # literal-variant re-dispatch of a shared parameterized
            # program: the digest IS the key (identical canonical
            # plan), the split produced no temps — rebind the split to
            # THIS variant's plan object, keeping the compiled entry
            # (eviction below is for id()-recycling, which a digest key
            # cannot suffer)
            plans = (planned, [], planned)
            self._stage_plans[key] = plans
        if plans is not None and plans[2] is planned:
            # overflow-retry re-dispatch of the staged MAIN plan
            # (_finish retries with `planned`, which for a staged query
            # IS the cached main): the temps are registered and the
            # sub-program bill is already parked in _stage_timings —
            # re-running the subs would only waste the retry
            return planned
        if plans is not None and plans[0] is not planned:
            # stale entry: id() recycling (the pinning ref was evicted)
            # or the caller rebound this key to a new plan — either way
            # the cached split belongs to ANOTHER plan object. Its
            # compiled programs (main AND recursive sub-program keys)
            # are just as stale as the split itself
            self._evict_query_state(key)
            plans = None
        if plans is None:
            subs, main = [], planned
            base_digest = None
            while staging.plan_weight(main) > self.STAGE_WEIGHT:
                cut = staging.choose_cut(main)
                if cut is None:
                    break
                if base_digest is None:
                    # DETERMINISTIC temp names (plan-digest + index, not
                    # a process counter): the staged main plan's scan
                    # buffer keys embed them, and the persistent AOT
                    # plan cache (nds_tpu/cache/) can only hit across
                    # processes when identical plans stage identical
                    # names. Distinct plans get distinct digests, so
                    # names stay collision-free; re-splits after
                    # eviction re-mint the SAME names and
                    # _register_staged's content fingerprint keeps the
                    # buffers honest
                    from nds_tpu.cache.fingerprint import plan_digest
                    base_digest = plan_digest(planned)
                temp = staging.stage_temp_name(base_digest, len(subs))
                sub, main = staging.build_stage(main, cut, temp)
                subs.append((sub, temp))
            plans = (planned, subs, main)
            self._stage_plans[key] = plans
        _orig, subs, main = plans
        agg = {}
        tracer = get_tracer()
        for i, (sub, temp) in enumerate(subs):
            with tracer.span("stage.sub", temp=temp, index=i):
                # recursive: an oversized sub-program splits again here
                rt = self.execute(sub, key=(key, "__stage__", i))
            for k, v in self.last_timings.items():
                if k in ("compile_ms", "execute_ms", "materialize_ms",
                         "bytes_scanned", "bytes_scanned_raw",
                         "ops_est"):
                    agg[k] = agg.get(k, 0.0) + v
                elif k == "__kernels":
                    kacc = agg.setdefault("__kernels", {})
                    for kn, cnt in v.items():
                        kacc[kn] = kacc.get(kn, 0) + cnt
            self._register_staged(temp, staging.result_to_host_table(
                temp, rt))
        if subs:
            agg["staged_programs"] = len(subs)
            self._stage_timings[key] = agg
            obs_metrics.counter("staged_subprograms_total").inc(len(subs))
        return main

    @staticmethod
    def _stage_key_derives_from(k: object, base: object) -> bool:
        """True when k is a recursive staged-sub-program key rooted at
        base ((base, "__stage__", i) and deeper)."""
        while isinstance(k, tuple) and len(k) == 3 and k[1] == "__stage__":
            k = k[0]
            if k == base:
                return True
        return False

    def _unregister_staged(self, temp: str) -> None:
        """Free everything _register_staged created for a temp table:
        the host table, its fingerprint, and its per-table caches
        (device buffers, bounds, scan views)."""
        # ndslint: waive[NDS119] -- tear-down of the executor-internal staged temp registered above; the session catalog never saw it
        self.tables.pop(temp, None)
        self._stage_fps.pop(temp, None)
        self._drop_col_buffers(temp + ".")
        for k in [k for k in self._bounds if k[0] == temp]:
            del self._bounds[k]
        for k in [k for k in self._scan_views if k[0] == temp]:
            del self._scan_views[k]

    def _evict_query_state(self, key: object) -> None:
        """Drop the staging state tied to a compile-cache key being
        evicted — including the recursive sub-program entries keyed off
        it — so _stage_plans/_stage_timings/_compiled never hold a
        stale split for a plan whose pinning ref is gone (and never
        grow unboundedly across a long run). The evicted split's temp
        tables and their host/device caches free here too: a DIFFERENT
        plan rebound to this key would stage different digest-named
        temps, and eviction+rerun cycles must not leak the old
        intermediates."""
        for d in (self._stage_plans, self._stage_timings, self._compiled):
            for k in [key] + [k for k in d
                              if self._stage_key_derives_from(k, key)]:
                entry = d.pop(k, None)
                if d is self._stage_plans and entry is not None:
                    for _sub, temp in entry[1]:
                        self._unregister_staged(temp)

    def _merge_stage_timings(self, timings: dict,
                             key: object = None) -> None:
        """Fold the accumulated sub-program bill into the main
        program's timings (staging targets exactly the queries where
        dropping the sub bill would misreport the roofline)."""
        for k, v in (self._stage_timings.pop(key, None) or {}).items():
            if k == "__kernels":
                kacc = timings.setdefault("__kernels", {})
                for kn, cnt in v.items():
                    kacc[kn] = kacc.get(kn, 0) + cnt
            else:
                timings[k] = timings.get(k, 0.0) + v

    def execute(self, planned: P.PlannedQuery, key: object = None):
        return self.execute_async(planned, key).result()

    def execute_async(self, planned: P.PlannedQuery,
                      key: object = None) -> "_AsyncResult":
        """Dispatch a query without blocking on its completion. jax's
        async dispatch returns device futures immediately, so a caller
        can keep N queries in flight (`engine.concurrent_tasks`, the
        analog of spark.rapids.sql.concurrentGpuTasks,
        `nds/power_run_gpu.template:38`) and overlap device execution
        with host-side materialization of earlier results."""
        from nds_tpu.resilience import faults
        faults.fault_point("device.execute",
                           executor=type(self).__name__)
        planned = self._plan_for_dispatch(planned)
        key = key if key is not None else self._plan_key(planned)
        tracer = get_tracer()
        # a failed query must never inherit the previous query's span
        # (query_timings would serve stale numbers into its summary)
        self.last_query_span = None
        # explicitly-owned query span: the async half (_finish) may run
        # after other queries dispatched, so stack discipline can't own it
        qspan = tracer.begin("device.execute", **self._execute_attrs)
        timings = {"compile_ms": 0.0}
        try:
            return self._dispatch_traced(planned, key, timings, tracer,
                                         qspan)
        except BaseException as exc:
            # nested staged sub-programs set last_query_span on THEIR
            # success; a failing main program must not leave a sub's
            # span masquerading as the whole query's
            self.last_query_span = None
            # what the failed dispatch had taken (a pre-upload failure
            # releases nothing)
            self._dispatch_over(timings)
            if qspan and qspan.t1 is None:
                qspan.set(error=f"{type(exc).__name__}: {exc}").end()
            raise

    def _dispatch_over(self, timings: dict) -> None:
        """Clean up after a dispatch whose program has been read back,
        has overflowed or has raised: give back the scan bytes it
        accounted live (obs/memwatch). ``__live_bytes`` is the release
        token: every release POPS it, so the success, overflow and
        failure paths can never double-release (stripped from all
        published timings)."""
        memwatch.sub_live(timings.pop("__live_bytes", 0.0))

    def _plan_for_dispatch(self, planned):
        """Pre-dispatch plan normalization hook. Base rule:
        parameterized plans heavy enough to SPLIT (engine/staging.py)
        fall back to their inlined-literal form — staged temps
        re-encode dictionaries and carry value-dependent content, so a
        shared parameterized program cannot span a staging cut (and
        the temp tables' content digests would defeat the shared
        fingerprint anyway). The sharded executor overrides to always
        inline."""
        from nds_tpu.sql import params as sqlparams
        if not sqlparams.has_params(planned) or not self.STAGE_WEIGHT:
            return planned
        from nds_tpu.engine import staging
        if staging.plan_weight(planned) > self.STAGE_WEIGHT:
            return sqlparams.inline(planned)
        return planned

    # entry bound for the per-query compile cache: power runs hold at
    # most 125 statements, but a serving workload cycles an unbounded
    # population of plan objects through id-keyed entries — without a
    # bound the pinned plans + compiled programs grow for the process
    # lifetime (compactor entries and in-flight staged sub-keys are
    # exempt from eviction)
    MAX_COMPILED = 256

    def _plan_key(self, planned):
        """Compile-cache key: plan identity for ordinary plans; for
        PARAMETERIZED plans the shared canonical-digest key
        (sql/params.plan_key — the same key the server batches on), so
        every literal variant of one template lands on ONE in-process
        compiled entry."""
        from nds_tpu.sql import params as sqlparams
        return sqlparams.plan_key(planned) or id(planned)

    def _entry(self, planned, orig, key) -> dict:
        """The compile-cache entry of ``key``, the one shape every
        executor keeps: ``slack`` (doubled by an overflow), ``ref``,
        and — once compiled or loaded — ``compiled`` and ``side``
        (what the program's trace left beside it). ``ref`` holds strong
        refs: id()-keyed entries must keep THE CALLER'S plan object
        alive (its id is the key — a recycled address could serve
        another query's compiled program), plus the staged main plan
        actually compiled. Bounded: least recently dispatched first,
        an order kept only once the bound is reached (a hit below it
        touches nothing)."""
        entry = self._compiled.get(key)
        if entry is None:
            entry = self._compiled[key] = {"slack": self.slack,
                                           "ref": (orig, planned)}
            self._bound_compiled(key)
        elif len(self._compiled) >= self.MAX_COMPILED:
            # LRU refresh: move the hit to the back of the dict order
            self._compiled[key] = self._compiled.pop(key)
        return entry

    def _bound_compiled(self, active_key) -> None:
        """Evict query-level entries past MAX_COMPILED, oldest first
        (and their staged state, via _evict_query_state). Never evicts
        the entry being dispatched, compactor programs, or staged
        sub-entries (those die with their base key)."""
        def evictable(k) -> bool:
            if k == active_key:
                return False
            if isinstance(k, tuple) and k and k[0] == "__compact__":
                return False
            if isinstance(k, tuple) and len(k) == 3 \
                    and k[1] == "__stage__":
                return False
            return True

        while len(self._compiled) > self.MAX_COMPILED:
            victim = next((k for k in self._compiled if evictable(k)),
                          None)
            if victim is None:
                return
            self._evict_query_state(victim)

    def _dispatch_traced(self, planned, key, timings, tracer, qspan):
        """``device.dispatch``: everything up to and including the
        launch of ``planned``'s program, under the statement's span.
        The overflow loop (_finish) comes through here again with the
        same ``timings``: the compile bill adds up across attempts."""
        from nds_tpu.resilience import watchdog
        # engine-side heartbeat: a query inside compile/execute still
        # shows liveness to the hang watchdog at every dispatch
        watchdog.beat("engine", phase="device.execute",
                      executor=type(self).__name__)
        orig = planned
        with tracer.attach(qspan), tracer.span("device.dispatch"):
            planned = self._staged_effective(planned, key)
            from nds_tpu.analysis import plan_verify
            if plan_verify.verify_enabled():
                # post-staging verification: _staged_effective has run
                # and registered every sub-program temp, so the staged
                # main plan's StagedScan nodes must now resolve against
                # this executor's table registry
                plan_verify.assert_valid(planned, tables=self.tables,
                                         label="staged plan")
            self.last_timings = timings
            entry = self._entry(planned, orig, key)
            if "compiled" not in entry:
                self._compile_or_load(planned, entry, timings, tracer)
            bufs, pvals = self._bind(planned, tracer)
            # bytes the query reads from HBM-resident scan buffers: the
            # roofline denominator (achieved GB/s lands in scan_gbps at
            # _finish) so wins/losses are judged against memory
            # bandwidth, not only against a host CPU
            timings["bytes_scanned"] = float(
                sum(b.nbytes for b in bufs.values()))
            self._attach_compression(timings, bufs)
            self._attach_delta(timings, planned)
            obs_metrics.counter("device_executions_total").inc()
            obs_metrics.counter("bytes_scanned_total").inc(
                timings["bytes_scanned"])
            # memory HWM (obs/memwatch): scan buffers go live here and
            # release when the dispatch is over (_dispatch_over); the
            # device-stats sample around the execute bracket dominates
            # the accounting when available
            memwatch.add_live(timings["bytes_scanned"])
            timings["__live_bytes"] = timings["bytes_scanned"]
            memwatch.sample_device()
            # bufs/pvals are device-resident by the bind above
            t1, devs = self._launch_program(tracer, entry, bufs, pvals)
        return _AsyncResult(self, planned, key, entry, timings, t1,
                            devs, qspan)

    def _call_args(self, side: dict, bufs: dict, pvals) -> tuple:
        """What the plan's executable is called with — lowered with,
        checked against after a plan-cache load, launched with."""
        return (bufs, pvals) if pvals is not None else (bufs,)

    def _launch_program(self, tracer, entry, bufs, pvals):
        """Launch the statement's program -> (perf_counter at the
        call, its device outputs)."""
        return self._launch(
            tracer, type(self).__name__, entry["compiled"],
            *self._call_args(entry["side"], bufs, pvals))

    def _bind(self, planned, tracer) -> tuple:
        """``device.bind``: the (buffers, parameters) one call of the
        plan's program takes.  Every repeat finds its scan views and
        buffers cached; the first builds the views on the host and
        uploads them, which the span says (``first``, ``uploads``,
        ``upload_bytes``)."""
        with tracer.span("device.bind") as span:
            u0, b0, v0 = (self._uploads, self._upload_bytes,
                          self._views_built)
            bufs = self._collect_buffers(planned)
            pvals = self._collect_params(planned)
            uploads = self._uploads - u0
            if uploads:
                obs_metrics.counter("device_uploads_total").inc(uploads)
                obs_metrics.counter("upload_bytes_total").inc(
                    self._upload_bytes - b0)
            span.set(first=bool(uploads or self._views_built - v0),
                     uploads=uploads,
                     upload_bytes=self._upload_bytes - b0)
        return bufs, pvals

    def _to_device(self, arr, place=jnp.asarray):
        """One host->device placement, counted where it is made."""
        self._uploads += 1
        self._upload_bytes += int(getattr(arr, "nbytes", 0))
        return place(arr)

    def _launch(self, tracer, kind: str, compiled, *args,
                attrs: "dict | None" = None) -> tuple:
        """``device.launch``: bill the executable's compiler-truth cost
        (obs/costs; memoized, so before the execute bracket opens and
        never inside ``device.run``) and make the compiled call.
        ``attrs``: what else the caller knows of the program (the
        sharded executor's exchange totals); ``program`` is its number
        in the registry (``obs_costs.sites``).
        -> (perf_counter at the call, what it returned)."""
        import time as _time
        cost = obs_costs.record_program(kind, compiled) or {}
        attrs = dict(attrs) if attrs else {}
        attrs["program"] = obs_costs.program_id(compiled)
        for k in ("bytes_accessed", "flops"):
            if k in cost:
                attrs[k] = cost[k]
        if "bytes_accessed" in attrs:
            obs_metrics.counter("program_bytes_accessed_total").inc(
                attrs["bytes_accessed"])
        with tracer.span("device.launch", **attrs):
            # ndslint: waive[NDS102] -- execute bracket opens here; _finish_traced closes it after device_get
            t1 = _time.perf_counter()
            # jitsan dispatch scope (analysis/jitsan): armed windows
            # count the crossing and forbid implicit h2d
            with jitsan.dispatch(kind):
                out = compiled(*args)
        return t1, out

    def _readback(self, tracer, devs, describe=None):
        """``device.readback``: ONE blocking device->host transfer of a
        program's outputs, counted where it is made. ``describe(host)``
        gives further attributes read off what came back (the sharded
        program's overflow and skew scalars)."""
        with tracer.span("device.readback") as rb:
            host = jax.device_get(devs)
            nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(host))
            rb.set(syncs=1, bytes=nbytes,
                   **(describe(host) if describe else {}))
        obs_metrics.counter("device_readbacks_total").inc()
        obs_metrics.counter("readback_bytes_total").inc(nbytes)
        return host

    def _attach_compression(self, timings: dict, bufs: dict) -> None:
        """Per-query compression accounting (nds_tpu/columnar/):
        ``bytes_scanned`` already measures the ENCODED buffer bytes
        (the sum above counts what is actually resident); this adds
        the raw bytes those buffers replace (_finalize_timings derives
        compression_ratio from them). Emitted only under an active
        mode, and by executors that upload encoded at all, so
        ``columnar.encode=off`` summaries stay byte-identical."""
        from nds_tpu import columnar
        if not (self.COLUMNAR_UPLOAD and columnar.enabled()):
            return
        raw = 0.0
        for k, b in bufs.items():
            base = k[:-2] if k.endswith(("#v", "#x")) else k
            if base in self._enc_specs:
                if k == base:
                    raw += self._raw_nbytes.get(base, float(b.nbytes))
            else:
                raw += float(b.nbytes)
        timings["bytes_scanned_raw"] = raw

    def _attach_delta(self, timings: dict, planned) -> None:
        """Per-query delta accounting (columnar/delta.py): how many
        append-only segments and deleted-row mask entries rode under
        the tables THIS query scanned. Emitted only when a scanned
        table actually carries delta state, so pre-maintenance (and
        delta-free) summaries stay byte-identical — the ndsreport
        delta column keys off the field's presence."""
        from nds_tpu.columnar import delta
        scanned = {node.table
                   for root in [planned.root, *planned.scalar_subplans]
                   for node in P.walk_plan(root)
                   if isinstance(node, P.Scan)}
        segments = appended = masked = 0
        hit = False
        for t in sorted(scanned):
            rep = delta.delta_report(self.tables.get(t))
            if rep is None:
                continue
            hit = True
            segments += rep["segments"]
            appended += rep["appended_rows"]
            masked += rep["masked_rows"]
        if hit:
            timings["delta_segments"] = float(segments)
            timings["delta_appended_rows"] = float(appended)
            timings["delta_masked_rows"] = float(masked)

    # ------------------------------------------------- plan cache (AOT)

    def _fingerprint_parts(self) -> dict:
        """Executor-family facts every plan fingerprint folds in —
        anything (beyond the plan and the tables) that changes the
        traced program. Subclasses extend."""
        return {
            "float_dtype": str(self.float_dtype),
            "scan_reduce": bool(
                self.SCAN_REDUCE and os.environ.get(
                    "NDS_TPU_SCAN_REDUCE", "1") != "0"),
            "stage_weight": self.STAGE_WEIGHT,
        }

    def _fingerprint_roots(self) -> list:
        """Plan trees OUTSIDE the PlannedQuery that still shape the
        program (the partial-agg executor's merge plan)."""
        return []

    def _plan_fingerprint(self, planned, slack: float):
        """(cache, fingerprint) for this staged plan at this slack, or
        (None, None) when caching is off. A fingerprint failure is a
        warned cache miss, never a query failure."""
        from nds_tpu.cache import aot as cache_aot
        return cache_aot.try_fingerprint(
            type(self).__name__,
            {"slack": slack, **self._fingerprint_parts()},
            planned=planned, tables=self.tables,
            extra_roots=self._fingerprint_roots())

    # what a program's trace leaves beside its executable (``side``),
    # persisted with it so that a loaded program says the same of
    # itself
    SIDE_KEYS: tuple = ("dicts", "kernels", "ops_est")

    def _devices(self):
        """The devices the statement's program is compiled for, in
        assignment order; None = the one default device
        (cache.aot.deserialize_compiled)."""
        return None

    def _compile_or_load(self, planned, entry: dict, timings: dict,
                         tracer) -> None:
        """Fill ``entry['compiled']``/``entry['side']`` for a plan: a
        verified plan-cache hit deserializes the persisted executable
        (0 compiles, ``compile_ms`` stays 0, ``cache_load_ms``
        recorded); otherwise compile as always and persist for the
        next process."""
        import time as _time
        from nds_tpu.cache import aot as cache_aot
        kind = type(self).__name__
        pc, fp = self._plan_fingerprint(planned, entry["slack"])
        if fp:
            with tracer.span("cache.load", fp=fp[:12]):
                bufs, pvals = self._bind(planned, tracer)
                hit = cache_aot.load_cached(
                    pc, fp, kind, timings,
                    args=lambda side: self._call_args(side, bufs, pvals),
                    devices=self._devices())
            if hit is not None:
                entry["compiled"], extra = hit
                entry["side"] = {k: extra.get(k) for k in self.SIDE_KEYS}
                # an overflow retry served from another process's
                # persisted recompile consumed no compile here
                entry.pop("recompile", None)
                return
        # ndslint: waive[NDS102] -- raw bracket feeds compile_ms; the span records it too
        t0 = _time.perf_counter()
        with tracer.span("device.compile", slack=entry["slack"]):
            jitted, side = self._compile(planned, entry["slack"])
            bufs, pvals = self._bind(planned, tracer)
            # AOT-compile now so compile cost is attributed
            # separately from steady-state execution (fresh when the
            # blob will persist: see lower_and_compile)
            entry["compiled"] = cache_aot.lower_and_compile(
                jitted, *self._call_args(side, bufs, pvals),
                fresh=cache_aot.fresh_for(pc, fp), kind=kind)
        entry["side"] = side
        timings["compile_ms"] += (
            # ndslint: waive[NDS102,NDS103] -- .compile() is synchronous; the execute bracket closes via device_get in _finish_traced
            _time.perf_counter() - t0) * 1000
        # overflow retries recompile the SAME query: count them
        # apart from first compiles (README counter contract)
        obs_metrics.counter(
            "recompiles_total" if entry.pop("recompile", False)
            else "compiles_total").inc()
        if fp:
            cache_aot.persist(pc, fp, kind, entry["compiled"],
                              {k: side.get(k) for k in self.SIDE_KEYS},
                              meta={"slack": entry["slack"]},
                              devices=self._devices())

    # capacity at or above which results compact ON DEVICE before the
    # host transfer: a masked full-capacity result of a 576k-slot query
    # with 39 valid rows is ~8MB of dead bytes to copy to the host and
    # mask there. Below the threshold the extra dispatch costs more
    # than it saves (threshold not yet measured on a chip: ROADMAP A3).
    COMPACT_MIN_ROWS = 1 << 17

    def _compactor(self, row_d, outs_d, timings: dict):
        """AOT-compiled presence-compaction program: one stable sort
        moves valid rows to the front; the host then transfers only a
        power-of-two prefix covering the valid count. First-use compile
        is attributed to compile_ms (the executor's AOT contract), not
        the execution bracket."""
        import time as _time
        n = row_d.shape[0]
        sig = tuple((a.dtype.name, v.dtype.name) for a, v in outs_d)
        key = ("__compact__", n, sig)
        cf = self._compiled.get(key)
        if cf is None:
            def fn(row, outs):
                # the root's output, compacted: its own program
                with jax.named_scope("op.root"):
                    iota = jnp.arange(n, dtype=jnp.int32)
                    k = jnp.where(row, 0, 1).astype(jnp.int32)
                    _, perm = lax.sort([k, iota], num_keys=1,
                                       is_stable=True)
                    cnt = jnp.sum(row)
                    outs2 = [(KX.take(a, perm, axis=0),
                              KX.take(v, perm, axis=0)) for a, v in outs]
                    return cnt, KX.take(row, perm), outs2
            # ndslint: waive[NDS102] -- compactor compile bracket (attributed to compile_ms)
            t0 = _time.perf_counter()
            avatars = (jax.ShapeDtypeStruct(row_d.shape, row_d.dtype),
                       [(jax.ShapeDtypeStruct(a.shape, a.dtype),
                         jax.ShapeDtypeStruct(v.shape, v.dtype))
                        for a, v in outs_d])
            from nds_tpu.cache import aot as cache_aot
            pc, fp = cache_aot.try_fingerprint(
                "compact", {"n": n, "sig": sig,
                            "donate": KX.donate_enabled()})
            # the masked full-capacity result arrays are single-use by
            # construction (the compaction replaces them): donate, so
            # the biggest intermediate of the query stops
            # double-buffering
            KX.silence_donation_warnings()
            cf, _extra, hit = cache_aot.cached_compile(
                pc, fp, "compact",
                lambda: KX.donate_jit(fn, (0, 1)), avatars,
                timings=timings)
            # ndslint: waive[NDS102,NDS103] -- .compile() is synchronous; no device work is in flight here
            dt = (_time.perf_counter() - t0) * 1000
            if not hit:
                timings["compile_ms"] = (timings.get("compile_ms", 0.0)
                                         + dt)
            # hit or miss, the bracket is fingerprint + compile-or-load
            # time, not device execution: _finish_traced shifts the
            # execute window past it (a hit's deserialize cost is
            # already billed to cache_load_ms by load_cached)
            timings["__compact_compile_ms"] = dt
            self._compiled[key] = cf
        return cf

    def _finalize_timings(self, timings: dict, key: object) -> None:
        """Shared tail of every executor's timing bill: the staged
        sub-program fold, then — once, over the WHOLE statement — the
        roofline derivation (achieved scan bandwidth vs the active
        backend's peak memory bandwidth — the denominator that turns
        "N GB/s" into "is it actually fast"), and the last_timings
        publication."""
        self._merge_stage_timings(timings, key)
        bs = timings.get("bytes_scanned", 0.0)
        if bs and timings.get("execute_ms", 0) > 0:
            timings["scan_gbps"] = (
                bs / (timings["execute_ms"] / 1000) / 1e9)
            peak = _peak_mem_gbps()
            if peak:
                timings["roofline_frac"] = round(
                    timings["scan_gbps"] / peak, 4)
                timings["roofline_peak_gbps"] = peak
        if bs and timings.get("ops_est"):
            # arithmetic intensity of the compiled program: traced
            # row-slots per scanned byte — the ops/byte model the
            # ndsreport roofline column pairs with roofline_frac
            timings["ops_per_byte"] = round(
                timings["ops_est"] / bs, 4)
        if bs and timings.get("bytes_scanned_raw"):
            timings["compression_ratio"] = round(
                timings["bytes_scanned_raw"] / bs, 4)
        self.last_timings = timings

    # the rounds at doubled slack a statement gets after its first
    # overflow, and what overflowed (the give-up message the ladder
    # classifies on)
    OVERFLOW_RETRIES = 3
    OVERFLOW_WHAT = "join expansion overflow"

    def _finish(self, handle: "_AsyncResult"):
        """Blocking half of execute_async, and the one overflow loop:
        a program whose read-back says rows did not fit (an M:N join's
        expansion, an exchange's bucket) goes round again at doubled
        slack — counted, recompiled, exact."""
        tracer = get_tracer()
        h, span, timings = handle, handle.span, handle.timings
        try:
            for attempt in range(self.OVERFLOW_RETRIES + 1):
                out, n_over = self._finish_traced(
                    h.planned, h.key, h.entry, timings, h.t1, h.devs,
                    span, tracer)
                if not n_over:
                    return out
                if attempt == self.OVERFLOW_RETRIES:
                    raise DeviceExecError(
                        f"{self.OVERFLOW_WHAT} persisted after retries")
                # the attempt's accounted scan bytes: the next
                # dispatch adds its own
                self._dispatch_over(timings)
                entry = h.entry
                slack = entry["slack"]
                entry.pop("compiled", None)
                entry["recompile"] = True
                entry["slack"] = slack * 2
                obs_metrics.counter("slack_retries_total").inc()
                self._note_overflow(n_over, slack)
                h = self._dispatch_traced(h.planned, h.key, timings,
                                          tracer, span)
        except BaseException as exc:
            # failed queries still close their span (with the error
            # attached) so trace durations stay truthful; and a staged
            # sub's span must not survive as the failed query's
            self.last_query_span = None
            if span and span.t1 is None:
                span.set(error=f"{type(exc).__name__}: {exc}").end()
            raise
        finally:
            # the statement is over either way (pop makes a second
            # release a no-op)
            self._dispatch_over(timings)

    def _note_overflow(self, n_over: int, slack: float) -> None:
        """An overflow at ``slack`` is about to be retried at twice
        that: a recovered task-level failure -> listener chain, the
        CompletedWithTaskFailures analog of `Manager.notifyAll`."""
        from nds_tpu.utils.report import TaskFailureCollector
        TaskFailureCollector.notify(
            f"{self.OVERFLOW_WHAT}: retry with slack {slack * 2}")

    def _read_outputs(self, tracer, devs) -> tuple:
        """``device.readback`` of an uncompacted program's outputs
        -> (row, outs, overflow) on the host."""
        return self._readback(tracer, devs)

    def _finish_traced(self, planned, key, entry, timings, t1, devs,
                       span, tracer) -> tuple:
        """One attempt's read-back, and — unless it overflowed — the
        materialized result and the closed bill -> (result, rows that
        did not fit). Large-capacity results compact on device first
        (see COMPACT_MIN_ROWS)."""
        import time as _time
        row_d, outs_d, overflow_d = devs[:3]
        n = row_d.shape[0]
        compact = n >= self.COMPACT_MIN_ROWS and bool(outs_d)
        with tracer.attach(span):
            if compact:
                cf = self._compactor(row_d, outs_d, timings)
                # first-use compactor compile must not count as execution
                t1 += timings.pop("__compact_compile_ms", 0.0) / 1000
                _t, (cnt_d, row2, outs2) = self._launch(
                    tracer, "compact", cf, row_d, outs_d)
            # every blocking device->host transfer of the statement is
            # made, and counted, here: one for execution + result
            # (rather than a separate block_until_ready + int(overflow)
            # + device_get: each is its own host sync)
            if compact:
                with tracer.span("device.readback") as rb:
                    cnt_h, overflow_h = jax.device_get((cnt_d, overflow_d))
                    syncs, nbytes = 1, cnt_h.nbytes + overflow_h.nbytes
                    row_h = outs_h = None
                    if int(overflow_h) == 0:
                        C = 1
                        while C < max(int(cnt_h), 1):
                            C <<= 1
                        C = min(C, n)
                        row_h, outs_h = jax.device_get(
                            (row2[:C], [(a[:C], v[:C]) for a, v in outs2]))
                        syncs = 2
                        nbytes += row_h.nbytes + sum(
                            a.nbytes + v.nbytes for a, v in outs_h)
                    rb.set(syncs=syncs, bytes=nbytes)
                obs_metrics.counter("device_readbacks_total").inc(syncs)
                obs_metrics.counter("readback_bytes_total").inc(nbytes)
            else:
                row_h, outs_h, overflow_h = self._read_outputs(
                    tracer, devs)
            # ndslint: waive[NDS102] -- bracket endpoint after device_get; becomes the device.run span via begin(t0=t1).end(t=t2)
            t2 = _time.perf_counter()
            n_over = int(overflow_h)
            if n_over:
                return None, n_over
            # the execute bracket closed at t2 (device_get blocks
            # until ready); record it as a span with the measured
            # endpoints
            tracer.begin("device.run", parent=span, t0=t1).end(t=t2)
            with tracer.span("device.materialize"):
                out = self._materialize(planned, row_h, outs_h,
                                        entry["side"])
            # ndslint: waive[NDS102] -- host materialize endpoint; the device.materialize span brackets the same region
            t3 = _time.perf_counter()
            with tracer.span("device.finish"):
                # post-materialize allocator sample: results + scan
                # buffers are all resident here, the per-query
                # memory peak
                memwatch.sample_device()
                timings["execute_ms"] = (t2 - t1) * 1000
                timings["materialize_ms"] = (t3 - t2) * 1000
                side = entry.get("side") or {}
                if side.get("ops_est"):
                    timings["ops_est"] = float(side["ops_est"])
                if side.get("kernels"):
                    # dunder: a dict, not part of the numeric
                    # timings vocabulary (engineTimings strips it;
                    # report.py publishes it as the summary's
                    # "kernels" block)
                    timings["__kernels"] = dict(side["kernels"])
                self._finalize_timings(timings, key)
        if span:
            # dunder keys are internal accounting state (e.g. the
            # __live_bytes release token), not part of the
            # published timings vocabulary
            span.set(timings={k: v for k, v in timings.items()
                              if not k.startswith("__")}).end()
            self.last_query_span = span
        return out, 0

    def _compile(self, planned: P.PlannedQuery,
                 slack: float = DEFAULT_SLACK):
        from nds_tpu.sql import params as sqlparams
        side = {}

        def _run(bufs, params):
            tr = _Trace(self, bufs, slack, params=params)
            row, outs, dicts = tr.run_query(planned)
            side["dicts"] = dicts
            side["kernels"] = tr.kernel_counts()
            side["ops_est"] = int(tr.ops_est)
            with jax.named_scope("op.root"):
                return row, outs, tr.total_overflow()

        if sqlparams.has_params(planned):
            # hoisted literals ride as a second runtime-input pytree:
            # one compiled program serves every literal variant
            def fn(bufs, params):
                return _run(bufs, params)
        else:
            def fn(bufs):
                return _run(bufs, None)

        # ndslint: waive[NDS111] -- builds the traced callable only; AOT lower+compile routes through cache.aot (_compile_or_load)
        return jax.jit(fn), side

    def _collect_params(self, planned: P.PlannedQuery):
        """Device inputs for a parameterized plan's hoisted literals
        (sql/params.bind_params), or None for ordinary plans."""
        from nds_tpu.sql import params as sqlparams
        if not sqlparams.has_params(planned):
            return None
        return {k: self._to_device(v) for k, v in
                sqlparams.bind_params(planned, self.tables).items()}

    # -------------------------------------------------------------- buffers

    def _collect_buffers(self, planned: P.PlannedQuery) -> dict:
        bufs = {}
        roots = [planned.root] + list(planned.scalar_subplans)
        for root in roots:
            for node in P.walk_plan(root):
                if isinstance(node, P.Scan):
                    rv = self.scan_view(node)
                    for name, _dt in node.output:
                        if rv is not None:
                            self._upload_reduced(bufs, rv, name)
                        else:
                            self._upload(bufs, node.table, name)
                    if rv is None:
                        # delta deleted-row bitmask rides along as a
                        # bool buffer the scan's row gate consumes
                        # (reduced views already gathered it out)
                        self._upload_live(bufs, node.table)
        return bufs

    def _upload_live(self, bufs: dict, table: str) -> None:
        from nds_tpu.columnar import delta
        live = delta.live_mask(self.tables[table])
        if live is None:
            return
        key = f"{table}.__live"
        if key not in self._buffers:
            self._buffers[key] = self._to_device(live)
        bufs[key] = self._buffers[key]

    # ------------------------------------------- filtered scan reduction
    #
    # The static-shape engine otherwise builds every gather join at the
    # scanned table's FULL capacity even when pushed-down filters keep a
    # few percent of rows (customer_demographics at 1.92M rows with 2-3%
    # survival was the whole NDS single-chip loss: q4/q10/q18). This is
    # the role build-side sizing plays behind spark-rapids'
    # concurrentGpuTasks tuning (`nds/power_run_gpu.template:38`): at
    # compile time the scan's filter conjunction is evaluated ONCE on
    # the host (per-predicate fallback, like chunked_exec's keep-mask),
    # and when few enough rows survive, the scan reads a reduced
    # power-of-two-capacity buffer set instead — shrinking every
    # downstream operator's compile-time capacity. Filters are still
    # re-applied on device, so a host-eval miss can only lose the
    # shrink, never correctness.

    SCAN_REDUCE = True          # subclasses with pre-reduced tables opt out
    REDUCE_MIN_ROWS = 1 << 14   # below this, full capacity is already cheap
    REDUCE_MAX_FRAC = 0.5       # only shrink when survivors fit in half
    MAX_SCAN_VIEWS = 96         # bound host+device copies across a power run

    def _view_key(self, node) -> "tuple | None":
        """The cache key of this scan's reduced view, or None where the
        scan reads the full table whatever its filters keep."""
        if not self.SCAN_REDUCE or os.environ.get(
                "NDS_TPU_SCAN_REDUCE", "1") == "0":
            return None
        if (not node.filters
                or self.tables[node.table].nrows < self.REDUCE_MIN_ROWS):
            return None
        # binding-normalized signature: the same table+filter pair under
        # different query aliases must share one reduced buffer set
        sig = "&".join(sorted(_pred_sig(f) for f in node.filters))
        return (node.table, sig)

    def resident_bytes(self, planned: P.PlannedQuery) -> int:
        """Bytes of ``planned``'s scan buffers on the device now: what
        a dispatch binds without an upload (the scheduler's memory
        governor takes them off its projection: the device's live bytes
        hold them already). Looks only: builds no view, uploads
        nothing; a scan whose view is not decided yet counts what is
        there of its full columns. The count is kept beside the
        statement's compiled program for as long as nothing came or
        went: a warm statement pays one comparison."""
        entry = self._compiled.get(self._plan_key(planned))
        stamp = (self._uploads, len(self._buffers), _RESIDENT.version)
        if isinstance(entry, dict):
            memo = entry.get("resident")
            if memo is not None and memo[0] == stamp:
                return memo[1]
        total = self._count_resident(planned)
        if isinstance(entry, dict):
            entry["resident"] = (stamp, total)
        return total

    def _count_resident(self, planned: P.PlannedQuery) -> int:
        total, seen, shared = 0, set(), []
        for root in [planned.root, *planned.scalar_subplans]:
            for node in P.walk_plan(root):
                if not isinstance(node, P.Scan):
                    continue
                ck = self._view_key(node)
                rv = self._scan_views.get(ck) if ck else None
                view = isinstance(rv, _ReducedScan)
                prefix = rv.prefix if view else node.table
                cols = self.tables[node.table].columns
                for name, _dt in node.output:
                    key = f"{prefix}.{name}"
                    if key in seen:
                        continue
                    seen.add(key)
                    if self.SHARE_COLUMNS and not view:
                        # another executor of the process may have
                        # placed it: it is on the device all the same
                        shared.append(cols[name])
                    elif key in self._buffers:
                        total += sum(
                            self._buffers[key + sfx].nbytes
                            for sfx in ("", "#v", "#x")
                            if key + sfx in self._buffers)
        return total + _RESIDENT.nbytes(shared)

    def scan_view(self, node):
        """_ReducedScan for this scan's (table, filters), or None for the
        full-table path. Deterministic per signature; cached."""
        ck = self._view_key(node)
        if ck is None:
            return None
        t, sig = self.tables[node.table], ck[1]
        hit = self._scan_views.get(ck)
        if hit is not None:
            obs_metrics.counter("scan_view_hits_total").inc()
            return hit if isinstance(hit, _ReducedScan) else None
        obs_metrics.counter("scan_view_misses_total").inc()
        self._views_built += 1
        keep = self._host_keep_mask(node, t)
        s = 0 if keep is None else int(keep.sum())
        if keep is None or s > t.nrows * self.REDUCE_MAX_FRAC:
            self._scan_views[ck] = "full"
            return None
        # deterministic digest (NOT hash(): per-process randomization
        # would rename buffer keys and miss the persistent XLA cache
        # across processes/driver runs)
        import hashlib
        h = hashlib.md5(sig.encode()).hexdigest()[:8]
        rv = _ReducedScan(f"{node.table}@{h}", node.table, s,
                          np.nonzero(keep)[0])
        while len(self._scan_views) >= self.MAX_SCAN_VIEWS:
            old = self._scan_views.pop(next(iter(self._scan_views)))
            if isinstance(old, _ReducedScan):
                self._drop_col_buffers(old.prefix + ".")
        self._scan_views[ck] = rv
        return rv

    def _host_keep_mask(self, node, t: HostTable):
        """Vectorized host evaluation of the scan's filters via the CPU
        evaluator. Predicates it cannot evaluate (scalar-subquery refs,
        q32/q92 shape) simply don't reduce. None = nothing evaluable."""
        from nds_tpu.engine import cpu_exec as cx
        ctx = cx.Context(t.nrows)
        # only the columns the filters name: a scan lists every column
        # of its table, and decoding lineitem's five string columns for
        # a filter on l_shipdate is 1.2 GB and seconds a scan at 30M
        # rows, six scans at a time in a concurrent warm-up
        named = {x.name for f in node.filters for x in ir.walk(f)
                 if isinstance(x, ir.ColRef)}
        for name, _dt in node.output:
            if name not in named:
                continue
            col = t.columns[name]
            # ndslint: waive[NDS116] -- host-side scan-reduction planning (compile-time filter eval via the CPU evaluator), not device dataflow; nothing decoded here reaches a device buffer
            arr = col.decode() if col.is_string else col.values
            ctx.put((node.binding, name), np.asarray(arr), col.null_mask)
        # ndslint: waive[NDS110] -- expression-evaluation helper inside the device scan path, not a placement: only eval()/like_mask run, never execute()
        helper = cx.CpuExecutor(self.tables)
        from nds_tpu.columnar import delta
        live = delta.live_mask(t)
        # seed from the delta deleted-row bitmask: a reduced view then
        # physically excludes deleted rows and needs no runtime gate
        keep = np.ones(t.nrows, dtype=bool) if live is None \
            else live.copy()
        handled = 1 if live is not None else 0
        for pred in node.filters:
            # under reduced-precision compute (f32/bf16 floats mode) a
            # float predicate can legitimately flip near a boundary
            # between host float64 and device float32 — a row the host
            # drops is gone for good, so float-touching predicates only
            # filter on device there. Exact f64 mode reduces only on
            # predicates whose every op is IEEE-exact on both sides
            # (_float_exact_safe; all of today's ops qualify).
            if _touches_float(pred) and (
                    self.float_dtype is not None
                    or not _float_exact_safe(pred)):
                continue
            try:
                m, mv = helper.eval(pred, ctx)
            except Exception:  # noqa: BLE001 - per-predicate fallback
                continue
            m = np.asarray(m).astype(bool)
            if mv is not None:
                m = m & mv
            keep &= m
            handled += 1
        return keep if handled else None

    def _reduced_to_device(self, arr: np.ndarray):
        """Device placement for reduced-scan buffers; DistributedExecutor
        overrides to build replicated global arrays in multiprocess
        mode."""
        return jnp.asarray(arr)

    # encoded upload is the default; executors whose buffer layout the
    # columnar subsystem does not understand yet (the sharded SPMD
    # shard/pad layout) opt out wholesale and keep raw uploads even
    # when the mode is on
    COLUMNAR_UPLOAD = True

    def _upload_reduced(self, bufs: dict, rv: "_ReducedScan",
                        name: str) -> None:
        key = f"{rv.prefix}.{name}"
        if key not in self._buffers:
            from nds_tpu import columnar
            col = self.tables[rv.table].columns[name]
            vals = col.values[rv.idx]
            nulls = (None if col.null_mask is None
                     else col.null_mask[rv.idx])
            pad = rv.capacity - rv.nrows
            if pad:
                vals = np.concatenate(
                    [vals, np.zeros(pad, dtype=vals.dtype)])
                if nulls is not None:
                    nulls = np.concatenate(
                        [nulls, np.zeros(pad, dtype=bool)])
            # reduced views re-plan their encoding on the SURVIVOR
            # rows (runs/bounds differ from the base column; the pad
            # tail is gated by the row mask, so its zeros must not
            # drag the bitpack bounds down to 0 and forfeit the
            # shrink on exactly the hot filtered-scan path); the spec
            # lives with the buffers and evicts with them
            spec = (columnar.plan_padded(vals, nulls, rv.nrows,
                                         is_string=col.is_string)
                    if self.COLUMNAR_UPLOAD and columnar.enabled()
                    else None)
            if spec is not None:
                for sfx, arr in columnar.encode_values(
                        spec, vals, nulls, nrows=rv.nrows).items():
                    self._buffers[key + sfx] = self._to_device(
                        arr, self._reduced_to_device)
                self._enc_specs[key] = spec
                self._raw_nbytes[key] = float(
                    columnar.raw_nbytes(vals, nulls))
            else:
                self._buffers[key] = self._to_device(
                    vals, self._reduced_to_device)
                if nulls is not None:
                    self._buffers[key + "#v"] = self._to_device(
                        nulls, self._reduced_to_device)
        for sfx in ("", "#v", "#x"):
            if key + sfx in self._buffers:
                bufs[key + sfx] = self._buffers[key + sfx]

    # whole base-table columns are placed once a process (_RESIDENT);
    # executors whose pools hold something else under a column's key
    # (chunk windows, shards) keep their own
    SHARE_COLUMNS = True

    def _upload(self, bufs: dict, table: str, name: str) -> None:
        self._pool_upload(self._buffers, bufs, table, name)

    def _place_column(self, spec, col) -> dict:
        """Key suffix -> device array of one whole column: encoded under
        ``spec``, else its values and, if it has one, its null mask."""
        if spec is not None:
            from nds_tpu import columnar
            return {sfx: self._to_device(arr) for sfx, arr in
                    columnar.encode_column(spec, col).items()}
        placed = {"": self._to_device(col.values)}
        if col.null_mask is not None:
            placed["#v"] = self._to_device(col.null_mask)
        return placed

    def _pool_upload(self, pool: dict, bufs: dict, table: str,
                     name: str) -> None:
        """One host->device column placement into ``pool`` (shared by
        the chunked engine's phase-B executors, whose pool choice
        differs). Under an active columnar mode the column uploads in
        its ENCODED form (nds_tpu/columnar/); the spec registers on
        THIS executor even when a sibling sharing the pool already
        placed the buffers — specs are deterministic per content+mode,
        so the recomputed choice always matches the resident bytes."""
        key = f"{table}.{name}"
        col = self.tables[table].columns[name]
        from nds_tpu import columnar
        spec = (columnar.column_spec(col)
                if (self.COLUMNAR_UPLOAD and columnar.enabled()
                    and table not in self._no_encode)
                else None)
        if key not in pool:
            if self.SHARE_COLUMNS and pool is self._buffers:
                placed = _RESIDENT.place(
                    col, spec, lambda: self._place_column(spec, col))
            else:
                placed = self._place_column(spec, col)
            for sfx, arr in placed.items():
                pool[key + sfx] = arr
        if spec is not None:
            self._enc_specs[key] = spec
            self._raw_nbytes[key] = float(
                columnar.raw_nbytes(col.values, col.null_mask))
        for sfx in ("", "#v", "#x"):
            if key + sfx in pool:
                bufs[key + sfx] = pool[key + sfx]

    def col_is_sorted(self, table: str, name: str) -> bool:
        """Host-cached: column is non-null and nondecreasing. The
        generators emit surrogate keys in ascending order, so most star
        dimensions' PKs qualify — their gather-join build sort (the
        whole-table lax.sort per compiled program, 1.92M rows for
        customer_demographics) is then skipped entirely."""
        ck = (table, name, "sorted")
        if ck not in self._bounds:
            col = self.tables[table].columns[name]
            ok = (col.null_mask is None and not col.is_string
                  and np.issubdtype(col.values.dtype, np.number)
                  and (len(col.values) < 2
                       or bool(np.all(np.diff(col.values) >= 0))))
            self._bounds[ck] = ok
        return self._bounds[ck]

    def col_bounds(self, table: str, name: str):
        """Host-side (min,max) of an integer-typed column, for key packing."""
        ck = (table, name)
        if ck not in self._bounds:
            col = self.tables[table].columns[name]
            if col.is_string:
                self._bounds[ck] = (0, max(len(col.dictionary) - 1, 0))
            elif np.issubdtype(col.values.dtype, np.integer):
                vals = col.values
                if col.null_mask is not None:
                    vals = vals[col.null_mask]
                if len(vals) == 0:
                    self._bounds[ck] = (0, 0)
                else:
                    self._bounds[ck] = (int(vals.min()), int(vals.max()))
            else:
                self._bounds[ck] = (None, None)
        return self._bounds[ck]

    # ---------------------------------------------------------- materialize

    def _materialize(self, planned: P.PlannedQuery, row, outs, side):
        # inputs are already host-side (execute() batches the transfer);
        # device_get is a no-op passthrough for numpy but kept so direct
        # callers with device arrays still work
        row, outs = jax.device_get((row, outs))
        row = np.asarray(row)
        idx = np.nonzero(row)[0]
        arrs, valids, dtypes = [], [], []
        for (arr, valid), (name, dt), sd in zip(
                outs, planned.root.output, side["dicts"]):
            a = np.asarray(arr)[idx]
            v = np.asarray(valid)[idx]
            if sd is not None:
                a = sd[np.clip(a, 0, len(sd) - 1)]
                a = np.asarray(a, dtype=object)
            arrs.append(a)
            valids.append(None if v.all() else v)
            dtypes.append(dt)
        names = planned.column_names or [n for n, _ in planned.root.output]
        return ResultTable(names, arrs, dtypes, valids)


class _AsyncResult:
    """Handle for an in-flight query: dispatch happened, completion and
    materialization wait until the first result()."""

    __slots__ = ("ex", "planned", "key", "entry", "timings", "t1",
                 "devs", "span", "out")

    def __init__(self, ex, planned, key, entry, timings, t1, devs,
                 span=None):
        self.ex = ex
        self.planned = planned
        self.key = key
        self.entry = entry
        self.timings = timings
        self.t1 = t1
        self.devs = devs
        self.span = span

    def result(self):
        if self.devs is not None:
            self.out = self.ex._finish(self)
            self.devs = None    # finished: the device outputs can go
        return self.out


class _Trace:
    """Interprets a plan while being traced by jax.jit. All python control
    flow here runs at trace time; host-side numpy work (dictionary
    predicate tables, key bounds) becomes XLA constants."""

    def __init__(self, ex: DeviceExecutor, bufs: dict,
                 slack: float = 2.0, params: "dict | None" = None):
        self.ex = ex
        self.bufs = bufs
        self.slack = slack
        # hoisted-literal runtime inputs (sql/params.py): slot -> traced
        # array; empty for ordinary plans
        self.params = params or {}
        # float compute dtype (engine.precision); distributed executors
        # without the attribute inherit the exact-f64 default
        self.fdt = getattr(ex, "float_dtype", None) or jnp.float64
        self.scalars: dict[int, tuple] = {}
        self._cache: dict[int, DCtx] = {}
        self._overflows: list = []
        # kernel-use accounting (engine/kernels.py): which kernel each
        # hot operator actually compiled with, counted at trace time
        # and published per query (BenchReport "kernels" block)
        self.kernels: dict[str, int] = {}
        # ops estimate: total row-slots processed across plan nodes —
        # the numerator of the per-query ops/byte model ndsreport's
        # roofline column reads
        self.ops_est: int = 0
        # rows x 32-bit words this trace's gathers move, summed over its
        # take sites at trace time: the TPU compiler emits one N-row
        # gather per 32-bit word of the operand (two for an int64 column)
        self.gather_words: int = 0

    def _note(self, kernel: str) -> None:
        self.kernels[kernel] = self.kernels.get(kernel, 0) + 1

    def kernel_counts(self) -> dict:
        """The per-query ``kernels`` block: trace-time kernel uses plus
        the program's static ``gather_words``."""
        out = dict(self.kernels)
        if self.gather_words:
            out["gather_words"] = self.gather_words
        return out

    def _take(self, arr, idx, **kw):
        """``jnp.take`` in the ``gather`` scope, tallied into
        ``gather_words``."""
        self.gather_words += int(idx.size) * _words_per_row(arr)
        return KX.take(arr, idx, **kw)

    def _gather(self, ctx: DCtx, idx, clear_valid=None) -> DCtx:
        """``ctx.gather``, tallied into ``gather_words``."""
        return ctx.gather(idx, clear_valid, take=self._take)

    def total_overflow(self):
        if not self._overflows:
            return jnp.zeros((), jnp.int64)
        tot = self._overflows[0].astype(jnp.int64)
        for o in self._overflows[1:]:
            tot = tot + o.astype(jnp.int64)
        return tot

    def run_query(self, planned: P.PlannedQuery):
        """The program's outputs: the scalar subplans' values, read in
        the scope ``op.subplan``, then the root's row mask and columns,
        assembled in ``op.root`` (where the caller's overflow total goes
        too)."""
        for i, sub in enumerate(planned.scalar_subplans):
            ctx = self.run(sub)
            with jax.named_scope("op.subplan"):
                ctx = self._everywhere(sub, ctx, "subplan")
                name, dt = sub.output[0]
                dv = ctx.cols[(sub.binding, name)]
                pos = jnp.argmax(ctx.row)
                v = dv.arr[pos]
                ok = ctx.row[pos]
                if dv.valid is not None:
                    ok = ok & dv.valid[pos]
                self.scalars[i] = (v, ok, dv.sdict, dt)
        ctx = self.run(planned.root)
        root = planned.root
        outs, dicts = [], []
        with jax.named_scope("op.root"):
            ctx = self._everywhere(root, ctx, "root")
            for name, _dt in root.output:
                dv = ctx.cols[(root.binding, name)]
                valid = dv.valid if dv.valid is not None else jnp.ones(
                    ctx.n, dtype=bool)
                outs.append((dv.arr, valid))
                dicts.append(dv.sdict)
        return ctx.row, outs, dicts

    def _everywhere(self, node: P.Node, ctx: DCtx, who: str) -> DCtx:
        """``ctx`` as every device holds it, for ``who`` (a scalar
        subplan or the root) to read: here, as it is (the sharded trace
        gathers it)."""
        return ctx

    # ----------------------------------------------------------- plan nodes

    def stash(self, node: P.Node, ctx: DCtx) -> None:
        """The trace's one node-result cache write point. id()-keying
        is sound here (and only here): the cache dies with this trace,
        and the traced PlannedQuery pins every node for that whole
        lifetime — no address can recycle while its entry is live."""
        # ndslint: waive[NDS101] -- trace-scoped; the traced plan pins its nodes
        self._cache[id(node)] = ctx

    def run(self, node: P.Node) -> DCtx:
        """The one dispatch point of every plan node: ``_run_<kind>``
        in the scope ``op.<kind>``, which names the node's instructions
        in the compiled program (scopes nest as the plan does; the
        innermost ``op.*`` of an instruction is its operator)."""
        nid = id(node)
        if nid in self._cache:
            return self._cache[nid]
        kind = type(node).__name__.lower()
        with jax.named_scope("op." + kind):
            ctx = getattr(self, "_run_" + kind)(node)
        # ops/byte model numerator: row-slots this node's context holds
        # (deduplicated — shared CTE bodies count once via the cache)
        self.ops_est += int(getattr(ctx, "n", 0))
        self.stash(node, ctx)
        return ctx

    def _run_scan(self, node: P.Scan) -> DCtx:
        t = self.ex.tables[node.table]
        rv = self.ex.scan_view(node)
        if rv is not None:
            n, nrows, prefix = rv.capacity, rv.nrows, rv.prefix
        else:
            n, nrows, prefix = max(t.nrows, 1), t.nrows, node.table
        row = jnp.arange(n, dtype=jnp.int32) < nrows
        live = self.bufs.get(f"{node.table}.__live") if rv is None \
            else None
        if live is not None:
            # delta deleted-row bitmask: DF_*-deleted rows leave every
            # scan's row population before predicates run (base column
            # buffers stay resident and encoded — deletion is one bool
            # AND, not a re-upload)
            row = row & live
        ctx = DCtx(n, row)
        for name, _dt in node.output:
            col = t.columns[name]
            key = f"{prefix}.{name}"
            spec = self.ex._enc_specs.get(key)
            if spec is not None:
                # encoded buffer set (nds_tpu/columnar/): the decode
                # traces INTO this program, so XLA fuses the unpack
                # into every consumer and the full-width values never
                # round-trip through HBM
                from nds_tpu.columnar import device as columnar_dev
                arr, valid = columnar_dev.decode(spec, self.bufs, key)
            else:
                arr = self.bufs[key]
                valid = self.bufs.get(key + "#v")
            if arr.shape[0] == 0:
                arr = jnp.zeros((1,), dtype=arr.dtype)
                valid = None
            lo, hi = self.ex.col_bounds(node.table, name)
            sdict = col.dictionary if col.is_string else None
            ctx.cols[(node.binding, name)] = DVal(
                arr, valid, sdict, lo, hi, scan=(id(node), nrows))
        for pred in node.filters:
            # re-applied even on a reduced view (host-eval misses lose
            # only the shrink; unhandled predicates still filter here)
            ctx = self._apply_filter(ctx, pred)
        # runtime marker for the presorted-build fast path: this ctx's
        # arrays are in host storage order with a prefix row mask.
        # Contexts rebuilt elsewhere (hash exchanges, merges) never set
        # it, so a static plan check alone can't mistake an exchanged
        # build side for a sorted one. A live mask breaks the
        # prefix-row-mask property the fast path assumes.
        ctx.pristine = not node.filters and live is None
        return ctx

    def _apply_filter(self, ctx: DCtx, pred: ir.IR) -> DCtx:
        dv = self.eval(pred, ctx)
        m = dv.arr.astype(bool)
        if dv.valid is not None:
            m = m & dv.valid
        out = DCtx(ctx.n, ctx.row & m)
        out.cols = ctx.cols
        return out

    def _run_derivedscan(self, node: P.DerivedScan) -> DCtx:
        """A CTE or view read under this node's binding. Its columns
        take this node as their origin: a body referenced twice is two
        origins, so a self-join of it is bounded by the product of the
        two sides' rows, never by one side's."""
        child = self.run(node.child)
        cb = node.child.binding
        out = DCtx(child.n, child.row)
        rows = self._slots_everywhere(child)
        for name, _dt in node.child.output:
            dv = child.cols[(cb, name)]
            out.cols[(node.binding, name)] = DVal(
                dv.arr, dv.valid, dv.sdict, dv.lo, dv.hi,
                scan=(id(node), rows))
        return out

    def _slots_everywhere(self, ctx: DCtx) -> int:
        """The slots a relation holds over every device: here, its
        capacity (the sharded trace counts every device's)."""
        return ctx.n

    def _run_stagedscan(self, node: P.StagedScan) -> DCtx:
        """Host-staged intermediate (engine/staging.py): scan the temp
        table, then restore each column's original (binding, name)
        address so the ancestors' expressions resolve unchanged."""
        inner = self.run(node.child)
        sb = node.child.binding
        out = DCtx(inner.n, inner.row)
        for b, name, mangled, _dt in node.cols:
            out.cols[(b, name)] = inner.cols[(sb, mangled)]
        out.pristine = getattr(inner, "pristine", False)
        return out

    def _run_filter(self, node: P.Filter) -> DCtx:
        return self._apply_filter(self.run(node.child), node.predicate)

    def _run_project(self, node: P.Project) -> DCtx:
        ctx = self.run(node.child)
        out = DCtx(ctx.n, ctx.row)
        for name, e in node.exprs:
            dv = self.eval(e, ctx)
            if dv.arr.ndim == 0:
                dv = dv.with_arrays(
                    jnp.broadcast_to(dv.arr, (ctx.n,)),
                    None if dv.valid is None
                    else jnp.broadcast_to(dv.valid, (ctx.n,)))
            out.cols[(node.binding, name)] = dv
        return out

    # -------------------------------------------------------------- joins

    def _join_key_arrays(self, lvals, rvals, lctx, rctx):
        """Align key pairs (string dictionary union, decimal rescale), then
        bit-pack multi-column keys into one int64 per side.
        Returns (lkey, lok, rkey, rok, span): span is the host-known
        (lo, hi) value range of the combined key — the dense-kernel
        feasibility input (engine/kernels.py) — or None when either
        side lacks bounds."""
        lok = lctx.row
        rok = rctx.row
        if len(lvals) == 1 and lvals[0].sdict is None \
                and rvals[0].sdict is None:
            lv, rv = lvals[0], rvals[0]
            lk, rk = lv.arr.astype(jnp.int64), rv.arr.astype(jnp.int64)
            span = None
            if (lv.lo is not None and rv.lo is not None
                    and lv.hi is not None and rv.hi is not None):
                span = (min(lv.lo, rv.lo), max(lv.hi, rv.hi))
                # int32 keys sort/search natively on TPU; int64 is
                # emulated
                if span[0] > -2**31 and span[1] < 2**31 - 1:
                    lk, rk = lk.astype(jnp.int32), rk.astype(jnp.int32)
            return lk, _ok(lv, lok), rk, _ok(rv, rok), span
        lks, rks, widths = [], [], []
        for lv, rv in zip(lvals, rvals):
            la, ra, lo, hi = self._align_pair(lv, rv)
            lok = _ok(lv, lok)
            rok = _ok(rv, rok)
            lks.append((la, lo, hi))
            rks.append((ra, lo, hi))
            span = hi - lo
            widths.append(max(span.bit_length(), 1))
        if sum(widths) > 62:
            raise DeviceExecError(
                f"join key too wide to pack: {widths} bits")
        lkey = self._pack(lks, widths)
        rkey = self._pack(rks, widths)
        if sum(widths) <= 30:
            lkey = lkey.astype(jnp.int32)
            rkey = rkey.astype(jnp.int32)
        # packed keys normalize each part to [0, hi-lo], so the combined
        # key lives in [0, 2^sum(widths))
        return lkey, lok, rkey, rok, (0, (1 << sum(widths)) - 1)

    @staticmethod
    def _pack(keys, widths):
        acc = None
        for (arr, lo, hi), w in zip(keys, widths):
            norm = jnp.clip(arr.astype(jnp.int64) - lo, 0, hi - lo)
            acc = norm if acc is None else ((acc << w) | norm)
        return acc

    # bound on memoized dictionary unions (each entry pins two host
    # dictionaries plus two host remap tables): ``columnar.
    # dict_union_cap`` / NDS_TPU_DICT_UNION_CAP — a serving workload
    # cycling many table pairs thrashed the old hard 256 silently
    @staticmethod
    def _union_cap() -> int:
        from nds_tpu import columnar
        return columnar.dict_union_cap()

    def _dict_union(self, lsd, rsd):
        """Memoized string-dictionary union for one (left, right)
        dictionary pair: np.union1d + the two searchsorted remaps run
        ONCE per pair per executor instead of once per execution of
        every join over the same two string columns. The cache holds
        HOST arrays only — a jnp array minted here would be a
        trace-local constant, and replaying it into a later trace
        desyncs that program's hoisted-constant inputs. Returns
        (union[np str], lmap[device], rmap[device])."""
        ex = self.ex
        key = (id(lsd), id(rsd))
        hit = ex._union_cache.get(key)
        if hit is None or hit[0] is not lsd or hit[1] is not rsd:
            union = np.union1d(lsd.astype(str), rsd.astype(str))
            lmap = np.searchsorted(union, lsd.astype(str))
            rmap = np.searchsorted(union, rsd.astype(str))
            cap = self._union_cap()
            while len(ex._union_cache) >= cap:
                ex._union_cache.pop(next(iter(ex._union_cache)))
            # the stored tuple pins both keyed dictionaries, and the
            # identity re-check above rejects any recycled address
            hit = (lsd, rsd, union, lmap, rmap)
            ex._union_cache[key] = hit
        return hit[2], jnp.asarray(hit[3]), jnp.asarray(hit[4])

    def _align_pair(self, lv: DVal, rv: DVal):
        """Make one key pair comparable as integers; returns
        (l_arr, r_arr, lo, hi) with host-known bounds."""
        if lv.sdict is not None or rv.sdict is not None:
            if lv.sdict is None or rv.sdict is None:
                raise DeviceExecError("string vs non-string join key")
            if lv.sdict is rv.sdict or (
                    len(lv.sdict) == len(rv.sdict)
                    and np.array_equal(lv.sdict, rv.sdict)):
                hi = max(len(lv.sdict) - 1, 0)
                return lv.arr, rv.arr, 0, hi
            union, lmap, rmap = self._dict_union(lv.sdict, rv.sdict)
            return (self._take(lmap, lv.arr), self._take(rmap, rv.arr),
                    0, max(len(union) - 1, 0))
        la, ra = lv.arr, rv.arr
        if (lv.lo is None or lv.hi is None or rv.lo is None
                or rv.hi is None):
            raise DeviceExecError(
                "join key without host bounds (needed for packing)")
        return la, ra, min(lv.lo, rv.lo), max(lv.hi, rv.hi)

    def _presorted_build(self, right: P.Node, right_keys) -> bool:
        """True when the build side is a bare unfiltered Scan whose
        single join key is a host-proven sorted non-null column: then
        the row mask is the scan's prefix and the key array is already
        in sort order, so _build_lookup's whole-table sort is a no-op
        to skip. Filters (mid-array masks), multi-column packs, strings
        and reduced views all disqualify."""
        if not isinstance(right, P.Scan) or right.filters:
            return False
        if len(right_keys) != 1:
            return False
        k = right_keys[0]
        if not isinstance(k, ir.ColRef) or k.binding != right.binding:
            return False
        # col_is_sorted is the single source of eligibility: it already
        # rejects strings, nullable and non-numeric columns
        return self.ex.col_is_sorted(right.table, k.name)

    @staticmethod
    def _build_lookup(key, ok):
        """Sort build keys (invalid rows to the sentinel end). Explicit
        int32 iota operand: jnp.argsort would carry an int64 index
        operand under x64, pushing the whole sort onto the TPU's
        emulated 64-bit path."""
        sentinel = jnp.iinfo(key.dtype).max
        k = jnp.where(ok, key, sentinel)
        iota = jnp.arange(k.shape[0], dtype=jnp.int32)
        ks, order = lax.sort([k, iota], num_keys=1, is_stable=True)
        return ks, order

    @staticmethod
    def _probe(ks, order, pkey, pok, take=jnp.take):
        """``take``: the trace passes its tallying ``_take``."""
        n = ks.shape[0]
        pos = jnp.clip(_ss(ks, pkey), 0, n - 1)
        hit = (take(ks, pos) == pkey) & pok
        return take(order, pos), hit

    def _full_join(self, node: P.Join, lctx, rctx, lkey, lok, rkey,
                   rok) -> DCtx:
        """FULL OUTER over unique keys on BOTH sides (q51/q97 join
        grouped CTEs on their group keys): capacity = |L| + |R|. Slots
        [0, |L|) hold every left row with the right side gathered (null
        where unmatched); slots [|L|, |L|+|R|) hold only the right rows
        with no left match, left side null-extended."""
        if not node.right_unique:
            raise DeviceExecError(
                "FULL OUTER JOIN requires unique join keys")
        ks, order = self._build_lookup(rkey, rok)
        ridx, hit = self._probe(ks, order, lkey, lok, take=self._take)
        ks2, order2 = self._build_lookup(lkey, lok)
        _lidx, rhit = self._probe(ks2, order2, rkey, rok, take=self._take)
        unmatched_r = rctx.row & ~rhit

        falsev = jnp.zeros(rctx.n, dtype=bool)
        out = DCtx(lctx.n + rctx.n,
                   jnp.concatenate([lctx.row, unmatched_r]))
        gathered = self._gather(rctx, ridx, clear_valid=hit)
        for k, dv in lctx.cols.items():
            # left columns: present in block A, null in block B
            pad = jnp.zeros((rctx.n,) + dv.arr.shape[1:], dv.arr.dtype)
            arr = jnp.concatenate([dv.arr, pad])
            lv = dv.valid if dv.valid is not None else jnp.ones(
                lctx.n, dtype=bool)
            out.cols[k] = dv.with_arrays(
                arr, jnp.concatenate([lv, falsev]))
        for k, dv in rctx.cols.items():
            g = gathered.cols[k]
            arr = jnp.concatenate([g.arr, dv.arr])
            gv = g.valid if g.valid is not None else hit
            dvv = dv.valid if dv.valid is not None else jnp.ones(
                rctx.n, dtype=bool)
            out.cols[k] = dv.with_arrays(
                arr, jnp.concatenate([gv, dvv]))
        return out

    def _run_join(self, node: P.Join) -> DCtx:
        lctx, rctx = self.run(node.left), self.run(node.right)
        if not node.left_keys:
            return self._cross_join(node, lctx, rctx)
        lvals = [self.eval(k, lctx) for k in node.left_keys]
        rvals = [self.eval(k, rctx) for k in node.right_keys]
        lkey, lok, rkey, rok, span = self._join_key_arrays(
            lvals, rvals, lctx, rctx)
        if node.kind == "full":
            return self._full_join(node, lctx, rctx, lkey, lok, rkey,
                                   rok)
        if node.right_unique:
            # gather join: probe from the left, build on the unique
            # right. The planner's kernel choice (engine/kernels.py)
            # picks the probe machinery; infeasible choices (missing
            # bounds, oversized domain) demote to the sort path and the
            # demotion shows in the per-query kernel counts
            ridx = hit = None
            if (node.kernel == KX.JOIN_MATMUL
                    and rctx.n <= 4 * KX.MATMUL_MAX_BUILD):
                ridx, hit = KX.matmul_probe_join(rkey, rok, lkey, lok)
                self._note("join.matmul")
            elif node.kernel in (KX.JOIN_MATMUL, KX.JOIN_DIRECT):
                dom = (None if span is None
                       else KX.domain_of(span[0], span[1]))
                if KX.direct_feasible(dom, rctx.n):
                    ridx, hit = KX.direct_lookup_join(
                        rkey, rok, lkey, lok, int(span[0]), dom)
                    self._note("join.direct")
            if ridx is None:
                if (getattr(rctx, "pristine", False)
                        and self._presorted_build(node.right,
                                                  node.right_keys)):
                    # host-proven sorted PK build on a pristine scan
                    # ctx: rok is the scan's prefix mask, so masked
                    # tail rows -> sentinel keeps ks ascending with NO
                    # device sort
                    sentinel = jnp.iinfo(rkey.dtype).max
                    ks = jnp.where(rok, rkey, sentinel)
                    order = jnp.arange(rkey.shape[0], dtype=jnp.int32)
                    self._note("join.presorted")
                else:
                    ks, order = self._build_lookup(rkey, rok)
                    self._note("join.sortmerge")
                ridx, hit = self._probe(ks, order, lkey, lok,
                                        take=self._take)
            if node.kind == "left":
                out = DCtx(lctx.n, lctx.row)
                out.cols.update(lctx.cols)
                gathered = self._gather(rctx, ridx, clear_valid=hit)
                out.cols.update(gathered.cols)
                if node.residual is not None:
                    resid = self.eval(node.residual, out)
                    rk = resid.arr.astype(bool)
                    if resid.valid is not None:
                        rk = rk & resid.valid
                    keep = hit & rk
                    out2 = DCtx(lctx.n, lctx.row)
                    out2.cols.update(lctx.cols)
                    out2.cols.update(self._gather(
                        rctx, ridx, clear_valid=keep).cols)
                    return out2
                return out
            out = DCtx(lctx.n, lctx.row & hit)
            out.cols.update(lctx.cols)
            out.cols.update(self._gather(rctx, ridx).cols)
            if node.residual is not None:
                out = self._apply_filter(out, node.residual)
            return out
        # right side not unique
        if node.kind == "inner":
            K = max(int(self.slack * max(lctx.n, rctx.n)), 1)
            if (node.kernel == KX.JOIN_PARTITIONED
                    and min(lctx.n, rctx.n) >= 2 * KX.NPART):
                # radix-partitioned sort-merge (engine/kernels.py):
                # per-partition sort depth is log(n/R) and all R sorts
                # batch into one lax.sort — the q21-class large-by-
                # large path. part_slack rides the executor's overflow
                # retry (doubled slack grows partition AND output
                # capacity together)
                part_slack = max(2.0, self.slack)
                lidx2, ridx, present, over = KX.partitioned_mn_join(
                    lkey, lok, rkey, rok, K, part_slack)
                self._overflows.append(over)
                self._note("join.partitioned")
                out = DCtx(int(lidx2.shape[0]), present)
                out.cols.update(self._gather(lctx, lidx2).cols)
                out.cols.update(self._gather(rctx, ridx).cols)
                if node.residual is not None:
                    out = self._apply_filter(out, node.residual)
                return out
            # generic M:N join: sort the left side by key, find each
            # right row's match RANGE via two searchsorteds, expand into
            # a fixed-capacity slot array (cumsum offsets -> slot->pair
            # mapping). Capacity = slack * max(|L|, |R|); overflow is
            # counted in-program and the executor retries with doubled
            # slack — the static-shape answer to data-dependent join
            # cardinality (SURVEY §7 hard part 2)
            self._note("join.sortmerge")
            ks, order = self._build_lookup(lkey, lok)
            lo = _ss(ks, rkey, side="left")
            hi = _ss(ks, rkey, side="right")
            cnt = jnp.where(rok, hi - lo, 0).astype(jnp.int64)
            offs = jnp.cumsum(cnt)
            total = offs[-1]
            slots = jnp.arange(K, dtype=jnp.int32)
            # slot->pair search runs on int32: offsets clamp to K+1
            # (order-preserving for every slot < K <= INT32_MAX, and
            # the clamped values can never be selected), keeping the
            # searchsorted sort native — an int64 offs sort is emulated
            # on TPU and was the q16-class M:N cost center
            if K + 1 >= 2**31:  # pragma: no cover - absurd capacity
                raise DeviceExecError(f"join capacity {K} exceeds int32")
            offs32 = jnp.minimum(offs, K + 1).astype(jnp.int32)
            ridx = jnp.clip(_ss(offs32, slots, side="right"),
                            0, rctx.n - 1)
            prev = jnp.where(ridx > 0, self._take(offs32, ridx - 1), 0)
            within = slots - prev
            lpos = jnp.clip(self._take(lo, ridx) + within, 0, lctx.n - 1)
            lidx2 = self._take(order, lpos)
            present = slots < jnp.minimum(total, K)
            self._overflows.append(jnp.maximum(total - K, 0))
            out = DCtx(K, present)
            out.cols.update(self._gather(lctx, lidx2).cols)
            out.cols.update(self._gather(rctx, ridx).cols)
            if node.residual is not None:
                out = self._apply_filter(out, node.residual)
            return out
        # left outer: probe from the right against a unique left
        # (FK-side expansion; the planner orients star joins the other
        # way, this path serves customer LEFT JOIN orders plans, q13)
        self._note("join.sortmerge")
        ks, order = self._build_lookup(lkey, lok)
        lidx, hit = self._probe(ks, order, rkey, rok, take=self._take)
        # left outer with expansion: block A = matched right rows with
        # gathered left columns; block B = left rows with no surviving match
        presentA = rctx.row & hit
        if node.residual is not None:
            combined = DCtx(rctx.n, presentA)
            combined.cols.update(rctx.cols)
            combined.cols.update(self._gather(lctx, lidx).cols)
            resid = self.eval(node.residual, combined)
            rk = resid.arr.astype(bool)
            if resid.valid is not None:
                rk = rk & resid.valid
            presentA = presentA & rk
        scat = jnp.zeros(lctx.n, dtype=jnp.int32).at[lidx].max(
            presentA.astype(jnp.int32))
        matched = scat > 0
        n_out = rctx.n + lctx.n
        out = DCtx(n_out, jnp.concatenate(
            [presentA, lctx.row & ~matched]))
        gatheredA = self._gather(lctx, lidx)
        for k, dv in lctx.cols.items():
            ga = gatheredA.cols[k]
            arr = jnp.concatenate([ga.arr, dv.arr])
            valid = None
            if ga.valid is not None or dv.valid is not None:
                gav = ga.valid if ga.valid is not None else jnp.ones(
                    rctx.n, bool)
                dvv = dv.valid if dv.valid is not None else jnp.ones(
                    lctx.n, bool)
                valid = jnp.concatenate([gav, dvv])
            out.cols[k] = dv.with_arrays(arr, valid)
        falses = jnp.zeros(lctx.n, dtype=bool)
        for k, dv in rctx.cols.items():
            arr = jnp.concatenate(
                [dv.arr, jnp.zeros(lctx.n, dtype=dv.arr.dtype)])
            av = dv.valid if dv.valid is not None else jnp.ones(rctx.n, bool)
            out.cols[k] = dv.with_arrays(arr, jnp.concatenate([av, falses]))
        return out

    def _cross_join(self, node: P.Join, lctx: DCtx, rctx: DCtx) -> DCtx:
        if lctx.n * rctx.n > 1 << 24:
            raise DeviceExecError(
                f"cross join too large: {lctx.n} x {rctx.n}")
        li = jnp.repeat(jnp.arange(lctx.n, dtype=jnp.int32), rctx.n)
        ri = jnp.tile(jnp.arange(rctx.n, dtype=jnp.int32), lctx.n)
        out = self._gather(lctx, li).merge(self._gather(rctx, ri))
        out.row = self._take(lctx.row, li) & self._take(rctx.row, ri)
        if node.residual is not None:
            out = self._apply_filter(out, node.residual)
        return out

    def _run_semijoin(self, node: P.SemiJoin) -> DCtx:
        lctx, rctx = self.run(node.left), self.run(node.right)
        lvals = [self.eval(k, lctx) for k in node.left_keys]
        rvals = [self.eval(k, rctx) for k in node.right_keys]
        if not node.left_keys:
            raise DeviceExecError("semi join without keys")
        lkey, lok, rkey, rok, span = self._join_key_arrays(
            lvals, rvals, lctx, rctx)
        dom = None if span is None else KX.domain_of(span[0], span[1])
        want_bitmask = (node.kernel == KX.SEMI_BITMASK
                        and KX.direct_feasible(dom, rctx.n))
        if node.residual is None:
            if want_bitmask:
                # EXISTS as a dense membership bitmap: one scatter on
                # the build, one gather on the probe — no sort anywhere
                exists = KX.bitmask_semi(rkey, rok, lkey, lok,
                                         int(span[0]), dom)
                self._note("semi.bitmask")
            else:
                ks, order = self._build_lookup(rkey, rok)
                _idx, hit = self._probe(ks, order, lkey, lok,
                                        take=self._take)
                exists = hit
                self._note("semi.sortmerge")
        else:
            exists = self._exists_with_residual(
                node, lctx, rctx, lkey, lok, rkey, rok,
                dom if want_bitmask else None,
                None if span is None else int(span[0]))
        keep = (lctx.row & ~exists) if node.anti else (lctx.row & exists)
        out = DCtx(lctx.n, keep)
        out.cols = lctx.cols
        return out

    def _exists_with_residual(self, node, lctx, rctx, lkey, lok, rkey,
                              rok, dom=None, key_lo=None):
        """EXISTS with a cross-side residual of the q21 shape
        `r.col <> l.col`: exists a right row with the key and a DIFFERENT
        (non-NULL) col value  <=>  the per-key [min, max] of col over
        right rows is not exactly [l.col, l.col].

        Two formulations: when the kernel choice is ``bitmask`` and the
        key domain is dense enough (``dom``/``key_lo`` from the
        caller), the min/max tables build by scatter into domain-sized
        arrays and the probe is three gathers — no sort at all
        (engine/kernels.keyed_minmax_semi, the q21 EXISTS-chain path).
        Otherwise one 2-key native sort of (key, col) makes col sorted
        within each key run, so min/max are gathers at the run's ends —
        still no row expansion and no emulated 64-bit sorts."""
        e = node.residual
        if not (isinstance(e, ir.Cmp) and e.op == "<>"):
            raise DeviceExecError(
                f"unsupported semi-join residual: {e!r}")
        rbinds = _plan_bindings(node.right)
        if _expr_bindings(e.left) <= rbinds:
            r_ir, l_ir = e.left, e.right
        elif _expr_bindings(e.right) <= rbinds:
            r_ir, l_ir = e.right, e.left
        else:
            raise DeviceExecError("residual does not split by side")
        lcol = self.eval(l_ir, lctx)
        rcol = self.eval(r_ir, rctx)
        la, ra, lo, hi = self._align_pair(lcol, rcol)
        lok2 = _ok(lcol, lok)
        # rows whose col is NULL can never satisfy `<>` — exclude them
        # from the build entirely (the count-difference formulation this
        # replaces over-counted such rows)
        rok2 = _ok(rcol, rok)
        rcol_n = ra
        lcol_n = la
        if (rkey.dtype == jnp.int32 and -2**31 < lo
                and hi < 2**31 - 1):
            rcol_n = ra.astype(jnp.int32)
            lcol_n = la.astype(jnp.int32)
        if dom is not None and jnp.issubdtype(rcol_n.dtype, jnp.integer):
            self._note("semi.minmax")
            return lok2 & KX.keyed_minmax_semi(
                rkey, rok2, rcol_n, lkey, lok2, lcol_n, key_lo, dom)
        self._note("semi.sortmerge")
        k_sent = jnp.iinfo(rkey.dtype).max
        rkey_s = jnp.where(rok2, rkey, k_sent)
        sk, sc = lax.sort([rkey_s, rcol_n], num_keys=2, is_stable=False)
        pos_l = _ss(sk, lkey, side="left")
        pos_r = _ss(sk, lkey, side="right")
        n = sk.shape[0]
        cmin = self._take(sc, jnp.clip(pos_l, 0, n - 1))
        cmax = self._take(sc, jnp.clip(pos_r - 1, 0, n - 1))
        has_key = pos_r > pos_l
        differs = (cmin != lcol_n) | (cmax != lcol_n)
        return lok & lok2 & has_key & differs

    # --------------------------------------------------------- aggregation

    def _run_aggregate(self, node: P.Aggregate) -> DCtx:
        ctx = self.run(node.child)
        b = node.binding
        if not node.group_keys:
            out = DCtx(1, jnp.ones(1, dtype=bool))
            for name, spec in node.aggs:
                arr, valid, sdict = self._agg_global(spec, ctx)
                lo, hi = self._agg_bounds(spec, ctx)
                out.cols[(b, name)] = DVal(arr, valid, sdict, lo, hi)
            return out
        keyvals = [self.eval(e, ctx) for _, e in node.group_keys]
        G = self._group_capacity(ctx.n, keyvals)
        if (G < ctx.n and G <= KX.DENSE_AGG_MAX_GROUPS
                and not any(spec.distinct for _, spec in node.aggs)):
            return self._run_aggregate_dense(node, ctx, keyvals, G)
        perm, gid, present_s, ngroups, keys_s = self._group_ids(ctx, keyvals)
        G_scan = self._scan_bound(ctx.n, node.group_keys, keyvals)
        if G_scan < G:
            # the bound is the trace's reading of where the keys came
            # from: a group past it fails the statement through the
            # overflow path instead of merging into the last slot
            G = G_scan
            self._overflows.append(jnp.maximum(ngroups - G, 0))
            self._note("agg.scan_bound")
        gid = jnp.minimum(gid, G - 1)
        out_row = jnp.arange(G, dtype=jnp.int32) < ngroups
        out = DCtx(G, out_row)
        # first sorted position per group (n for empty groups): gid is
        # sorted, so this is a sorted search, not a segment_min scatter
        starts2 = _ss(gid, jnp.arange(G, dtype=gid.dtype))
        starts = jnp.clip(starts2, 0, ctx.n - 1)
        for (kname, _kexpr), dv in zip(
                node.group_keys, self._group_keys(keyvals, keys_s, starts)):
            out.cols[(b, kname)] = dv
        for name, spec in node.aggs:
            arr, valid, sdict = self._agg_grouped(
                spec, ctx, perm, gid, present_s, G, starts2,
                kernel=node.kernel)
            lo, hi = self._agg_bounds(spec, ctx)
            out.cols[(b, name)] = DVal(arr, valid, sdict, lo, hi)
        return out

    def _run_aggregate_dense(self, node: P.Aggregate, ctx: DCtx,
                             keyvals, G: int) -> DCtx:
        """The grouped form for a handful of slots: every key domain is
        known on the host (_group_capacity), so a row's slot is the
        mixed-radix code of its key digits and each aggregate is
        _agg_global's reduction under ``slot == g``. No group sort, no
        permutation, no gather, no cumsum. Slots stand in the order the
        group sort gives its groups (first key most significant, values
        ascending, a nullable key's NULL last); a slot no row falls in
        is an absent output row."""
        b = node.binding
        radix = [_key_radix(kv) for kv in keyvals]
        slot = jnp.zeros(ctx.n, jnp.int32)
        for kv, (lo, dom) in zip(keyvals, radix):
            digit = (kv.arr - lo).astype(jnp.int32)
            if kv.valid is not None:
                digit = jnp.where(kv.valid, digit, dom - 1)
            slot = slot * dom + digit
        g = jnp.arange(G, dtype=jnp.int32)
        # [G, n], consumed only by reductions over n: never materialised
        hit = slot == g[:, None]
        out = DCtx(G, jnp.any(hit & ctx.row, axis=1))
        stride = G
        for (kname, _kexpr), kv, (lo, dom) in zip(
                node.group_keys, keyvals, radix):
            stride //= dom
            digit = (g // stride) % dom
            arr_g, valid_g = digit.astype(kv.arr.dtype) + lo, None
            if kv.valid is not None:
                valid_g = digit < dom - 1
                arr_g = jnp.where(valid_g, arr_g,
                                  jnp.zeros((), arr_g.dtype))
            out.cols[(b, kname)] = kv.with_arrays(arr_g, valid_g)
        for name, spec in node.aggs:
            arr, valid, sdict = self._agg_global(spec, ctx, hit)
            lo, hi = self._agg_bounds(spec, ctx)
            out.cols[(b, name)] = DVal(arr, valid, sdict, lo, hi)
        self._note("agg.dense")
        return out

    def _seg_sum(self, data, starts2, G):
        """Per-segment sum over the SORTED row space via inclusive-cumsum
        differences. segment_sum lowers to scatter-add (~160ms for i64 at
        1.8M rows on TPU, measured); cumsum runs at memory speed.
        starts2[g] = first sorted row of group g, n for empty groups;
        rows outside any real group must carry data == 0.

        Integer sums stay exact. Float sums pick up cancellation error
        bounded by ulp(global prefix): at SF100 scale (6e8 rows of ~1e9
        squared values) that is ~512 absolute against per-group sums of
        ~1e10+ — orders below the benchmark's float validation epsilon
        (`utils/validate_core.py`), and float aggregation order is
        already unspecified (the reference gates it behind
        `spark.rapids.sql.variableFloatAgg.enabled`)."""
        n = data.shape[0]
        csum = jnp.cumsum(data)
        nxt = jnp.concatenate(
            [starts2[1:], jnp.full((1,), n, starts2.dtype)])
        end = jnp.clip(nxt - 1, 0, n - 1)
        hi = self._take(csum, end)
        lo = jnp.where(starts2 > 0,
                       self._take(csum, jnp.clip(starts2 - 1, 0, n - 1)),
                       jnp.zeros((), csum.dtype))
        return hi - lo

    def _agg_bounds(self, spec: P.AggSpec, ctx: DCtx):
        """Host-known value bounds of an aggregate output (lets downstream
        joins against aggregate results bit-pack their keys, q2)."""
        if spec.func == "count":
            return 0, ctx.n
        dv = None
        if spec.arg is not None:
            dv = self.eval(spec.arg, ctx)  # cached via column DVals
        if dv is None or dv.lo is None or dv.hi is None:
            return None, None
        if spec.func in ("min", "max"):
            return dv.lo, dv.hi
        if spec.func == "sum" and not isinstance(spec.dtype, FloatType):
            return min(0, dv.lo) * ctx.n, max(0, dv.hi) * ctx.n
        return None, None

    @staticmethod
    def _group_capacity(n: int, keyvals) -> int:
        """Static bound on distinct groups: min(rows, product of key
        domains). Collapses the post-aggregation capacity for
        small-domain keys (q1: returnflag x linestatus -> ~6 slots
        instead of the scan's millions), which shrinks every downstream
        sort — the big TPU win since s64 sorts are emulated."""
        prod = 1
        for kv in keyvals:
            radix = _key_radix(kv)
            if radix is None:
                return n
            prod *= radix[1]
            if prod >= n:
                return n
        return max(min(prod, n), 1)

    @staticmethod
    def _scan_bound(n: int, group_keys, keyvals) -> int:
        """The sorted form's bound on distinct groups from the scans
        the keys were read from (``DVal.scan``). A slot holds the
        columns of at most one row of each origin, or an outer join's
        NULLs for all of them, so the key tuples over one origin number
        at most its rows plus one; each origin contributes that or the
        product of its keys' domains, whichever is less. A key that is
        not a bare column, or has no origin, contributes its domain (n
        where the host does not know it). At most n."""
        prod, origins = 1, {}
        for (_name, e), kv in zip(group_keys, keyvals):
            radix = _key_radix(kv)
            dom = n if radix is None else radix[1]
            if isinstance(e, ir.ColRef) and kv.scan is not None:
                origin, rows = kv.scan
                doms = origins.get(origin, (0, 1))[1]
                origins[origin] = (rows + 1, doms * dom)
            else:
                prod *= dom
        for tuples, doms in origins.values():
            prod *= min(tuples, doms)
        return max(1, min(n, prod))

    def _group_ids(self, ctx: DCtx, keyvals):
        """Stable sort rows by (presence, key validity+values...); returns
        (perm, gid per sorted row, presence per sorted row, ngroups,
        sorted key operands). Present rows sort to the front. The sorted
        key operands, one (values, NULL flags or None) pair a key, are
        what _group_keys reads the output keys from."""
        n = ctx.n
        ops = [jnp.where(ctx.row, 0, 1).astype(jnp.int32)]
        key_ops = []
        for kv in keyvals:
            null_op = None
            if kv.valid is not None:
                vop = jnp.where(kv.valid, 0, 1).astype(jnp.int32)
                ops.append(vop)
                null_op = len(ops) - 1
            arr = _narrow_key(kv)
            filled = jnp.where(_ok(kv, ctx.row), arr,
                               jnp.zeros((), dtype=arr.dtype))
            ops.append(filled)
            key_ops.append((len(ops) - 1, null_op))
        ops.append(jnp.arange(n, dtype=jnp.int32))
        sorted_ops = lax.sort(ops, num_keys=len(ops) - 1, is_stable=True)
        perm = sorted_ops[-1]
        # the presence flag is the sort's first operand
        present_s = sorted_ops[0] == 0
        iota = jnp.arange(n, dtype=jnp.int32)
        diff = jnp.zeros(n, dtype=bool).at[0].set(True)
        keys_s = [(sorted_ops[i], None if j is None else sorted_ops[j])
                  for i, j in key_ops]
        for o in (o for pair in keys_s for o in pair if o is not None):
            diff = diff | jnp.concatenate(
                [jnp.ones(1, bool), o[1:] != o[:-1]])
        first_s = present_s & (diff | (iota == 0))
        gid = jnp.cumsum(first_s.astype(jnp.int32)) - 1
        gid = jnp.clip(gid, 0, n - 1)
        ngroups = jnp.sum(first_s)
        return perm, gid, present_s, ngroups, keys_s

    def _group_keys(self, keyvals, keys_s, starts) -> list:
        """Each group's key columns, read from the group sort's own
        sorted key operands at the group's first sorted row: the column
        is not gathered through the permutation. A sorted operand is the
        column as _narrow_key narrowed it (cast back here) with 0 under
        NULL and absent rows, which validity and the row mask cover."""
        out = []
        for kv, (val_s, null_s) in zip(keyvals, keys_s):
            arr_g = self._take(val_s, starts).astype(kv.arr.dtype)
            valid_g = (None if null_s is None
                       else self._take(null_s, starts) == 0)
            out.append(kv.with_arrays(arr_g, valid_g))
        self._note("agg.sorted_keys")
        return out

    def _agg_arg(self, spec: P.AggSpec, ctx: DCtx):
        if spec.arg is None:
            return None
        return self.eval(spec.arg, ctx)

    def _agg_global(self, spec: P.AggSpec, ctx: DCtx, hit=None):
        """One aggregate over every present row, shape [1]; under
        ``hit`` ([G, n]: row r falls in slot g) the same reduction a
        slot, shape [G]: the dense grouped form, never ``distinct``."""
        if hit is None:
            axis, rows, shaped = None, (lambda w: w), (
                lambda x: x.reshape(1))
        else:
            axis, rows, shaped = 1, (lambda w: hit & w), (lambda x: x)
        dv = self._agg_arg(spec, ctx)
        if spec.func == "count":
            if dv is None:
                cnt = shaped(jnp.sum(rows(ctx.row), axis=axis))
                return (cnt.astype(jnp.int64),
                        jnp.ones(cnt.shape[0], bool), None)
            w = _ok(dv, ctx.row)
            if spec.distinct:
                # sentinel-FREE distinct: validity is its own sort
                # operand, so no value (INT32_MAX, +inf, a bool True)
                # can collide with the invalid marker; _narrow_key
                # keeps the value operand on the native i32 sort path
                arr = _narrow_key(dv)
                iv = jnp.where(w, 0, 1).astype(jnp.int32)
                iv_s, v_s = lax.sort([iv, arr], num_keys=2,
                                     is_stable=False)
                w_s = iv_s == 0  # valid rows form the sorted prefix
                newv = jnp.concatenate(
                    [jnp.ones(1, bool), v_s[1:] != v_s[:-1]])
                cnt = jnp.sum(newv & w_s)
            else:
                cnt = jnp.sum(rows(w), axis=axis)
            cnt = shaped(cnt)
            return (cnt.astype(jnp.int64),
                    jnp.ones(cnt.shape[0], bool), None)
        w = rows(_ok(dv, ctx.row))
        cnt = jnp.sum(w, axis=axis)
        valid = shaped(cnt > 0)
        if spec.func == "sum":
            if isinstance(spec.dtype, FloatType):
                s = jnp.sum(jnp.where(w, dv.arr.astype(self.fdt), 0.0),
                            axis=axis)
            else:
                s = jnp.sum(jnp.where(w, dv.arr.astype(jnp.int64), 0),
                            axis=axis)
            return shaped(s), valid, None
        if spec.func == "avg":
            f = _to_float(dv.arr, spec.arg.dtype, self.fdt)
            s = jnp.sum(jnp.where(w, f, 0.0), axis=axis)
            return shaped(s / jnp.maximum(cnt, 1)), valid, None
        if spec.func in ("min", "max"):
            if jnp.issubdtype(dv.arr.dtype, jnp.floating):
                fill = jnp.inf if spec.func == "min" else -jnp.inf
                masked = jnp.where(w, dv.arr, fill)
            else:
                fill = I64_MAX if spec.func == "min" else I64_MIN
                masked = jnp.where(w, dv.arr.astype(jnp.int64), fill)
            red = (jnp.min(masked, axis=axis) if spec.func == "min"
                   else jnp.max(masked, axis=axis))
            return shaped(red), valid, dv.sdict
        if spec.func in ("stddev_samp", "stddev"):
            f = _to_float(dv.arr, spec.arg.dtype, self.fdt)
            s1 = jnp.sum(jnp.where(w, f, 0.0), axis=axis)
            s2 = jnp.sum(jnp.where(w, f * f, 0.0), axis=axis)
            c = cnt.astype(self.fdt)
            var = (s2 - s1 * s1 / jnp.maximum(c, 1)) / jnp.maximum(
                c - 1, 1)
            sd = jnp.sqrt(jnp.maximum(var, 0.0))
            return (shaped(jnp.where(cnt > 1, sd, jnp.nan)),
                    valid, None)
        raise DeviceExecError(spec.func)

    def _agg_grouped(self, spec: P.AggSpec, ctx: DCtx, perm, gid,
                     present_s, G, starts2, kernel: str = ""):
        dv = self._agg_arg(spec, ctx)
        if spec.func == "count" and spec.distinct:
            return self._count_distinct_grouped(
                spec, ctx, perm, gid, present_s, G)
        if dv is None:  # count(*)
            cnt = self._seg_sum(present_s.astype(jnp.int32), starts2,
                                G).astype(jnp.int64)
            return cnt, None, None
        arr_s = self._take(dv.arr, perm)
        w = present_s
        if dv.valid is not None:
            w = w & self._take(dv.valid, perm)
        # counts fit int32 (<= capacity); widen only the G-sized result
        cnt = self._seg_sum(w.astype(jnp.int32), starts2,
                            G).astype(jnp.int64)
        if spec.func == "count":
            return cnt, None, None
        valid = cnt > 0
        if spec.func == "sum":
            if isinstance(spec.dtype, FloatType):
                data = jnp.where(w, arr_s.astype(self.fdt), 0.0)
            else:
                data = jnp.where(w, arr_s.astype(jnp.int64), 0)
            return self._seg_sum(data, starts2, G), valid, None
        if spec.func == "avg":
            f = _to_float(arr_s, spec.arg.dtype, self.fdt)
            s = self._seg_sum(jnp.where(w, f, 0.0), starts2, G)
            return s / jnp.maximum(cnt, 1).astype(self.fdt), valid, None
        if spec.func in ("min", "max"):
            isf = jnp.issubdtype(arr_s.dtype, jnp.floating)
            if isf:
                fill = jnp.inf if spec.func == "min" else -jnp.inf
                data = jnp.where(w, arr_s, fill)
            else:
                # stay int32 when host bounds allow: segment_min/max
                # scatter i64 is emulated on TPU
                arr_i = arr_s.astype(jnp.int64)
                if (dv.lo is not None and dv.hi is not None
                        and -2**31 < dv.lo and dv.hi < 2**31 - 1):
                    arr_i = arr_s.astype(jnp.int32)
                fill = (jnp.iinfo(arr_i.dtype).max if spec.func == "min"
                        else jnp.iinfo(arr_i.dtype).min)
                data = jnp.where(w, arr_i, fill)
            if kernel == KX.AGG_SEGSCAN:
                # scan-based grouped min/max over the sorted gids: a
                # segmented scan + a gather at segment ends, riding the
                # same group sort every other AggSpec of this node
                # amortizes — no scatter (segment_min/max emulates
                # element-at-a-time for 64-bit operands on TPU)
                op = (jnp.minimum if spec.func == "min"
                      else jnp.maximum)
                red = KX.seg_reduce_at_ends(op, data, gid, starts2)
                self._note("agg.segscan")
            else:
                seg = (jax.ops.segment_min if spec.func == "min"
                       else jax.ops.segment_max)
                red = seg(data, gid, num_segments=G,
                          indices_are_sorted=True)
                self._note("agg.scatter")
            if not isf and not isinstance(spec.dtype,
                                          (FloatType, DecimalType)):
                red = red.astype(arr_s.dtype)
            elif not isf:
                red = red.astype(jnp.int64)
            return red, valid, dv.sdict
        if spec.func in ("stddev_samp", "stddev"):
            f = _to_float(arr_s, spec.arg.dtype, self.fdt)
            s1 = self._seg_sum(jnp.where(w, f, 0.0), starts2, G)
            s2 = self._seg_sum(jnp.where(w, f * f, 0.0), starts2, G)
            c = cnt.astype(self.fdt)
            var = (s2 - s1 * s1 / jnp.maximum(c, 1)) / jnp.maximum(
                c - 1, 1)
            sd = jnp.sqrt(jnp.maximum(var, 0.0))
            return jnp.where(cnt > 1, sd, jnp.nan), valid, None
        raise DeviceExecError(spec.func)

    def _count_distinct_grouped(self, spec, ctx, perm, gid, present_s, G):
        """Re-sort by (presence, gid, value); count first occurrences of
        (gid, value) among valid rows."""
        dv = self.eval(spec.arg, ctx)
        n = ctx.n
        # narrowed when bounds fit: keeps the 5-operand sort below on
        # the native i32 TPU sort path
        val = _narrow_key(dv)
        if val.dtype not in (jnp.int32, jnp.int64):
            val = dv.arr.astype(jnp.int64)
        w0 = _ok(dv, ctx.row)
        # group id per ORIGINAL row: scatter sorted gid back through perm
        gid_orig = jnp.zeros(n, dtype=gid.dtype).at[perm].set(gid)
        # valid rows sort before invalid within each group so the
        # first-occurrence flag below can't be shadowed by a NULL row
        ops = [jnp.where(ctx.row, 0, 1).astype(jnp.int32),
               gid_orig,
               jnp.where(w0, 0, 1).astype(jnp.int32),
               jnp.where(w0, val, 0), jnp.arange(n, dtype=jnp.int32)]
        sorted_ops = lax.sort(ops, num_keys=4, is_stable=True)
        g2 = sorted_ops[1]
        v2 = sorted_ops[3]
        w2 = sorted_ops[2] == 0
        newpair = jnp.concatenate(
            [jnp.ones(1, bool), (g2[1:] != g2[:-1]) | (v2[1:] != v2[:-1])])
        flag = w2 & newpair
        starts2 = _ss(g2, jnp.arange(G, dtype=g2.dtype))
        cnt = self._seg_sum(flag.astype(jnp.int32), starts2,
                            G).astype(jnp.int64)
        return cnt, None, None

    # ------------------------------------------------------------- windows

    def _run_window(self, node: P.Window) -> DCtx:
        """Sort-based window evaluation: ONE multi-operand lax.sort into
        partition-major/order-minor space, then segmented scans/segment
        reductions, scattered back through the permutation. Stays inside
        the single XLA program (no host round trips)."""
        ctx = self.run(node.child)
        out = DCtx(ctx.n, ctx.row)
        out.cols.update(ctx.cols)
        for name, spec in node.specs:
            out.cols[(node.binding, name)] = self._window_col(spec, ctx)
        return out

    def _window_col(self, spec: P.WindowSpec, ctx: DCtx) -> DVal:
        n = ctx.n
        iota = jnp.arange(n, dtype=jnp.int32)
        ops = [jnp.where(ctx.row, 0, 1).astype(jnp.int32)]
        part_ops = []
        for p in spec.partition:
            dv = self.eval(p, ctx)
            if dv.valid is not None:
                vop = jnp.where(dv.valid, 0, 1).astype(jnp.int32)
                ops.append(vop)
                part_ops.append(len(ops) - 1)
            arr = _narrow_key(dv)
            filled = jnp.where(_ok(dv, ctx.row), arr,
                               jnp.zeros((), dtype=arr.dtype))
            ops.append(filled)
            part_ops.append(len(ops) - 1)
        order_ops = []
        for e, asc, nulls_first in spec.order:
            dv = self.eval(e, ctx)
            if dv.valid is not None:
                rank = (jnp.where(dv.valid, 1, 0) if nulls_first
                        else jnp.where(dv.valid, 0, 1))
                ops.append(rank.astype(jnp.int32))
                order_ops.append(len(ops) - 1)
            arr = _narrow_key(dv)
            if jnp.issubdtype(arr.dtype, jnp.bool_):
                arr = arr.astype(jnp.int32)
            key = arr if asc else -arr
            if dv.valid is not None:
                key = jnp.where(dv.valid, key, jnp.zeros((), key.dtype))
            ops.append(key)
            order_ops.append(len(ops) - 1)
        ops.append(iota)
        sorted_ops = lax.sort(ops, num_keys=len(ops) - 1, is_stable=True)
        perm = sorted_ops[-1]
        present_s = sorted_ops[0] == 0
        part_start = jnp.zeros(n, dtype=bool).at[0].set(True)
        for i in part_ops:
            o = sorted_ops[i]
            part_start = part_start | jnp.concatenate(
                [jnp.ones(1, bool), o[1:] != o[:-1]])
        start_pos = lax.cummax(jnp.where(part_start, iota, 0))
        pid = jnp.cumsum(part_start.astype(jnp.int32)) - 1

        def scatter(res_sorted, valid_sorted=None, lo=None, hi=None):
            arr = jnp.zeros(n, res_sorted.dtype).at[perm].set(res_sorted)
            valid = None
            if valid_sorted is not None:
                valid = jnp.zeros(n, bool).at[perm].set(valid_sorted)
            return DVal(arr, valid, None, lo, hi)

        if spec.func in ("rank", "dense_rank", "row_number"):
            if spec.func == "row_number":
                return scatter((iota - start_pos + 1).astype(jnp.int64),
                               lo=1, hi=n)
            change = part_start
            for i in order_ops:
                o = sorted_ops[i]
                change = change | jnp.concatenate(
                    [jnp.ones(1, bool), o[1:] != o[:-1]])
            if spec.func == "dense_rank":
                c = jnp.cumsum(change.astype(jnp.int64))
                cstart = lax.cummax(jnp.where(part_start, c, 0))
                return scatter(c - cstart + 1, lo=1, hi=n)
            lastchg = lax.cummax(jnp.where(change, iota, 0))
            return scatter((lastchg - start_pos + 1).astype(jnp.int64),
                           lo=1, hi=n)

        # aggregate windows
        if spec.arg is not None:
            dv = self.eval(spec.arg, ctx)
            w = self._take(_ok(dv, ctx.row), perm)
            vals = self._take(dv.arr, perm)
        else:  # count(*)
            w = present_s
            vals = jnp.ones(n, dtype=jnp.int64)
        running = bool(spec.order)
        is_f = isinstance(spec.dtype, FloatType)
        if spec.func == "avg":
            vals = _to_float(vals, spec.arg.dtype, self.fdt)
        elif is_f:
            vals = vals.astype(self.fdt)
        else:
            vals = vals.astype(jnp.int64)
        G = n
        # per-row partition total, scatter-free: inclusive cumsum
        # differenced at the partition's bounding rows (start_pos is the
        # running partition start; the next start comes from a reversed
        # cummin). segment_sum over n segments is a scatter — emulated
        # and slow for 64-bit operands on TPU.
        nstart = jnp.where(part_start, iota, n)
        nxt = jnp.concatenate(
            [lax.cummin(nstart, reverse=True)[1:],
             jnp.full((1,), n, jnp.int32)])
        pend = jnp.clip(nxt - 1, 0, n - 1)

        def part_total(data):
            csum = jnp.cumsum(data)
            hi = self._take(csum, pend)
            lo = jnp.where(start_pos > 0,
                           self._take(csum, jnp.clip(start_pos - 1, 0, n - 1)),
                           jnp.zeros((), csum.dtype))
            return hi - lo

        if spec.func == "count":
            src = w.astype(jnp.int32)
            if running:
                res = _seg_scan(lambda a, b: a + b, src, part_start)
            else:
                res = part_total(src)
            return self._window_range_fix(
                spec, scatter, res.astype(jnp.int64), None, part_start,
                order_ops, sorted_ops, pid, running)
        cnt_src = w.astype(jnp.int32)
        if running:
            cnt = _seg_scan(lambda a, b: a + b, cnt_src, part_start)
        else:
            cnt = part_total(cnt_src)
        valid = cnt > 0
        if spec.func in ("sum", "avg"):
            data = jnp.where(w, vals, jnp.zeros((), vals.dtype))
            if running:
                res = _seg_scan(lambda a, b: a + b, data, part_start)
            else:
                res = part_total(data)
            if spec.func == "avg":
                res = res.astype(self.fdt) / jnp.maximum(cnt, 1)
        elif spec.func in ("min", "max"):
            if jnp.issubdtype(vals.dtype, jnp.floating):
                fill = jnp.inf if spec.func == "min" else -jnp.inf
            else:
                fill = I64_MAX if spec.func == "min" else I64_MIN
            data = jnp.where(w, vals, fill)
            op = jnp.minimum if spec.func == "min" else jnp.maximum
            if running:
                res = _seg_scan(op, data, part_start)
            else:
                # whole-partition min/max via the segmented scan's
                # value at the partition's last row (pend is already
                # per-row) — replaces the segment_min/max scatter
                res = KX.part_reduce_broadcast(op, data, part_start,
                                               pend)
        else:
            raise DeviceExecError(f"window func {spec.func}")
        return self._window_range_fix(
            spec, scatter, res, valid, part_start, order_ops, sorted_ops,
            pid, running)

    def _window_range_fix(self, spec, scatter, res, valid, part_start,
                          order_ops, sorted_ops, pid, running):
        """SQL default frame with ORDER BY is RANGE ..CURRENT ROW: peer
        (order-key-tied) rows share the value at the peer group's LAST
        row. 'cum' (ROWS) keeps the per-row running value."""
        if running and spec.frame is None:
            n = res.shape[0]
            change = part_start
            for i in order_ops:
                o = sorted_ops[i]
                change = change | jnp.concatenate(
                    [jnp.ones(1, bool), o[1:] != o[:-1]])
            # each peer group's last row via a reversed running-min
            # over future change positions — no segment_max scatter
            last = KX.last_of_group(change, n)
            res = self._take(res, last)
            if valid is not None:
                valid = self._take(valid, last)
        return scatter(res, valid)

    # ------------------------------------------------------- sort and misc

    def _sort_perm(self, node: P.Sort):
        """The Sort's input, its stable full sort as (permutation,
        presence per sorted row), present rows first: what _run_sort
        gathers every row through and a Limit above only the rows it
        keeps."""
        ctx = self.run(node.child)
        n = ctx.n
        ops = [jnp.where(ctx.row, 0, 1).astype(jnp.int32)]
        for e, asc, nulls_first in node.keys:
            dv = self.eval(e, ctx)
            if dv.valid is not None:
                rank = jnp.where(dv.valid, 1, 0) if nulls_first \
                    else jnp.where(dv.valid, 0, 1)
                ops.append(rank.astype(jnp.int32))
            arr = _narrow_key(dv)
            if jnp.issubdtype(arr.dtype, jnp.bool_):
                arr = arr.astype(jnp.int32)
            key = arr if asc else -arr  # negation stays in range: bounds checked
            if dv.valid is not None:
                key = jnp.where(dv.valid, key, jnp.zeros((), key.dtype))
            ops.append(key)
        ops.append(jnp.arange(n, dtype=jnp.int32))
        sorted_ops = lax.sort(ops, num_keys=len(ops) - 1, is_stable=True)
        return ctx, sorted_ops[-1], sorted_ops[0] == 0

    def _run_sort(self, node: P.Sort) -> DCtx:
        ctx, perm, present_s = self._sort_perm(node)
        out = self._gather(ctx, perm)
        out.row = present_s
        return out

    def _run_limit(self, node: P.Limit) -> DCtx:
        """Top-N: the columns are gathered at the rows LIMIT keeps, not
        through the whole permutation and sliced (the TPU compiler does
        not push a slice through a gather)."""
        if isinstance(node.child, P.Sort):
            ctx, perm, present_s = self._sort_perm(node.child)
            self.ops_est += ctx.n  # the Sort node, which run() did not see
        else:
            # stable-sort present rows to the front: the child did not
            # order them
            ctx = self.run(node.child)
            ops = [jnp.where(ctx.row, 0, 1).astype(jnp.int32),
                   jnp.arange(ctx.n, dtype=jnp.int32)]
            sorted_ops = lax.sort(ops, num_keys=1, is_stable=True)
            perm, present_s = sorted_ops[-1], sorted_ops[0] == 0
        cap = min(node.count, ctx.n)
        out = self._gather(ctx, perm[:cap])
        out.row = present_s[:cap]
        self._note("sort.topn")
        return out

    def _run_distinct(self, node: P.Distinct) -> DCtx:
        ctx = self.run(node.child)
        b = node.binding
        return self._distinct_rows(
            ctx, [(b, name) for name, _ in node.output])

    def _distinct_rows(self, ctx: DCtx, keys: list) -> DCtx:
        """One row a distinct combination of ctx's columns ``keys``,
        capacity ctx.n."""
        keyvals = [ctx.cols[k] for k in keys]
        _perm, gid, _present_s, ngroups, keys_s = self._group_ids(
            ctx, keyvals)
        G = ctx.n
        starts = jnp.clip(_ss(gid, jnp.arange(G, dtype=gid.dtype)),
                          0, G - 1)
        out = DCtx(G, jnp.arange(G, dtype=jnp.int32) < ngroups)
        out.cols = dict(zip(keys, self._group_keys(keyvals, keys_s,
                                                   starts)))
        return out

    def _run_setop(self, node: P.SetOp) -> DCtx:
        lctx, rctx = self.run(node.left), self.run(node.right)
        lb, rb = node.left.binding, node.right.binding
        if node.kind.startswith("union"):
            out = DCtx(lctx.n + rctx.n,
                       jnp.concatenate([lctx.row, rctx.row]))
            for (lname, _), (rname, _) in zip(node.left.output,
                                              node.right.output):
                lv = lctx.cols[(lb, lname)]
                rv = rctx.cols[(rb, rname)]
                la, ra = lv.arr, rv.arr
                sdict = lv.sdict
                if lv.sdict is not None or rv.sdict is not None:
                    la, ra, sdict = self._union_dict(lv, rv)
                if la.dtype != ra.dtype:
                    tgt = jnp.promote_types(la.dtype, ra.dtype)
                    la, ra = la.astype(tgt), ra.astype(tgt)
                arr = jnp.concatenate([la, ra])
                valid = None
                if lv.valid is not None or rv.valid is not None:
                    lvv = lv.valid if lv.valid is not None else jnp.ones(
                        lctx.n, bool)
                    rvv = rv.valid if rv.valid is not None else jnp.ones(
                        rctx.n, bool)
                    valid = jnp.concatenate([lvv, rvv])
                out.cols[(lb, lname)] = DVal(
                    arr, valid, sdict,
                    None if (lv.lo is None or rv.lo is None)
                    else min(lv.lo, rv.lo),
                    None if (lv.hi is None or rv.hi is None)
                    else max(lv.hi, rv.hi))
            if node.kind == "union":
                # distinct over the concatenated context, inline
                return self._distinct_rows(
                    out, [(lb, name) for name, _ in node.left.output])
            return out
        # INTERSECT / EXCEPT: whole-row membership against the right
        # side. Rows pack into one int64 (pair-aligned per column, plus a
        # validity bit so NULLs compare equal, the SQL set-op rule); the
        # probe is a sorted-membership check. A Distinct above (planner-
        # inserted) provides the set semantics.
        lvals = [lctx.cols[(lb, name)] for name, _ in node.left.output]
        rvals = [rctx.cols[(rb, name)] for name, _ in node.right.output]
        lkey = jnp.zeros(lctx.n, dtype=jnp.int64)
        rkey = jnp.zeros(rctx.n, dtype=jnp.int64)
        total_w = 0
        for lv, rv in zip(lvals, rvals):
            la, ra, lo, hi = self._align_pair(lv, rv)
            w = max((hi - lo).bit_length(), 1)
            ln = jnp.clip(la.astype(jnp.int64) - lo, 0, hi - lo)
            rn = jnp.clip(ra.astype(jnp.int64) - lo, 0, hi - lo)
            if lv.valid is not None or rv.valid is not None:
                lval = (lv.valid if lv.valid is not None
                        else jnp.ones(lctx.n, bool))
                rval = (rv.valid if rv.valid is not None
                        else jnp.ones(rctx.n, bool))
                ln = jnp.where(lval, ln, 0) | (
                    lval.astype(jnp.int64) << w)
                rn = jnp.where(rval, rn, 0) | (
                    rval.astype(jnp.int64) << w)
                w += 1
            total_w += w
            if total_w > 62:
                raise DeviceExecError(
                    f"set-op row too wide to pack ({total_w} bits)")
            lkey = (lkey << w) | ln
            rkey = (rkey << w) | rn
        sent = I64_MAX
        if total_w <= 30:
            # packed whole-row keys fit int32: the membership sort and
            # search run on TPU's native i32 path instead of emulated
            # 64-bit (NDS112)
            lkey = lkey.astype(jnp.int32)
            rkey = rkey.astype(jnp.int32)
            sent = 2**31 - 1
        # ndslint: waive[NDS112] -- keys narrow to int32 above whenever the pack fits 30 bits; wider whole-row packs genuinely need int64
        ks = jnp.sort(jnp.where(rctx.row, rkey, sent))
        pos = jnp.clip(_ss(ks, lkey), 0, rctx.n - 1)
        hit = self._take(ks, pos) == lkey
        keep = hit if node.kind == "intersect" else ~hit
        out = DCtx(lctx.n, lctx.row & keep)
        out.cols = lctx.cols
        return out

    def _union_dict(self, lv: DVal, rv: DVal):
        if lv.sdict is None or rv.sdict is None:
            raise DeviceExecError("union of string and non-string column")
        if lv.sdict is rv.sdict or (
                len(lv.sdict) == len(rv.sdict)
                and np.array_equal(lv.sdict, rv.sdict)):
            return lv.arr, rv.arr, lv.sdict
        union, lmap, rmap = self._dict_union(lv.sdict, rv.sdict)
        return (self._take(lmap, lv.arr), self._take(rmap, rv.arr),
                union.astype(object))

    # ---------------------------------------------------------- expressions

    def eval(self, e: ir.IR, ctx: DCtx) -> DVal:
        if isinstance(e, ir.ColRef):
            return ctx.cols[(e.binding, e.name)]
        if isinstance(e, ir.Lit):
            return self._eval_lit(e, ctx)
        if isinstance(e, ir.ScalarRef):
            v, ok, sdict, _dt = self.scalars[e.plan_id]
            return DVal(jnp.broadcast_to(v, (ctx.n,)),
                        jnp.broadcast_to(ok, (ctx.n,)), sdict)
        if isinstance(e, ir.ParamRef):
            return self._eval_param(e, ctx)
        if isinstance(e, ir.DictParamIR):
            return self._eval_dict_param(e, ctx)
        if isinstance(e, ir.InListParamIR):
            return self._eval_inlist_param(e, ctx)
        if isinstance(e, ir.Arith):
            return self._eval_arith(e, ctx)
        if isinstance(e, ir.Cmp):
            return self._eval_cmp(e, ctx)
        if isinstance(e, ir.BoolOp):
            vals = [self.eval(a, ctx) for a in e.args]
            out = vals[0].arr.astype(bool)
            valid = vals[0].valid
            for dv in vals[1:]:
                if e.op == "and":
                    out = out & dv.arr.astype(bool)
                else:
                    out = out | dv.arr.astype(bool)
                valid = _and_valid(valid, dv.valid)
            return DVal(out, valid)
        if isinstance(e, ir.Not):
            dv = self.eval(e.operand, ctx)
            return DVal(~dv.arr.astype(bool), dv.valid)
        if isinstance(e, ir.Neg):
            dv = self.eval(e.operand, ctx)
            lo = None if dv.hi is None else -dv.hi
            hi = None if dv.lo is None else -dv.lo
            return DVal(-dv.arr, dv.valid, None, lo, hi)
        if isinstance(e, ir.CaseIR):
            return self._eval_case(e, ctx)
        if isinstance(e, ir.LikeIR):
            dv = self.eval(e.operand, ctx)
            if dv.sdict is None:
                raise DeviceExecError("LIKE over non-string")
            table = like_mask(dv.sdict, e.pattern)
            if e.negated:
                table = ~table
            return DVal(self._take(jnp.asarray(table), dv.arr), dv.valid)
        if isinstance(e, ir.InListIR):
            return self._eval_inlist(e, ctx)
        if isinstance(e, ir.IsNullIR):
            dv = self.eval(e.operand, ctx)
            if dv.valid is None:
                isnull = jnp.zeros(ctx.n, dtype=bool)
            else:
                isnull = ~dv.valid
            return DVal(~isnull if e.negated else isnull, None)
        if isinstance(e, ir.ExtractIR):
            dv = self.eval(e.operand, ctx)
            y, m, d = _epoch_days_to_civil(dv.arr)
            if e.part == "year":
                return DVal(y, dv.valid, None, 1970, 2199)
            if e.part == "month":
                return DVal(m, dv.valid, None, 1, 12)
            if e.part == "day":
                return DVal(d, dv.valid, None, 1, 31)
            raise DeviceExecError(f"extract {e.part}")
        if isinstance(e, ir.StrMapIR):
            return self._eval_strmap(e, ctx)
        if isinstance(e, ir.ConcatIR):
            return self._eval_concat(e, ctx)
        if isinstance(e, ir.SubstrIR):
            return self._eval_substr(e, ctx)
        if isinstance(e, ir.CastIR):
            return self._eval_cast(e, ctx)
        raise DeviceExecError(f"cannot eval {e!r}")

    def _eval_param(self, e: ir.ParamRef, ctx: DCtx) -> DVal:
        """A hoisted scalar literal: broadcast of the runtime input. No
        value bounds (unlike an inlined Lit) — consumers needing bounds
        fall back to their general paths, identically for every
        variant."""
        v = self.params[f"p{e.index}"]
        if isinstance(e.dtype, FloatType):
            v = v.astype(self.fdt)
        return DVal(jnp.broadcast_to(v, (ctx.n,)), None)

    def _eval_dict_param(self, e: ir.DictParamIR, ctx: DCtx) -> DVal:
        """A hoisted string predicate: boolean membership table over
        the operand's dictionary, bound per request on the host
        (sql/params.bind_params replicates the dictionary transform
        chain, so table length must match the traced dictionary)."""
        dv = self.eval(e.operand, ctx)
        if dv.sdict is None:
            raise DeviceExecError("dict-param predicate over "
                                  "non-string operand")
        tab = self.params[f"d{e.index}"]
        if tab.shape[0] != len(dv.sdict):
            raise DeviceExecError(
                f"dict-param table length {tab.shape[0]} != traced "
                f"dictionary length {len(dv.sdict)} for "
                f"{e.table}.{e.column}")
        if e.negated:
            tab = ~tab
        return DVal(self._take(tab, dv.arr), dv.valid)

    def _eval_inlist_param(self, e: ir.InListParamIR, ctx: DCtx) -> DVal:
        """A hoisted numeric IN-list: fixed-width vector input, any-of
        equality (the same compare chain the inlined path unrolls)."""
        dv = self.eval(e.operand, ctx)
        vals = self.params[f"v{e.index}"]
        m = jnp.zeros(ctx.n, dtype=bool)
        for i in range(e.width):
            m = m | (dv.arr == vals[i])
        return DVal(~m if e.negated else m, dv.valid)

    def _eval_lit(self, e: ir.Lit, ctx: DCtx) -> DVal:
        if isinstance(e.dtype, StringType):
            # string literals only appear inside comparisons, which bind
            # them against a dictionary; standalone use keeps the raw value
            if e.value is None:  # NULL string (rolled-up group key)
                return DVal(jnp.zeros(ctx.n, jnp.int32),
                            jnp.zeros(ctx.n, dtype=bool),
                            np.array([""], dtype=object), 0, 0)
            return DVal(jnp.zeros(ctx.n, jnp.int32), None,
                        np.array([e.value], dtype=object), 0, 0)
        v = e.value
        if v is None:
            if isinstance(e.dtype, FloatType):
                return DVal(jnp.zeros(ctx.n, self.fdt),
                            jnp.zeros(ctx.n, dtype=bool))
            dt = jnp.int32 if isinstance(e.dtype, DateType) else jnp.int64
            return DVal(jnp.zeros(ctx.n, dt),
                        jnp.zeros(ctx.n, dtype=bool), None, 0, 0)
        if isinstance(e.dtype, FloatType):
            arr = jnp.full(ctx.n, float(v), dtype=self.fdt)
            return DVal(arr, None)
        iv = int(v)
        dtype = jnp.int64
        if isinstance(e.dtype, (IntType,)) and e.dtype.bits <= 32 \
                and -2**31 <= iv < 2**31:
            dtype = jnp.int32
        if isinstance(e.dtype, DateType):
            dtype = jnp.int32
        return DVal(jnp.full(ctx.n, iv, dtype=dtype), None, None, iv, iv)

    def _eval_arith(self, e: ir.Arith, ctx: DCtx) -> DVal:
        l = self.eval(e.left, ctx)
        r = self.eval(e.right, ctx)
        valid = _and_valid(l.valid, r.valid)
        lt, rt = e.left.dtype, e.right.dtype
        if isinstance(e.dtype, DateType):
            return DVal(l.arr + r.arr, valid)
        if e.op == "/":
            la = _to_float(l.arr, lt, self.fdt)
            ra = _to_float(r.arr, rt, self.fdt)
            return DVal(la / ra, valid)
        if isinstance(e.dtype, FloatType):
            return DVal(_apply(e.op, _to_float(l.arr, lt, self.fdt),
                               _to_float(r.arr, rt, self.fdt)), valid)
        if isinstance(e.dtype, DecimalType):
            if e.op == "*":
                return DVal(l.arr.astype(jnp.int64) * r.arr.astype(jnp.int64),
                            valid)
            s = e.dtype.scale
            la = _rescale(l.arr, _scale_of(lt), s)
            ra = _rescale(r.arr, _scale_of(rt), s)
            return DVal(_apply(e.op, la, ra), valid)
        out = _apply(e.op, l.arr, r.arr)
        lo = hi = None
        if (l.lo is not None and r.lo is not None
                and l.hi is not None and r.hi is not None):
            if e.op == "+":
                lo, hi = l.lo + r.lo, l.hi + r.hi
            elif e.op == "-":
                lo, hi = l.lo - r.hi, l.hi - r.lo
            elif e.op == "*":
                cands = [l.lo * r.lo, l.lo * r.hi, l.hi * r.lo, l.hi * r.hi]
                lo, hi = min(cands), max(cands)
        return DVal(out, valid, None, lo, hi)

    def _eval_cmp(self, e: ir.Cmp, ctx: DCtx) -> DVal:
        lt, rt = e.left.dtype, e.right.dtype
        if isinstance(lt, StringType) or isinstance(rt, StringType):
            return self._string_cmp(e, ctx)
        l = self.eval(e.left, ctx)
        r = self.eval(e.right, ctx)
        valid = _and_valid(l.valid, r.valid)
        la, ra = l.arr, r.arr
        if isinstance(lt, DecimalType) or isinstance(rt, DecimalType):
            if isinstance(lt, FloatType) or isinstance(rt, FloatType):
                la, ra = (_to_float(la, lt, self.fdt),
                          _to_float(ra, rt, self.fdt))
            else:
                s = max(_scale_of(lt), _scale_of(rt))
                la = _rescale(la.astype(jnp.int64), _scale_of(lt), s)
                ra = _rescale(ra.astype(jnp.int64), _scale_of(rt), s)
        elif isinstance(lt, FloatType) or isinstance(rt, FloatType):
            la, ra = (_to_float(la, lt, self.fdt),
                          _to_float(ra, rt, self.fdt))
        return DVal(_cmp(e.op, la, ra), valid)

    def _string_cmp(self, e: ir.Cmp, ctx: DCtx) -> DVal:
        lit, col_ir, flipped = None, None, False
        if isinstance(e.right, ir.Lit):
            lit, col_ir = e.right.value, e.left
        elif isinstance(e.left, ir.Lit):
            lit, col_ir, flipped = e.left.value, e.right, True
        if lit is not None:
            dv = self.eval(col_ir, ctx)
            if dv.sdict is None:
                raise DeviceExecError("string compare on non-dict column")
            vals = dv.sdict.astype(str)
            op = e.op
            if flipped:
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
            table = _np_cmp(op, vals, str(lit))
            return DVal(self._take(jnp.asarray(table), dv.arr), dv.valid)
        l = self.eval(e.left, ctx)
        r = self.eval(e.right, ctx)
        valid = _and_valid(l.valid, r.valid)
        la, ra, _sd = self._union_dict(l, r)
        return DVal(_cmp(e.op, la, ra), valid)

    def _eval_case(self, e: ir.CaseIR, ctx: DCtx) -> DVal:
        if isinstance(e.dtype, StringType):
            return self._eval_case_string(e, ctx)
        conds, vals, branch_valids = [], [], []
        for c, v in e.whens:
            cdv = self.eval(c, ctx)
            cm = cdv.arr.astype(bool)
            if cdv.valid is not None:
                cm = cm & cdv.valid
            vdv = self.eval(v, ctx)
            conds.append(cm)
            vals.append(self._coerce(vdv, v.dtype, e.dtype))
            branch_valids.append(vdv.valid)
        if e.else_ is not None:
            edv = self.eval(e.else_, ctx)
            default = self._coerce(edv, e.else_.dtype, e.dtype)
            valid = edv.valid  # else-branch validity; refined per row below
        else:
            if isinstance(e.dtype, FloatType):
                default = jnp.zeros(ctx.n, self.fdt)
            else:
                default = jnp.zeros(ctx.n, jnp.int64)
            valid = jnp.zeros(ctx.n, dtype=bool)  # no branch -> NULL
        out = default
        # the result's validity is the SELECTED branch's validity
        need_valid = valid is not None or any(
            bv is not None for bv in branch_valids)
        if need_valid and valid is None:
            valid = jnp.ones(ctx.n, dtype=bool)
        for c, v, bv in zip(reversed(conds), reversed(vals),
                            reversed(branch_valids)):
            out = jnp.where(c, v, out)
            if need_valid:
                bvv = bv if bv is not None else jnp.ones(ctx.n, bool)
                valid = jnp.where(c, bvv, valid)
        return DVal(out, valid)

    def _eval_case_string(self, e: ir.CaseIR, ctx: DCtx) -> DVal:
        """String-valued CASE: union the branch dictionaries on the host,
        remap every branch's codes, then where-chain over int codes —
        strings still never reach the device."""
        branches = []       # (cond_mask, DVal)
        for c, v in e.whens:
            cdv = self.eval(c, ctx)
            cm = cdv.arr.astype(bool)
            if cdv.valid is not None:
                cm = cm & cdv.valid
            branches.append((cm, self.eval(v, ctx)))
        else_dv = (self.eval(e.else_, ctx) if e.else_ is not None
                   else DVal(jnp.zeros(ctx.n, jnp.int32),
                             jnp.zeros(ctx.n, dtype=bool),
                             np.array([""], dtype=object)))
        dvals = [dv for _, dv in branches] + [else_dv]
        for dv in dvals:
            if dv.sdict is None:
                raise DeviceExecError(
                    "string CASE branch without dictionary")
        union = np.array(sorted(set().union(
            *[set(dv.sdict.astype(str)) for dv in dvals])), dtype=object)
        remapped = []
        for dv in dvals:
            table = jnp.asarray(np.searchsorted(
                union.astype(str), dv.sdict.astype(str)).astype(np.int32))
            arr = self._take(table, dv.arr)
            if arr.ndim == 0:
                arr = jnp.broadcast_to(arr, (ctx.n,))
            remapped.append(arr)
        out = remapped[-1]
        valid = (else_dv.valid if else_dv.valid is not None
                 else jnp.ones(ctx.n, dtype=bool))
        for (cm, dv), arr in zip(reversed(branches),
                                 reversed(remapped[:-1])):
            out = jnp.where(cm, arr, out)
            bv = (dv.valid if dv.valid is not None
                  else jnp.ones(ctx.n, dtype=bool))
            valid = jnp.where(cm, bv, valid)
        return DVal(out, valid, union, 0, max(len(union) - 1, 0))

    def _coerce(self, dv: DVal, src: DType, dst: DType):
        if repr(src) == repr(dst):
            return dv.arr
        if isinstance(dst, FloatType):
            return _to_float(dv.arr, src, self.fdt)
        if isinstance(dst, DecimalType):
            return _rescale(dv.arr.astype(jnp.int64), _scale_of(src),
                            dst.scale)
        return dv.arr

    def _eval_inlist(self, e: ir.InListIR, ctx: DCtx) -> DVal:
        dv = self.eval(e.operand, ctx)
        if dv.sdict is not None:
            table = np.isin(dv.sdict.astype(str),
                            np.array([str(v) for v in e.values]))
            if e.negated:
                table = ~table
            return DVal(self._take(jnp.asarray(table), dv.arr), dv.valid)
        vals = e.values
        if isinstance(e.operand.dtype, DecimalType):
            s = e.operand.dtype.scale
            vals = [int(round(float(x) * 10 ** s)) for x in vals]
        m = jnp.zeros(ctx.n, dtype=bool)
        for v in vals:
            m = m | (dv.arr == v)
        return DVal(~m if e.negated else m, dv.valid)

    def _rewrite_dict(self, dv: DVal, fn) -> DVal:
        """Apply a per-entry string transform to a dictionary-encoded
        value: codes stay on device; the host-side dictionary is
        rewritten, DEDUPED (entries may collide, e.g. upper('abc') ==
        upper('ABC') — grouping hashes codes, so equal strings must
        share a code), re-sorted, and codes remapped."""
        if dv.sdict is None:
            raise DeviceExecError("string transform over non-string")
        newvals = np.array([fn(s) for s in dv.sdict.astype(str)],
                           dtype=object)
        uniq, inverse = np.unique(newvals.astype(str),
                                  return_inverse=True)
        table = jnp.asarray(inverse.astype(np.int32))
        return DVal(self._take(table, dv.arr), dv.valid,
                    uniq.astype(object), 0, max(len(uniq) - 1, 0))

    def _eval_strmap(self, e: ir.StrMapIR, ctx: DCtx) -> DVal:
        dv = self.eval(e.operand, ctx)
        f = str.upper if e.op == "upper" else str.lower
        return self._rewrite_dict(dv, f)

    def _eval_concat(self, e: ir.ConcatIR, ctx: DCtx) -> DVal:
        """Literal ⊕ column concat as a dictionary rewrite (q5's
        'store' || s_store_id ids)."""
        dv = self.eval(e.operand, ctx)
        return self._rewrite_dict(
            dv, lambda s: e.prefix + s + e.suffix)

    def _eval_substr(self, e: ir.SubstrIR, ctx: DCtx) -> DVal:
        dv = self.eval(e.operand, ctx)
        if dv.sdict is None:
            raise DeviceExecError("substr over non-string")
        lo = e.start - 1
        hi = None if e.length is None else lo + e.length
        subs = np.array([s[lo:hi] for s in dv.sdict.astype(str)],
                        dtype=object)
        newdict, remap = np.unique(subs.astype(str), return_inverse=True)
        table = jnp.asarray(remap.astype(np.int32))
        return DVal(self._take(table, dv.arr), dv.valid,
                    newdict.astype(object), 0, max(len(newdict) - 1, 0))

    def _eval_cast(self, e: ir.CastIR, ctx: DCtx) -> DVal:
        dv = self.eval(e.operand, ctx)
        src = e.operand.dtype
        if isinstance(e.dtype, FloatType):
            return DVal(_to_float(dv.arr, src, self.fdt), dv.valid)
        if isinstance(e.dtype, IntType):
            if isinstance(src, DecimalType):
                return DVal((dv.arr // 10 ** src.scale).astype(jnp.int64),
                            dv.valid)
            return DVal(dv.arr.astype(jnp.int64), dv.valid, None,
                        dv.lo, dv.hi)
        if isinstance(e.dtype, DecimalType):
            s = e.dtype.scale
            if isinstance(src, DecimalType):
                return DVal(_rescale(dv.arr, src.scale, s), dv.valid)
            if isinstance(src, IntType):
                return DVal(dv.arr.astype(jnp.int64) * 10 ** s, dv.valid)
            return DVal(jnp.round(dv.arr * 10 ** s).astype(jnp.int64),
                        dv.valid)
        raise DeviceExecError(f"cast to {e.dtype}")


def _apply(op, l, r):
    if op == "+":
        return l + r
    if op == "-":
        return l - r
    if op == "*":
        return l * r
    if op == "%":
        return l % r
    raise DeviceExecError(op)


def _cmp(op, l, r):
    if op == "=":
        return l == r
    if op == "<>":
        return l != r
    if op == "<":
        return l < r
    if op == "<=":
        return l <= r
    if op == ">":
        return l > r
    if op == ">=":
        return l >= r
    raise DeviceExecError(op)


def _np_cmp(op, vals, lit):
    if op == "=":
        return vals == lit
    if op == "<>":
        return vals != lit
    if op == "<":
        return vals < lit
    if op == "<=":
        return vals <= lit
    if op == ">":
        return vals > lit
    if op == ">=":
        return vals >= lit
    raise DeviceExecError(op)


PRECISIONS = {"f64": None, "f32": "float32", "bf16": "bfloat16"}


def make_device_factory(precision: str = "f64"):
    """Session executor factory that keeps ONE DeviceExecutor per table
    registry, preserving its device buffers and compile cache across
    queries (the load-once, query-many lifecycle of a power run,
    `nds/nds_power.py:184-322`).

    precision selects the on-device float compute dtype
    (`engine.precision`): f64 matches the CPU oracle exactly (emulated
    on TPU); f32/bf16 run native on the VPU at reduced precision — the
    floats-mode analog of the reference's variableFloatAgg tradeoff."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown engine.precision {precision!r}")
    fdt = PRECISIONS[precision]
    holder: dict = {}

    def factory(tables):
        ex = holder.get("ex")
        if ex is None or ex.tables is not tables:
            ex = DeviceExecutor(
                tables, None if fdt is None else getattr(jnp, fdt))
            holder["ex"] = ex
        return ex

    # DML invalidation hooks (Session.invalidate): a wholesale
    # invalidate drops the executor; the SCOPED variant keeps it —
    # only the mutated tables' buffers/bounds/scan-views go, and every
    # other table's warm buffers and the whole compile cache survive
    factory.invalidate = holder.clear

    def invalidate_tables(names):
        ex = holder.get("ex")
        if ex is not None:
            ex.invalidate_tables(names)

    factory.invalidate_tables = invalidate_tables
    return factory
