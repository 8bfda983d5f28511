"""Compiler-truth cost ledger: XLA cost/memory analysis per query.

"Query Processing on Tensor Computation Runtimes" (PAPERS.md) makes
the case that accelerator benchmark numbers are only interpretable
next to operator-level cost accounting; this engine's roofline column
rode a hand-rolled ``ops_est`` instead.  This module is the
compiler-truth replacement: every program the engine compiles or
loads (device, sharded, chunkscan, compact, staged subs — all funnel
through ``cache/aot.py``) has its ``compiled.cost_analysis()`` (flops,
bytes accessed, transcendentals) and ``memory_analysis()``
(temp/argument/output bytes) extracted ONCE and attached to the
executable, and every DISPATCH records those numbers into a per-query
ledger the power loop reads out into the BenchReport ``cost`` block.

Recording happens at dispatch, not at compile: warmup compiles run
before the per-query ledger reset, so a compile-time-only hook would
leave every warm in-process query with an empty block.  Warm
AOT-cache hits carry their cost dict inside the cache payload and
manifest (``cache/aot.py`` persists it), so a ``compile_ms=0`` run
still bills compiler-truth numbers — extraction on a deserialized
executable is a fallback, not the design.

Per-dispatch semantics: flops/bytes/transcendentals SUM over
dispatches (a 40-chunk scan costs 40x its program), memory sizes MAX
(concurrency aside, temp arenas are per-dispatch peaks, not
cumulative).  Overflow-retry re-dispatches bill again, matching the
wall-clock they consume.

The same registry names each executable: ``program_id()`` gives it a
small number the first time it is dispatched (the ``device.launch``
span's ``program``), and ``sites(program)`` reads its compiled text,
only when a reader asks, into ``{instruction: (op_name, opcode)}``: the
scope path (``op.<kind>`` / ``exchange`` / ``replicate`` / ``gather``,
README "Observability") of every instruction a profile's op events are
named by.

``cross_check()`` reconciles the block against PR 8's hand-rolled
``ops_est``: a flops/ops ratio outside a generous sanity corridor
flags ``ops_est_drift`` so the legacy estimator can't silently rot.

``platform_peaks()`` is the per-platform peak table behind analyze's
predicted-time model: env override, then measured numbers from
``ndsperf --calibrate`` (``configs/platform_peaks.json``), then the
datasheet builtins; a TPU kind in neither is an error, not a blank.
"""

from __future__ import annotations

import itertools
import json
import os
import re
import weakref

from nds_tpu.analysis import locksan
from nds_tpu.obs import trace

_LOCK = locksan.lock("obs.costs._LOCK")

# normalized cost-dict keys and how the ledger folds them per dispatch
_SUM_KEYS = ("flops", "bytes_accessed", "transcendentals")
_MAX_KEYS = ("temp_bytes", "argument_bytes", "output_bytes")

# XLA cost_analysis() vocabulary -> our normalized keys (the XLA keys
# contain spaces; some backends report sentinel negatives — dropped)
_COST_KEYS = {"flops": "flops", "bytes accessed": "bytes_accessed",
              "transcendentals": "transcendentals"}

# memory_analysis() attributes -> normalized keys
_MEM_ATTRS = {"temp_size_in_bytes": "temp_bytes",
              "argument_size_in_bytes": "argument_bytes",
              "output_size_in_bytes": "output_bytes"}

# datasheet peak dense FLOP/s (f32-ish sustained, not marketing bf16
# numbers) keyed by device_kind prefix; the bandwidth twin lives in
# engine/device_exec._PEAK_MEM_GBPS. Calibrated measurements from
# ``ndsperf --calibrate`` override both (see platform_peaks()).
_PEAK_FLOPS = {"tpu v4": 275e12, "tpu v5 lite": 197e12,
               "tpu v5e": 197e12, "tpu v5": 459e12,
               "tpu v6 lite": 918e12, "cpu": 5e10}
_PEAK_MEM_GBPS = {"tpu v4": 1228.0, "tpu v5 lite": 819.0,
                  "tpu v5e": 819.0, "tpu v5": 2765.0,
                  "tpu v6 lite": 1640.0, "cpu": 25.0}

PEAKS_ENV = "NDS_TPU_PLATFORM_PEAKS"
PEAKS_BASENAME = os.path.join("configs", "platform_peaks.json")

# sanity corridor for compiler-flops vs hand-rolled ops_est: the
# estimator counts logical column ops, the compiler counts fused HLO
# flops — they disagree by fusion and padding factors, not by orders
# of magnitude beyond these
DRIFT_CORRIDOR = (0.1, 10000.0)


# ------------------------------------------------------------ extraction

def compute_cost(compiled) -> "dict | None":
    """Normalized cost dict straight off a jax.stages.Compiled, or
    None when the backend exposes neither analysis. Never raises —
    cost accounting must not fail a query."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: list per device
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            for xla_key, key in _COST_KEYS.items():
                v = ca.get(xla_key)
                if isinstance(v, (int, float)) and v > 0:
                    out[key] = float(v)
    except Exception:  # noqa: BLE001 - analysis is best-effort
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, key in _MEM_ATTRS.items():
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)) and v > 0:
                out[key] = int(v)
    except Exception:  # noqa: BLE001 - analysis is best-effort
        pass
    return out or None


def attach(compiled, cost: "dict | None") -> None:
    """Pin a (possibly store-served) cost dict onto the executable so
    dispatch-time extraction is a dict read. Best-effort: some stages
    objects reject attributes — extract() just recomputes then."""
    if not isinstance(cost, dict):
        return
    try:
        setattr(compiled, "_nds_cost", dict(cost))
    except Exception:  # noqa: BLE001 - frozen object: memo is optional
        pass


def extract(compiled) -> "dict | None":
    """Memoized cost dict for an executable: the attached copy when a
    compile/load site already paid for it, else computed and attached
    here."""
    cost = getattr(compiled, "_nds_cost", None)
    if isinstance(cost, dict):
        return cost
    cost = compute_cost(compiled)
    if cost is not None:
        attach(compiled, cost)
    return cost


def _device_kind() -> "str | None":
    """Lowercased device_kind of the process's jax backend, or None in
    a process that never imported jax (harness-only paths; importing it
    here would race the main thread's first import — memwatch's
    thread-safety rule)."""
    import sys
    mod = sys.modules.get("jax")
    if mod is None or getattr(getattr(mod, "__spec__", None),
                              "_initializing", False):
        return None
    return str(mod.devices()[0].device_kind).lower()


# ---------------------------------------------------------------- ledger

# obs.costs.enabled (default on): the ledger's only knob. Dispatch
# hooks check it so a disabled run pays one predicate per dispatch and
# emits no cost block at all (summaries keep their pre-cost shape)
_ENABLED = True


def set_enabled(flag: bool) -> None:
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def configure_from(config=None) -> None:
    """Apply ``obs.costs.enabled`` from an EngineConfig (the power
    loop's entry point, next to telemetry.start_from_config)."""
    if config is None:
        set_enabled(True)
        return
    try:
        set_enabled(config.get_bool("obs.costs.enabled", True))
    except Exception:  # noqa: BLE001 - config typo: ledger stays on
        set_enabled(True)


class CostLedger:
    """Per-query accumulator the executors feed at every program
    dispatch; read out once per query by the power loop."""

    def __init__(self) -> None:
        self._sums: dict = {}
        self._maxes: dict = {}
        self._programs: dict = {}

    def reset_query(self) -> None:
        with _LOCK:
            self._sums = {}
            self._maxes = {}
            self._programs = {}

    def record(self, kind: str, cost: "dict | None") -> None:
        """Bill one dispatch of one program. ``cost=None`` (backend
        without analyses) still counts the program so the block's
        ``programs`` census stays truthful."""
        if not _ENABLED:
            return
        with _LOCK:
            self._programs[kind] = self._programs.get(kind, 0) + 1
            if not cost:
                return
            for k in _SUM_KEYS:
                v = cost.get(k)
                if v:
                    self._sums[k] = self._sums.get(k, 0.0) + float(v)
            for k in _MAX_KEYS:
                v = cost.get(k)
                if v and v > self._maxes.get(k, 0):
                    self._maxes[k] = int(v)

    def query_block(self) -> "dict | None":
        """BenchReport ``cost`` block, or None when the query
        dispatched no tracked programs (harness-only paths, the CPU
        oracle)."""
        with _LOCK:
            if not self._programs:
                return None
            block: dict = {k: float(self._sums.get(k, 0.0))
                           for k in _SUM_KEYS}
            for k in _MAX_KEYS:
                if k in self._maxes:
                    block[k] = self._maxes[k]
            block["programs"] = dict(self._programs)
        kind = _device_kind()
        if kind:
            block["platform"] = kind
        return block


LEDGER = CostLedger()


def reset_query() -> None:
    LEDGER.reset_query()


def record(kind: str, cost: "dict | None") -> None:
    LEDGER.record(kind, cost)


def record_program(kind: str, compiled) -> "dict | None":
    """The executor dispatch hook: extract (memoized) + bill.  Returns
    the cost dict it billed (the ``device.launch`` span's attributes),
    None with the ledger off or no analysis on this backend."""
    if not _ENABLED:
        return None
    cost = extract(compiled)
    LEDGER.record(kind, cost)
    return cost


def query_block() -> "dict | None":
    return LEDGER.query_block()


# -------------------------------------------------------------- programs

# program id -> executable, held weakly: the registry keeps alive
# nothing its executor dropped.  While a profile is live a dispatched
# executable is also pinned, so that a reader of that profile can still
# name its instructions after the session that ran it has gone
_PROGRAMS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_PROFILED: dict = {}
_NEXT_PROGRAM = itertools.count(1)

# one HLO instruction of ``as_text()``: its name, the opcode after its
# shape (a layout's ``T(..)`` / ``S(..)`` follow no blank), the metadata
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def program_id(compiled) -> int:
    """The executable's number, given at its first dispatch."""
    pid = getattr(compiled, "_nds_program", None)
    if pid is None:
        with _LOCK:
            pid = getattr(compiled, "_nds_program", None)
            if pid is None:
                pid = next(_NEXT_PROGRAM)
                setattr(compiled, "_nds_program", pid)
                _PROGRAMS[pid] = compiled
    if pid not in _PROFILED and _profile_live():
        _PROFILED[pid] = compiled
    return pid


def _profile_live() -> bool:
    ann = trace._annotation_cls()
    return ann is not None and ann.is_enabled()


def parse_sites(text: str) -> dict:
    """``{instruction name: (op_name, opcode)}`` of an HLO module's
    text.  An instruction the compiler made without metadata (a copy,
    the TPU's tree of a cumsum's ``reduce-window``) takes the operator
    scopes of its first operand that has any: its ``op_name`` is that
    operand's path up to its innermost ``op.*`` scope, then its own
    opcode.  '' where no operand has one."""
    out: dict = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        scope = _OP_NAME.search(line)
        op_name = scope.group(1) if scope else ""
        if "/op." not in op_name:
            for operand in _OPERAND.findall(line, m.end(), _close(line,
                                                                m.end())):
                path = out.get(operand, ("",))[0]
                cut = path.rfind("/op.")
                if cut >= 0:
                    end = path.find("/", cut + 1)
                    op_name = (path if end < 0 else path[:end]) + "/" + \
                        m.group(2)
                    break
        out[m.group(1)] = (op_name, m.group(2))
    return out


def _close(line: str, start: int) -> int:
    """Where the operand list that opens before ``start`` closes."""
    depth = 1
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if not depth:
                return i
    return len(line)


def sites(program: int) -> "dict | None":
    """What ``parse_sites`` reads from the program's compiled text,
    parsed the first time it is asked for; None where the executable
    has gone or gives no text.  A program served from a compile cache
    carries the metadata of the tree that compiled it."""
    compiled = _PROGRAMS.get(program) or _PROFILED.get(program)
    if compiled is None:
        return None
    found = getattr(compiled, "_nds_sites", None)
    if found is None:
        try:
            found = parse_sites(compiled.as_text())
        except Exception:  # noqa: BLE001 - no text: nothing to name
            return None
        setattr(compiled, "_nds_sites", found)
    return found


# ----------------------------------------------------------- cross-check

def cross_check(block: "dict | None",
                ops_est: "float | None") -> "dict | None":
    """Reconcile the compiler-truth block against the hand-rolled
    ``ops_est`` roofline input (PR 8). Adds ``ops_est`` /
    ``flops_per_op`` and flags ``ops_est_drift`` when the ratio falls
    outside DRIFT_CORRIDOR — either estimator rotting shows up in the
    summary instead of silently skewing the roofline column."""
    if block is None:
        return None
    out = dict(block)
    try:
        ops = float(ops_est) if ops_est else 0.0
    except (TypeError, ValueError):
        ops = 0.0
    flops = out.get("flops") or 0.0
    if ops > 0 and flops > 0:
        ratio = flops / ops
        out["ops_est"] = ops
        out["flops_per_op"] = ratio
        lo, hi = DRIFT_CORRIDOR
        if not lo <= ratio <= hi:
            out["ops_est_drift"] = True
    return out


# -------------------------------------------------------- platform peaks

_calibrated_cache: "tuple | None" = None  # (path, mtime, dict)


def peaks_path() -> str:
    """Where ``ndsperf --calibrate`` writes and this module reads the
    measured per-platform peaks (env NDS_TPU_PLATFORM_PEAKS
    overrides; default: configs/platform_peaks.json at the repo
    root)."""
    env = os.environ.get(PEAKS_ENV)
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, PEAKS_BASENAME)


def calibrated_peaks() -> dict:
    """The measured peaks file as ``{device_kind: {"flops": F,
    "mem_gbps": B}}``, mtime-cached; {} when absent/unreadable."""
    global _calibrated_cache
    path = peaks_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    with _LOCK:
        if (_calibrated_cache is not None
                and _calibrated_cache[0] == path
                and _calibrated_cache[1] == mtime):
            return _calibrated_cache[2]
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        data = {}
    data = {str(k).lower(): v for k, v in data.items()
            if isinstance(v, dict)}
    with _LOCK:
        _calibrated_cache = (path, mtime, data)
    return data


def _prefix_lookup(table: dict, kind: str):
    """Longest device-kind prefix match (the device_exec idiom):
    "tpu v5 lite" must beat "tpu v5" for a "TPU v5 lite" device."""
    kind = (kind or "").lower()
    for prefix, val in sorted(table.items(),
                              key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return val
    return None


def platform_peaks(kind: "str | None") -> "dict | None":
    """Peak ``{"flops": FLOP/s, "mem_gbps": GB/s}`` for a device kind:
    calibrated measurements (ndsperf --calibrate) win over the
    datasheet builtins, per key. None when a non-TPU platform is
    unknown to both; an unknown TPU kind raises — its roofline would
    otherwise silently read blank (or, worse, as some other row)."""
    if not kind:
        return None
    kind = kind.lower()
    measured = _prefix_lookup(calibrated_peaks(), kind) or {}
    flops = measured.get("flops")
    gbps = measured.get("mem_gbps")
    if not isinstance(flops, (int, float)) or flops <= 0:
        flops = _prefix_lookup(_PEAK_FLOPS, kind)
    if not isinstance(gbps, (int, float)) or gbps <= 0:
        gbps = _prefix_lookup(_PEAK_MEM_GBPS, kind)
    if not flops and not gbps:
        if kind.startswith("tpu"):
            raise ValueError(
                f"no peak row for TPU device_kind {kind!r} "
                f"(obs/costs._PEAK_FLOPS/_PEAK_MEM_GBPS or "
                f"{PEAKS_BASENAME}); add its published figures")
        return None
    out = {}
    if flops:
        out["flops"] = float(flops)
    if gbps:
        out["mem_gbps"] = float(gbps)
    return out


def calibrated_mem_gbps(kind: "str | None") -> "float | None":
    """Measured memory bandwidth for a device kind, or None — the
    hook device_exec._peak_mem_gbps() consults between its env
    override and the builtin table."""
    if not kind:
        return None
    measured = _prefix_lookup(calibrated_peaks(), kind.lower())
    if isinstance(measured, dict):
        v = measured.get("mem_gbps")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def predicted_ms(block: "dict | None") -> "float | None":
    """Roofline-model predicted execute time for a query's cost block:
    max(flops/peak_flops, bytes/peak_bw), in ms. None when the block
    or its platform's peaks are missing — callers render a blank
    column, never a guess."""
    if not isinstance(block, dict):
        return None
    peaks = platform_peaks(block.get("platform"))
    if not peaks:
        return None
    flops = block.get("flops") or 0.0
    nbytes = block.get("bytes_accessed") or 0.0
    t_flops = (flops / peaks["flops"]) if peaks.get("flops") else 0.0
    t_bytes = ((nbytes / (peaks["mem_gbps"] * 1e9))
               if peaks.get("mem_gbps") else 0.0)
    t = max(t_flops, t_bytes)
    return t * 1000.0 if t > 0 else None
