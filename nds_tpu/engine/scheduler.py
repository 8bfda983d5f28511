"""Unified execution pipeline: placement as a scheduling decision.

ROADMAP item 5. The four execution strategies — single-device
(engine/device_exec.py), sharded mesh (parallel/dist_exec.py),
out-of-core chunked (engine/chunked_exec.py), and the host/CPU oracle
(engine/cpu_exec.py) — used to be four separate Session executor
factories, each carrying its own copy of the retry/heartbeat/memwatch
wiring, and recovery was a one-shot stream-wide ``engine.fallback=cpu``
demotion that multi-process SPMD had to disable outright (rank-local
demotion deadlocks collectives). This module replaces all of that with
ONE pipeline that treats the strategies as *placements*:

- **Cost model** — per query, an initial placement is chosen from the
  plan verifier's size estimates (analysis/plan_verify.estimate_plan)
  plus this process's per-query device-memory HWM history
  (obs/memwatch): plans whose working set exceeds the device budget
  start out-of-core, everything else starts on the fastest placement
  the backend offers. Pure Python — tools/ndsverify.py assigns
  placements for all 125 statements with no accelerator.

- **Degradation ladder** — a classified transient failure reschedules
  THAT QUERY one rung down instead of demoting the stream:
  device OOM -> chunked (chunk_rows halved) -> cpu; sharded exchange
  overflow -> re-plan with grown slack -> chunked -> cpu. Deterministic
  failures (planner bugs) never walk the ladder. Generic transients
  retry at the same rung under the config retry policy
  (``engine.retry.*`` / ``engine.query_deadline_s``) before stepping.

- **Promotion** — repeated ladder walks sticky-demote the *starting*
  rung (Execution-Templates-style caching of the control-plane
  decision); ``engine.placement.promote_after`` clean queries at the
  demoted rung promote the stream back to the cost model's choice.

- **Consensus** — on multi-process SPMD every placement switch is a
  collective decision: all ranks vote (an allgather over the existing
  multihost layer), the deepest demotion proposed by any rank wins, and
  either every rank switches or none does. A rank that cannot reach
  consensus keeps its placement and fails the query instead of
  deadlocking the others inside the next collective. Single-process
  runs use the degenerate one-voter channel, so the code path is
  identical everywhere.

This is also the single home of the engine-layer retry wiring: the
pipeline owns the per-query RetryPolicy, and the executors' internal
adaptive loops (exchange slack doubling, partial-agg overflow, chunk
halving) borrow their no-sleep policies from :func:`adaptive_policy`
here instead of instantiating their own (ndslint NDS110 keeps direct
executor construction from reappearing outside this module).

Config keys (README "Placement & degradation"):
``engine.placement.force`` pins the initial placement;
``engine.placement.ladder`` (default on) / ``engine.placement.floor``
(default cpu); ``engine.placement.demote_after`` /
``engine.placement.promote_after`` shape the sticky demotion;
``engine.placement.device_budget_bytes`` is the cost-model budget.
``engine.fallback=cpu`` survives as an alias forcing floor=cpu.
Metrics: ``query_reschedules_total``, ``placement_consensus_total``,
``placement_demotions_total``, ``placement_promotions_total``.
"""

from __future__ import annotations

import os

from nds_tpu.obs import memwatch
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs.trace import get_tracer
from nds_tpu.resilience import faults, watchdog
from nds_tpu.resilience.retry import (
    DETERMINISTIC, QueryDeadlineExceeded, RetryPolicy, RetryStats,
    classify, deadline_scope, is_oom,
)

# placement names, fastest-first per backend universe
DEVICE = "device"
SHARDED = "sharded"
CHUNKED = "chunked"
CPU = "cpu"

# sharded pseudo-rung: same placement, slack doubled + plan recompiled
SHARDED_REPLAN = "sharded+slack"

UNIVERSES = {
    "tpu": (DEVICE, CHUNKED, CPU),
    "distributed": (SHARDED, CHUNKED, CPU),
    "cpu": (CPU,),
}

# default device working-set budget for the cost model: conservative
# half of a 16G-HBM chip, leaving room for join expansion and results
DEFAULT_DEVICE_BUDGET = 8 << 30
# estimated bytes inflate by this factor before comparing to the budget
# (intermediates, padding, exchange buffers)
EXPANSION = 2.0


def working_set(est, expansion: float, resident: int = 0) -> int:
    """Bytes one dispatch of the estimated plan adds to the device:
    the scan columns it still has to place there, and its
    intermediates.

    The scans are ``held_bytes``: every column of a scanned table
    ONCE, however many Scan nodes read it, because that is what the
    executor keeps (one buffer a table and column), less ``resident``,
    the part of them already on the device, which the caller's live
    bytes hold already. The intermediates grow from ``read_bytes``, the
    columns the plan's expressions refer to, ``expansion - 1`` times
    over: a column no operator touches is uploaded, and inflates
    nothing. ``est.bytes`` (scans x scanned columns, a table counted
    once a Scan node) overstated both at 30M rows: q21 reads lineitem
    three times and its eleven columns of sixteen, 8.6 GB of estimate
    for 3.1 GB of buffers (PERF.md section 6, PR 31). An estimate that
    gives only ``bytes`` is taken at ``bytes x expansion`` as before."""
    scans = int(getattr(est, "bytes", 0) or 0)
    held = getattr(est, "held_bytes", None)
    read = getattr(est, "read_bytes", None)
    held = scans if held is None else held
    read = scans if read is None else read
    return (max(held - int(resident), 0)
            + int(read * (expansion - 1.0)))

# consecutive ladder-walked queries before the STARTING rung demotes
DEFAULT_DEMOTE_AFTER = 2
# consecutive clean queries at a demoted start before promotion back
DEFAULT_PROMOTE_AFTER = 3


def adaptive_policy(max_attempts: int) -> RetryPolicy:
    """No-sleep retry policy for executor-internal adaptive loops (the
    exchange slack-doubling / partial-agg overflow / chunk-halving
    shapes): each retry already pays a recompile or re-scan, so backoff
    would only add latency. Centralized here so the pipeline module is
    the one place engine-layer retry wiring is instantiated."""
    return RetryPolicy(max_attempts=max_attempts, base_delay_s=0.0)


def load_policy(policy: RetryPolicy) -> RetryPolicy:
    """The warehouse-load variant of a query policy: same
    attempts/backoff shape, NO per-query deadline (a 25-table load is
    not a query)."""
    return RetryPolicy(
        max_attempts=policy.max_attempts,
        base_delay_s=policy.base_delay_s,
        max_delay_s=policy.max_delay_s, jitter=policy.jitter,
        deadline_s=None, seed=policy.seed)


def is_exchange_overflow(exc: BaseException) -> bool:
    return "exchange overflow" in str(exc)


# ------------------------------------------------------------ consensus

class NullChannel:
    """Single-process world: one voter, trivially unanimous."""

    world = 1

    def gather(self, vote: int) -> "list[int] | None":
        return [vote]


class MultihostChannel:
    """Vote transport over the multi-controller SPMD runtime
    (parallel/multihost.gather_votes — an allgather across processes
    over DCN). On a multi-rank world the pipeline enters exactly ONE
    vote per query, at the query boundary, success or failure
    (ExecutionPipeline._boundary_vote) — so the allgathers pair
    deterministically across ranks even when the triggering failure
    was rank-local, and no rank waits on a collective another rank
    skipped."""

    def __init__(self):
        import jax
        self.world = jax.process_count()

    def gather(self, vote: int) -> "list[int] | None":
        from nds_tpu.parallel import multihost
        votes = multihost.gather_votes(vote)
        if votes is None:
            from nds_tpu.utils.report import TaskFailureCollector
            TaskFailureCollector.notify(
                "placement consensus allgather failed; "
                "keeping placement")
        return votes


class Consensus:
    """All-or-none placement agreement. Votes are rung indices into the
    shared ladder (higher = more demoted); after the gather every rank
    applies the same deterministic rule — the DEEPEST demotion any rank
    proposed wins — so all ranks switch together or, when the gather
    fails (a lagging/dead rank), nobody switches."""

    def __init__(self, channel=None):
        self.channel = channel or NullChannel()

    def decide(self, vote: int) -> "int | None":
        obs_metrics.counter("placement_consensus_total").inc()
        votes = self.channel.gather(vote)
        if votes is None or len(votes) < getattr(self.channel, "world", 1):
            obs_metrics.counter("placement_consensus_failed_total").inc()
            return None
        return max(votes)


# ------------------------------------------------------------ cost model

class CostModel:
    """Initial-placement chooser. Inputs: the plan verifier's static
    size estimates and the per-query device-memory HWM history this
    process has observed (a query that blew past the budget last time
    starts out-of-core this time — Execution Templates' re-validated
    cached decision, PAPERS.md)."""

    def __init__(self, device_budget: int = DEFAULT_DEVICE_BUDGET,
                 stream_bytes: int = 0,
                 expansion: float = EXPANSION):
        self.device_budget = device_budget
        self.stream_bytes = stream_bytes
        self.expansion = expansion
        # query name -> observed device HWM bytes (max over runs)
        self.hwm_history: dict[str, int] = {}

    def observe(self, qname: str | None, hwm_bytes: int) -> None:
        """``hwm_bytes``: the statement's OWN high-water, the device's
        less what other statements kept resident there while it ran
        (ExecutionPipeline._note_success): the budget is one
        statement's working set, and a warehouse resident beside it is
        not part of that."""
        if qname and hwm_bytes:
            self.hwm_history[qname] = max(
                self.hwm_history.get(qname, 0), int(hwm_bytes))

    def choose(self, planned, universe: tuple,
               tables: "dict | None" = None, catalog=None,
               qname: "str | None" = None, est=None) -> tuple:
        """-> (placement, reason). Deterministic over identical inputs,
        which multi-process SPMD relies on: every rank computes the
        same initial placement without a consensus round. ``est``
        accepts a precomputed plan estimate (the pipeline shares one
        estimate between this choice and the memory governor)."""
        from nds_tpu.analysis import plan_verify
        if est is None:
            est = plan_verify.estimate_plan(planned, tables=tables,
                                            catalog=catalog)
        fast = universe[0]
        if CHUNKED in universe and fast != CHUNKED:
            hwm = self.hwm_history.get(qname or "")
            if hwm and hwm > self.device_budget:
                return CHUNKED, f"hwm-history:{hwm}>{self.device_budget}"
            if (self.stream_bytes
                    and est.widest_table_bytes > self.stream_bytes):
                return CHUNKED, (f"table-exceeds-stream-bytes:"
                                 f"{est.widest_table_bytes}")
            # join/sort/window/agg intermediates inflate the working
            # set beyond the raw scans: pad the expansion per operator
            ops = est.joins + est.aggregates + est.sorts + est.windows
            factor = self.expansion * (1.0 + 0.1 * ops)
            need = working_set(est, factor)
            if need > self.device_budget:
                return CHUNKED, (f"working-set:{need}b of "
                                 f"{est.bytes}b scanned x{factor:.1f}")
        return fast, f"fits:{est.bytes}b"


# ------------------------------------------------------ memory governor

# once governing, projections must fall below this fraction of the
# budget before the governor stands down (hysteresis: borderline
# queries must not flap between device and chunked every other query)
GOVERNOR_LOW_FRAC = 0.8


class MemoryGovernor:
    """Proactive memory-pressure pre-admission check.

    Today OOM is handled REACTIVELY: the query dies on device, the
    ladder walks it to chunked at halved chunk_rows, and the whole
    program re-executes. On a multi-hour run every one of those walks
    is minutes of wasted re-execution. The governor moves the decision
    BEFORE dispatch: project the post-admission high-water mark as

        live bytes now (obs/memwatch.live_bytes — allocator stats when
        a backend is live, accounted buffers otherwise)
      + what the dispatch adds to them (``working_set``: the plan's
        scan columns not yet resident, and its intermediates at the
        expansion factor)

    and when the projection exceeds
    ``engine.placement.device_budget_bytes``, demote the query's
    placement (device -> chunked) or — when it is already bound for
    the chunked placement — pre-shrink its ``chunk_rows``, before
    anything is dispatched. Hysteresis keeps the decision sticky: once
    governing, projections must fall below ``GOVERNOR_LOW_FRAC`` x
    budget to stand down. Every preemptive demotion counts on
    ``governor_preemptive_demotions_total``; on the summary side the
    query carries ``governed: true`` (BenchReport.attach_schedule).

    Rank-local by construction (live memory diverges across ranks), so
    the pipeline only consults it on single-process worlds — the same
    rule the HWM history follows."""

    def __init__(self, budget: int = DEFAULT_DEVICE_BUDGET,
                 expansion: float = EXPANSION,
                 low_frac: float = GOVERNOR_LOW_FRAC):
        self.budget = int(budget)
        self.expansion = expansion
        self.low_frac = low_frac
        self.governing = False
        # what the last decision projected (the sched.place span's
        # ``projected_bytes``)
        self.projected = 0

    def project(self, est, resident: int = 0,
                live: "int | None" = None) -> int:
        """``resident``: bytes of the plan's scan columns on the device
        already. The live bytes hold them, so the projection must not
        add them a second time; at SF1 nobody saw the difference, at
        SF5 it is 3 GB of an 8 GiB budget. ``live``: a reading the
        caller has taken already."""
        if int(getattr(est, "bytes", 0) or 0) <= 0:
            return 0
        if live is None:
            live = memwatch.live_bytes()
        return live + working_set(est, self.expansion, resident)

    def decide(self, est, resident: int = 0,
               live: "int | None" = None) -> "str | None":
        """Non-None reason string when the query must be demoted /
        pre-shrunk before dispatch."""
        self.projected = 0
        if self.budget <= 0:
            return None
        projected = self.projected = self.project(est, resident, live)
        if projected <= 0:
            return None
        limit = (int(self.budget * self.low_frac) if self.governing
                 else self.budget)
        if projected > limit:
            self.governing = True
            obs_metrics.counter(
                "governor_preemptive_demotions_total").inc()
            return (f"governor:projected:{projected}"
                    f">budget:{self.budget}")
        self.governing = False
        return None

    def admit_prefetch(self, est, chunk_bytes: int, depth: int) -> int:
        """Deepest prefetch depth whose in-flight staged bytes still
        fit the budget ON TOP of the base projection — overlap must not
        reintroduce the OOMs the governor prevents, so depth demotes
        BEFORE placement does: a budget that admits the serial chunked
        loop but not depth x chunk of staged buffers runs the same
        placement shallower, not a deeper ladder rung. Returns
        ``depth`` unchanged when nothing constrains it (no budget, no
        estimate, chunk size unknown)."""
        if (self.budget <= 0 or depth <= 0 or chunk_bytes <= 0):
            return depth
        base = self.project(est)
        if base <= 0:
            return depth
        d = depth
        while d > 0 and base + d * chunk_bytes > self.budget:
            d -= 1
        return d


# ------------------------------------------------------------- pipeline

class _CompletedHandle:
    """Already-finished async handle. Carries the query's own
    stats/schedule — and the timings/span the sync execution left on
    the pipeline — so interleaved dispatches (the in-process throughput
    fleet keeps ``engine.concurrent_tasks`` queries in flight; the
    power loop's boundary pipelining dispatches query N+1 before
    resolving N) cannot clobber each other's accounting: ``result()``
    re-points the pipeline's per-query obs surface at THIS query's."""

    __slots__ = ("_value", "pipe", "stats", "sched", "timings", "span")

    def __init__(self, value, pipe=None, stats=None, sched=None):
        self._value = value
        self.pipe = pipe
        self.stats = stats
        self.sched = sched
        self.timings = getattr(pipe, "last_timings", {}) if pipe else {}
        self.span = (getattr(pipe, "last_query_span", None)
                     if pipe else None)

    def result(self):
        if self.pipe is not None:
            self.pipe.last_stats = self.stats
            self.pipe.last_schedule = self.sched
            self.pipe.last_timings = self.timings
            self.pipe.last_query_span = self.span
        return self._value


class _PipelineHandle:
    """Async handle preserving the device engine's dispatch/materialize
    overlap: the inner placement handle fails only at ``result()``, so
    the ladder rerun happens there, synchronously, on the blocked
    caller's thread — with this query's own stats/schedule objects."""

    __slots__ = ("pipe", "planned", "key", "inner", "placement",
                 "stats", "sched")

    def __init__(self, pipe, planned, key, inner, placement, stats,
                 sched):
        self.pipe = pipe
        self.planned = planned
        self.key = key
        self.inner = inner
        self.placement = placement
        self.stats = stats
        self.sched = sched

    def result(self):
        pipe = self.pipe
        pipe.last_stats = self.stats
        pipe.last_schedule = self.sched
        try:
            out = self.inner.result()
        except Exception as exc:  # noqa: BLE001 - classified in rerun
            self.stats.attempts += 1
            self.stats.errors.append(f"{type(exc).__name__}: {exc}")
            if classify(exc) != "transient":
                self.stats.gave_up_reason = DETERMINISTIC
                raise
            return pipe._run_ladder(
                self.planned, key=self.key, placement=self.placement,
                stats=self.stats, sched=self.sched, pending=exc)
        self.stats.attempts += 1
        pipe._adopt_executor_state(self.placement)
        self.sched["placement"] = self.placement
        with get_tracer().span("sched.note"):
            pipe._note_success(rescheduled=False)
        return out


class ExecutionPipeline:
    """The Session executor factory for every backend: owns the
    placement executors, the cost model, the ladder, and the query-level
    retry wiring that used to live in utils/power_core.py and (as
    near-copies) in the throughput stream loops."""

    def __init__(self, backend: str = "cpu", config=None,
                 mesh=None, precision: str = "f64",
                 stream_bytes: int = 0, chunk_rows: int | None = None,
                 consensus: "Consensus | None" = None,
                 cost_model: "CostModel | None" = None,
                 prefetch_depth: "int | None" = None):
        from nds_tpu.engine import pipeline_io
        from nds_tpu.engine.chunked_exec import DEFAULT_CHUNK_ROWS
        self.backend = backend
        self.config = config
        self.mesh = mesh
        if precision not in ("f64", "f32", "bf16"):
            # device_exec.PRECISIONS, validated HERE so a config typo
            # fails at session creation, not as a KeyError mid-stream
            # after the warehouse loaded (device_exec itself imports
            # lazily — it pulls in jax)
            raise ValueError(f"unknown engine.precision {precision!r}")
        self.precision = precision
        self.stream_bytes = stream_bytes
        self.chunk_rows = chunk_rows or DEFAULT_CHUNK_ROWS
        # double-buffered phase-A prefetch depth for the chunked
        # placement (engine/pipeline_io.py; engine.prefetch.* /
        # NDS_TPU_PREFETCH; 0 = serial). The governor may demote it
        # per query (_apply_prefetch) before demoting the placement
        self.prefetch_depth = (pipeline_io.resolve_depth(config)
                               if prefetch_depth is None
                               else max(0, int(prefetch_depth)))
        self._gov_depth: "int | None" = None
        self.universe = UNIVERSES.get(backend, (CPU,))
        self.policy = (RetryPolicy.from_config(config) if config
                       else RetryPolicy())
        self.consensus = consensus or Consensus(
            self._default_channel(backend))
        self.cost_model = cost_model or CostModel(
            device_budget=self._cfg_int(
                "engine.placement.device_budget_bytes",
                DEFAULT_DEVICE_BUDGET),
            stream_bytes=stream_bytes)
        # proactive memory-pressure governor (engine.placement.governor,
        # default on): pre-admission demotion/pre-shrink against the
        # same budget the cost model plans with
        self.governor = None
        if str(self._cfg("engine.placement.governor", "on")) not in (
                "off", "0", "false"):
            self.governor = MemoryGovernor(
                budget=self.cost_model.device_budget)
        self._gov_shrink = False
        self.ladder_on = self._cfg("engine.placement.ladder",
                                   "on") not in ("off", "0", "false")
        floor = self._cfg("engine.placement.floor", CPU)
        if self._cfg("engine.fallback") == CPU:
            # legacy alias: the one-shot stream demotion becomes
            # "the ladder bottoms out on the CPU oracle"
            floor = CPU
        self.floor = floor if floor in self.universe else self.universe[-1]
        force = self._cfg("engine.placement.force")
        if force and force not in self.universe:
            # a silently-dropped pin would hand the user unpinned
            # numbers while they believe placement is fixed
            raise ValueError(
                f"engine.placement.force={force!r} is not in the "
                f"{backend!r} backend's placement universe "
                f"{self.universe}")
        self.forced = force or None
        self.demote_after = self._cfg_int("engine.placement.demote_after",
                                          DEFAULT_DEMOTE_AFTER)
        self.promote_after = self._cfg_int(
            "engine.placement.promote_after", DEFAULT_PROMOTE_AFTER)
        # placement name -> live executor (built lazily; device buffers
        # and compile caches persist across queries per placement)
        self._executors: dict = {}
        self._tables: "dict | None" = None
        # sticky stream-level demotion state
        self._demoted_to: "str | None" = None
        self._reschedule_streak = 0
        self._clean_streak = 0
        self._just_promoted = False
        # executor-compatible surface (power loop resets these; the obs
        # layer scrapes them)
        self.last_timings: dict = {}
        self.last_query_span = None
        self.last_stats = RetryStats()
        self.last_schedule: dict = {}

    # -------------------------------------------------------- plumbing

    @property
    def _multi(self) -> bool:
        """Multi-rank world? The placement protocol then switches to
        exactly ONE consensus round per query (_boundary_vote):
        rank-local mid-query ladder walking cannot pair its
        collectives when only the failing rank enters them."""
        return getattr(self.consensus.channel, "world", 1) > 1

    def _cfg(self, key: str, default=None):
        return self.config.get(key, default) if self.config else default

    def _cfg_int(self, key: str, default: int) -> int:
        return (self.config.get_int(key, default) if self.config
                else default)

    @staticmethod
    def _default_channel(backend: str):
        # probe jax ONLY for the distributed backend: process_count()
        # initializes the platform, and a host-only phase must never
        # open a chip another process may need
        if backend != "distributed":
            return NullChannel()
        try:
            import jax
            if jax.process_count() > 1:
                return MultihostChannel()
        except Exception:  # noqa: BLE001 - no jax: single-process world
            pass
        return NullChannel()

    def __call__(self, tables: dict) -> "ExecutionPipeline":
        """Session executor-factory protocol: bind the registry. A NEW
        registry object (DML rebuilt the dict) invalidates the built
        executors the same way the per-backend factories did."""
        if self._tables is not tables:
            self._tables = tables
            self._executors.clear()
        return self

    def invalidate(self) -> None:
        """Session.invalidate hook (DML): drop every placement executor
        (device buffers + compiled programs key on table contents). The
        HWM history and demotion state survive — they describe the
        workload, not the table version."""
        self._executors.clear()

    def reset_query(self) -> None:
        """Pre-query reset (the power loop's stale-state contract): a
        query failing before dispatch must not inherit the previous
        query's span/timings/stats/schedule."""
        self.last_timings = {}
        self.last_query_span = None
        self.last_stats = RetryStats()
        self.last_schedule = {}

    # ------------------------------------------------------- executors

    def _executor(self, placement: str):
        ex = self._executors.get(placement)
        if ex is not None:
            return ex
        tables = self._tables or {}
        if placement == CPU:
            from nds_tpu.engine.cpu_exec import CpuExecutor
            ex = CpuExecutor(tables)
        elif placement == CHUNKED:
            from nds_tpu.engine.chunked_exec import ChunkedExecutor
            from nds_tpu.engine.chunked_exec import DEFAULT_STREAM_BYTES
            ex = ChunkedExecutor(
                tables, self.stream_bytes or DEFAULT_STREAM_BYTES,
                self.chunk_rows, self._float_dtype(),
                prefetch_depth=self.prefetch_depth)
        elif placement == DEVICE:
            from nds_tpu.engine.device_exec import DeviceExecutor
            ex = DeviceExecutor(tables, self._float_dtype())
        elif placement == SHARDED:
            from nds_tpu.parallel.dist_exec import DistributedExecutor
            ex = DistributedExecutor(tables, mesh=self.mesh)
        else:
            raise ValueError(f"unknown placement {placement!r}")
        self._executors[placement] = ex
        return ex

    def _float_dtype(self):
        from nds_tpu.engine.device_exec import PRECISIONS
        name = PRECISIONS[self.precision]
        if name is None:
            return None
        import jax.numpy as jnp
        return getattr(jnp, name)

    def _adopt_executor_state(self, placement: str) -> None:
        """Forward the serving executor's per-query obs surface so
        ``obs.query_timings(pipeline)`` and the power loop see the
        query exactly as before the unification."""
        ex = self._executors.get(placement)
        if ex is None:
            return
        self.last_timings = getattr(ex, "last_timings", {}) or {}
        self.last_query_span = getattr(ex, "last_query_span", None)

    # ------------------------------------------------------ the ladder

    def rungs_for(self, initial: str) -> list:
        """Orderered rung list for a query starting at ``initial``,
        truncated at the configured floor. The sharded re-plan rung is
        inserted conditionally at failure time (only an exchange
        overflow enters it — growing slack cannot fix an OOM). On a
        multi-rank world the list is a single rung: placement moves
        only between queries, through the per-query boundary vote
        every rank enters (_boundary_vote) — a rank-local mid-query
        walk would leave this rank off the collectives its peers are
        still inside."""
        if not self.ladder_on or self._multi:
            return [initial]
        order = list(self.universe)
        try:
            start = order.index(initial)
        except ValueError:
            return [initial]
        rungs = order[start:]
        if self.floor in rungs:
            rungs = rungs[:rungs.index(self.floor) + 1]
        return rungs

    def _encoded_estimates(self) -> bool:
        """Whether size estimates may use the columnar ENCODED widths:
        only when the universe's fast placement actually consumes
        encoded buffers. The sharded SPMD path uploads raw
        (DistributedExecutor.COLUMNAR_UPLOAD = False), so costing it
        at encoded widths would under-count residency by the
        compression ratio and admit queries that then OOM on device —
        the reactive failure the cost model exists to prevent."""
        return self.universe[0] != SHARDED

    def _resident_bytes(self, planned, placement: str) -> int:
        """Bytes of ``planned``'s scan buffers that ``placement``'s
        executor finds on the device already; 0 where there is no such
        executor yet, or it keeps no count."""
        count = getattr(self._executors.get(placement), "resident_bytes",
                        None)
        return int(count(planned)) if count else 0

    def _initial_placement(self, planned, qname) -> tuple:
        self._gov_shrink = False
        self._gov_depth = None
        # what the sched.place span says of this decision, and what
        # _note_success takes off the device's high-water
        self._placed = {"est_bytes": 0, "live_bytes": 0,
                        "projected_bytes": 0,
                        "budget_bytes": self.cost_model.device_budget}
        self._others_bytes = 0
        catalog = None
        from nds_tpu.analysis import plan_verify
        if self.forced or self._demoted_to:
            # pinned/sticky placements skip the cost model but NOT the
            # prefetch depth admission below (a forced chunked run
            # still must not stage depth x chunk past the budget)
            placement, why = ((self.forced, "forced") if self.forced
                              else (self._demoted_to,
                                    "sticky-demotion"))
            return self._admit_depth(planned, placement, catalog), why
        est = plan_verify.estimate_plan(planned, tables=self._tables,
                                        catalog=catalog,
                                        encoded=self._encoded_estimates())
        placement, why = self.cost_model.choose(
            planned, self.universe, tables=self._tables,
            catalog=catalog, qname=qname, est=est)
        self._placed["est_bytes"] = est.bytes
        if why.startswith("hwm-history:"):
            obs_metrics.counter("hwm_history_placements_total").inc()
        # pre-admission governor: projected HWM (live bytes + estimate
        # x expansion) over budget demotes BEFORE dispatch — every
        # avoided OOM is an avoided ladder walk and re-execute.
        # Single-process worlds only: live memory is rank-local, and a
        # divergent projection would start peers at different
        # placements (the consensus-avoidance rule the HWM history
        # follows)
        # only consult the governor when it could actually act: a
        # placement with no relief rung (the CPU oracle, a universe
        # without chunked) must not count phantom demotions or latch
        # the hysteresis
        if (self.governor is not None and not self._multi
                and CHUNKED in self.universe
                and placement in (DEVICE, SHARDED, CHUNKED)):
            live = memwatch.live_bytes()
            resident = (self._resident_bytes(planned, placement)
                        if placement != CHUNKED else 0)
            reason = self.governor.decide(est, resident, live)
            self._others_bytes = max(live - resident, 0)
            self._placed.update(
                live_bytes=live, projected_bytes=self.governor.projected,
                budget_bytes=self.governor.budget)
            if reason and placement in (DEVICE, SHARDED):
                placement, why = CHUNKED, reason
            elif reason and placement == CHUNKED:
                self._gov_shrink = True
                placement, why = CHUNKED, reason
        return self._admit_depth(planned, placement, catalog,
                                 est=est), why

    def _admit_depth(self, planned, placement: str, catalog,
                     est=None) -> str:
        """Prefetch depth admission (engine/pipeline_io.py): a
        chunked-bound query whose base projection fits the budget but
        whose depth x chunk of in-flight staged buffers does not runs
        SHALLOWER, not deeper down the ladder — depth demotes before
        placement (applied per query via _apply_prefetch, restored by
        _run_ladder's finally). Returns the placement unchanged."""
        if (placement != CHUNKED or self.governor is None
                or self._multi or self.prefetch_depth <= 0):
            return placement
        if est is None:
            from nds_tpu.analysis import plan_verify
            est = plan_verify.estimate_plan(
                planned, tables=self._tables, catalog=catalog,
                encoded=self._encoded_estimates())
        from nds_tpu.engine import pipeline_io
        chunk_bytes = pipeline_io.chunk_working_set(
            est, self.chunk_rows)
        allowed = self.governor.admit_prefetch(
            est, chunk_bytes, self.prefetch_depth)
        if allowed < self.prefetch_depth:
            self._gov_depth = allowed
        return placement

    def _apply_governor(self, sched: dict, placement: str) -> None:
        """Post-schedule governor bookkeeping: stamp ``governed`` on
        the summary and pre-shrink chunk_rows for THIS query (restored
        by _run_ladder's finally) when the governed placement is
        already the chunked one."""
        if not str(sched.get("reason", "")).startswith("governor:"):
            return
        sched["governed"] = True
        if self._gov_shrink and placement == CHUNKED:
            from nds_tpu.engine.chunked_exec import ChunkedExecutor
            ex = self._executor(CHUNKED)
            sched.setdefault("_restore", []).append(
                (ex, "chunk_rows", ex.chunk_rows))
            ex.chunk_rows = max(ex.chunk_rows // 2,
                                ChunkedExecutor.MIN_CHUNK_ROWS)
        self._gov_shrink = False

    def _apply_prefetch(self, sched: dict, placement: str) -> None:
        """Apply the depth admission verdict for THIS query (restored
        by _run_ladder's finally, like every per-query executor tweak):
        the chunked executor runs at the admitted depth, the summary
        records ``prefetch_depth``, and the demotion counts."""
        d, self._gov_depth = self._gov_depth, None
        if d is None or placement != CHUNKED:
            return
        ex = self._executor(CHUNKED)
        if not hasattr(ex, "prefetch_depth"):
            return
        sched.setdefault("_restore", []).append(
            (ex, "prefetch_depth", ex.prefetch_depth))
        ex.prefetch_depth = d
        sched["prefetch_depth"] = d
        obs_metrics.counter("prefetch_depth_demotions_total").inc()

    def admission_projection(self, planned) -> tuple:
        """(projected_bytes, budget_bytes) from the MemoryGovernor's
        pre-dispatch model — what the serving layer's admission control
        reads (nds_tpu/serve/server.py): live bytes now + the plan
        verifier's size estimate x expansion, against
        ``engine.placement.device_budget_bytes``. (0, 0) when no
        governor is armed (CPU universe, multi-rank worlds,
        ``engine.placement.governor=off``)."""
        if self.governor is None or self._multi:
            return 0, 0
        from nds_tpu.analysis import plan_verify
        est = plan_verify.estimate_plan(
            planned, tables=self._tables,
            encoded=self._encoded_estimates())
        resident = self._resident_bytes(planned, self.universe[0])
        return self.governor.project(est, resident), self.governor.budget

    def choose_placement(self, planned, qname: "str | None" = None,
                         catalog=None) -> tuple:
        """Cost-model choice WITHOUT executing (tools/ndsverify.py and
        the bench planners): -> (placement, reason)."""
        if self.forced:
            return self.forced, "forced"
        from nds_tpu.analysis import plan_verify
        est = plan_verify.estimate_plan(
            planned, tables=self._tables, catalog=catalog,
            encoded=self._encoded_estimates())
        return self.cost_model.choose(planned, self.universe,
                                      tables=self._tables,
                                      catalog=catalog, qname=qname,
                                      est=est)

    def _place(self, planned) -> tuple:
        """(placement, stats, schedule) for one query: the cost model's
        initial placement, the memory governor and the prefetch depth
        admission, under the ``sched.place`` span."""
        with get_tracer().span("sched.place") as span:
            placement, why = self._initial_placement(
                planned, self._current_query())
            stats, sched = self._new_schedule(placement, why)
            self._apply_governor(sched, placement)
            self._apply_prefetch(sched, placement)
            self.last_stats, self.last_schedule = stats, sched
            sched["_others_bytes"] = self._others_bytes
            # governed: the governor or the high-water history chose
            # the placement, not the plan's own size
            span.set(placement=placement, governed=int(
                why.startswith(("governor:", "hwm-history:"))),
                **self._placed)
        return placement, stats, sched

    def execute(self, planned, key: object = None):
        placement, stats, sched = self._place(planned)
        return self._run_ladder(planned, key=key, placement=placement,
                                stats=stats, sched=sched)

    def execute_async(self, planned, key: object = None):
        """Async dispatch with the ladder armed at result() time: the
        fast path delegates to the placement executor's own
        execute_async (device pipelining preserved); any transient
        failure surfaces at result() and reruns down the ladder. Every
        handle carries its own stats/schedule, so interleaved dispatch
        (engine.concurrent_tasks) keeps per-query accounting intact."""
        qname = self._current_query()
        placement, stats, sched = self._place(planned)
        ex = self._executor(placement)
        dispatch = getattr(ex, "execute_async", None)
        # multi-rank worlds run synchronously: the per-query boundary
        # vote must fire in dispatch order on every rank, and the
        # compiled collective programs serialize execution anyway.
        # The sharded placement is sync even single-process — one
        # collective program is in flight a process (the
        # DistributedExecutor's dispatch lock), so its execute_async
        # finishes before it returns and there is nothing to
        # pipeline. Governed and depth-demoted queries
        # run synchronously too — the per-query chunk-shrink /
        # prefetch-depth restores ride _run_ladder's finally
        if dispatch is None or placement in (CPU, SHARDED) \
                or self._multi or sched.get("governed") \
                or "prefetch_depth" in sched:
            out = self._run_ladder(planned, key=key, placement=placement,
                                   stats=stats, sched=sched)
            return _CompletedHandle(out, self, stats, sched)
        try:
            # the dispatch half of the walk's first rung; the blocking
            # half hangs from the caller's root at result()
            with get_tracer().span("sched.run", placement=placement):
                self._predispatch(placement, qname, stats)
                inner = (dispatch(planned, key) if key is not None
                         else dispatch(planned))
        except Exception as exc:  # noqa: BLE001 - classified in rerun
            stats.attempts += 1
            stats.errors.append(f"{type(exc).__name__}: {exc}")
            if classify(exc) != "transient":
                stats.gave_up_reason = DETERMINISTIC
                raise
            out = self._run_ladder(planned, key=key, placement=placement,
                                   stats=stats, sched=sched, pending=exc)
            return _CompletedHandle(out, self, stats, sched)
        return _PipelineHandle(self, planned, key, inner, placement,
                               stats, sched)

    # ---------------------------------------------------- ladder walk

    def _current_query(self) -> "str | None":
        return faults.current_context().get("query")

    def _new_schedule(self, placement: str, why: str) -> tuple:
        stats = RetryStats()
        sched = {
            "initial": placement, "placement": placement,
            "reason": why, "reschedules": 0, "ladder": [placement],
        }
        if self._just_promoted:
            sched["promoted_back"] = True
            self._just_promoted = False
        return stats, sched

    def _predispatch(self, placement: str, qname=None,
                     stats: "RetryStats | None" = None) -> None:
        """The shared per-dispatch wiring every executor used to carry
        a copy of: liveness heartbeat + the per-attempt stream.query
        chaos site (previously fired by the power loop's retry body and
        the throughput loop's dispatch — now exactly once, here)."""
        unit = os.environ.get(watchdog.STREAM_ENV) or "engine"
        watchdog.beat(unit, query=qname, phase="pipeline.dispatch",
                      placement=placement,
                      attempt=stats.attempts if stats else 0)
        faults.fault_point("stream.query")

    def _run_ladder(self, planned, key: object = None,
                    placement: str = CPU,
                    stats: "RetryStats | None" = None,
                    sched: "dict | None" = None,
                    pending: "Exception | None" = None):
        """Walk the ladder for one query. Same-rung generic transients
        retry under the config policy's backoff/attempt budget;
        OOM/exchange-overflow step down immediately (re-running the
        identical program at the identical placement cannot help);
        deterministic failures raise. Every placement switch is a
        consensus decision (degenerate single-voter channel in
        single-process runs). ``pending`` carries an async dispatch's
        already-raised failure so its spent attempt counts against the
        same budget."""
        qname = self._current_query()
        stats = stats if stats is not None else self.last_stats
        sched = sched if sched is not None else self.last_schedule
        rungs = self.rungs_for(placement)
        start = self._clock()
        deadline_s = self.policy.deadline_s
        unit = os.environ.get(watchdog.STREAM_ENV) or "engine"

        def overrun() -> bool:
            return (deadline_s is not None
                    and self._clock() - start > deadline_s)

        def flag_deadline() -> None:
            if not stats.deadline_exceeded:
                stats.deadline_exceeded = True
                obs_metrics.counter(
                    "query_deadline_exceeded_total").inc()

        tracer = get_tracer()
        try:
            with tracer.span("sched.run", placement=placement):
                out = self._walk(planned, key, rungs, stats, sched,
                                 pending, qname, unit, deadline_s, start,
                                 overrun, flag_deadline)
            with tracer.span("sched.note"):
                self._note_success(rescheduled=sched["reschedules"] > 0,
                                   qname=qname, sched=sched)
            return out
        except BaseException:
            if sched.pop("_gave_up", False):
                # the walk exhausted what it could try (not a
                # deterministic failure): counts toward the demotion
                with tracer.span("sched.note", failed=True):
                    self._note_failure()
            raise
        finally:
            # per-query executor tweaks (the ladder's chunk halving /
            # stream-threshold lowering / prefetch-depth admission)
            # roll back whether the walk succeeded or raised — in
            # REVERSE order: two entries for the same attribute (depth
            # admitted pre-dispatch, then zeroed by the relief entry)
            # must unwind to the ORIGINAL value, not the intermediate
            for obj, attr, val in reversed(sched.pop("_restore", [])):
                setattr(obj, attr, val)
            sched.pop("_stream_lowered", None)
            ok = sched.pop("_succeeded", False)
            if self._multi:
                # multi-rank placement protocol: EVERY rank votes
                # exactly once per query, success or failure — the
                # only collective the scheduler runs, so vote rounds
                # pair deterministically across ranks even when a
                # failure (OOM, deadline) was rank-local
                self._boundary_vote(failed=not ok)

    def _walk(self, planned, key, rungs, stats, sched, pending, qname,
              unit, deadline_s, start, overrun, flag_deadline):
        with deadline_scope(deadline_s, self._clock, start=start):
            i = 0
            while i < len(rungs):
                rung = rungs[i]
                last_rung = i == len(rungs) - 1
                if pending is not None:
                    exc, pending = pending, None
                else:
                    if rung == CHUNKED and (
                            sched["reschedules"] > 0
                            or str(sched.get("reason", "")
                                   ).startswith("working-set")):
                        # out-of-core as a RELIEF placement must
                        # actually stream something
                        self._ensure_chunked_streams(planned, sched)
                    try:
                        self._predispatch(rung, qname, stats)
                        out = (self._executor(rung).execute(planned)
                               if key is None else
                               self._executor(rung).execute(planned,
                                                            key))
                    except QueryDeadlineExceeded as exc2:
                        stats.errors.append(
                            f"{type(exc2).__name__}: {exc2}")
                        stats.gave_up_reason = "deadline"
                        flag_deadline()
                        raise
                    except Exception as exc2:  # noqa: BLE001
                        stats.attempts += 1
                        stats.errors.append(
                            f"{type(exc2).__name__}: {exc2}")
                        exc = exc2
                    else:
                        stats.attempts += 1
                        if overrun():
                            flag_deadline()
                        self._adopt_executor_state(rung)
                        sched["placement"] = rung
                        sched["_succeeded"] = True
                        return out
                # ---- failure handling at this rung
                if classify(exc) != "transient":
                    stats.gave_up_reason = DETERMINISTIC
                    if overrun():
                        flag_deadline()
                    raise exc
                stepping = (not last_rung
                            and (is_oom(exc)
                                 or is_exchange_overflow(exc)))
                if stepping:
                    # propose first, AGREE, then act: the slack
                    # re-plan mutates executor state every rank must
                    # share, so no side effect may precede the vote
                    proposal, replan = self._propose(rungs, i, exc,
                                                     sched)
                    agreed = self.consensus.decide(proposal)
                    if agreed is None or agreed >= len(rungs):
                        # no agreement: keep placement, fail the query
                        # rather than diverge from the other ranks
                        stats.gave_up_reason = "consensus"
                        sched["_gave_up"] = True
                        raise exc
                    if agreed == i and replan:
                        self._apply_replan(sched)
                    elif agreed > i:
                        i = agreed
                        self._reschedule(rungs[i], sched, qname)
                    continue
                # generic transient (or OOM at the floor): same-rung
                # retry under the policy budget, then step down if a
                # rung remains, else give up
                if stats.attempts >= self.policy.max_attempts:
                    if not last_rung:
                        proposal, _replan = self._propose(
                            rungs, i, exc, sched, force_step=True)
                        agreed = self.consensus.decide(proposal)
                        if agreed is not None and agreed < len(rungs) \
                                and agreed > i:
                            i = agreed
                            self._reschedule(rungs[i], sched, qname)
                            stats.attempts = 0
                            continue
                    stats.gave_up_reason = (
                        f"attempts_exhausted({stats.attempts})")
                    if overrun():
                        flag_deadline()
                    sched["_gave_up"] = True
                    raise exc
                d = self.policy.delay_for(stats.retries)
                if (deadline_s is not None
                        and self._clock() - start + d > deadline_s):
                    stats.gave_up_reason = "deadline"
                    flag_deadline()
                    sched["_gave_up"] = True
                    raise exc
                stats.retries += 1
                stats.backoff_s += d
                obs_metrics.counter("query_retries_total").inc()
                watchdog.beat(unit, query=qname, phase="retry",
                              attempt=stats.retries)
                if d > 0:
                    self.policy._sleep(d)
        raise RuntimeError("unreachable: ladder exhausted without raise")

    def _clock(self):
        return self.policy._clock()

    def _ensure_chunked_streams(self, planned, sched: dict) -> None:
        """The chunked placement only relieves memory when something
        actually streams: with ``engine.stream_bytes`` unset, no
        sub-threshold table chunks, and a ladder entry (or cost-model
        working-set choice) would re-execute the identical full-upload
        program. Lower the executor's stream threshold FOR THIS QUERY
        (restored after the walk) so the largest scanned table
        streams."""
        if sched.get("_stream_lowered") or not self._tables:
            return
        ex = self._executor(CHUNKED)
        from nds_tpu.sql import plan as P
        biggest = 0
        roots = [planned.root, *planned.scalar_subplans] \
            if isinstance(planned, P.PlannedQuery) else []
        for root in roots:
            for node in P.walk_plan(root):
                if (isinstance(node, P.Scan)
                        and node.table in self._tables):
                    biggest = max(biggest, memwatch.table_bytes(
                        self._tables[node.table]))
        if biggest and ex.stream_bytes >= biggest:
            sched["_stream_lowered"] = True
            sched.setdefault("_restore", []).append(
                (ex, "stream_bytes", ex.stream_bytes))
            ex.stream_bytes = max(biggest - 1, 1)

    def _propose(self, rungs: list, i: int, exc: Exception,
                 sched: dict, force_step: bool = False
                 ) -> "tuple[int, bool]":
        """This rank's vote: (rung index, is_slack_replan). Pure — NO
        side effect happens until the consensus round agrees; the
        sharded re-plan (slack growth) is only proposed once per
        query, and only for exchange overflow (growing slack cannot
        fix an OOM)."""
        if (not force_step and rungs[i] == SHARDED
                and is_exchange_overflow(exc)
                and not sched.get("slack_grown")):
            ex = self._executors.get(SHARDED)
            if ex is not None and hasattr(ex, "grow_slack"):
                return i, True  # re-vote the SAME rung, re-planned
        return i + 1, False

    def _apply_replan(self, sched: dict) -> None:
        """Consensus-agreed sharded re-plan: double the base slack and
        invalidate compiled programs — on every rank, together (the
        vote already passed when this runs)."""
        self._executors[SHARDED].grow_slack()
        sched["slack_grown"] = True
        sched.setdefault("ladder", []).append(SHARDED_REPLAN)
        obs_metrics.counter("query_reschedules_total").inc()
        sched["reschedules"] += 1

    def _reschedule(self, rung: str, sched: dict, qname) -> None:
        if sched.get("ladder", [None])[-1] == rung:
            return  # slack re-plan already recorded this step
        sched["reschedules"] += 1
        sched["ladder"].append(rung)
        # reflect the rung being attempted even if it too fails — a
        # failed query's summary names the DEEPEST placement tried
        sched["placement"] = rung
        obs_metrics.counter("query_reschedules_total").inc()
        if rung == CHUNKED:
            # the ladder's chunked entry runs THIS query at half the
            # current chunk size (the device just proved the full
            # working set does not fit); per-query — _run_ladder
            # restores it afterwards, so repeated walks do not grind
            # every later chunked query down to the floor (the
            # executor's own OOM shrink loop stays the persistent
            # adaptation)
            ex = self._executor(CHUNKED)
            from nds_tpu.engine.chunked_exec import ChunkedExecutor
            sched.setdefault("_restore", []).append(
                (ex, "chunk_rows", ex.chunk_rows))
            ex.chunk_rows = max(ex.chunk_rows // 2,
                                ChunkedExecutor.MIN_CHUNK_ROWS)
            # the relief entry also runs serial: the OOM just proved
            # memory is the constraint, and depth x chunk of staged
            # prefetch buffers works against exactly that relief.
            # Registered in the same _restore list, so depth and
            # chunk_rows roll back TOGETHER after the walk (hasattr:
            # test stubs model only the fields they exercise)
            if hasattr(ex, "prefetch_depth"):
                sched["_restore"].append(
                    (ex, "prefetch_depth", ex.prefetch_depth))
                ex.prefetch_depth = 0
        # deliberately NOT a TaskFailureCollector notification: a
        # reschedule is a scheduling decision, not a recovered task
        # failure — the summary's placement/reschedules/ladder fields
        # and query_reschedules_total carry the signal without turning
        # every walked query into CompletedWithTaskFailures
        print(f"RESCHEDULED {qname or 'query'} -> {rung} "
              f"(ladder {'->'.join(sched['ladder'])})")

    # ------------------------------------------- demotion / promotion

    def _note_success(self, rescheduled: bool,
                      qname: "str | None" = None,
                      sched: "dict | None" = None) -> None:
        hwm = memwatch.high_water()
        if hwm and not self._multi:
            # the HWM history is RANK-LOCAL: feeding it to the cost
            # model on a multi-process world would let one rank's
            # observed peak start a query at a different placement
            # than its peers compute — the silent-divergence deadlock
            # the consensus step exists to prevent. Single-process
            # pipelines (where the initial choice needs no agreement)
            # use it freely. The device's high-water is the whole
            # process's: what was on the device at placement and is
            # not this statement's (a resident warehouse, another
            # session's buffers) comes off it first.
            others = (sched if sched is not None
                      else self.last_schedule).get("_others_bytes", 0)
            self.cost_model.observe(
                qname or self._current_query(),
                max(hwm.get("device_hwm_bytes", 0) - others, 0))
        if self._multi:
            return  # demotion/promotion run in the boundary vote
        if rescheduled:
            self._clean_streak = 0
            self._reschedule_streak += 1
            if (self._demoted_to is None
                    and self._reschedule_streak >= self.demote_after
                    and self.ladder_on):
                self._switch_start(self.last_schedule.get("placement"))
        else:
            self._reschedule_streak = 0
            if self._demoted_to is not None:
                self._clean_streak += 1
                if self._clean_streak >= self.promote_after:
                    self._promote()

    def _note_failure(self) -> None:
        """A query that exhausted the whole ladder counts toward the
        sticky demotion too — the old FALLBACK_AFTER contract, now
        reversible."""
        if self._multi:
            return  # demotion/promotion run in the boundary vote
        self._clean_streak = 0
        self._reschedule_streak += 1
        if (self._demoted_to is None
                and self._reschedule_streak >= self.demote_after
                and self.ladder_on and len(self.universe) > 1):
            self._switch_start(self.floor)

    def _boundary_vote(self, failed: bool) -> None:
        """Multi-rank placement protocol: one consensus round per
        query, entered by EVERY rank regardless of its local outcome,
        so the allgathers pair deterministically. Each rank votes the
        start-rung it wants next (its local streaks shape the vote;
        the SHARED outcome shapes the state), the deepest demotion
        wins, and either every rank switches or — on a failed/partial
        gather — none does."""
        order = list(self.universe)
        cur = order.index(self._demoted_to) if self._demoted_to else 0
        if failed:
            self._clean_streak = 0
            self._reschedule_streak += 1
            want = cur
            if (self.ladder_on and len(order) > 1
                    and self._reschedule_streak >= self.demote_after):
                floor_i = (order.index(self.floor)
                           if self.floor in order else len(order) - 1)
                want = min(cur + 1, floor_i)
        else:
            self._reschedule_streak = 0
            want = cur
            if cur:
                self._clean_streak += 1
                if self._clean_streak >= self.promote_after:
                    want = 0
        agreed = self.consensus.decide(want)
        if agreed is None:
            return  # no agreement: nobody moves
        agreed = min(agreed, len(order) - 1)
        new = None if agreed == 0 else order[agreed]
        if new == self._demoted_to:
            return
        if new is None:
            self._demoted_to = None
            self._reschedule_streak = 0
            self._clean_streak = 0
            self._just_promoted = True
            obs_metrics.counter("placement_promotions_total").inc()
            print("PLACEMENT PROMOTION: stream restored to the cost "
                  "model's placement after clean queries")
        else:
            self._demoted_to = new
            self._clean_streak = 0
            obs_metrics.counter("placement_demotions_total").inc()
            print(f"PLACEMENT DEMOTION: stream now starts at "
                  f"{new!r} (consensus)")

    def _switch_start(self, target: "str | None") -> None:
        if not target or target == self.universe[0]:
            return
        vote = list(self.universe).index(target) \
            if target in self.universe else len(self.universe) - 1
        agreed = self.consensus.decide(vote)
        if agreed is None:
            return
        agreed = min(agreed, len(self.universe) - 1)
        self._demoted_to = self.universe[agreed]
        self._clean_streak = 0
        obs_metrics.counter("placement_demotions_total").inc()
        print(f"PLACEMENT DEMOTION: stream now starts at "
              f"{self._demoted_to!r} after {self._reschedule_streak} "
              f"consecutive rescheduled queries")

    def _promote(self) -> None:
        agreed = self.consensus.decide(0)
        if agreed is None or agreed != 0:
            # some rank still wants the demotion: stay put, retry the
            # promotion after the next clean streak
            self._clean_streak = 0
            return
        self._demoted_to = None
        self._reschedule_streak = 0
        self._clean_streak = 0
        self._just_promoted = True
        obs_metrics.counter("placement_promotions_total").inc()
        print("PLACEMENT PROMOTION: stream restored to the cost "
              "model's placement after clean queries")


def make_pipeline(config, backend: "str | None" = None
                  ) -> ExecutionPipeline:
    """Build the pipeline a Session uses as its executor factory, from
    an EngineConfig — the single construction point make_session
    (utils/power_core.py) routes every backend through."""
    backend = backend or config.get("engine.backend", "cpu")
    mesh = None
    stream_bytes = config.get_int("engine.stream_bytes", 0)
    chunk_rows = config.get_int("engine.chunk_rows", 0) or None
    precision = "f64"
    if backend == "tpu" and config.get_bool("engine.floats"):
        precision = config.get("engine.precision", "f64")
    if backend == "distributed":
        from nds_tpu.parallel import multihost
        multihost.maybe_initialize()
        shards = config.get_int("engine.mesh.shards", 0)
        mesh = multihost.global_mesh(shards if shards > 1 else None)
    return ExecutionPipeline(
        backend=backend, config=config, mesh=mesh, precision=precision,
        stream_bytes=stream_bytes, chunk_rows=chunk_rows)
