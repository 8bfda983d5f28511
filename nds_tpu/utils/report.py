"""Per-query JSON summary reports.

Format-compatible with the reference's `nds/PysparkBenchReport.py:47-122`
summary dict (env/queryStatus/exceptions/startTime/queryTimes/query +
filename '{prefix}-{query}-{startTime}.json'), so downstream report
consumers keep working. Differences are TPU-native by design:

- env captures jax backend/devices instead of sparkConf/sparkVersion;
- "task failure" detection (reference: Scala SparkListener bridged over
  py4j, `nds/python_listener/PythonListener.py:21-61`) is an in-process
  failure collector — there is no JVM boundary in this stack;
- timing brackets call ``block_until_ready`` upstream so async dispatch
  cannot hide work (SURVEY.md §5 tracing note).

Schema additions over the reference format (README "Observability"):
the power loop attaches ``spans`` (the per-query span tree from
nds_tpu/obs/trace.py) and ``metrics`` (the per-query delta of the
global counter registry) to each summary; both are absent when the
corresponding subsystem recorded nothing. The resilience layer
(README "Resilience") adds ``retries`` plus, when set,
``gave_up_reason`` and ``deadline_exceeded`` via ``attach_retry``;
``attach_memory`` adds the per-query device-memory high-water mark
(``memory``, fed by obs/memwatch.py). ``tools/check_trace_schema.py
--summary`` validates the full shape.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from contextlib import contextmanager
from typing import Callable

from nds_tpu.analysis import locksan

_REDACTED_MARKERS = ("TOKEN", "SECRET", "PASSWORD", "KEY", "CREDENTIAL")


def redact_env(env: dict) -> dict:
    """Drop env vars whose *name* suggests a secret.

    Stricter than the reference (exact-name match on TOKEN/SECRET/PASSWORD,
    `PysparkBenchReport.py:72-73`): substring match plus KEY/CREDENTIAL.
    """
    return {
        k: v for k, v in env.items()
        if not any(m in k.upper() for m in _REDACTED_MARKERS)
    }


class TaskFailureCollector:
    """In-process stand-in for the reference's jvm/python listener chain.

    Engine internals append non-fatal anomalies (retries, padded-capacity
    overflows that were recovered by re-execution, host fallbacks). A query
    that completes with collected failures is reported
    'CompletedWithTaskFailures', matching `PysparkBenchReport.py:90-93`.
    """

    _active: list["TaskFailureCollector"] = []
    # concurrent throughput streams notify from their own threads; the
    # class-level listener list and each listener's failure store must
    # not race (lost appends silently under-report anomalies)
    _lock = locksan.lock("utils.TaskFailureCollector._lock")
    # per-thread focus stack: boundary pipelining (README "Pipelined
    # execution") keeps TWO report brackets — and therefore two
    # registered collectors — open at once on one thread; a focused
    # collector receives that thread's notifications EXCLUSIVELY, so
    # query N's recovered anomalies cannot cross-bill into query N+1's
    # summary (and vice versa). Empty stack = the legacy broadcast.
    _tls = threading.local()

    def __init__(self) -> None:
        # ordered UNIQUE reasons; repeats count in _counts so a noisy
        # anomaly (the same overflow retried 50 times) is one summary
        # line with a multiplier, not 50 identical lines
        self.failures: list[str] = []
        self._counts: dict[str, int] = {}

    def register(self) -> None:
        with TaskFailureCollector._lock:
            TaskFailureCollector._active.append(self)

    def unregister(self) -> None:
        with TaskFailureCollector._lock:
            if self in TaskFailureCollector._active:
                TaskFailureCollector._active.remove(self)

    def formatted(self) -> list[str]:
        """Unique reasons in first-seen order, deduplicated repeats
        annotated with their count."""
        with TaskFailureCollector._lock:
            return [r if self._counts[r] == 1 else
                    f"{r} (x{self._counts[r]})" for r in self.failures]

    @classmethod
    @contextmanager
    def focused(cls, collector: "TaskFailureCollector | None"):
        """Route the CALLING thread's notifications exclusively to one
        collector for the block (no-op on None): the dispatch/resolve
        halves of an overlapped query bracket each focus their own
        report's collector."""
        if collector is None:
            yield
            return
        stack = getattr(cls._tls, "stack", None)
        if stack is None:
            stack = cls._tls.stack = []
        stack.append(collector)
        try:
            yield
        finally:
            stack.pop()

    @classmethod
    def notify(cls, reason: str) -> None:
        """Called by engine internals on recoverable task-level
        failures. Every notification also increments the
        ``task_failures_total`` metrics counter, so anomaly volume is
        visible across a whole run even when no collector is
        registered (warmups, direct executor use)."""
        from nds_tpu.obs import metrics as obs_metrics
        obs_metrics.counter("task_failures_total").inc()
        stack = getattr(cls._tls, "stack", None)
        with cls._lock:
            for listener in (stack[-1],) if stack else cls._active:
                if reason in listener._counts:
                    listener._counts[reason] += 1
                else:
                    listener._counts[reason] = 1
                    listener.failures.append(reason)


class BenchReport:
    """Build and persist one per-query JSON summary."""

    def __init__(self, query_name: str, engine_info: dict | None = None) -> None:
        self.summary = {
            "env": {
                "envVars": {},
                "engineConf": {},
                "engineVersion": None,
            },
            "queryStatus": [],
            "exceptions": [],
            "startTime": None,
            "queryTimes": [],
            "query": query_name,
        }
        self._engine_info = engine_info or {}
        self._collector: "TaskFailureCollector | None" = None

    def capture_env(self) -> None:
        self.summary["env"]["envVars"] = redact_env(dict(os.environ))
        conf = dict(self._engine_info)
        try:
            import jax
            self.summary["env"]["engineVersion"] = f"jax-{jax.__version__}"
        except Exception:  # jax optional for harness-only paths
            jax = None
            self.summary["env"]["engineVersion"] = "cpu-harness"
        if jax is not None:
            # every summary names the device its numbers came from: a
            # run pinned to JAX_PLATFORMS=cpu says "platform: cpu" and
            # can never be read as a device time
            dev = jax.devices()[0]
            conf.setdefault("platform", dev.platform)
            conf.setdefault("device_kind", dev.device_kind)
            conf.setdefault("device_count", jax.device_count())
        self.summary["env"]["engineConf"] = {str(k): str(v) for k, v in conf.items()}

    def report_on(self, fn: Callable, *args):
        """Run fn(*args), recording status/exception/elapsed-ms.

        Statuses: Completed | CompletedWithTaskFailures | Failed — the same
        vocabulary the reference emits (`PysparkBenchReport.py:90-103`).
        """
        self.capture_env()
        collector = TaskFailureCollector()
        collector.register()
        start_time = int(time.time() * 1000)
        try:
            fn(*args)
            end_time = int(time.time() * 1000)
            if collector.failures:
                self.summary["queryStatus"].append("CompletedWithTaskFailures")
                self.summary["exceptions"].extend(collector.formatted())
            else:
                self.summary["queryStatus"].append("Completed")
        except Exception as e:
            print("ERROR BEGIN")
            traceback.print_exc()
            print("ERROR END")
            end_time = int(time.time() * 1000)
            self.summary["queryStatus"].append("Failed")
            self.summary["exceptions"].append(str(e))
        finally:
            collector.unregister()
        self.summary["startTime"] = start_time
        self.summary["queryTimes"].append(end_time - start_time)
        return self.summary

    def begin_async(self) -> None:
        """Open the report bracket without a body: the split form of
        ``report_on`` the query-boundary pipelining uses (README
        "Pipelined execution") — the dispatch half runs now, the
        result() half may run after the NEXT query dispatched, and
        ``end_async`` closes the bracket with the same status
        vocabulary. The bracket endpoints are dispatch-start and
        result-done, the same contract the throughput loop's
        dispatch->result walls already use."""
        self.capture_env()
        self._collector = TaskFailureCollector()
        self._collector.register()
        self._t0 = int(time.time() * 1000)

    def focus_failures(self):
        """Context manager for the dispatch/resolve halves of an open
        ``begin_async`` bracket: this thread's TaskFailureCollector
        notifications go to THIS report only (under boundary
        pipelining two brackets' collectors are registered at once —
        broadcast would cross-bill one query's recovered anomalies
        into the other's summary). No-op before begin_async."""
        return TaskFailureCollector.focused(self._collector)

    def end_async(self, error: "BaseException | None" = None):
        """Close a ``begin_async`` bracket: status/exception/elapsed
        recording identical to ``report_on``'s (Completed |
        CompletedWithTaskFailures | Failed)."""
        end_time = int(time.time() * 1000)
        collector = self._collector
        self._collector = None
        collector.unregister()
        if error is not None:
            print("ERROR BEGIN")
            traceback.print_exception(type(error), error,
                                      error.__traceback__)
            print("ERROR END")
            self.summary["queryStatus"].append("Failed")
            self.summary["exceptions"].append(str(error))
        elif collector.failures:
            self.summary["queryStatus"].append(
                "CompletedWithTaskFailures")
            self.summary["exceptions"].extend(collector.formatted())
        else:
            self.summary["queryStatus"].append("Completed")
        self.summary["startTime"] = self._t0
        self.summary["queryTimes"].append(end_time - self._t0)
        return self.summary

    def attach_retry(self, stats) -> None:
        """Record a resilience.retry.RetryStats into the summary:
        ``retries`` always (0 is meaningful — the query needed no
        recovery), ``gave_up_reason`` / ``deadline_exceeded`` only
        when set (README "Resilience" schema)."""
        self.summary["retries"] = stats.retries
        if stats.retries:
            # how much of the query's wall clock was backoff, so a
            # retried query's TimeLog row can be decomposed
            self.summary["retry_backoff_s"] = round(stats.backoff_s, 3)
        if stats.gave_up_reason:
            self.summary["gave_up_reason"] = stats.gave_up_reason
        if stats.deadline_exceeded:
            self.summary["deadline_exceeded"] = True

    def attach_schedule(self, sched: dict | None) -> None:
        """Record the pipeline's scheduling decision
        (engine/scheduler.py): ``placement`` (the placement that served
        the query) and ``reschedules`` always when the pipeline ran;
        ``ladder`` (the rungs walked) only when the query was
        rescheduled; ``promoted_back`` only on the query where a
        stream promotion took effect (README "Placement &
        degradation" schema)."""
        if not sched or "placement" not in sched:
            return
        self.summary["placement"] = sched["placement"]
        self.summary["reschedules"] = int(sched.get("reschedules", 0))
        if sched.get("reschedules"):
            self.summary["ladder"] = list(sched.get("ladder", []))
        if sched.get("promoted_back"):
            self.summary["promoted_back"] = True
        if sched.get("governed"):
            # the memory governor demoted/pre-shrank this query BEFORE
            # dispatch (engine/scheduler.MemoryGovernor)
            self.summary["governed"] = True
        if sched.get("prefetch_depth") is not None:
            # governor depth admission lowered the phase-A prefetch
            # depth for this query (engine/pipeline_io.py; depth
            # demotes before placement)
            self.summary["prefetch_depth"] = int(sched["prefetch_depth"])

    def attach_cache(self, mdelta: dict | None,
                     timings: dict | None = None) -> None:
        """Record the query's persistent plan-cache activity (README
        "Plan cache") as the ``cache`` block, derived from the
        per-query metrics delta: ``{"hits": int, "misses": int}``
        always when the cache was consulted, plus ``errors`` /
        ``bytes_read`` / ``bytes_written`` / ``load_ms`` (deserialize
        wall-clock from engineTimings' ``cache_load_ms``) when
        non-zero. Absent entirely when no plan cache is active — the
        pre-cache summary shape is unchanged."""
        counters = (mdelta or {}).get("counters", {})
        hits = counters.get("compile_cache_hits_total", 0)
        misses = counters.get("compile_cache_misses_total", 0)
        errors = counters.get("compile_cache_errors_total", 0)
        if not (hits or misses or errors):
            return
        block = {"hits": int(hits), "misses": int(misses)}
        if errors:
            block["errors"] = int(errors)
        for key, name in (("bytes_read",
                           "compile_cache_bytes_read_total"),
                          ("bytes_written",
                           "compile_cache_bytes_written_total")):
            if counters.get(name):
                block[key] = int(counters[name])
        load_ms = (timings or {}).get("cache_load_ms")
        if load_ms:
            block["load_ms"] = round(load_ms, 3)
        self.summary["cache"] = block

    def attach_kernels(self, timings: dict | None) -> None:
        """Record which relational kernels the query's compiled
        program actually used (engine/kernels.py trace counts, carried
        in engineTimings' dunder side-channel) as the ``kernels``
        block: ``{"join.direct": 2, "semi.bitmask": 4, ...}``. Absent
        for queries with no kernel-lowered operators (pure scans, the
        CPU oracle). ``ndsreport diff`` watches this block for silent
        demotions — a planner regression that drops q21 back to
        ``join.sortmerge`` fails the gate like a compile-count change
        does."""
        kern = (timings or {}).get("__kernels")
        if kern:
            self.summary["kernels"] = {str(k): int(v)
                                       for k, v in sorted(kern.items())}

    def attach_profile(self, info: dict | None) -> None:
        """Record an on-demand XLA profiler capture (obs/profile.py)
        as the ``profile`` block: ``{"path", "trigger", "bytes"}``.
        Absent when no trigger fired for this query — the common
        summary shape is unchanged."""
        if info and info.get("path"):
            block = {"path": str(info["path"]),
                     "trigger": str(info.get("trigger", "query"))}
            if "bytes" in info:
                block["bytes"] = int(info["bytes"])
            self.summary["profile"] = block

    def attach_flight(self, path: str | None,
                      reason: str | None = None,
                      entries: int | None = None) -> None:
        """Record a flight-recorder dump (obs/fleet.py) triggered by
        this query's final failure as the ``flight`` block:
        ``{"path", "reason", "entries"}`` — the summary points at the
        post-mortem instead of leaving it to a directory listing."""
        if path:
            block: dict = {"path": str(path)}
            if reason:
                block["reason"] = str(reason)
            if entries is not None:
                block["entries"] = int(entries)
            self.summary["flight"] = block

    def attach_tenant(self, tenant: str | None) -> None:
        """Serving-layer attribution (nds_tpu/serve/): which tenant
        submitted the request this summary bills. Absent on benchmark
        summaries; ndsreport analyze groups per-tenant latency
        quantiles over it."""
        if tenant:
            self.summary["tenant"] = str(tenant)

    def attach_replica(self, replica: str | None) -> None:
        """Fleet attribution (nds_tpu/serve/fleet.py): which engine
        replica answered the request this summary bills. Absent on
        single-process serving; ndsreport analyze rolls per-replica
        latency quantiles over it and flags divergent replicas."""
        if replica:
            self.summary["replica"] = str(replica)

    def attach_incarnation(self, incarnation: int | None) -> None:
        """Record which resume incarnation produced this summary
        (resilience/journal.QueryJournal). 0 = the original process;
        a resumed process stamps 1, 2, ... — ``merge_incarnations``
        and ndsreport's merged billing key on it."""
        if incarnation is not None:
            self.summary["incarnation"] = int(incarnation)

    def attach_result_digest(self, digest: str | None) -> None:
        """Record the query result's content fingerprint
        (io/result_io.result_digest) — the value the soak gate compares
        between an interrupted-then-resumed run and a clean one."""
        if digest:
            self.summary["result_digest"] = str(digest)

    def attach_degradations(self) -> None:
        """Surface torn-state degradations in the summary: nonzero
        ``journal_resets_total`` / ``snapshot_resets_total`` mean prior
        on-disk state was thrown away somewhere in this process — a
        silent fresh start must be visible in every summary it could
        have affected, not only in a log line that scrolled away."""
        from nds_tpu.obs import metrics as obs_metrics
        counters = obs_metrics.snapshot().get("counters", {})
        block = {}
        for key, name in (("journal_resets", "journal_resets_total"),
                          ("snapshot_resets", "snapshot_resets_total")):
            if counters.get(name):
                block[key] = int(counters[name])
        if block:
            self.summary["degradations"] = block

    def attach_memory(self, hwm: dict | None) -> None:
        """Record the per-query device-memory high-water mark
        (obs/memwatch.py) as the ``memory`` block:
        ``{"device_hwm_bytes": int, "source": "device"|"accounted"}``.
        Absent when the query touched no tracked memory (README
        "Observability" schema)."""
        if hwm:
            self.summary["memory"] = dict(hwm)

    def attach_cost(self, block: dict | None) -> None:
        """Record the compiler-truth cost ledger (obs/costs.py) as the
        ``cost`` block: summed XLA cost_analysis (flops/bytes/
        transcendentals), maxed memory_analysis sizes, the per-kind
        program census, and the ops_est cross-check. Absent when the
        query dispatched no compiled programs (CPU oracle, harness
        paths) — pre-cost summaries keep their shape."""
        if block:
            self.summary["cost"] = dict(block)

    def attach_telemetry(self, block: dict | None) -> None:
        """Record the per-query HBM-occupancy time series summary
        (obs/telemetry.py) as the ``telemetry`` block. Absent when the
        sampler is off or the backend has no allocator stats — CPU
        summaries stay byte-identical to pre-telemetry runs."""
        if block:
            self.summary["telemetry"] = dict(block)

    def write_summary(self, prefix: str = "",
                      out_dir: str | None = None) -> str:
        """Write '{prefix}-{query}-{startTime}.json' (reference filename
        contract, `PysparkBenchReport.py:117-119`), into ``out_dir``
        when given (the recorded ``filename`` stays bare either way),
        and return the written path."""
        filename = f"{prefix}-{self.summary['query']}-{self.summary['startTime']}.json"
        self.summary["filename"] = filename
        path = (os.path.join(out_dir, filename) if out_dir
                else filename)
        with open(path, "w") as f:
            # ndslint: waive[NDS109] -- filename embeds query+startTime so every write is to a fresh unique path; no reader races a first write
            json.dump(self.summary, f, indent=2)
        return path

    def is_success(self) -> bool:
        return self.summary["queryStatus"] == ["Completed"]


def merge_incarnations(summaries: list, phase: str = "") -> dict:
    """Merge the partial per-query BenchReports of EVERY incarnation of
    a resumed phase into one phase report (README "Preemption &
    resume"): one entry per statement, where a statement reported by
    more than one incarnation (the kill-between-summary-and-journal
    window) is billed ONCE, by its latest (incarnation, startTime)
    report — the same rule ``ndsreport analyze`` applies, so the merged
    report and the analysis agree by construction. The merged wall
    clock is the sum of per-query walls: the only phase total that is
    invariant under where the interruptions fell."""
    best: dict = {}
    for s in summaries:
        if not isinstance(s, dict) or "query" not in s \
                or "queryStatus" not in s:
            continue
        q = str(s["query"])
        key = (int(s.get("incarnation") or 0), s.get("startTime") or 0)
        if q not in best or key > best[q][0]:
            best[q] = (key, s)
    ordered = sorted(best.values(), key=lambda kv: kv[1].get(
        "startTime") or 0)
    merged: dict = {
        "phase": phase,
        "merged": True,
        "incarnations": max((k[0] for k, _s in ordered),
                            default=0) + 1,
        "queries": [s["query"] for _k, s in ordered],
        "queryStatus": [s["queryStatus"][-1] if s.get("queryStatus")
                        else "Failed" for _k, s in ordered],
        "queryTimes": [(s.get("queryTimes") or [0])[-1]
                       for _k, s in ordered],
        "startTime": min((s.get("startTime") or 0
                          for _k, s in ordered), default=0),
    }
    merged["wall_ms_total"] = sum(merged["queryTimes"])
    digests = {s["query"]: s["result_digest"] for _k, s in ordered
               if s.get("result_digest")}
    if digests:
        merged["result_digests"] = digests
    return merged
