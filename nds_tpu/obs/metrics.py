"""Engine-wide metrics registry: counters, gauges, histograms.

The reference gets its operational counters (task retries, shuffle
spills, bytes read) from Spark's metrics system for free; this is the
in-process equivalent for the TPU engine.  One global registry, named
instruments created on first use, thread-safe behind a single lock
(instrument updates are query-granularity events, never per-row, so
one lock is cheaper than per-instrument locking everywhere).

Metric names in use across the stack (documented in README
"Observability"):

- ``queries_total`` / ``query_failures_total`` / ``query_seconds`` —
  power loop (utils/power_core.py)
- ``plans_total`` — SQL planner
- ``device_executions_total`` / ``compiles_total`` /
  ``recompiles_total`` / ``slack_retries_total`` /
  ``bytes_scanned_total`` — device executors
- ``device_readbacks_total`` / ``readback_bytes_total`` /
  ``device_uploads_total`` / ``upload_bytes_total`` /
  ``scan_view_hits_total`` / ``scan_view_misses_total`` /
  ``program_bytes_accessed_total`` — the base device executor's
  host<->device crossings, counted where it makes them (the same
  numbers ride the ``device.readback`` / ``device.bind`` /
  ``device.launch`` spans as attributes)
- ``plan_cache_hits_total`` / ``plan_cache_misses_total`` — the
  Session's text -> plan cache
- ``staged_subprograms_total`` — host-staged plan splitting
- ``exchanges_traced_total`` / ``exchange_overflow_retries_total`` /
  ``exchange_overflow_rows_total`` — distributed exchange;
  ``exchange_rows_total`` / ``exchange_bytes_total`` — bucket capacity
  and bytes one chip hands to all_to_all, added at every launch of a
  sharded program (the same numbers ride its ``device.launch`` span);
  ``replicate_bytes_total`` — bytes one chip receives through the
  program's replicates (all_gather of a sharded relation), likewise
- ``chunk_scans_total`` / ``chunk_fallbacks_total`` /
  ``chunk_shrink_total`` — out-of-core executor
- ``task_failures_total`` — TaskFailureCollector bridge
  (utils/report.py)
- ``faults_injected_total`` / ``query_retries_total`` /
  ``query_deadline_exceeded_total`` — resilience layer
  (nds_tpu/resilience/)
- ``query_reschedules_total`` / ``placement_consensus_total`` /
  ``placement_demotions_total`` / ``placement_promotions_total`` —
  unified execution pipeline (engine/scheduler.py)

Per-query deltas (``delta(before, after)``) land in each BenchReport
JSON under ``metrics``.
"""

from __future__ import annotations

from collections import deque

from nds_tpu.analysis import locksan


class Counter:
    """Monotonic accumulator (floats allowed: bytes_scanned_total)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str, lock):
        self.name = name
        self.value = 0
        self._lock = lock

    def inc(self, n: int | float = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Last-write-wins value (e.g. live compile-cache entries)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str, lock):
        self.name = name
        self.value = 0
        self._lock = lock

    def set(self, v: int | float) -> None:
        with self._lock:
            self.value = v


class Histogram:
    """count/sum/min/max plus p50/p95/p99 — latency distributions at
    query granularity without bucket-boundary bikeshedding. Quantiles
    come from a bounded window of the most recent observations (a
    99-query power run fits entirely; beyond that the tail quantiles
    track recent behavior, which is what a live snapshot wants)."""

    # recent-observation window the quantiles are computed over
    WINDOW = 2048

    __slots__ = ("name", "count", "sum", "min", "max", "_samples",
                 "_lock")

    def __init__(self, name: str, lock):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None
        self._samples: deque = deque(maxlen=self.WINDOW)
        self._lock = lock

    def observe(self, v: float) -> None:
        with self._lock:
            self.count += 1
            self.sum += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            self._samples.append(v)

    def _percentiles_locked(self) -> dict:
        s = sorted(self._samples)
        if not s:
            return {}
        n = len(s)
        return {f"p{q}": s[min(n - 1, max(0, (q * n + 99) // 100 - 1))]
                for q in (50, 95, 99)}

    def percentiles(self) -> dict:
        """Nearest-rank p50/p95/p99 over the recent-sample window
        ({} before the first observation)."""
        with self._lock:
            return self._percentiles_locked()

    def summary(self) -> dict:
        with self._lock:
            return {"count": self.count, "sum": self.sum,
                    "min": self.min, "max": self.max,
                    **self._percentiles_locked()}


class MetricsRegistry:
    """One lock for the registry AND every instrument it creates —
    REENTRANT, so snapshot() can roll up instrument summaries while
    holding it and instruments can guard their own reads for direct
    callers. Instrument updates are query-granularity events, never
    per-row, so one shared lock stays cheaper than per-instrument
    locking everywhere."""

    def __init__(self) -> None:
        self._lock = locksan.rlock("obs.MetricsRegistry._lock")
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name, self._lock)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name, self._lock)
            return g

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name, self._lock)
            return h

    def snapshot(self) -> dict:
        """Point-in-time copy: {"counters": {...}, "gauges": {...},
        "histograms": {name: {count,sum,min,max}}}."""
        with self._lock:
            return {
                "counters": {n: c.value
                             for n, c in self._counters.items()},
                "gauges": {n: g.value for n, g in self._gauges.items()},
                "histograms": {n: h.summary()
                               for n, h in self._histograms.items()},
            }

    def reset(self) -> None:
        """Drop every instrument (tests)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


def delta(before: dict, after: dict) -> dict:
    """What changed between two snapshots, for per-query attribution:
    counter increments, histogram count/sum increments, current gauge
    values. Unchanged instruments are omitted."""
    out: dict = {}
    counters = {}
    for name, v in after.get("counters", {}).items():
        d = v - before.get("counters", {}).get(name, 0)
        if d:
            counters[name] = d
    if counters:
        out["counters"] = counters
    gauges = {
        name: v for name, v in after.get("gauges", {}).items()
        if before.get("gauges", {}).get(name) != v}
    if gauges:
        out["gauges"] = gauges
    hists = {}
    for name, h in after.get("histograms", {}).items():
        b = before.get("histograms", {}).get(
            name, {"count": 0, "sum": 0.0})
        dc = h["count"] - b["count"]
        if dc:
            entry = {"count": dc, "sum": h["sum"] - b["sum"]}
            # quantiles are distribution state, not increments: carry
            # the AFTER snapshot's values so each BenchReport shows the
            # latency distribution as of that query
            entry.update({k: h[k] for k in ("p50", "p95", "p99")
                          if k in h})
            hists[name] = entry
    if hists:
        out["histograms"] = hists
    return out


def labeled(name: str, **labels) -> str:
    """Instrument name carrying OpenMetrics-style labels:
    ``labeled("server_requests_total", tenant="a")`` ->
    ``server_requests_total{tenant="a"}``. The registry treats the
    whole string as the instrument key (one instrument per label set);
    the snapshot emitter (obs/snapshot.py) splits it back into family +
    labels when rendering the exposition."""
    if not labels:
        return name
    def esc(v) -> str:
        # OpenMetrics escaping (\\ then \"): distinct values must stay
        # distinct — deleting the metachars would collapse tenants
        # like 'acme' and 'acme"' onto one instrument
        return str(v).replace("\\", "\\\\").replace('"', '\\"')
    inner = ",".join(f'{k}="{esc(v)}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def split_labels(name: str) -> "tuple[str, str]":
    """(base_name, label_block) — label_block is '' or '{k="v",...}'."""
    i = name.find("{")
    if i < 0:
        return name, ""
    return name[:i], name[i:]


REGISTRY = MetricsRegistry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


def snapshot() -> dict:
    return REGISTRY.snapshot()
