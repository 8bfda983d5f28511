"""Live device-memory telemetry: HBM occupancy over time, not one HWM.

memwatch answers "how high did it get"; it cannot answer "when, for
how long, and was it climbing" — the questions an OOM post-mortem or
a prefetch-depth decision actually asks. This module is the
time-series twin: a background daemon thread (the
``obs/snapshot.MetricsSnapshotter`` lifecycle pattern) samples summed
per-device ``memory_stats()["bytes_in_use"]`` every
``obs.telemetry.interval_ms`` into a bounded ring of
``(perf_counter_t, bytes)`` samples, and three readouts drain it:

- ``query_block()`` — the per-query BenchReport ``telemetry`` block:
  sample count, interval, and an HBM min/max/mean plus a decimated
  ``series`` of ``[t_offset_ms, bytes]`` points (at most
  SERIES_MAX_POINTS — a summary, not a firehose);
- ``snapshot_block()`` — the live-metrics-snapshot lane
  (obs/snapshot.py) so a watcher sees occupancy mid-run;
- ``drain_counter_events()`` — timestamped samples for Chrome-trace
  counter lanes (obs/trace.export_counters) so Perfetto renders a
  device-memory track under the span tree.

Backends without allocator stats (CPU, virtual mesh) are a graceful
no-op: the default reader is memwatch's device probe, which returns
None there, so the ring stays empty, every block is None, and summaries/snapshots
keep their pre-telemetry shape byte-identically.

Config: ``obs.telemetry.enabled`` (default on — the sampler is idle
on no-stats backends anyway) and ``obs.telemetry.interval_ms``
(default 250). Env ``NDS_TPU_TELEMETRY`` overrides: ``off``/``0``
disables, a number becomes the interval in ms. All mutation is under
one locksan-registered lock; start/stop are idempotent.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from nds_tpu.analysis import locksan

_LOCK = locksan.lock("obs.telemetry._LOCK")

TELEMETRY_ENV = "NDS_TPU_TELEMETRY"
DEFAULT_INTERVAL_MS = 250
DEFAULT_CAPACITY = 512
SERIES_MAX_POINTS = 64


def _decimate(samples: list) -> list:
    """At most SERIES_MAX_POINTS evenly-strided samples, endpoints
    kept — the block is a shape summary, not a raw dump."""
    n = len(samples)
    if n <= SERIES_MAX_POINTS:
        return list(samples)
    stride = (n - 1) / (SERIES_MAX_POINTS - 1)
    return [samples[min(n - 1, round(i * stride))]
            for i in range(SERIES_MAX_POINTS)]


class TelemetrySampler:
    """Bounded-ring background sampler of device bytes-in-use."""

    def __init__(self, interval_ms: float = DEFAULT_INTERVAL_MS,
                 capacity: int = DEFAULT_CAPACITY, read_fn=None):
        from nds_tpu.obs import memwatch
        self.interval_ms = max(1.0, float(interval_ms))
        self.capacity = max(2, int(capacity))
        self._read_fn = read_fn or memwatch._device_bytes_in_use
        self._ring: deque = deque(maxlen=self.capacity)
        self._query_t0 = time.perf_counter()
        self._drained_t = float("-inf")
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    # --------------------------------------------------------- lifecycle

    def start(self) -> "TelemetrySampler":
        """Idempotent: a running sampler keeps running."""
        with _LOCK:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="nds-tpu-telemetry",
                daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        """Idempotent; takes one final sample so short windows still
        carry at least one point on stats-capable backends."""
        with _LOCK:
            thread, self._thread = self._thread, None
        if thread is None:
            return
        self._stop.set()
        thread.join(timeout=5.0)
        self.sample()

    def running(self) -> bool:
        with _LOCK:
            return self._thread is not None

    def _loop(self) -> None:
        # sample at t=0, then every interval until stopped
        self.sample()
        while not self._stop.wait(self.interval_ms / 1000.0):
            self.sample()

    # ---------------------------------------------------------- sampling

    def sample(self) -> None:
        """One reading into the ring; silently nothing on backends
        without stats (telemetry must never fail or slow a query)."""
        try:
            v = self._read_fn()
        except Exception:  # noqa: BLE001 - gauge, not a query step
            v = None
        if v is None:
            return
        t = time.perf_counter()
        with _LOCK:
            self._ring.append((t, int(v)))

    # ---------------------------------------------------------- readouts

    def reset_query(self) -> None:
        """Open a fresh per-query window (the power loop's per-query
        reset point, next to memwatch.reset_query)."""
        with _LOCK:
            self._query_t0 = time.perf_counter()

    def _window(self) -> list:
        with _LOCK:
            t0 = self._query_t0
            return [s for s in self._ring if s[0] >= t0]

    def query_block(self) -> "dict | None":
        """BenchReport ``telemetry`` block for the current query
        window, or None when no samples landed (no-stats backends,
        sub-interval queries)."""
        window = self._window()
        if not window:
            return None
        t0 = window[0][0]
        vals = [b for _t, b in window]
        return {
            "samples": len(window),
            "interval_ms": self.interval_ms,
            "hbm": {
                "min_bytes": min(vals),
                "max_bytes": max(vals),
                "mean_bytes": int(sum(vals) / len(vals)),
                "series": [[round((t - t0) * 1000.0, 3), b]
                           for t, b in _decimate(window)],
            },
        }

    def snapshot_block(self) -> "dict | None":
        """Compact lane for the live metrics snapshot: ring-wide count
        plus the latest reading, or None when the ring is empty."""
        with _LOCK:
            if not self._ring:
                return None
            t, b = self._ring[-1]
            return {"samples": len(self._ring),
                    "interval_ms": self.interval_ms,
                    "last_bytes": b,
                    "age_s": round(time.perf_counter() - t, 3)}

    def drain_counter_events(self) -> list:
        """Samples newer than the previous drain, as ``(t, bytes)``
        with perf_counter timestamps (trace.py's clock) — the feed for
        Chrome counter lanes. The drain mark is independent of ring
        retention: each sample exports at most once."""
        with _LOCK:
            out = [s for s in self._ring if s[0] > self._drained_t]
            if out:
                self._drained_t = out[-1][0]
            return out


# ------------------------------------------------------ module lifecycle

_ACTIVE: "TelemetrySampler | None" = None


def configured_interval_ms(config=None) -> "float | None":
    """The effective sampling interval, or None when telemetry is
    disabled. Env NDS_TPU_TELEMETRY wins over ``obs.telemetry.*``
    config keys."""
    env = os.environ.get(TELEMETRY_ENV)
    if env is not None:
        env = env.strip().lower()
        if env in ("off", "0", "false", "no"):
            return None
        try:
            return max(1.0, float(env))
        except ValueError:
            pass  # unparseable env falls through to config
    if config is not None:
        try:
            if not config.get_bool("obs.telemetry.enabled", True):
                return None
            return float(config.get_int("obs.telemetry.interval_ms",
                                        DEFAULT_INTERVAL_MS))
        except Exception:  # noqa: BLE001 - config typo: use defaults
            return float(DEFAULT_INTERVAL_MS)
    return float(DEFAULT_INTERVAL_MS)


def start_from_config(config=None) -> "TelemetrySampler | None":
    """Start (or return the already-running) module sampler per
    config/env; None when disabled. The power loop's entry point."""
    global _ACTIVE
    interval = configured_interval_ms(config)
    if interval is None:
        return None
    with _LOCK:
        sampler = _ACTIVE
    if sampler is not None and sampler.running():
        return sampler
    sampler = TelemetrySampler(interval_ms=interval)
    with _LOCK:
        _ACTIVE = sampler
    return sampler.start()


def active() -> "TelemetrySampler | None":
    with _LOCK:
        return _ACTIVE


def stop() -> None:
    sampler = active()
    if sampler is not None:
        sampler.stop()


def reset_query() -> None:
    sampler = active()
    if sampler is not None:
        sampler.reset_query()


def query_block() -> "dict | None":
    sampler = active()
    return sampler.query_block() if sampler is not None else None


def snapshot_block() -> "dict | None":
    sampler = active()
    return sampler.snapshot_block() if sampler is not None else None


def drain_counter_events() -> list:
    sampler = active()
    return (sampler.drain_counter_events()
            if sampler is not None else [])
