"""Per-query device-memory high-water-mark tracking.

"Large Scale Distributed Linear Algebra With Tensor Processing Units"
(PAPERS.md) plans its whole decomposition around explicit per-chip
memory budgets; this engine had no per-query memory signal at all — an
OOM was the first and only indication a query was near the edge.  This
module is the gauge: a process-global tracker the executors feed, read
out once per query by the power loop into the BenchReport ``memory``
block (``{"device_hwm_bytes": int, "source": "device"|"accounted"}``).

Two signal sources, best available wins:

- **Device stats** (``source="device"``): ``jax`` device
  ``memory_stats()["bytes_in_use"]`` summed across addressable
  devices, sampled at the bracketing points the executors already own
  (post-dispatch, post-materialize).  Only consulted in a process
  that already imported jax (harness-only paths never pay the import).
- **Live-buffer accounting** (``source="accounted"``): executors
  ``add_live``/``sub_live`` the bytes they upload (scan buffers, chunk
  windows); the high-water mark is the max concurrent total.  This is
  the fallback on backends without allocator stats (CPU, virtual mesh)
  and the only signal the pure-pandas CPU oracle has.

The HWM is monotone within a query and resets between queries
(``reset_query()`` in the power loop); the current value also lands on
the ``device_hwm_bytes`` metrics gauge so live snapshots
(obs/snapshot.py) expose it mid-run.
"""

from __future__ import annotations

from nds_tpu.analysis import locksan

_LOCK = locksan.lock("obs.memwatch._LOCK")


def table_bytes(table) -> int:
    """Host-side byte size of a HostTable (values + null masks) — the
    unit of live-buffer accounting for executors that never upload."""
    total = 0
    for c in table.columns.values():
        total += c.values.nbytes
        if c.null_mask is not None:
            total += c.null_mask.nbytes
    return total


def _device_bytes_in_use() -> int | None:
    """Sum of ``bytes_in_use`` across the process's jax devices, or
    None when stats are unavailable. Never INITIATES the jax import:
    the telemetry sampler (obs/telemetry.py) calls this from a daemon
    thread, and a thread-side ``import jax`` racing the main thread's
    first import deadlock-breaks into partially-initialized modules."""
    import sys
    mod = sys.modules.get("jax")
    if mod is None or getattr(getattr(mod, "__spec__", None),
                              "_initializing", False):
        return None
    try:
        total, seen = 0, False
        for d in mod.devices():
            stats = getattr(d, "memory_stats", None)
            stats = stats() if callable(stats) else None
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                seen = True
        return total if seen else None
    except Exception:  # noqa: BLE001 - gauge must never fail a query
        return None


class MemoryTracker:
    """Monotone-within-query high-water mark over both signal
    sources."""

    def __init__(self) -> None:
        self._live = 0          # accounted live-buffer bytes
        self._hwm = 0
        self._source = "accounted"

    # ------------------------------------------------------- accounting

    def reset_query(self) -> None:
        """Start a fresh query window. Accounted live bytes CARRY OVER
        (session-pooled scan buffers outlive queries); only the
        high-water mark resets — to the current live level, so the new
        query's HWM reflects what is resident while IT runs."""
        with _LOCK:
            self._hwm = self._live
            self._source = "accounted"
            self._publish()

    def add_live(self, nbytes: float) -> None:
        with _LOCK:
            self._live += int(nbytes)
            if self._live > self._hwm:
                self._hwm = self._live
                self._publish()

    def sub_live(self, nbytes: float) -> None:
        with _LOCK:
            self._live = max(0, self._live - int(nbytes))

    def sample_device(self) -> None:
        """Fold an allocator reading into the HWM (device stats
        dominate accounting whenever available)."""
        v = _device_bytes_in_use()
        if v is None:
            return
        with _LOCK:
            self._source = "device"
            if v > self._hwm:
                self._hwm = v
                self._publish()

    def _publish(self) -> None:
        # inside _LOCK; the metrics registry has its own lock and never
        # takes this one, so the ordering cannot deadlock
        from nds_tpu.obs import metrics as obs_metrics
        obs_metrics.gauge("device_hwm_bytes").set(self._hwm)

    # ---------------------------------------------------------- readout

    def live(self) -> int:
        """CURRENT usage (not the HWM): allocator ``bytes_in_use`` when
        a jax backend is live, else the accounted live-buffer total —
        the pre-admission signal the scheduler's memory governor
        (engine/scheduler.MemoryGovernor) projects forward before
        dispatching a query."""
        v = _device_bytes_in_use()
        if v is not None:
            return v
        with _LOCK:
            return self._live

    def high_water(self) -> dict | None:
        """BenchReport ``memory`` block, or None when the query touched
        no tracked memory (the harness-only paths)."""
        with _LOCK:
            if self._hwm <= 0:
                return None
            return {"device_hwm_bytes": self._hwm,
                    "source": self._source}


TRACKER = MemoryTracker()


def reset_query() -> None:
    TRACKER.reset_query()


def add_live(nbytes: float) -> None:
    TRACKER.add_live(nbytes)


def sub_live(nbytes: float) -> None:
    TRACKER.sub_live(nbytes)


def sample_device() -> None:
    TRACKER.sample_device()


def live_bytes() -> int:
    return TRACKER.live()


def high_water() -> dict | None:
    return TRACKER.high_water()
