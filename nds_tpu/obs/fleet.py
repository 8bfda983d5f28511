"""Fleet observability: per-rank trace shards, clock alignment, and
the always-on flight recorder.

Everything the obs layer built so far — tracer, metrics, memwatch,
snapshots — is rank-local and host-side: a multi-host run produces one
trace file per process with UNALIGNED clocks (each rank's Chrome ``ts``
is its own ``time.time()`` calibration), no way to tell which rank
stalled a collective, and no post-mortem at all when a supervisor
kills a wedged process before the trace buffer flushed. This module is
the fleet-side contract:

- **Per-rank trace shards** (``init_fleet``): on a multi-process world
  every rank re-points ``NDS_TPU_TRACE`` at its own
  ``<base>-r<rank>.jsonl`` shard (shared storage, no write collisions),
  pins the Chrome-trace export pid to the RANK (deterministic lanes —
  obs/trace.set_export_pid), and writes a ``fleet-r<rank>.json``
  sidecar stamped with ``(rank, world, host, pid, boot_offset_s)`` so
  ``ndsreport analyze`` can merge every shard into one clock-aligned
  fleet timeline (obs/analyze.py consumes the sidecars).

- **Clock handshake** (``clock_handshake``): an allgather barrier over
  the same DCN channel as the placement-consensus votes
  (parallel/multihost.gather_floats) — no rank's clock read happens
  before every rank entered the collective, so the readings are taken
  at (approximately) one fleet-wide instant and the per-rank offsets
  ``t_r - t_0`` correct exactly the clock basis the exported events
  are stamped with (obs/trace.epoch_offset). A failed gather degrades
  to unaligned shards (``aligned: false`` in the sidecar), never a
  hang.

- **Flight recorder** (``FlightRecorder``): a bounded in-memory ring
  of the last N completed span trees + per-query metric deltas,
  dumped ATOMICALLY to ``flight-r<rank>.json`` on watchdog stall (via
  the stall-hook registry, so the stall report points at the dump),
  on a query's final-attempt failure / a ``CorruptArtifact`` load
  failure, and on SIGTERM (the supervisor-kill path) — a dead stream
  in a multi-hour run leaves a post-mortem even when its full trace
  file never flushed. ``NDS_TPU_FLIGHT=N`` resizes the ring (0
  disables); dumps count on ``flight_dumps_total``.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import threading
import time
from collections import deque

from nds_tpu.analysis import locksan
from nds_tpu.obs import metrics as obs_metrics
from nds_tpu.obs import trace as obs_trace

SIDECAR_PREFIX = "fleet-r"
FLIGHT_PREFIX = "flight-r"
FLIGHT_ENV = "NDS_TPU_FLIGHT"
DEFAULT_RING = 16

# stream names a supervisor assigns end in their index (query_3,
# query_3#r1): the deterministic export pid for subprocess throughput
# traces, replacing colliding / run-arbitrary OS pids
_STREAM_IDX_RE = re.compile(r"_(\d+)(?:#r\d+)?$")


def rank_info(distributed: bool = False) -> dict:
    """``{rank, world, host, pid}``. The world is probed from the
    jax.distributed COORDINATION state (``global_state.process_id`` /
    ``num_processes``), which exists before any backend does. A
    process that never called ``jax.distributed.initialize`` is a
    rank-0 world-of-1; ``distributed`` only widens the probe to jax's
    own accessors as a fallback (the distributed backend has already
    initialized)."""
    rank, world = 0, 1
    try:
        from jax._src import distributed as jdist
        st = jdist.global_state
        if getattr(st, "client", None) is not None \
                and (st.num_processes or 0) > 1:
            rank, world = st.process_id, st.num_processes
        elif distributed:
            import jax
            rank, world = jax.process_index(), jax.process_count()
    except Exception:  # noqa: BLE001 - no jax / private-API drift
        pass
    return {"rank": int(rank), "world": int(world),
            "host": socket.gethostname(), "pid": os.getpid()}


_handshake_seq = 0

# the operator's ORIGINAL trace base, memoized before the first shard
# re-point: init_fleet mutates NDS_TPU_TRACE in place (children and
# later exports must see the shard), so a second run in the same
# process would otherwise shard the already-sharded name
# (trace-r0-r0.jsonl)
_trace_base: "str | None" = None


def clock_handshake() -> "list[float] | None":
    """Per-rank clock offsets (seconds, ``offset[r] = t_r - t_0``)
    measured around a coordination-service barrier: the barrier
    releases every rank at (approximately) one fleet-wide instant,
    the clock reads happen in the narrow window right after it, and a
    KV-store allgather ships them (parallel/multihost.gather_floats —
    the same coordination channel the consensus layer rides). The
    reading is ``perf_counter + epoch_offset`` — the exact basis
    exported Chrome ``ts`` values use, so subtracting ``offset[r]``
    from rank r's events puts every shard on rank 0's timeline. None
    on barrier/gather failure (caller degrades to unaligned)."""
    global _handshake_seq
    from nds_tpu.parallel import multihost
    _handshake_seq += 1
    if not multihost.barrier(f"nds_tpu/clock/{_handshake_seq}"):
        return None
    reading = time.perf_counter() + obs_trace.epoch_offset()
    votes = multihost.gather_floats(reading)
    if votes is None:
        return None
    return [v - votes[0] for v in votes]


def shard_path(base: str, rank: int) -> str:
    """``/runs/trace.jsonl`` -> ``/runs/trace-r3.jsonl``."""
    root, ext = os.path.splitext(base)
    return f"{root}-r{rank}{ext or '.jsonl'}"


def init_fleet(run_dir: str | None,
               distributed: bool = False) -> "dict | None":
    """Session-start fleet wiring (called by the power loop after the
    session exists, so the SPMD world is initialized and every rank
    enters the handshake together).

    Single-process worlds only pin the deterministic export pid (the
    stream index when a supervisor named this process) and return
    None. Multi-rank worlds additionally: run the clock handshake,
    re-point ``NDS_TPU_TRACE`` at this rank's shard, pin
    ``export pid = rank``, and write the ``fleet-r<rank>.json``
    sidecar into ``run_dir``. Returns the sidecar dict."""
    info = rank_info(distributed)
    if info["world"] <= 1:
        stream = os.environ.get("NDS_TPU_STREAM")
        m = _STREAM_IDX_RE.search(stream or "")
        if m:
            obs_trace.set_export_pid(int(m.group(1)))
        return None
    rank = info["rank"]
    obs_trace.set_export_pid(rank)
    offsets = clock_handshake()
    doc = dict(info)
    doc["boot_offset_s"] = (round(offsets[rank], 6)
                            if offsets is not None else 0.0)
    doc["aligned"] = offsets is not None
    if offsets is not None:
        doc["offsets_s"] = [round(o, 6) for o in offsets]
    global _trace_base
    base = (_trace_base if _trace_base is not None
            else os.environ.get(obs_trace.TRACE_ENV))
    if base:
        _trace_base = base
        shard = shard_path(base, rank)
        os.environ[obs_trace.TRACE_ENV] = shard
        doc["trace_shard"] = os.path.basename(shard)
    doc["ts"] = time.time()
    if run_dir:
        from nds_tpu.io.integrity import write_json_atomic
        os.makedirs(run_dir, exist_ok=True)
        write_json_atomic(
            os.path.join(run_dir, f"{SIDECAR_PREFIX}{rank}.json"), doc)
    return doc


def load_fleet(run_dir: str) -> "list[dict]":
    """Every rank sidecar under ``run_dir`` (non-recursive — sidecars
    land next to the summaries), rank-sorted. [] when the run was not
    a fleet (single-process dirs analyze exactly as before)."""
    import json
    out = []
    try:
        names = sorted(os.listdir(run_dir))
    except OSError:
        return []
    for name in names:
        if not (name.startswith(SIDECAR_PREFIX)
                and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(run_dir, name)) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict) and "rank" in doc:
            out.append(doc)
    out.sort(key=lambda d: d.get("rank", 0))
    return out


# ------------------------------------------------------ flight recorder

class FlightRecorder:
    """Bounded ring of the last N completed queries' span trees +
    metric deltas, dumpable as one atomic post-mortem JSON."""

    def __init__(self, run_dir: str, rank: int = 0,
                 maxlen: int | None = None):
        if maxlen is None:
            try:
                maxlen = int(os.environ.get(FLIGHT_ENV, DEFAULT_RING))
            except ValueError:
                maxlen = DEFAULT_RING
        self.run_dir = run_dir or "."
        self.rank = int(rank)
        self.enabled = maxlen > 0
        self.ring: deque = deque(maxlen=max(maxlen, 1))
        self.dumps = 0
        self.reasons: list[str] = []
        self._lock = locksan.lock("obs.FlightRecorder._lock")

    @property
    def path(self) -> str:
        return os.path.join(self.run_dir,
                            f"{FLIGHT_PREFIX}{self.rank}.json")

    def record(self, query: str, status: str, root_span=None,
               wall_ms: float | None = None,
               metrics_delta: dict | None = None) -> None:
        """One completed (or finally-failed) query into the ring. The
        span tree serializes NOW — a later dump must not chase live
        Span objects from the watchdog thread."""
        if not self.enabled:
            return
        entry: dict = {"query": query, "status": status,
                       "ts": time.time()}
        if wall_ms is not None:
            entry["wall_ms"] = round(float(wall_ms), 3)
        if root_span is not None and isinstance(root_span,
                                                obs_trace.Span):
            try:
                entry["spans"] = root_span.to_dict()
            except Exception:  # noqa: BLE001 - recorder never fails a query
                pass
        if metrics_delta:
            entry["metrics"] = metrics_delta
        with self._lock:
            self.ring.append(entry)

    def _gather(self, reason: str) -> dict:
        """Lock-taking part of a dump (ring + metrics + heartbeats)."""
        from nds_tpu.resilience import watchdog
        with self._lock:
            entries = list(self.ring)
            self.dumps += 1
            self.reasons.append(reason)
            reasons, dumps = list(self.reasons), self.dumps
        return {
            "rank": self.rank,
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "reason": reason,
            "reasons": reasons,
            "dumps": dumps,
            "ts": time.time(),
            "entries": entries,
            "metrics": obs_metrics.snapshot(),
            "heartbeats": watchdog.snapshot_heartbeats(),
        }

    # ndsraces: waive[NDSR203] -- bounded boundary: lock-taking gather runs on a worker thread joined with timeout_s on the signal path
    def dump(self, reason: str,
             timeout_s: "float | None" = None) -> "str | None":
        """Atomic ``flight-r<rank>.json`` write (latest dump wins; the
        ``reasons`` list keeps the trigger history). Never raises —
        a post-mortem writer that crashes the process it is documenting
        would be worse than no dump.

        ``timeout_s`` is the SIGNAL-HANDLER mode: the handler runs on
        the main thread, which may have been interrupted INSIDE one of
        the locks this dump needs (the ring lock, the watchdog/metrics
        registry locks) — acquiring them inline would self-deadlock
        and absorb the SIGTERM forever. The lock-taking gather then
        runs in a bounded worker thread; on timeout a partial header
        doc is written instead of blocking the handler."""
        if not self.enabled:
            return None
        if timeout_s is None:
            doc = self._gather(reason)
        else:
            box: dict = {}

            def _worker():
                box["doc"] = self._gather(reason)

            t = threading.Thread(target=_worker,
                                 name="nds-tpu-flight-dump",
                                 daemon=True)
            t.start()
            t.join(timeout=timeout_s)
            doc = box.get("doc") or {
                "rank": self.rank, "host": socket.gethostname(),
                "pid": os.getpid(), "reason": reason,
                # ndsraces: waive[NDSR201] -- signal-path fallback: taking the ring lock here is the self-deadlock this branch avoids
                "reasons": [reason], "dumps": self.dumps + 1,
                "ts": time.time(), "entries": [], "metrics": {},
                "partial": True,
            }
        try:
            from nds_tpu.io.integrity import write_json_atomic
            # write_json_atomic's tmp names are thread-unique, so the
            # watchdog thread (a stall dump) and the main thread (a
            # SIGTERM dump — the exact stall-then-supervisor-kill
            # sequence) can dump the same recorder concurrently
            write_json_atomic(self.path, doc)
        except Exception as exc:  # noqa: BLE001 - post-mortem best effort
            print(f"[obs] flight-recorder dump failed: "
                  f"{type(exc).__name__}: {exc}")
            return None
        if timeout_s is None:
            # not on the signal path: the registry lock may be held by
            # the very frame the handler interrupted
            obs_metrics.counter("flight_dumps_total").inc()
        return self.path


_RECORDER: "FlightRecorder | None" = None


def _flight_stall_hook(run_dir: str, entry: dict) -> "dict | None":
    rec = _RECORDER
    if rec is None:
        return None
    path = rec.dump(f"stall:{entry.get('query') or entry.get('phase')}")
    return {"flight": path} if path else None


def arm_flight_recorder(run_dir: str,
                        rank: int = 0) -> "FlightRecorder | None":
    """Install the process-wide recorder for this run (replacing any
    previous run's), register its watchdog stall hook, and install the
    SIGTERM dump. Returns None when ``NDS_TPU_FLIGHT=0``."""
    global _RECORDER
    from nds_tpu.resilience import watchdog
    rec = FlightRecorder(run_dir, rank=rank)
    if not rec.enabled:
        _RECORDER = None
        watchdog.unregister_stall_hook(_flight_stall_hook)
        return None
    _RECORDER = rec
    watchdog.register_stall_hook(_flight_stall_hook)
    _install_sigterm()
    return rec


def flight_recorder() -> "FlightRecorder | None":
    return _RECORDER


def disarm_flight_recorder() -> None:
    """End-of-run teardown: later runs in this process re-arm with
    their own dir (the SIGTERM handler stays installed — it no-ops
    with no recorder armed)."""
    global _RECORDER
    from nds_tpu.resilience import watchdog
    _RECORDER = None
    watchdog.unregister_stall_hook(_flight_stall_hook)


def signal_flush(reason: str = "sigterm",
                 timeout_s: float = 2.0) -> None:
    """The SIGNAL-PATH post-mortem flush, callable from any handler
    (the SIGTERM chain below AND the drain manager's handlers,
    resilience/drain.py): dump the armed recorder and flush any parked
    trace roots, both BOUNDED — the interrupted frame may hold the
    very locks the dump and the export need (see FlightRecorder.dump),
    so neither step may block the handler forever."""
    rec = _RECORDER
    if rec is not None:
        rec.dump(reason, timeout_s=timeout_s)

    def _flush():
        try:
            obs_trace.get_tracer().flush_exports(close_roots=True)
        except Exception:  # noqa: BLE001 - dying anyway
            pass

    ft = threading.Thread(target=_flush, daemon=True)
    ft.start()
    ft.join(timeout=1.0)


_sigterm_installed = False


def _install_sigterm() -> None:
    """Chainable SIGTERM handler (installed once per process, main
    thread only): dump the armed recorder + flush any parked trace
    roots, then hand the signal to whatever handler was there before —
    the supervisor's kill escalation still sees a SIGTERM death, with
    a flight dump on disk next to the stall report. When a drain
    manager is installed on top (resilience/drain.py — the power loop
    installs it AFTER this), ITS handler runs instead and performs the
    same flush via signal_flush before draining resumably."""
    global _sigterm_installed
    if _sigterm_installed:
        return
    if threading.current_thread() is not threading.main_thread():
        return
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            signal_flush("sigterm")
            if callable(prev):
                prev(signum, frame)
            elif prev != signal.SIG_IGN:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_term)
        _sigterm_installed = True
    except (ValueError, OSError):
        # not the main thread / exotic platform: the stall + failure
        # dump paths still cover the ring
        pass
