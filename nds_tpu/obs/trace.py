"""Span-based query tracer: where the time goes, per query, per phase.

The reference harness delegates all timing depth to the Spark UI /
event logs; this engine's only accounting used to be a mutable
``last_timings`` dict scraped off the executor after the fact.  This
module is the replacement contract: every pipeline phase (parse, plan,
compile, execute, materialize, staged sub-programs, chunk scans) runs
inside a *span* — a named wall-clock bracket with attributes, nestable
into a per-query tree.  Spans bracket ``block_until_ready`` boundaries
upstream (the utils/report.py contract), so async dispatch cannot hide
work.

Design constraints, in order:

- **Zero-cost when disabled.** ``NDS_TPU_OBS=0`` makes ``span()`` /
  ``begin()`` return one shared no-op object; no allocation, no clock
  read, no lock.
- **Thread/executor-safe.** The "current span" is thread-local; async
  executors carry their span explicitly (``begin`` + ``attach``)
  instead of relying on stack discipline that interleaved queries
  would break.
- **Export is a side effect of finishing a root.** When a root span
  (no parent) ends, its whole tree appends to the Chrome trace-event
  JSONL named by ``NDS_TPU_TRACE`` (one JSON object per line, "X"
  complete events — Perfetto-loadable after wrapping in ``[...]``, see
  README "Observability").  Whoever needs the tree afterwards (the
  power loop, for the BenchReport JSON) keeps the root it was handed;
  the tracer keeps no trees, only ``totals()``: count, total and self
  seconds per span name.
- **A tree only where it is read.** ``Span``s link into a tree under
  a root that is *kept*: the Chrome export is on, a profile is live,
  the root's owner asked (``begin(keep=True)``), or the tracer was made
  by hand.  Under any other root the ``with`` spans are ``_TimedSpan``s:
  timed into ``totals()`` under the same names and gone.  The statement
  path opens ten spans a statement; in a run nobody traces they cost a
  third this way.
- **One mechanism, two sinks.** A context-managed span also opens a
  ``jax.profiler.TraceAnnotation`` named ``nds.<span name>`` while a
  profile is live (whoever started it), with its numeric attributes
  as the annotation's metadata: the program's spans then sit in the
  ``.xplane.pb`` on the calling thread's host line, on the clock of
  the PJRT launch events and beside the device's planes.  ``jax`` is
  never imported from here; owned spans (``begin``/``end``, explicit
  timestamps) cannot be annotations and stay Chrome-only.

The span catalogue and the event schema are documented in the README
and enforced by ``tools/check_trace_schema.py``.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time

from nds_tpu.analysis import locksan

TRACE_ENV = "NDS_TPU_TRACE"
_OBS_ENV = "NDS_TPU_OBS"

# perf_counter -> epoch calibration, done once: Chrome trace "ts" wants
# one consistent microsecond timeline, perf_counter wants to be the
# only clock spans ever read
_EPOCH_OFFSET = time.time() - time.perf_counter()

_EXPORT_LOCK = locksan.lock("obs.trace._EXPORT_LOCK")
_TOTALS_LOCK = locksan.lock("obs.trace._TOTALS_LOCK")

# profiler-annotation name prefix: what benchmarks/span_reduce.py and
# any other .xplane.pb reader selects the program's spans by
ANNOTATION_PREFIX = "nds."
# suffix of the second totals() row a span with a truthy ``first``
# attribute is summed under (``device.bind:first``: the binds that
# built a scan view or uploaded, i.e. set-up work, apart from the
# window's cached binds of the same name)
FIRST_SUFFIX = ":first"

_ANNOTATION = None


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else
    None.  Never INITIATES the import (memwatch's rule: a host-only
    phase must not pull jax in, and a thread-side first import races
    the main thread's)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None or getattr(getattr(mod, "__spec__", None),
                                  "_initializing", False):
            return None
        _ANNOTATION = getattr(mod, "TraceAnnotation", None)
    return _ANNOTATION


def _bump(totals: dict, name: str, dur: float, own: float) -> None:
    """One more span of ``name`` in a totals table; the caller holds
    ``_TOTALS_LOCK``."""
    row = totals.get(name)
    if row is None:
        row = totals[name] = [0, 0.0, 0.0]
    row[0] += 1
    row[1] += dur
    row[2] += own


def _numeric(attrs: dict) -> dict:
    """The attributes a profiler annotation can carry: numbers (bools
    as 0/1); strings and dicts stay Chrome-only."""
    return {k: (int(v) if v is True or v is False else v)
            for k, v in attrs.items()
            if isinstance(v, (int, float))}

# deterministic export identity (obs/fleet.py): multi-process fleets
# export with pid=rank and supervised throughput streams with
# pid=stream index, so merged Chrome traces get stable, collision-free
# lanes instead of OS pids that can collide across hosts (and are
# arbitrary between runs). None = the legacy os.getpid() default.
_EXPORT_PID: int | None = None
# thread ident -> small stable lane id (1 = first exporting thread,
# usually main): Chrome/Perfetto lanes stay readable and two shards
# merged into one timeline cannot alias each other's giant pthread ids
_TID_MAP: dict[int, int] = {}


def set_export_pid(pid: int | None) -> None:
    """Pin the pid every exported event carries (rank in a fleet,
    stream index in a subprocess throughput fleet). ``None`` restores
    the os.getpid() default."""
    global _EXPORT_PID
    _EXPORT_PID = None if pid is None else int(pid)


def export_pid() -> int:
    return os.getpid() if _EXPORT_PID is None else _EXPORT_PID


def _compact_tid(ident: int) -> int:
    tid = _TID_MAP.get(ident)
    if tid is None:
        with _EXPORT_LOCK:
            tid = _TID_MAP.setdefault(ident, len(_TID_MAP) + 1)
    return tid


def epoch_offset() -> float:
    """The perf_counter->epoch calibration exported ``ts`` values use —
    the clock basis the fleet clock handshake (obs/fleet.py) must
    measure, or per-rank offsets would correct a different clock than
    the one stamping the events."""
    return _EPOCH_OFFSET


def _shift_epoch_offset(seconds: float) -> None:
    """TEST HOOK: skew this process's export clock by ``seconds`` —
    how the fleet-merge tests simulate two hosts with disagreeing
    wall clocks without touching the host clock."""
    global _EPOCH_OFFSET
    _EPOCH_OFFSET += seconds

# begin() default-parent sentinel: "whatever span is current on this
# thread" (None must stay expressible as "force a root")
_CURRENT = object()


class Span:
    """One named wall-clock bracket. Usable as a context manager (sync
    code: nests via the tracer's thread-local stack) or via explicit
    ``begin``/``end`` (async executors that outlive their dispatch
    thread turn)."""

    __slots__ = ("name", "attrs", "parent", "children", "t0", "t1",
                 "tid", "kept", "kids_s", "_tracer", "_ann",
                 "_totalled", "_under")

    def __init__(self, tracer: "Tracer", name: str, parent: "Span | None",
                 attrs: dict, t0: float | None = None, kept: bool = True):
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self.children: list[Span] = []
        self.t0 = time.perf_counter() if t0 is None else t0
        self.t1: float | None = None
        # kept: somebody will read this tree (a profile is live, the
        # Chrome export is on, or the root's owner keeps it), so the
        # ``with`` spans under it are Spans too and link in.  Under a
        # span that is not kept they are _TimedSpans: timed into the
        # totals (their seconds add up in ``kids_s`` here) and gone.
        self.kept = kept
        self.kids_s = 0.0
        self._under = None
        self.tid = threading.get_ident()
        self._tracer = tracer
        self._ann = None
        self._totalled = False
        if parent is not None:
            parent.children.append(self)

    def __bool__(self) -> bool:
        return True

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_numeric(attrs))
        return self

    def end(self, t: float | None = None) -> "Span":
        """Close the bracket (idempotent). ``t`` overrides the end
        timestamp for phases whose start/stop were measured by the
        caller's own perf_counter reads."""
        if self.t1 is None:
            self.t1 = time.perf_counter() if t is None else t
            # totals are taken a tree at a time, when its root ends (one
            # pass, one lock); a span that outlives that brings its own
            # subtree in now
            if self.parent is None:
                if self._under is not None:
                    self._under.kids_s += self.t1 - self.t0
                self._tracer._finish_root(self)
            elif self.parent._totalled:
                self._tracer._add_totals(self)
        return self

    def self_s(self) -> float:
        """Seconds of this (ended) span that no child covers: children
        may overlap (``device.run`` is given the launch-to-read-back
        bracket after the fact), so their union is what is taken off.
        A span that is not kept takes off what its ``with`` spans
        summed up instead: the brackets handed to it after the fact lie
        over them."""
        lo, hi = self.t0, self.t1
        if not self.kept:
            return (hi - lo) - self.kids_s
        covered, upto = 0.0, lo
        for c in sorted(self.children, key=lambda c: c.t0):
            e = hi if c.t1 is None or c.t1 > hi else c.t1
            if e > upto:
                covered += e - max(c.t0, upto)
                upto = e
        return (hi - lo) - covered

    @property
    def dur_ms(self) -> float:
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return (end - self.t0) * 1000.0

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str) -> "list[Span]":
        return [s for s in self.walk() if s.name == name]

    # ------------------------------------------------------- conversions

    def to_dict(self) -> dict:
        """JSON-ready tree for the BenchReport ``spans`` field."""
        return {
            "name": self.name,
            "dur_ms": round(self.dur_ms, 3),
            "attrs": _json_safe(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def to_events(self, pid: int | None = None) -> list[dict]:
        """Chrome trace-event dicts ("X" complete events) for this span
        and every descendant. ``pid`` defaults to the process's export
        identity (``set_export_pid`` — rank in a fleet, stream index in
        a throughput fleet, os.getpid() otherwise); tids are compact
        per-process lane ids, not raw pthread idents, so merged
        multi-shard traces never alias lanes."""
        pid = export_pid() if pid is None else pid
        out = []
        for s in self.walk():
            out.append({
                "name": s.name,
                "cat": s.name.split(".", 1)[0],
                "ph": "X",
                "ts": (s.t0 + _EPOCH_OFFSET) * 1e6,
                "dur": s.dur_ms * 1000.0,
                "pid": pid,
                "tid": _compact_tid(s.tid),
                "args": _json_safe(s.attrs),
            })
        return out

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        ann = _ANNOTATION or _annotation_cls()
        if ann is not None and ann.is_enabled():
            # a profile is live: the span is also an event of the
            # .xplane.pb, on this thread's host line
            self._ann = ann(ANNOTATION_PREFIX + self.name,
                            **_numeric(self.attrs))
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self._tracer._pop(self)
        if exc is not None:
            self.attrs.setdefault("error", f"{type(exc).__name__}: {exc}")
        self.end()


class _NoopSpan:
    """Shared do-nothing span: the entire disabled-mode cost is one
    attribute load and a falsy check."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def end(self, t=None) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


NOOP_SPAN = _NoopSpan()


class _TimedSpan:
    """A ``with`` span under a root nobody will read as a tree: no
    profile is live, the Chrome export is off and no owner keeps the
    root (`Span.kept`).  It is timed into ``Tracer.totals()``, count,
    seconds and self seconds like any other, and leaves nothing else
    behind: no attributes, no links, no annotation.  This is the
    statement path of a run nobody traces, ten spans a statement, and
    the reason it costs a third of a `Span` there (ISSUE 25)."""

    __slots__ = ("name", "t0", "kids_s", "first", "_tracer", "_st")
    kept = False

    def __init__(self, tracer: "Tracer", name: str, stack: list):
        self.name = name
        self.kids_s = 0.0
        self.first = False
        self._tracer = tracer
        self._st = stack

    def __bool__(self) -> bool:
        return True

    def set(self, **attrs) -> "_TimedSpan":
        if attrs.get("first"):           # `<name>:first` in the totals
            self.first = True
        return self

    def end(self, t=None) -> "_TimedSpan":
        return self

    def __enter__(self) -> "_TimedSpan":
        self._st.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter() - self.t0
        st = self._st
        if st and st[-1] is self:
            st.pop()
        else:
            self._tracer._pop(self)
        if st:
            st[-1].kids_s += dur
        totals = self._tracer._totals
        with _TOTALS_LOCK:
            _bump(totals, self.name, dur, dur - self.kids_s)
            if self.first:
                _bump(totals, self.name + FIRST_SUFFIX, dur,
                      dur - self.kids_s)


def _json_safe(obj):
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


class _Attach:
    """Context manager that makes an explicitly-owned span the
    thread-local current span WITHOUT ending it on exit (the async
    executors' bridge between begin/end ownership and ``with span``
    nesting for everything called underneath)."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "Tracer", span):
        self._tracer = tracer
        self._span = span

    def __enter__(self):
        if self._span:
            self._tracer._push(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._span:
            self._tracer._pop(self._span)


class Tracer:
    """Owns the thread-local span stack, the totals by span name, and
    the Chrome-trace export."""

    def __init__(self, enabled: bool | None = None,
                 keep_trees: "bool | None" = True):
        if enabled is None:
            enabled = os.environ.get(_OBS_ENV, "1") != "0"
        self.enabled = enabled
        # whether the spans under a new root are kept as a tree: a
        # tracer made by hand keeps them (its maker holds the roots);
        # the process's own (None) only where something reads them,
        # see _tree_wanted
        self.keep_trees = keep_trees
        self._tls = threading.local()
        # span name -> [count, total seconds, self seconds] over every
        # span ended so far, under _TOTALS_LOCK (warm-ups run sessions
        # in threads): what outlives the trees, which nobody keeps
        self._totals: dict = {}
        # defer_exports=True parks finished roots on _pending instead
        # of writing them inline: the power loop's root spans end
        # INSIDE the timed bracket, and even a ~ms export skews the
        # span-vs-TimeLog agreement; the loop flushes after the bracket
        self.defer_exports = False
        self._pending: list = []
        # root spans begun but not yet ended: an abnormal exit (crash,
        # deadline kill that unwinds) salvages these as a truncated
        # trace instead of losing the in-flight query entirely
        self._open_roots: set = set()

    # ------------------------------------------------------------- stack

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:
            # tolerate mismatched exits: drop through to the span
            while st and st.pop() is not span:
                pass

    def current(self):
        """The innermost open span of this thread (a ``Span``, or the
        ``_TimedSpan`` standing in for one that nobody will read)."""
        st = self._stack()
        return st[-1] if st else None

    # --------------------------------------------------------------- API

    def span(self, name: str, **attrs):
        """Context-managed span, parented to the thread's current
        span."""
        if not self.enabled:
            return NOOP_SPAN
        st = getattr(self._tls, "stack", None)
        if st:
            if st[-1].kept:
                return Span(self, name, st[-1], attrs)
            return _TimedSpan(self, name, st)
        if not self._tree_wanted():
            return _TimedSpan(self, name, self._stack())
        s = Span(self, name, None, attrs)
        self._open_roots.add(s)
        return s

    def _tree_wanted(self) -> bool:
        """Whether a new root's tree has a reader: the Chrome export is
        on, or a profile is live and wants the annotations."""
        if self.keep_trees is not None:
            return self.keep_trees
        if os.environ.get(TRACE_ENV):
            return True
        ann = _ANNOTATION or _annotation_cls()
        return ann is not None and ann.is_enabled()

    def begin(self, name: str, parent: "Span | None | object" = _CURRENT,
              t0: float | None = None, keep: "bool | None" = None,
              **attrs):
        """Explicitly-owned span (caller must ``end()`` it). ``parent``
        defaults to the thread's current span; pass ``None`` to force a
        root.  ``keep=True`` on a root says its owner will read the
        tree (the power loop, for the BenchReport ``spans`` field): the
        spans under it are kept whatever else is on."""
        if not self.enabled:
            return NOOP_SPAN
        if parent is _CURRENT:
            parent = self.current()
        elif isinstance(parent, _NoopSpan):
            parent = None
        under = None
        if parent is None:
            kept = self._tree_wanted() if keep is None else keep
        elif type(parent) is _TimedSpan:
            # nobody reads the tree this would hang in: a root of its
            # own, whose seconds the span it runs under still takes off
            under, parent, kept = parent, None, False
        else:
            kept = parent.kept
        s = Span(self, name, parent, attrs, t0=t0, kept=kept)
        s._under = under
        if s.parent is None:
            self._open_roots.add(s)
        return s

    def attach(self, span) -> _Attach:
        """Make an owned span current for a ``with`` block (no end on
        exit). Accepts the no-op span and does nothing."""
        return _Attach(self, span if isinstance(span, Span) else None)

    # ------------------------------------------------------------ totals

    def _add_totals(self, top: Span) -> None:
        """Take the ended spans of ``top``'s tree into the totals.  A
        span still open is left out with everything under it: it comes
        in when it ends (``Span.end``)."""
        todo = [top]
        with _TOTALS_LOCK:
            while todo:
                span = todo.pop()
                if span.t1 is None or span._totalled:
                    continue
                span._totalled = True
                todo += span.children
                dur, own = span.t1 - span.t0, span.self_s()
                _bump(self._totals, span.name, dur, own)
                if span.attrs.get("first"):
                    _bump(self._totals, span.name + FIRST_SUFFIX, dur, own)

    def totals(self) -> dict:
        """``{span name: {"count", "total_s", "self_s"}}`` over every
        ended span of every tree whose root has ended (self = the span
        minus what its children cover).  Spans ended with a truthy
        ``first`` attribute are summed a second time under
        ``<name>:first``."""
        with _TOTALS_LOCK:
            return {name: {"count": n, "total_s": total, "self_s": own}
                    for name, (n, total, own) in self._totals.items()}

    # ------------------------------------------------------------ export

    def _finish_root(self, root: Span) -> None:
        self._open_roots.discard(root)
        self._add_totals(root)
        path = os.environ.get(TRACE_ENV)
        if not path:
            return
        if self.defer_exports:
            self._pending.append((root, path))
            return
        try:
            export_chrome(root, path)
        except OSError:  # tracing must never fail the query
            pass

    def flush_exports(self, close_roots: bool = False) -> None:
        """Write every parked root tree (defer_exports mode).
        Idempotent — the pending list drains on the first call, and a
        second call is a no-op. ``close_roots=True`` (the atexit path)
        first ends any still-open root span so a crashed or
        deadline-killed run leaves a readable, truncated trace instead
        of losing the in-flight tree."""
        if close_roots:
            self.defer_exports = False  # nothing re-parks at exit
            for root in list(self._open_roots):
                try:
                    root.set(truncated=True).end()
                except Exception:  # noqa: BLE001 - exit path
                    self._open_roots.discard(root)
        pending, self._pending = self._pending, []
        for root, path in pending:
            try:
                export_chrome(root, path)
            except OSError:
                pass
        if close_roots:
            with _EXPORT_LOCK:
                for f in _EXPORT_FILES.values():
                    try:
                        if not f.closed:
                            f.flush()
                    except OSError:
                        pass


# held-open export handles, one per trace path: the export runs inside
# the power loop's per-query timing bracket (root spans end there), and
# an open/close pair per query on a slow filesystem costs multiple ms —
# visible skew between span totals and the TimeLog CSV. Flushed per
# tree so readers always see complete trees; the OS closes at exit.
_EXPORT_FILES: dict = {}


def _append_events(events: list, path: str) -> None:
    """JSONL-append pre-built trace events through the held-open
    handle for ``path`` (shared by span trees and counter lanes)."""
    with _EXPORT_LOCK:
        f = _EXPORT_FILES.get(path)
        if f is None or f.closed:
            f = _EXPORT_FILES[path] = open(path, "a")
            if len(_EXPORT_FILES) > 8:  # bound leaked handles (tests)
                old = next(iter(_EXPORT_FILES))
                if old != path:
                    _EXPORT_FILES.pop(old).close()
        f.write("".join(json.dumps(ev) + "\n" for ev in events))
        f.flush()


def export_chrome(root: Span, path: str) -> None:
    """Append one JSONL line per span in ``root``'s tree to ``path``."""
    _append_events(root.to_events(), path)


def counter_event(name: str, values: dict, t: "float | None" = None,
                  pid: "int | None" = None) -> dict:
    """One Chrome-trace counter sample (``ph: "C"``): Perfetto renders
    each numeric key in ``values`` as a stacked counter lane next to
    the span tracks. ``t`` is a perf_counter timestamp (defaults to
    now) — exported on the same calibrated epoch as spans so lanes
    line up."""
    if t is None:
        t = time.perf_counter()
    return {"name": name, "cat": "counter", "ph": "C",
            "ts": (t + _EPOCH_OFFSET) * 1e6,
            "pid": export_pid() if pid is None else int(pid),
            "tid": 0,
            "args": {str(k): float(v) for k, v in values.items()}}


def export_counters(events: list, path: str) -> None:
    """Append counter events (``counter_event``) to a trace file —
    the device-memory telemetry lane rides the same JSONL stream as
    the spans."""
    if events:
        _append_events(events, path)


# timing keys the per-phase spans map onto (the legacy last_timings
# vocabulary — TimeLog/engineTimings consumers parse these names)
PHASE_TIMING_KEYS = {
    "device.compile": "compile_ms",
    "device.run": "execute_ms",
    "device.materialize": "materialize_ms",
}


def timings_from_span(root) -> dict:
    """last_timings-shaped dict from a query span tree: the executor
    attaches the authoritative dict as the root's ``timings`` attr
    (retry folding, staged-bill merge and roofline derivation live in
    the executor); absent that, phase child durations are summed under
    the legacy key names."""
    if not isinstance(root, Span):
        return {}
    t = root.attrs.get("timings")
    if isinstance(t, dict):
        return dict(t)
    out: dict = {}
    for s in root.walk():
        key = PHASE_TIMING_KEYS.get(s.name)
        if key:
            out[key] = out.get(key, 0.0) + s.dur_ms
    return out


_TRACER = Tracer(keep_trees=None)

# exit-time flush for the GLOBAL tracer only (per-instance registration
# would pin every test-constructed tracer and its span trees forever):
# a crashed/deadline-killed run keeps whatever the buffer held, and any
# still-open root exports as a truncated tree (idempotent — a clean run
# flushes nothing twice)
atexit.register(_TRACER.flush_exports, close_roots=True)


def get_tracer() -> Tracer:
    return _TRACER


def set_enabled(enabled: bool) -> None:
    """Test/CLI hook: flip the global tracer without rebuilding it."""
    _TRACER.enabled = enabled
