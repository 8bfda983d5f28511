"""Runtime recompile & transfer sanitizer for the jit dispatch path.

The static auditor (``nds_tpu/analysis/jit_hazards.py``, driven by
``tools/ndsjit.py``) PROPOSES where recompiles and hidden host<->device
syncs could happen; this module WITNESSES that they don't, on the real
serving workloads. "0 compiles warm" is the engine's core serving
claim (README "Plan cache"), and PR 16's cost ledger made compiles
countable — jitsan promotes the count from a bench observation to an
enforced runtime invariant:

- :func:`arm` opens a measurement window (serve_check arms after its
  warmup phase; cost_check arms its warm run). While armed, every
  compile that reaches the engine's single lower/compile funnel
  (``cache/aot.py lower_and_compile``, which calls :func:`on_compile`)
  is recorded with its Python stack — a post-warmup compile is the
  recompile the plan cache exists to prevent.
- While armed, implicit device->host transfers are interposed at the
  array type itself: ``ArrayImpl.__array__`` / ``.item()`` /
  ``.tolist()`` and the scalar dunders (``float()``/``int()``/
  ``bool()``) on a live device array each force a blocking sync, and
  each firing outside a :func:`declared` scope records an UNDECLARED
  transfer with its stack. (CPU caveat: ``np.asarray`` on a local
  array shares the buffer zero-copy without consulting ``__array__``,
  so that one route is witnessed only on real accelerators —
  scalarization and the dunders fire everywhere, and the static rule
  NDSJ303 covers ``np.asarray`` textually.) The explicit APIs — ``jax.device_get`` /
  ``jax.device_put`` — stay legal and are merely counted (they are
  the engine's sanctioned, attributed transfer points; device_get
  delegates through ``np.asarray`` internally, so the wrapper marks
  its own scope declared to avoid self-flagging).
- :func:`dispatch` scopes the five executor dispatch sites (the
  ``obs_costs.record_program`` call sites in device_exec /
  chunked_exec / dist_exec). While armed it additionally raises jax's
  ``transfer_guard_host_to_device("disallow")`` around the compiled
  call: dispatch buffers are staged device-resident ahead of time, so
  an implicit h2d here means a host buffer leaked into the hot path.
  (The symmetric d2h guard is useless on CPU — zero-copy transfers
  never consult it — which is why the interposition above exists.)
- :func:`disarm` closes the window and returns a verdict; every
  window is kept for the process-wide ``static_checks`` ``jitsan``
  section, and an exit report lands in
  ``$NDS_TPU_JITSAN_REPORT/jitsan-<pid>.json`` when that names a
  directory (same contract as locksan's).

Disabled (``NDS_TPU_JITSAN`` unset/0), nothing is patched and
:func:`arm` is a no-op returning an inactive window — zero overhead,
zero behavior change. The hooks never alter behavior even when armed:
they record and delegate, so a violating workload still completes and
the gate fails on the evidence, not on a mid-query crash.
``selftest()`` (run by ``tools/ndsjit.py --jitsan-selftest`` and the
static_checks section) seeds a deliberate post-warmup recompile and a
hidden ``.item()`` on a PRIVATE sanitizer and proves both are caught.
"""

from __future__ import annotations

import atexit
import contextlib
import os
import sys
import threading
import time
import traceback

ENV = "NDS_TPU_JITSAN"
REPORT_ENV = "NDS_TPU_JITSAN_REPORT"

# witness stacks are trimmed like locksan's: the engine frame matters,
# the jax/pytest frames above it don't
_STACK_FRAMES = 12


def enabled() -> bool:
    return os.environ.get(ENV, "0") == "1"


def _stack() -> "list[str]":
    frames = traceback.format_stack()[:-2]
    return [ln.rstrip("\n") for ln in frames[-_STACK_FRAMES:]]


def _ledger_compiles() -> int:
    """The cost ledger's compile counters (PR 16): the cross-check
    that catches a compile which somehow bypassed the aot funnel."""
    try:
        from nds_tpu.obs import metrics as obs_metrics
        c = obs_metrics.snapshot().get("counters", {})
        return int(c.get("compiles_total", 0)
                   + c.get("recompiles_total", 0))
    except Exception:  # noqa: BLE001 - detector must not crash
        return 0


class Sanitizer:
    """One measurement state: armed window, recorded events, verdicts.

    The global instance backs the installed hooks; tests and the
    selftest swap in PRIVATE instances (:func:`swapped`) so seeded
    hazards never pollute the process verdict."""

    def __init__(self, metric: bool = True):
        # plain lock on purpose: the sanitizer must be invisible to
        # locksan and nothing is ever acquired inside it
        self._lock = threading.Lock()
        self.metric = metric
        self.armed = False
        self.label = ""
        self.compiles: list = []      # post-arm compiles (stacks)
        self.undeclared: list = []    # implicit transfers (stacks)
        self.declared = 0             # device_get/device_put count
        self.dispatches = 0           # dispatch sites crossed armed
        self._ledger0 = 0
        self.windows: list = []       # closed-window verdicts

    # ----------------------------------------------------------- window

    def arm(self, label: str) -> None:
        with self._lock:
            self.armed = True
            self.label = label
            self.compiles = []
            self.undeclared = []
            self.declared = 0
            self.dispatches = 0
            self._ledger0 = _ledger_compiles()

    def disarm(self) -> dict:
        with self._lock:
            v = {
                "label": self.label,
                "active": True,
                "compiles": list(self.compiles),
                "ledger_compiles": _ledger_compiles() - self._ledger0,
                "undeclared_transfers": list(self.undeclared),
                "declared_transfers": self.declared,
                "dispatches": self.dispatches,
                "ts": time.time(),
            }
            self.armed = False
            self.label = ""
            self.windows.append(v)
            return v

    # -------------------------------------------------------- recording

    def on_compile(self, kind: str) -> None:
        if not self.armed:  # ndsraces: waive[NDSR201] -- benign racy fast-path gate: runs on every compile even disarmed; the authoritative re-check is under _lock below and disarm() closes accounting under the same lock
            return
        rec = {"kind": kind, "stack": _stack(),
               "thread": threading.current_thread().name,
               "ts": time.time()}
        with self._lock:
            if not self.armed:
                return
            self.compiles.append(rec)
        self._announce(f"post-warmup compile of {kind!r}")

    def on_transfer(self, what: str, declared: bool) -> None:
        if not self.armed:  # ndsraces: waive[NDSR201] -- benign racy fast-path gate: interposed on every scalarization tree-wide; both branches re-check under _lock before recording
            return
        if declared:
            with self._lock:
                if not self.armed:
                    return
                self.declared += 1
            return
        rec = {"what": what, "stack": _stack(),
               "thread": threading.current_thread().name,
               "ts": time.time()}
        with self._lock:
            if not self.armed:
                return
            self.undeclared.append(rec)
        self._announce(f"undeclared implicit transfer via {what}")

    def on_dispatch(self, kind: str) -> None:
        del kind
        if not self.armed:  # ndsraces: waive[NDSR201] -- benign racy fast-path gate: per-dispatch hot path; the count mutates only under the _lock re-check below
            return
        with self._lock:
            if not self.armed:
                return
            self.dispatches += 1

    def _announce(self, msg: str) -> None:
        if self.metric:
            try:
                from nds_tpu.obs import metrics as obs_metrics
                obs_metrics.counter("jitsan_violations_total").inc()
            except Exception:  # noqa: BLE001 - detector must not crash
                pass
        print(f"[jitsan] {msg} "
              f"(thread {threading.current_thread().name})",
              file=sys.stderr)

    # --------------------------------------------------------- readout

    def violation_count(self) -> int:
        """Violations across CLOSED windows plus the open one."""
        with self._lock:
            n = len(self.compiles) + len(self.undeclared)
            for w in self.windows:
                n += len(w["compiles"]) + len(w["undeclared_transfers"])
            return n

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "pid": os.getpid(),
                "ts": time.time(),
                "armed": self.armed,
                "windows": [dict(w) for w in self.windows],
                "open_compiles": list(self.compiles),
                "open_undeclared": list(self.undeclared),
            }

    def reset(self) -> None:
        with self._lock:
            self.armed = False
            self.compiles = []
            self.undeclared = []
            self.declared = 0
            self.dispatches = 0
            self.windows = []


_SAN = Sanitizer()
_ACTIVE = _SAN


def sanitizer() -> Sanitizer:
    return _SAN


def _active() -> Sanitizer:
    return _ACTIVE


@contextlib.contextmanager
def swapped(san: Sanitizer):
    """Route the installed hooks to a PRIVATE sanitizer (selftest and
    tests): seeded hazards must never pollute the process verdict."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = san
    try:
        yield san
    finally:
        _ACTIVE = prev


# --------------------------------------------------------- interposition

_tls = threading.local()


def _declared_depth() -> int:
    return getattr(_tls, "declared", 0)


@contextlib.contextmanager
def declared(why: str = ""):
    """Scope in which implicit device->host syncs are sanctioned (the
    engine's attributed read-back points). ``why`` documents the site;
    it is not recorded — the scope IS the declaration."""
    del why
    _tls.declared = _declared_depth() + 1
    try:
        yield
    finally:
        _tls.declared = _declared_depth() - 1


_installed = False
_originals: dict = {}


def _hook_method(cls, name: str, what: str) -> bool:
    orig = getattr(cls, name, None)
    if orig is None:
        return False

    def hooked(self, *args, **kwargs):
        san = _active()
        if san.armed and _declared_depth() == 0:
            san.on_transfer(what, declared=False)
        # delegate under a declared scope: np.asarray(x) reaching
        # __array__ must not double-count through nested dunders
        with declared():
            return orig(self, *args, **kwargs)

    hooked.__name__ = getattr(orig, "__name__", name)
    try:
        setattr(cls, name, hooked)
    except (TypeError, AttributeError):
        return False
    _originals[(cls, name)] = orig
    return True


def install() -> bool:
    """Patch the array interposition + wrap the explicit transfer
    APIs. Idempotent; returns True once the hooks are live and RAISES
    when they cannot be installed: a sanitizer that silently failed to
    hook would leave the "0 undeclared transfers" gate passing on no
    evidence. Lazy on purpose: nothing is touched until a window is
    armed (or a test installs explicitly), so the disabled path never
    pays."""
    global _installed
    if _installed:
        return True
    import jax
    from jaxlib._jax import ArrayImpl
    missed = [name for name, what in (
        ("__array__", "np.asarray()/__array__"),
        ("item", ".item()"),
        ("tolist", ".tolist()"),
        ("__float__", "float()"),
        ("__int__", "int()"),
        ("__bool__", "bool()"),
        ("__index__", "__index__"))
        if not _hook_method(ArrayImpl, name, what)]
    if missed:
        _restore_originals()
        raise RuntimeError(
            f"jitsan: cannot interpose ArrayImpl.{'/'.join(missed)} on "
            f"this jaxlib; the transfer gate would be unenforced")

    dg, dp = jax.device_get, jax.device_put

    def device_get(*args, **kwargs):
        san = _active()
        if san.armed:
            san.on_transfer("jax.device_get", declared=True)
        with declared():
            return dg(*args, **kwargs)

    def device_put(*args, **kwargs):
        san = _active()
        if san.armed:
            san.on_transfer("jax.device_put", declared=True)
        with declared():
            return dp(*args, **kwargs)

    jax.device_get, jax.device_put = device_get, device_put
    _originals[("jax", "device_get")] = dg
    _originals[("jax", "device_put")] = dp
    _installed = True
    return True


def _restore_originals() -> None:
    """Restore every attribute recorded in ``_originals``."""
    import jax
    for (owner, name), orig in list(_originals.items()):
        setattr(jax if owner == "jax" else owner, name, orig)
    _originals.clear()


def uninstall() -> None:
    """Restore every patched attribute (tests only; production leaves
    the hooks in place for the life of the process)."""
    global _installed
    if not _installed:
        return
    _restore_originals()
    _installed = False


# ------------------------------------------------------------ engine API

def arm(label: str, force: bool = False) -> bool:
    """Open a measurement window on the GLOBAL sanitizer. Returns
    whether the window is live: under ``NDS_TPU_JITSAN=1`` (or
    ``force=True``) the hooks install (raising if they cannot) and
    recording starts; otherwise this is a no-op and :func:`disarm`
    reports an inactive window."""
    if not (enabled() or force):
        return False
    install()
    _ensure_exit_report()
    _SAN.arm(label)
    return True


def disarm() -> dict:
    if not _SAN.armed:
        return {"active": False, "label": "", "compiles": [],
                "ledger_compiles": 0, "undeclared_transfers": [],
                "declared_transfers": 0, "dispatches": 0}
    return _SAN.disarm()


def on_compile(kind: str) -> None:
    """Called by ``cache/aot.py lower_and_compile`` — the engine's
    single compile funnel — on EVERY lower+compile, counted or not.
    Armed windows record it; disarmed, this is a branch and a return."""
    san = _active()
    if san.armed:
        san.on_compile(kind)


@contextlib.contextmanager
def dispatch(kind: str):
    """Scope one executor dispatch (the five record_program sites).
    Disarmed: a no-op. Armed: counts the crossing and raises jax's
    h2d transfer guard — dispatch buffers are device-resident by
    contract, so an implicit h2d inside the compiled call is a host
    buffer leaking into the hot path (the guard raises, the retry
    policy classifies it deterministic, and the gate shows the site)."""
    san = _active()
    if not san.armed:
        yield
        return
    san.on_dispatch(kind)
    import jax
    with jax.transfer_guard_host_to_device("disallow"):
        yield


def windows() -> "list[dict]":
    return [dict(w) for w in _SAN.windows]


def violation_count() -> int:
    return _SAN.violation_count()


def reset() -> None:
    _SAN.reset()


# ------------------------------------------------------------ exit report

_exit_registered = False


def write_report(path: "str | None" = None) -> "str | None":
    if path is None:
        d = os.environ.get(REPORT_ENV)
        if not d:
            return None
        path = os.path.join(d, f"jitsan-{os.getpid()}.json")
    from nds_tpu.io.integrity import write_json_atomic
    write_json_atomic(path, _SAN.snapshot())
    return path


def _at_exit() -> None:
    try:
        wrote = write_report()
    except Exception:  # noqa: BLE001 - exit path, best effort
        wrote = None
    n = _SAN.violation_count()
    if n and not wrote:
        print(f"[jitsan] exiting with {n} unreported violation(s) — "
              f"set {REPORT_ENV} to capture them", file=sys.stderr)


def _ensure_exit_report() -> None:
    global _exit_registered
    if not _exit_registered:
        _exit_registered = True
        atexit.register(_at_exit)


# -------------------------------------------------------------- selftest

def selftest() -> bool:
    """Seed a deliberate post-warmup recompile and a hidden ``.item()``
    on a PRIVATE sanitizer and return whether BOTH were caught — the
    tier-1 proof the detector fires (static_checks ``jitsan`` section;
    ``tools/ndsjit.py --jitsan-selftest``)."""
    if not install():
        return False
    import jax
    import jax.numpy as jnp
    from nds_tpu.cache import aot as cache_aot
    g = Sanitizer(metric=False)
    with swapped(g):
        g.arm("selftest")
        # the seeded recompile: a compile through the engine's funnel
        # INSIDE the armed window — exactly what a fingerprint gap
        # would cause after warmup
        jitted = jax.jit(lambda x: x + 1)
        buf = jnp.ones((4,), jnp.float32)
        compiled = cache_aot.lower_and_compile(jitted, buf)
        with dispatch("selftest"):
            out = compiled(buf)
        # the hidden sync: an implicit d2h outside any declared scope
        _ = out[0].item()
        # and the sanctioned path must NOT flag: explicit device_get
        _ = jax.device_get(out)
        v = g.disarm()
    caught_compile = len(v["compiles"]) == 1
    caught_sync = len(v["undeclared_transfers"]) >= 1
    counted_declared = v["declared_transfers"] >= 1
    return caught_compile and caught_sync and counted_declared
