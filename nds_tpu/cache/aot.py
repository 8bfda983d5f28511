"""jax-facing half of the plan cache: AOT lower/compile + executable
(de)serialization.

This module is the ONE place engine code lowers and compiles XLA
programs (ndslint NDS111 keeps ``.lower().compile()`` chains from
reappearing inside ``engine/``/``parallel/``): executors build their
traced callables with ``jax.jit`` and hand them here, so the cache
consult wraps every compile the same way —

    compiled, extra, hit = cached_compile(fp, kind, build, args, ...)

On a HIT the serialized executable deserializes against the live
backend and the query pays ZERO compiles (``compile_ms`` stays 0; the
deserialize cost is reported separately as ``cache_load_ms``). On a
MISS the program compiles exactly as before and — when the cache is
writable — persists for every later process. Programs jax cannot
serialize (no unloaded executable on this backend) compile normally
and simply skip the persist, once-warned.

Payload shape (pickled by store.PlanCache):
``{"exec": bytes, "in_tree": PyTreeDef, "out_tree": PyTreeDef,
"extra": {...}}`` — ``extra`` carries the host-side trace byproducts a
hit must restore without re-tracing (output string dictionaries; the
distributed executor's sharded/replicated key split).
"""

from __future__ import annotations

import threading
import time

from nds_tpu.analysis import locksan
from nds_tpu.cache import fingerprint as fpmod
from nds_tpu.obs.trace import get_tracer

_unserializable_warned: set = set()

# the jax.monitoring event jax records, on the compiling thread, when
# its persistent compilation cache serves an executable (the one
# chip_smoke.py's and the benchmark's CompileCounter count)
_PERSISTENT_HIT = "/jax/compilation_cache/cache_hits"
_hit_seen = threading.local()
_hit_listener_on = False


def _on_jax_event(name: str, **_kw) -> None:
    if name == _PERSISTENT_HIT:
        _hit_seen.flag = True

# Traces never interleave: tracing is where the executors fill their
# trace-time collectors (the exchange's module-level skew sink, the
# per-program side dicts), and a second thread tracing at the same
# moment would hand one program the other's tracers. The XLA compile
# that follows runs OUTSIDE the lock — it is single-threaded, releases
# the GIL and costs minutes per sort-bearing program on the TPU, so
# compiling several programs from several threads is how a cold start
# stays inside a time limit (chip_smoke.py's warm-up does).
_TRACE_LOCK = locksan.rlock("cache.aot._TRACE_LOCK")


def platform_parts() -> dict:
    """The backend facts every fingerprint must include: a CPU-compiled
    executable must never key-collide with a TPU one, nor jax 0.4.36
    with 0.4.37, nor x64 with x32."""
    import jax
    import jaxlib
    parts = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "x64": bool(jax.config.jax_enable_x64),
    }
    try:
        dev = jax.devices()[0]
        parts["platform"] = dev.platform
        parts["device_kind"] = dev.device_kind
    except Exception:  # noqa: BLE001 - no live backend: still keyable
        parts["platform"] = "unknown"
    return parts


def try_fingerprint(kind: str, parts: dict, planned=None, tables=None,
                    extra_roots=None):
    """The consult preamble every executor compile site shares:
    ``(cache, fingerprint)`` — ``(None, None)`` when no cache is
    active, ``(cache, None)`` when fingerprinting fails (warned +
    error-counted; the caller compiles uncached — a fingerprint
    problem is never a query failure). ``platform_parts()`` is merged
    into ``parts`` automatically."""
    from nds_tpu import cache as plan_cache
    pc = plan_cache.active()
    if pc is None:
        return None, None
    from nds_tpu.cache.store import _warn
    try:
        fp = fpmod.fingerprint(planned, tables or {}, kind=kind,
                               parts={**platform_parts(), **parts},
                               extra_roots=list(extra_roots or []))
    except Exception as exc:  # noqa: BLE001 - cache is best-effort
        _warn(f"fingerprint failed for {kind} "
              f"({type(exc).__name__}: {exc}); compiling uncached")
        return pc, None
    return pc, fp


def serialize_compiled(compiled) -> "tuple | None":
    """(payload_bytes, in_tree, out_tree) for a jax.stages.Compiled, or
    None when this backend/program does not support serialization
    (warned once per program kind, never raised)."""
    from jax.experimental import serialize_executable as se
    try:
        return se.serialize(compiled)
    except Exception as exc:  # noqa: BLE001 - capability probe
        key = type(exc).__name__
        if key not in _unserializable_warned:
            _unserializable_warned.add(key)
            print(f"PLAN-CACHE NOTE: executable not serializable on "
                  f"this backend ({key}: {exc}); compiles will not "
                  f"persist")
        return None


def deserialize_compiled(payload: dict, devices=None):
    """payload dict -> live jax.stages.Compiled (raises on failure; the
    caller treats any raise as a miss).

    ``devices`` are the devices the program was compiled for, in
    assignment order: the mesh's devices for a sharded program; None
    means the one default device every DeviceExecutor / chunk-scan /
    compactor program runs on. jax must be told — left to itself it
    loads the executable across ALL local devices and the first
    dispatch dies on a shard-count mismatch (8 virtual CPU devices,
    or a four-chip host)."""
    import jax
    from jax.experimental import serialize_executable as se
    return se.deserialize_and_load(
        payload["exec"], payload["in_tree"], payload["out_tree"],
        execution_devices=(list(devices) if devices is not None
                           else [jax.devices()[0]]))


def lower_and_compile(jitted, *args, fresh: bool = False,
                      kind: str = "program"):
    """The engine's single ``.lower().compile()`` site.

    ``fresh=True`` — used for every compile destined for the plan
    cache — bypasses jax's persistent compilation cache for THIS
    compile only: an executable jax's cache serves back re-serializes
    into a blob that cannot reload, so a blob we intend to persist
    must come from a real compile regardless of the ambient
    process-wide cache state (tests and mixed sessions flip it).

    Being the single funnel is what makes the jitsan recompile claim
    airtight: EVERY engine compile — counted or not — announces here
    (``analysis/jitsan.on_compile``), so a compile inside an armed
    post-warmup window is caught even when its call site forgot the
    compiles_total/recompiles_total increment."""
    from nds_tpu.analysis import jitsan
    jitsan.on_compile(kind)
    import jax
    with get_tracer().span("compile.lower", kind=kind):
        with _TRACE_LOCK:
            lowered = jitted.lower(*args)
    if not fresh or not jax.config.jax_enable_compilation_cache:
        return _compile(lowered, kind)
    from nds_tpu.utils import xla_cache
    jax.config.update("jax_enable_compilation_cache", False)
    xla_cache.reset()
    try:
        return _compile(lowered, kind)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        xla_cache.reset()


def _compile(lowered, kind: str):
    """The compiler's refusal surfaces as CompileRefused with its own
    message (resilience/retry.py: deterministic, never an OOM)."""
    import jax
    import jax.monitoring
    from nds_tpu.resilience.retry import CompileRefused
    global _hit_listener_on
    if not _hit_listener_on:
        _hit_listener_on = True
        jax.monitoring.register_event_listener(_on_jax_event)
    _hit_seen.flag = False
    # the compiler on a cold run, a read of jax's persistent cache on a
    # warm one: `persistent_cache_hit` says which
    with get_tracer().span("compile.xla", kind=kind) as span:
        try:
            return lowered.compile()
        except jax.errors.JaxRuntimeError as exc:
            raise CompileRefused(
                f"XLA refused to compile the {kind} program: {exc}"
            ) from exc
        finally:
            span.set(persistent_cache_hit=bool(_hit_seen.flag))


def fresh_for(cache, fp: "str | None") -> bool:
    """Whether a compile at this consult site must bypass jax's own
    compilation cache (``lower_and_compile(fresh=True)``): only when
    the result will actually PERSIST — a writable cache and a real
    fingerprint. A readonly cache never persists, so its misses may
    (and should) amortize through jax's cache like any uncached
    compile."""
    return bool(cache is not None and fp and not cache.readonly)


def call_compatible(compiled, *args) -> bool:
    """Whether a deserialized executable can be invoked with ``args``
    (pytree structure + per-leaf shape/dtype against the executable's
    recorded args_info). A False here means the fingerprint failed to
    capture something — treat as a miss, never as a crash at call
    time."""
    import jax.tree_util as tu
    try:
        info_flat, info_tree = tu.tree_flatten(compiled.args_info)
        arg_flat, arg_tree = tu.tree_flatten((tuple(args), {}))
        if info_tree != arg_tree or len(info_flat) != len(arg_flat):
            return False
        for info, arg in zip(info_flat, arg_flat):
            aval = getattr(info, "_aval", None)
            if aval is None:
                continue
            if (tuple(aval.shape) != tuple(arg.shape)
                    or str(aval.dtype) != str(arg.dtype)):
                return False
        return True
    except Exception:  # noqa: BLE001 - unknown stages API drift: miss
        return False


def load_cached(cache, fp: str, kind: str,
                timings: "dict | None" = None, args=None,
                devices=None):
    """Cache consult: -> (compiled, extra) on a verified hit, else
    None. Deserialize failures and signature-incompatible executables
    degrade to a miss (warned + counted); ``timings`` gains
    ``cache_load_ms`` on the hit path. ``args``: what the caller would
    call the executable with, or a function giving that from what was
    persisted beside it (the sharded executor's key split rides
    there): an entry they cannot be built from, or do not fit, is a
    miss. ``devices``: see :func:`deserialize_compiled`."""
    from nds_tpu.cache.store import _warn, obs_metrics
    t0 = time.perf_counter()
    payload = cache.get(fp, expect_kind=kind)
    if payload is None:
        return None
    try:
        with get_tracer().span("compile.deserialize", kind=kind,
                               bytes=len(payload.get("exec") or b"")):
            compiled = deserialize_compiled(payload, devices)
    except Exception as exc:  # noqa: BLE001 - degrade to fresh compile
        _warn(f"deserialize failed for {fp[:12]}… "
              f"({type(exc).__name__}: {exc}); recompiling fresh")
        cache._quarantine(fp)
        obs_metrics.counter("compile_cache_misses_total").inc()
        return None
    extra = payload.get("extra", {})
    if args is not None and not _fits(compiled, args, extra):
        _warn(f"entry {fp[:12]}… is signature-incompatible with this "
              f"query's buffers; recompiling fresh")
        obs_metrics.counter("compile_cache_misses_total").inc()
        return None
    # the hit counts HERE, after the executable proved loadable and
    # signature-compatible — store.get alone is not a served program
    obs_metrics.counter("compile_cache_hits_total").inc()
    if timings is not None:
        timings["cache_load_ms"] = (
            timings.get("cache_load_ms", 0.0)
            + (time.perf_counter() - t0) * 1000)
    # warm hits still bill compiler-truth costs: the persisted cost
    # dict rides the payload, pinned here so dispatch-time extraction
    # (obs/costs.record_program) is a dict read, not a re-analysis
    from nds_tpu.obs import costs as obs_costs
    obs_costs.attach(compiled, payload.get("cost"))
    return compiled, extra


def _fits(compiled, args, extra: dict) -> bool:
    if callable(args):
        try:
            args = args(extra)
        except (KeyError, TypeError):  # extra lacks what the call is built from
            return False
    return call_compatible(compiled, *args)


def persist(cache, fp: str, kind: str, compiled,
            extra: "dict | None" = None,
            meta: "dict | None" = None, devices=None) -> bool:
    """Serialize + store a freshly compiled program (no-op on readonly
    caches and unserializable backends).

    On CPU the blob is test-deserialized BEFORE it is written: an
    executable that came out of jax's own compile cache (or any future
    backend quirk) can serialize into a blob that cannot reload —
    persisting it would turn every later process's hit into a warned
    recompile. Skipping the persist keeps the store hit-or-miss clean.
    (TPU skips the check: a trial load would claim device memory.)"""
    if cache.readonly:
        return False
    with get_tracer().span("compile.persist", kind=kind) as span:
        stored = _persist(cache, fp, kind, compiled, extra, meta,
                          devices)
        span.set(stored=stored)
    return stored


def _persist(cache, fp, kind, compiled, extra, meta, devices) -> bool:
    ser = serialize_compiled(compiled)
    if ser is None:
        return False
    blob, in_tree, out_tree = ser
    if platform_parts().get("platform") == "cpu":
        try:
            deserialize_compiled({"exec": blob, "in_tree": in_tree,
                                  "out_tree": out_tree}, devices)
        except Exception as exc:  # noqa: BLE001 - capability probe
            key = f"roundtrip:{type(exc).__name__}"
            if key not in _unserializable_warned:
                _unserializable_warned.add(key)
                print(f"PLAN-CACHE NOTE: executable does not survive a "
                      f"serialize round-trip ({type(exc).__name__}); "
                      f"not persisting {kind} {fp[:12]}…")
            return False
    # compiler cost/memory analyses persist alongside the executable
    # (payload for the hit path, manifest meta for offline tooling) so
    # warm runs carry program costs without a live re-analysis
    from nds_tpu.obs import costs as obs_costs
    cost = obs_costs.extract(compiled)
    payload = {"exec": blob, "in_tree": in_tree, "out_tree": out_tree,
               "extra": dict(extra or {})}
    meta_out = {"kind": kind, "fp_version": fpmod.FP_VERSION,
                **platform_parts(), **(meta or {})}
    if cost is not None:
        payload["cost"] = dict(cost)
        meta_out["cost"] = dict(cost)
    return cache.put(fp, payload, meta=meta_out)


def cached_compile(cache, fp: "str | None", kind: str, build, args,
                   extra_fn=None, meta: "dict | None" = None,
                   timings: "dict | None" = None):
    """Compile-or-load one program (the one-shot form the compactor
    and chunk-scan programs use).

    ``build()`` -> jitted is only invoked on a miss; ``args`` are the
    lowering avatars/buffers; ``extra_fn()`` runs AFTER the compile
    (tracing fills the executors' side dicts at lower time) and
    returns the host-side byproducts a future hit must restore.
    Returns ``(compiled, extra, hit)``. With no active cache or no
    fingerprint the compile happens inline, unchanged. ``timings``
    (the executor's per-query bill) gains ``cache_load_ms`` on a hit —
    ``compile_ms`` stays untouched, which is the whole point."""
    if cache is not None and fp:
        hit = load_cached(cache, fp, kind, timings)
        if hit is not None:
            return hit[0], hit[1], True
    compiled = lower_and_compile(build(), *args,
                                 fresh=fresh_for(cache, fp), kind=kind)
    extra = extra_fn() if extra_fn is not None else {}
    if cache is not None and fp:
        persist(cache, fp, kind, compiled, extra, meta)
    return compiled, extra, False
