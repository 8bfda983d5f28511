"""Persistent AOT plan cache: compile once, serve every statement warm.

ROADMAP item 1. The engine compiles one XLA program per (plan, table
content, precision, mesh) — expensive on TPU (tens of seconds for the
wide NDS templates) and, before this package, paid again by EVERY
process. This package persists the compiled executables themselves
(jax AOT serialization), keyed by a full plan fingerprint
(cache/fingerprint.py), in a sha256-stamped on-disk store
(cache/store.py): a warm process answers any statement the cache has
seen with ZERO compiles (``compile_ms: 0`` + ``cache_load_ms`` in the
per-query timings, ``compile_cache_hits_total`` in the metrics).
Fingerprint mismatch, version skew, or a corrupt entry always degrades
to a fresh compile with a warning — never a query failure.

Activation (off by default — no cache dir, no cache):

- ``NDS_TPU_PLAN_CACHE=/path`` (+ ``NDS_TPU_PLAN_CACHE_READONLY=1``)
  — environment, inherited by bench phase subprocesses;
- ``cache.dir`` / ``cache.readonly`` EngineConfig keys (the power
  drivers' ``--cache_dir`` flag and the bench YAML ``cache:`` block
  set these) — applied by the execution pipeline at session creation
  via :func:`configure`.

``tools/ndscache.py`` is the admin CLI (ls/verify/prune/warm).
"""

from __future__ import annotations

import os

from nds_tpu.cache.store import PlanCache

ENV_DIR = "NDS_TPU_PLAN_CACHE"
ENV_READONLY = "NDS_TPU_PLAN_CACHE_READONLY"

# (dir, readonly) -> PlanCache the env resolution is memoized under, so
# monkeypatched env vars in tests re-resolve without a reset
_resolved_key: "tuple | None" = None
_resolved: "PlanCache | None" = None
# explicit configure() overrides the environment until reset
_override: "PlanCache | None" = None
_override_set = False


def configure(cache_dir: "str | None",
              readonly: bool = False) -> "PlanCache | None":
    """Programmatic activation (EngineConfig ``cache.dir`` path).
    ``cache_dir=None`` explicitly disables the cache regardless of the
    environment. Returns the active cache."""
    global _override, _override_set
    _override = PlanCache(cache_dir, readonly) if cache_dir else None
    _override_set = True
    return _override


def reset() -> None:
    """Drop every resolution (tests)."""
    global _override, _override_set, _resolved, _resolved_key
    _override = None
    _override_set = False
    _resolved = None
    _resolved_key = None


def active() -> "PlanCache | None":
    """The process's plan cache, or None when caching is off. Explicit
    :func:`configure` wins; otherwise the ``NDS_TPU_PLAN_CACHE``
    environment decides (re-resolved whenever the variable changes)."""
    global _resolved, _resolved_key
    if _override_set:
        return _override
    d = os.environ.get(ENV_DIR) or None
    ro = os.environ.get(ENV_READONLY, "0") == "1"
    key = (d, ro)
    if key != _resolved_key:
        _resolved_key = key
        _resolved = PlanCache(d, ro) if d else None
    return _resolved


def export_env(cache_cfg) -> None:
    """Bench-orchestrator activation (YAML ``cache: {dir, readonly}``):
    exports ``NDS_TPU_PLAN_CACHE``(+``_READONLY``) into THIS process's
    environment so every engine phase — subprocess or in-process —
    inherits one shared cache directory. A YAML without the block is a
    no-op (the operator's own environment stays in charge)."""
    cache_cfg = cache_cfg or {}
    d = cache_cfg.get("dir")
    if not d:
        return
    os.makedirs(d, exist_ok=True)
    os.environ[ENV_DIR] = d
    # only an EXPLICIT yaml readonly key overrides the operator's
    # environment: a `cache: {dir}` block without it must not silently
    # clear a fleet-wide NDS_TPU_PLAN_CACHE_READONLY=1 pin and start
    # writing into a cache the operator declared read-only
    if "readonly" in cache_cfg:
        if cache_cfg.get("readonly"):
            os.environ[ENV_READONLY] = "1"
        else:
            os.environ.pop(ENV_READONLY, None)


def configure_from(config) -> "PlanCache | None":
    """Apply an EngineConfig's ``cache.*`` keys when present; configs
    without them leave the environment-driven resolution untouched (a
    session created with no cache keys must not clear another's
    explicit configure)."""
    if config is None or not config.get("cache.dir"):
        return active()
    return configure(config.get("cache.dir"),
                     config.get_bool("cache.readonly"))
