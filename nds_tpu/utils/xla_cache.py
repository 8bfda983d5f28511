"""Persistent XLA compilation cache.

The engine compiles one XLA program per (query, scale factor), and on
the TPU every program that carries a 64-bit sort costs minutes to
compile (CHANGES.md PR 21 has the rehearsed seconds); jax's persistent
compilation cache amortizes them across processes and across benchmark
rounds — the engine-side analog of the reference's warmed-JVM steady
state (`nds/nds_power.py:184-322` keeps one Spark session across the
whole stream for the same reason).

Where it lives is decided from OUTSIDE the program: if
``JAX_COMPILATION_CACHE_DIR`` is set, jax itself reads it and this
module sets no directory at all; otherwise the cache is the fixed
``<checkout>/.xla_cache``. Nothing is derived from a pid, the time, a
temp name or the order of calls — a cache whose path moves between
runs never hits.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn on jax's persistent compilation cache. Idempotent; returns
    the directory in use."""
    import jax

    path = os.environ.get(ENV_DIR)
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # a prior disable() must not stick
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every program: benchmark queries are all worth persisting
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    reset()
    return path


def disable() -> None:
    """Turn jax's persistent compilation cache OFF (process-wide
    setting). The plan cache (nds_tpu/cache/) requires this: an
    executable jax's cache serves back re-serializes into a blob that
    cannot reload, so plan-cache sessions must see only REAL
    compiles."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    reset()


def reset() -> None:
    """jax memoizes the cache's on/off verdict (and the directory-bound
    cache object) at the FIRST compile and then ignores every later
    config update; ``reset_cache()`` drops both so the next compile
    re-reads the config."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
