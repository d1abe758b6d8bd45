"""JAX persistent compilation cache, placed from outside.

One rule for every process of this repo — trainer, serving engine,
supervised replica, bench, ``chip_smoke.py``:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing here sets a directory;
- where it is not, the cache lives in ONE fixed directory inside the
  checkout (``.jax_cache/``, git-ignored).

The directory is part of JAX's cache key, so a path that moves (a
temporary name, a pid, a per-run log dir) never hits: a restarted
engine, a replica child and the next run of a tool only share compiled
programs because they all resolve the same path. Children inherit the
rule with the environment; nothing has to be threaded through.

Tests that want a hermetic cache pass ``enable_compile_cache(path)`` or
set the variable for the children they spawn.

Every compile is persisted (the size and compile-time thresholds are
dropped): the engine's small programs are as worth skipping on a warm
start as its large ones.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["enable_compile_cache", "disable_compile_cache",
           "compile_cache_dir", "ENV_VAR", "DEFAULT_DIR"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — this file is paddle_tpu/core/compile_cache.py
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> Optional[str]:
    """The directory JAX's persistent cache uses now (None = off)."""
    import jax
    return jax.config.jax_compilation_cache_dir


def _reset_jax_cache() -> None:
    # jax memoizes the cache object (or its absence) at the FIRST
    # compile of the process; a directory set or cleared after that is
    # ignored until the memo is dropped
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn the persistent compilation cache on and return its
    directory. ``path=None`` follows the module rule: $ENV_VAR where
    set (no directory is set in code), else `DEFAULT_DIR`. An explicit
    ``path`` is a caller's own hermetic directory. Idempotent."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if path is None:
        from_env = os.environ.get(ENV_VAR, "").strip()
        if from_env:
            return from_env
        path = DEFAULT_DIR
    path = os.path.abspath(path)
    if compile_cache_dir() != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        _reset_jax_cache()
    return path


def disable_compile_cache() -> None:
    """Detach jax from its cache directory: config cleared AND the
    memoized cache object dropped, so later compiles neither read from
    nor write to a directory that may be gone (a hermetic test
    directory deleted at teardown). ``enable_compile_cache``
    re-attaches."""
    import jax

    if compile_cache_dir() is None:
        return
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache()
