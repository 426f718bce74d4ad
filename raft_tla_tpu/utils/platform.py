"""Platform selection and compile-cache placement.

A process that wants the CPU is started with ``JAX_PLATFORMS=cpu``; a
process started without it uses the accelerator and fails at start-up if
there is none.  Nothing in this package chooses a backend on the user's
behalf.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_cpu() -> None:
    """Pin jax to the CPU backend.  Must run before backend init."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def cache_dir() -> str:
    """The persistent compile cache's directory:
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else the
    one fixed ``<checkout>/.jax_cache``.  The path is part of the cache's
    key, so it depends on nothing else (host, process, time)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def enable_persistent_cache() -> None:
    """Turn on jax's persistent compilation cache (the B=2048 BFS chunk
    program takes about two minutes to compile cold on a v5e).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it, and no
    directory is set in code.  Safe to call more than once, before or
    after backend init.  Every entry point calls this first, so it also
    turns on the process record's jax listeners (obs/metrics.py: what
    is traced, compiled or loaded from here on is counted) and stamps
    the record's ``cache_enabled`` mark."""
    import jax

    from ..obs.metrics import process_record, watch_compiles

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    watch_compiles()
    process_record().mark("cache_enabled")
