"""JAX's persistent compilation cache, placed once per process.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path inside the checkout (listed in ``.gitignore``), never one
derived from a temporary name, a pid or the time, so a second run of the
same program finds what the first one compiled.

The cache key holds the programs' metadata (op names with their stage
scopes, source locations): without it a program loaded from the cache
keeps the op names of whichever build compiled it first, and a profile
then attributes device time to stale stages.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache():
    """Turn the persistent cache on for every program.

    Returns ``(path, counts)``: ``counts`` holds this process's
    persistent-cache ``hits`` and ``misses`` from now on, and the programs
    it handed to the XLA backend (``backend_compiles``: each compiled, or
    loaded from the persistent cache, which ``hits`` counts) with their
    summed seconds (``backend_compile_s``), kept current by JAX monitoring
    listeners.  A program already built in this process is neither."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    counts = {"hits": 0, "misses": 0, "backend_compiles": 0,
              "backend_compile_s": 0.0}

    def count(event: str, **_) -> None:
        if event == _HITS:
            counts["hits"] += 1
        elif event == _MISSES:
            counts["misses"] += 1

    def time_compile(event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            counts["backend_compiles"] += 1
            counts["backend_compile_s"] += seconds

    jax.monitoring.register_event_listener(count)
    jax.monitoring.register_event_duration_secs_listener(time_compile)
    return path, counts
