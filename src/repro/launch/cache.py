"""JAX's persistent compilation cache, placed once per process.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path inside the checkout (listed in ``.gitignore``), never one
derived from a temporary name, a pid or the time, so a second run of the
same program finds what the first one compiled.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"


def enable_compile_cache():
    """Turn the persistent cache on for every program.

    Returns ``(path, counts)``: ``counts`` holds this process's
    persistent-cache ``hits`` and ``misses`` from now on, kept current by
    a JAX monitoring listener."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counts = {"hits": 0, "misses": 0}

    def count(event: str, **_) -> None:
        if event == _HITS:
            counts["hits"] += 1
        elif event == _MISSES:
            counts["misses"] += 1

    jax.monitoring.register_event_listener(count)
    return path, counts
