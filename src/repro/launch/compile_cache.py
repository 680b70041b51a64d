"""JAX's persistent compilation cache, configured from outside the code.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves JAX's config alone. Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` (git-ignored): the directory is part of
what a later run must find again, so it never depends on a temp
directory, a pid or the time.

:func:`use_compile_cache` also counts the cache's lookups and hits off
JAX's monitoring events, so entry points can print whether a run
reused compiled executables.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


class CompileCacheStats:
    """Where the cache lives and how often it was consulted and hit."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source  # "env" or "checkout"
        self.requests = 0
        self.hits = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _REQUESTS:
            self.requests += 1
        elif event == _HITS:
            self.hits += 1

    def line(self) -> str:
        return (f"compile cache: {self.path} (from {self.source}); "
                f"lookups={self.requests} hits={self.hits} "
                f"hit={'yes' if self.hits else 'no'}")


def use_compile_cache(checkout) -> CompileCacheStats:
    """Point JAX's persistent cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    counters, live for the rest of the process."""
    env = os.environ.get(ENV_VAR)
    if env:
        stats = CompileCacheStats(env, "env")
    else:
        path = Path(checkout).resolve() / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(path))
        stats = CompileCacheStats(str(path), "checkout")
    jax.monitoring.register_event_listener(stats._on_event)
    return stats
