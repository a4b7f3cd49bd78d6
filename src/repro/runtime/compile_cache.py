"""Persistent XLA compilation cache, placed from outside the program.

Entry points call `enable_compile_cache()` once, before their first
compile. The rule for where the cache lives:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it, and the
    program sets no other directory;
  * otherwise: `.jax_cache/` at the root of the checkout (gitignored).
    The path is fixed — never built from a temp name, a process id or
    the time — because the directory is part of what a later run must
    find again.

`CacheProbe` counts persistent-cache hits and misses through JAX's
monitoring events, so a run can report whether it was served warm.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/runtime/compile_cache.py -> the checkout root
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def cache_dir() -> str:
    """Where the persistent compilation cache lives for this process."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_DIR)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache (see the module
    docstring for where it goes) and cache every program, however fast
    it compiled: a chip run's kernels compile in about a second each,
    under JAX's default one-second floor. Returns the directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CacheProbe:
    """Counts persistent-cache hits and misses from the moment it is
    created (JAX monitoring listeners cannot be removed, so create one
    per process)."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
