"""Host process environment helpers: which backend a process (tree) may
use, and where its compiled programs are cached."""

from __future__ import annotations

import os
from pathlib import Path
from typing import MutableMapping

# <checkout>/.jax_cache — a fixed path, because the directory is part of
# the cache key: a cache that moves (temp name, pid, timestamp) never hits
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent.parent / ".jax_cache"


def force_cpu(env: MutableMapping[str, str]) -> MutableMapping[str, str]:
    """Pin ``env`` (e.g. ``os.environ`` or a child env dict) to the CPU
    backend. Returns ``env``.

    Note: if jax was already imported in this process, also run
    ``jax.config.update("jax_platforms", "cpu")`` — an early import freezes
    the platform default from the pre-call environment."""
    env["JAX_PLATFORMS"] = "cpu"
    return env


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call once at each process
    entry, before the first compile. Returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
    nothing. Unset: the cache goes to ``<checkout>/.jax_cache`` and keeps
    every program, however quick its compile — with JAX's 1 s floor a
    program compiling in 0.9 s one run and 1.1 s the next would add an
    entry to an already-warm cache."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(_DEFAULT_CACHE_DIR)
