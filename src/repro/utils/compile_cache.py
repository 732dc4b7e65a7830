"""JAX's persistent compilation cache for the repo's entry points.

A cold process compiles every jitted driver again; on the chip that is a
large part of a short run. ``enable()`` turns the persistent cache on:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that
  directory and no other is configured here;
* otherwise the cache lives at the fixed ``<repo>/.jax_cache`` (the
  directory is part of each entry's key, so it must not move between
  runs; it is listed in ``.gitignore``).

Either way every program is kept, however quickly it compiled: JAX's
default writes only programs that take a second or more, and the
engine's kernel-bearing scheduler rounds compile in 0.6-1.2 s on a v5e,
so a second run compiled them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
