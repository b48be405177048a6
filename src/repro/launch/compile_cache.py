"""Where JAX keeps its persistent compilation cache.

A persistent cache is keyed in part by its own path, so a path that moves
between runs never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, wins
and nothing else is touched (JAX reads the variable itself). Otherwise
the cache goes to one fixed directory inside the checkout,
``<repo>/.jax_cache`` (listed in ``.gitignore``). Tests never call this.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
