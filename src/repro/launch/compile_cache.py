"""Persistent compilation cache for the entry points.

A full-width step takes tens of seconds to compile, and a fresh process
compiles everything again unless JAX finds it in its persistent cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing. Otherwise the cache lives at a fixed path in the
checkout: the path is part of the cache key, so a directory that moved
between runs (a temp name, a pid, a time) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``DEFAULT_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one. Call before the first
    compile; returns the directory in use."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
