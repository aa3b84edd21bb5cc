"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`enable_compile_cache` before its first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing here touches the config.  Otherwise the cache lives at
a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored), so
repeated runs from one checkout hit the same entries — the directory is
part of every entry's key, so a path that moved between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` — this file sits at ``<repo>/src/repro/launch/``.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
