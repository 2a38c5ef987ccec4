"""Where compiled programs persist between processes.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set the cache
lives there and nothing here overrides it.  Otherwise the entry points
keep it at ``<checkout>/.jax_cache`` — a fixed path (the path is part of
the cache key, so a directory that moves never hits), git-ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache (this file is src/repro/launch/compile_cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call from a program's entry point, before its first compile — never at
    import time, never from tests."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
