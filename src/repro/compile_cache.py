"""Where JAX keeps its persistent compilation cache.

A launcher calls `use_compile_cache()` once, before its first compile.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache goes to ``<repo root>/.jax_cache``,
a fixed path: the directory is part of what a later run looks up, so a
path made from a temporary name, a process id or the time never hits.
Tests do not call this.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
