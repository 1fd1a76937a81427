"""Where JAX's persistent compilation cache lives, decided in one place.

Entry points that run on the chip (``chip_smoke.py``, ``launch.stats_serve``)
call ``enable_compile_cache()`` before their first compile.  A set
``JAX_COMPILATION_CACHE_DIR`` wins and is left alone (JAX reads it itself);
otherwise the cache goes to ``.jax_cache/`` at the repository root — a fixed
path, because the path is part of the cache key, so a directory that moves
(a tempdir, a pid, a timestamp) would never hit.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
