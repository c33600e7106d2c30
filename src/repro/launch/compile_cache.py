"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing. A directory named by
``JAX_COMPILATION_CACHE_DIR`` is JAX's to use as it stands. Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout: a fixed path,
because the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
