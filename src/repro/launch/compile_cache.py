"""Where JAX keeps its persistent compilation cache for this repository.

The directory is part of every cache key, so it is fixed: the one that
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the variable
itself), else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; call before the
    first compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
