"""JAX's persistent compilation cache, for entry points that run on a chip.

Call ``enable_compile_cache()`` from a script's ``__main__``, never at
import: tests and library callers keep JAX's own default (no cache).
"""
from __future__ import annotations

import os
import pathlib

import jax

# A fixed path: the cache directory is part of what a later run must find
# again, so it never moves with the working directory.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache goes to ``<repo>/.jax_cache``
    (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
