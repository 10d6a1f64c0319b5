"""Where this checkout keeps JAX's persistent compilation cache.

The directory is part of every cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
one fixed git-ignored directory of the checkout, found from this file
so that a worker started from another working directory agrees. JAX
reads the variable when it is imported: the raylet puts it in the
environment of each worker that holds a chip, and the single-process
device scripts call :func:`export` at start-up.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    return os.environ.get(ENV_VAR) or os.path.join(_REPO_ROOT, ".jax_cache")


def export() -> str:
    """Point THIS process's jax at the cache, before its first compile:
    through the environment, and through ``jax.config`` too when jax
    was imported already (it reads the environment only once)."""
    path = os.environ[ENV_VAR] = cache_dir()
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
