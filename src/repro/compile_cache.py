"""Where the command-line entry points keep JAX's persistent compile cache.

``enable()`` is called by ``chip_smoke.py`` and the benchmark CLIs, never
on import, so library users and the tests keep JAX's own defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: a later run finds what an earlier one compiled only when
# the directory has not moved
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
    from there and nothing is set in code.  Otherwise the cache goes to
    ``<repo>/.jax_cache`` (listed in .gitignore)."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
