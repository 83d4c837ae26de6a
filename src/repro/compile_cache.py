"""Where JAX keeps its persistent compilation cache.

A cold full-width training step takes tens of seconds to compile for the
TPU; the cache turns a second run of the same program into a load.  JAX keys
cache entries on the directory too, so the directory must not move between
runs: no temporary name, pid or time in it.
"""

from __future__ import annotations

import os
import pathlib

import jax

# Fixed in-checkout default (listed in .gitignore).
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX itself reads
    it, and nothing is set here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
