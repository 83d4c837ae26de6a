"""Where JAX keeps its persistent compilation cache.

A cold full-width training step takes tens of seconds to compile for the
TPU; the cache turns a second run of the same program into a load.  JAX keys
cache entries on the directory too, so the directory must not move between
runs: no temporary name, pid or time in it.

By default JAX keys an entry on the module with its debug info stripped
(``jax_compilation_cache_include_metadata_in_key`` off).  The step's named
scopes (``repro.scopes``) are debug info, so an entry compiled before a
scope was added or renamed is still loaded, and its operations carry the old
``op_name``s.  Code that reads the scopes from a compiled step, such as a
profile, turns that flag on around the compile; as locations hold absolute
paths, the first such compile in each checkout then misses the cache.
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
