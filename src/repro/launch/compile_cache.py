"""JAX's persistent compilation cache for the launchers.

A cold compile of a full-width train step takes a minute; the cache lets a
later process of the same checkout skip it.  The cache key includes the
directory, so the directory has to stay put between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's fixed cache directory (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing else is set here.  Otherwise the cache lives in the
    checkout's fixed :data:`DEFAULT_DIR`.  Call it from an entry point,
    before the first compile; importing this module changes nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
