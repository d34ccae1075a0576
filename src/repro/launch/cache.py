"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every per-bucket jit entry of the served path is its own executable, so a
cold process compiles a few dozen programs before it answers a query.  The
persistent cache lets the next process in the same checkout load them
instead.
"""

from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives at
    ``<root>/.jax_cache`` — a fixed path, never one built from a temp
    name, a pid or the time, so a later run from the same ``root`` finds
    what this one compiled.  Entries are kept whatever their compile time,
    so the small per-bucket programs are cached too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
