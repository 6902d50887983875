"""JAX's persistent compilation cache for the runnable entry points.

A published-width model compiles for tens of seconds per program; the
cache lets a later process (or a second replica in this one) load the
executable instead.  Entry points call ``enable_compile_cache()`` once,
before their first compile; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (and git-ignored): the cache only hits when
# the directory stays put from one run to the next
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
