"""Where JAX's persistent compilation cache lives for the entry-point scripts.

Call :func:`enable_compile_cache` from a script's ``main`` (never on import):
``$JAX_COMPILATION_CACHE_DIR``, when set, is the cache and JAX reads it
itself, so nothing is set in code; otherwise the cache is the fixed
directory ``<repo>/.jax_cache/`` (gitignored).  A fixed path matters: the
path is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
