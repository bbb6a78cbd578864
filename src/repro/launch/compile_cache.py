"""Where JAX keeps its persistent compilation cache for this checkout.

The cache key includes the cache directory, so a path that moves between
runs never hits: the default is one fixed directory inside the checkout
(listed in ``.gitignore``), never a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left alone (JAX reads it
    itself); otherwise the cache goes to ``DEFAULT_DIR``.  Call before the
    first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
