"""Where JAX keeps its persistent compilation cache.

A run on a fresh machine compiles every program again unless the cache
directory survives between its processes.  :func:`use_compile_cache`
places it before the first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and that
  directory stays the only setting;
* otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path (the
  path is part of the cache key, so a directory that moves never hits).

Entry points call it; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
