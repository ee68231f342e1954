"""Where JAX keeps its persistent compilation cache.

The entry points (``launch.serve``, ``launch.train``, ``benchmarks.run``,
``chip_smoke.py``) call ``use_compile_cache()`` once at start-up, before
they compile anything. The cache directory is part of each entry's key, so
it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the cache is
  there; no other directory is set in code.
* unset: ``<checkout>/.jax_cache`` (gitignored), one fixed path.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
