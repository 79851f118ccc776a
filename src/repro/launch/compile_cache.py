"""JAX's persistent compilation cache, for runs on the chip.

A cold process compiles every XLA program and every Pallas kernel
again; with the cache on, a later process in the same checkout reads
them back instead.  Call :func:`enable_compile_cache` from an entry
point (``chip_smoke.py``, ``benchmarks/run.py``), never at import.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (listed in .gitignore): a fixed path, because
# the directory is what a later run has to find again
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and no other directory is set; otherwise the cache goes to
    ``CACHE_DIR``.  Programs are kept however fast they compiled, so the
    kernels (each compiles in about a second) are kept too."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
