"""JAX persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile; importing
``repro`` never touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing else is configured.  Otherwise
the cache lives at the fixed ``<repo>/.jax_cache`` (gitignored): the
directory is part of the cache key, so it never moves with a temp
directory, a pid or the time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

#: The repository root (``src/repro/runtime`` is three levels below it).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache -> its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
