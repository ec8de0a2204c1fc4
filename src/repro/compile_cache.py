"""JAX's persistent compilation cache for the entry points.

A cold process compiles every kernel it runs; at the paper's widths that
is minutes.  Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable` first, so a second run in the same
checkout reuses the compiled programs.  Importing ``repro`` does not: the
CPU test runs write nothing to the cache.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads it itself; no other directory is configured), else one fixed path
inside the checkout, ``<checkout>/.jax_cache``.  Never a temporary or
per-process path: the directory is part of what makes an entry findable.
"""

from __future__ import annotations

import os

__all__ = ["cache_dir", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/src/repro/compile_cache.py -> <checkout>/.jax_cache
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The persistent cache directory the entry points use."""
    return os.environ.get(ENV) or _IN_CHECKOUT


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
