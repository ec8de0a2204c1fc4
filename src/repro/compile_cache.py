"""JAX's persistent compilation cache for the entry points, and a count of
what the process compiled and loaded.

A cold process compiles every kernel it runs; at the paper's widths that
is minutes.  Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable` first, so a second run in the same
checkout reuses the compiled programs.  Importing ``repro`` does not: the
CPU test runs write nothing to the cache.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads it itself; no other directory is configured), else one fixed path
inside the checkout, ``<checkout>/.jax_cache``.  Never a temporary or
per-process path: the directory is part of what makes an entry findable.

The cache key includes the programs' op metadata, so that an executable
loaded from the cache carries the stage scopes of `repro.tracing` that a
profiler trace reads: by default JAX leaves metadata out of the key, and
a cache filled by code without the scopes (or with other ones) would hand
back executables without them.  The metadata keeps one source frame per
op, the op's own line: with JAX's default of ten, the frames of whoever
first called a plan function (an autotune, a warm-up) would enter the
key, and the same program reached another way would compile again.  The
price is one recompile after a source edit that moves the traced lines.

:func:`stats` counts, from :func:`enable` on, the backend compiles and the
persistent-cache loads, with their seconds, from JAX's monitoring events.
"""

from __future__ import annotations

import os
import threading

__all__ = ["cache_dir", "enable", "stats"]

ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/src/repro/compile_cache.py -> <checkout>/.jax_cache
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: JAX's monitoring events: a backend compile, which wraps the cache
#: lookup (so a hit records both), and the read of a cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_counts = {"compiles": 0, "compile_s": 0.0, "loads": 0, "load_s": 0.0}
_hit = threading.local()        # a load seen inside this thread's compile
_listening = False


def cache_dir() -> str:
    """The persistent cache directory the entry points use."""
    return os.environ.get(ENV) or _IN_CHECKOUT


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _LOAD_EVENT:
        with _lock:
            _counts["loads"] += 1
            _counts["load_s"] += duration
        _hit.pending = True
    elif event == _COMPILE_EVENT:
        if getattr(_hit, "pending", False):
            _hit.pending = False         # that compile was a cache load
            return
        with _lock:
            _counts["compiles"] += 1
            _counts["compile_s"] += duration


def enable() -> str:
    """Turn on the persistent compilation cache (op metadata in its key)
    and start counting compiles; returns the cache directory."""
    global _listening
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
    return path


def stats() -> dict:
    """Backend compiles and persistent-cache loads since :func:`enable`:
    ``{"compiles", "compile_s", "loads", "load_s"}``."""
    with _lock:
        return dict(_counts)
