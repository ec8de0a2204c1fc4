"""Persistent precompute cache for transform plans.

The paper's precompute-vs-on-the-fly trade-off (§4.2.2), made explicit: a
plan's expensive host-side precomputation -- Gauss-Legendre nodes (Newton
iteration), ``pmm``/``pms`` recurrence seed tables, autotune decisions --
is cached by **plan signature** so repeated pipeline runs skip recompute.

Two tiers:

* **memory** -- a process-global dict keyed by signature hash.  Always
  consulted first; this is what makes a second ``make_plan`` with an
  identical signature free.
* **disk** -- ``.npz`` payloads (plus ``.json`` sidecars for autotune
  decisions) under ``$REPRO_CACHE_DIR`` (default ``~/.cache/repro_sht``),
  surviving across processes.  Written atomically (tmp + rename) so
  concurrent pipeline jobs never read torn files.

Every entry also records build/hit counters (`stats()`), which the tests
use to assert "no recompute" and `Plan.describe()` surfaces to users.

Payloads are flat ``dict[str, np.ndarray]`` (the npz model); anything
richer (autotune decisions) goes through the json decision store.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import warnings
from typing import Callable, Optional

import numpy as np

__all__ = [
    "CACHE_VERSION", "signature_key", "get_or_build", "cache_dir",
    "load_decision", "save_decision", "clear_memory", "clear_disk",
    "stats", "reset_stats", "LRU",
]

#: Bump when the payload layout of any cached builder changes; old disk
#: entries are then simply never matched (keys embed the version).
CACHE_VERSION = 2

_MEMORY: dict[str, dict[str, np.ndarray]] = {}
_DECISIONS: dict[str, dict] = {}


@dataclasses.dataclass
class CacheStats:
    """Counters for cache behaviour; reset with :func:`reset_stats`."""

    builds: int = 0          # times a builder actually ran
    memory_hits: int = 0
    disk_hits: int = 0
    misses: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_STATS = CacheStats()


class LRU:
    """Tiny bounded least-recently-used mapping.

    The unbounded signature caches above are right for precompute payloads
    (small, shared); live ``Plan`` objects are not -- each one owns seed
    tables and compiled executables -- so holders of *bounded* plan sets
    (the serving engine's warm pool) evict through this.  ``on_evict`` is
    called with ``(key, value)`` after removal so the holder can release
    external references (e.g. ``transform.drop_plan``).
    """

    def __init__(self, capacity: int, on_evict=None):
        assert capacity >= 1, capacity
        self.capacity = int(capacity)
        self._on_evict = on_evict
        self._data: dict = {}          # insertion-ordered; end = most recent
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    def keys(self):
        return list(self._data)

    def get(self, key, default=None):
        """Fetch and mark ``key`` most-recently-used."""
        if key not in self._data:
            return default
        value = self._data.pop(key)
        self._data[key] = value
        return value

    def put(self, key, value) -> None:
        """Insert/refresh ``key``, evicting the LRU entry when full."""
        if key in self._data:
            self._data.pop(key)
        self._data[key] = value
        while len(self._data) > self.capacity:
            old_key = next(iter(self._data))
            old_val = self._data.pop(old_key)
            self.evictions += 1
            if self._on_evict is not None:
                self._on_evict(old_key, old_val)

    def pop(self, key, default=None):
        return self._data.pop(key, default)

    def clear(self) -> None:
        self._data.clear()


def stats() -> CacheStats:
    """The process-global cache counters (live object)."""
    return _STATS


def reset_stats() -> None:
    global _STATS
    _STATS = CacheStats()


def clear_memory() -> None:
    """Drop the in-memory tier (disk entries survive).  Test hook."""
    _MEMORY.clear()
    _DECISIONS.clear()


def clear_disk(directory: Optional[str] = None) -> int:
    """Remove the persistent tier under ``directory`` (default resolution
    as in :func:`cache_dir`).  Only files this layer wrote are touched --
    32-hex-digit signature names plus the ``chardb_<16-hex>`` hardware
    characterization stores, ``.npz``/``.json`` suffixes -- so a
    mis-pointed ``$REPRO_CACHE_DIR`` cannot wipe unrelated data.  Returns
    the number of entries removed; a missing directory is a no-op.
    """
    d = cache_dir(directory)
    if not os.path.isdir(d):
        return 0
    removed = 0
    for name in os.listdir(d):
        stem, dot, ext = name.rpartition(".")
        if ext not in ("npz", "json"):
            continue
        if stem.startswith("chardb_"):
            stem = stem[len("chardb_"):]
            if len(stem) != 16:
                continue
        elif len(stem) != 32:
            continue
        if not all(c in "0123456789abcdef" for c in stem):
            continue
        try:
            os.unlink(os.path.join(d, name))
            removed += 1
        except OSError:  # concurrent clear / permissions: best effort
            pass
    return removed


def cache_dir(override: Optional[str] = None) -> str:
    if override:
        return override
    env = os.environ.get("REPRO_CACHE_DIR")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro_sht")


def signature_key(kind: str, **fields) -> str:
    """Stable content hash of a plan-signature field dict.

    numpy arrays hash by value (shape + dtype + bytes), so a ``RingGrid``
    passed by instance keys identically to one rebuilt from the same spec.
    """
    h = hashlib.sha256()
    h.update(f"v{CACHE_VERSION}:{kind}".encode())
    for name in sorted(fields):
        v = fields[name]
        h.update(name.encode())
        if isinstance(v, np.ndarray):
            h.update(str(v.shape).encode())
            h.update(str(v.dtype).encode())
            h.update(np.ascontiguousarray(v).tobytes())
        else:
            h.update(repr(v).encode())
    return h.hexdigest()[:32]


def _atomic_write(path: str, write_fn: Callable[[str], None]) -> None:
    """Best-effort atomic persist: an unwritable cache dir degrades to
    memory-only caching (warn once) instead of failing the plan build."""
    tmp = None
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        os.close(fd)
        write_fn(tmp)
        os.replace(tmp, path)
    except OSError as e:
        warnings.warn(f"repro cache: cannot persist {path!r} ({e}); "
                      "falling back to in-memory caching", RuntimeWarning)
    finally:
        if tmp is not None and os.path.exists(tmp):
            os.unlink(tmp)


def get_or_build(key: str, builder: Callable[[], dict],
                 *, cache: str = "memory",
                 directory: Optional[str] = None) -> dict:
    """Return the payload for ``key``, building it at most once.

    cache: ``"off"`` (always build), ``"memory"`` (process-local), or
    ``"disk"`` (memory first, then ``<dir>/<key>.npz``, else build+persist).
    Builders return flat ``dict[str, np.ndarray]``.
    """
    if cache == "off":
        _STATS.builds += 1
        return builder()
    if key in _MEMORY:
        _STATS.memory_hits += 1
        return _MEMORY[key]
    if cache == "disk":
        path = os.path.join(cache_dir(directory), key + ".npz")
        if os.path.exists(path):
            try:
                with np.load(path, allow_pickle=False) as z:
                    payload = {k: z[k] for k in z.files}
                _STATS.disk_hits += 1
                _MEMORY[key] = payload
                return payload
            except Exception:
                pass  # torn/stale file: fall through and rebuild
    _STATS.misses += 1
    _STATS.builds += 1
    payload = builder()
    _MEMORY[key] = payload
    if cache == "disk":
        path = os.path.join(cache_dir(directory), key + ".npz")

        def write(tmp: str) -> None:
            # write through a file object: np.savez must not append ".npz"
            with open(tmp, "wb") as f:
                np.savez(f, **payload)

        _atomic_write(path, write)
    return payload


def load_decision(key: str, *, cache: str = "memory",
                  directory: Optional[str] = None) -> Optional[dict]:
    """Fetch a cached autotune decision (json-able dict) or None."""
    if cache == "off":
        return None
    if key in _DECISIONS:
        _STATS.memory_hits += 1
        return _DECISIONS[key]
    if cache == "disk":
        path = os.path.join(cache_dir(directory), key + ".json")
        if os.path.exists(path):
            try:
                with open(path) as f:
                    d = json.load(f)
                _STATS.disk_hits += 1
                _DECISIONS[key] = d
                return d
            except Exception:
                return None
    return None


def save_decision(key: str, decision: dict, *, cache: str = "memory",
                  directory: Optional[str] = None) -> None:
    if cache == "off":
        return
    _DECISIONS[key] = decision
    if cache == "disk":
        path = os.path.join(cache_dir(directory), key + ".json")

        def write(tmp: str) -> None:
            with open(tmp, "w") as f:
                json.dump(decision, f, indent=1, sort_keys=True)

        _atomic_write(path, write)
