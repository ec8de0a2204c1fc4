"""Warm Plan pool: bounded LRU of live transform plans, keyed by signature.

``make_plan`` memoises globally and never forgets; a serving process that
sees many distinct signatures over its lifetime needs a *bounded* working
set of live plans (each one owns device seed tables and compiled
executables).  ``PlanPool`` keeps the ``capacity`` most-recently-used
plans, releasing evicted ones through ``transform.drop_plan`` so they can
actually be garbage-collected, and exposes hit/miss/eviction/warm-up
counters for the engine's ``stats()``.

Plans here are always built with ``K = k_plan`` -- the engine's coalesced
channel-bucket width -- so one pooled plan serves every micro-batch of its
signature with a dense, fixed-shape device step (libsharp's "never launch
a ragged step" rule applied to the K axis).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

from repro.core import cache as plancache

__all__ = ["PlanSig", "PlanPool"]


@dataclasses.dataclass(frozen=True)
class PlanSig:
    """The serving-level plan signature: everything that decides whether
    two requests may share one coalesced device batch (direction rides on
    the group key, not here -- one plan serves both directions)."""

    grid: str
    l_max: Optional[int] = None
    nside: Optional[int] = None
    m_max: Optional[int] = None
    spin: int = 0
    dtype: Optional[str] = None     # None: transform.default_dtype()

    def __post_init__(self):
        if self.dtype is None:
            from repro.core import transform
            object.__setattr__(self, "dtype", transform.default_dtype())

    def label(self) -> str:
        geo = f"nside{self.nside}" if self.nside else f"lmax{self.l_max}"
        return f"{self.grid}/{geo}/spin{self.spin}/{self.dtype}"


class PlanPool:
    """Bounded LRU of warm plans on top of ``make_plan``'s signature cache.

    Thread-safe: ``get``/``warm`` may be called from the engine's
    formation thread and from background warm-up threads concurrently.
    The pool lock only guards the LRU map; *building* a plan happens
    outside it behind a per-key build event, so a warm-up compiling one
    signature never blocks ``get`` for a different signature (the
    double-buffered engine's formation thread must keep staging), while
    two concurrent requests for the *same* key still build it once.
    """

    def __init__(self, capacity: int = 8, *, mode: str = "auto",
                 cache: str = "auto", cache_dir: Optional[str] = None):
        self.mode = mode
        self.cache = cache
        self.cache_dir = cache_dir
        self._lock = threading.RLock()
        self._lru = plancache.LRU(capacity, on_evict=self._release)
        self._building: dict = {}           # key -> threading.Event
        self.hits = 0
        self.misses = 0
        self.warmups = 0

    @staticmethod
    def _release(key, plan) -> None:
        from repro.core import transform
        transform.drop_plan(plan)

    @property
    def capacity(self) -> int:
        return self._lru.capacity

    @property
    def evictions(self) -> int:
        return self._lru.evictions

    def __len__(self) -> int:
        return len(self._lru)

    def _key(self, sig: PlanSig, k_plan: int):
        return (sig, int(k_plan))

    def get(self, sig: PlanSig, k_plan: int):
        """The pooled plan for ``(sig, k_plan)``, building it on a miss."""
        import repro
        key = self._key(sig, k_plan)
        while True:
            with self._lock:
                plan = self._lru.get(key)
                if plan is not None:
                    self.hits += 1
                    return plan
                done = self._building.get(key)
                if done is None:
                    done = threading.Event()
                    self._building[key] = done
                    self.misses += 1
                    break
            # another thread is building this key: wait it out, then
            # re-check the LRU (on build failure we retry as the builder)
            done.wait()
        try:
            plan = repro.make_plan(
                sig.grid, sig.l_max, nside=sig.nside, m_max=sig.m_max,
                K=int(k_plan), dtype=sig.dtype, spin=sig.spin,
                mode=self.mode, cache=self.cache, cache_dir=self.cache_dir)
            with self._lock:
                self._lru.put(key, plan)
            return plan
        finally:
            with self._lock:
                del self._building[key]
            done.set()

    def warm(self, sig: PlanSig, k_plan: int,
             directions=("synth", "anal")):
        """Build AND compile the plan for ``(sig, k_plan)`` so the first
        real request pays no trace/compile latency."""
        plan = self.get(sig, k_plan)
        plan.warmup(directions)
        with self._lock:
            self.warmups += 1
        return plan

    def stats(self) -> dict:
        from repro.roofline import chardb
        with self._lock:
            total = self.hits + self.misses
            return {
                "size": len(self._lru),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "warmups": self.warmups,
                "hit_rate": (self.hits / total) if total else float("nan"),
                # autotune corners behind the pooled plans: a warm pool
                # should show reuse, not re-measurement
                "chardb": chardb.stats(),
            }
