"""Persistent per-hardware autotune characterization DB.

``make_plan(mode="auto")`` used to one-shot time every candidate corner
(backend x direction x layout) on every cache-cold plan build.  This
module replaces that with a characterization database: measured corner
timings are cached under a *hardware fingerprint* (accelerator backend,
device kind/count, jax version, interpret flag), so

  * a corner is measured at most once per hardware per schema epoch --
    later plan builds (even after the decision cache is cleared) reuse
    the stored microseconds and re-measure zero corners;
  * stale corners (written by an older ``SCHEMA``) are transparently
    re-measured, gating regressions when the timing methodology changes;
  * smoke/CI runs (``REPRO_CHARDB_SMOKE=1``) *skip* corners absent from
    the DB instead of timing them, so CI runtime stays bounded --
    dispatch then falls back to the analytic cost-model ordering.

The store lives in process memory and, when a cache directory is in play
(the same disk tier ``core.cache`` uses, see `cache.cache_dir`), in a
``chardb_<fingerprint>.json`` file next to the other cached payloads.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Callable, Optional

__all__ = [
    "SCHEMA", "CharDB", "hardware_fingerprint", "get_db", "stats",
    "reset_stats", "clear",
]

#: bump when the timing methodology changes; older corners become stale.
#: 2: fused corners gained an ``lp_size`` coordinate (block-shape autotune)
#: and the fused-MXU kernels were restructured, invalidating old timings.
SCHEMA = 2

_SMOKE_ENV = "REPRO_CHARDB_SMOKE"

_lock = threading.Lock()
_DBS: dict[str, "CharDB"] = {}


def smoke_mode() -> bool:
    """True when CI asked for bounded runtime: never measure, only reuse.
    Refused on a TPU, where it would swap measurement for the model."""
    if os.environ.get(_SMOKE_ENV, "") in ("", "0"):
        return False
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(f"${_SMOKE_ENV} replaces measurement with the "
                           "cost model; it is for CPU CI runs, not a TPU")
    return True


def hardware_fingerprint(*, interpret: Optional[bool] = None) -> tuple:
    """(short-hash, human-readable string) identifying the hardware the
    timings are valid for.  Interpret-mode pallas timings are a different
    machine than compiled-TPU timings, so the flag is part of the key."""
    import jax
    dev = jax.devices()[0]
    if interpret is None:
        from repro.kernels.ops import should_interpret
        interpret = should_interpret()
    desc = "|".join([
        jax.default_backend(),
        dev.device_kind,
        str(jax.device_count()),
        jax.__version__,
        f"interpret={int(bool(interpret))}",
    ])
    return hashlib.sha1(desc.encode()).hexdigest()[:16], desc


class CharDB:
    """One characterization store for one hardware fingerprint."""

    def __init__(self, fingerprint: str, desc: str,
                 directory: Optional[str] = None):
        self.fingerprint = fingerprint
        self.desc = desc
        self.directory = directory
        self._store: dict[str, dict] = {}
        self.counters = {"measured": 0, "reused": 0, "skipped": 0,
                         "stale": 0}
        if directory:
            self._load()

    # -- persistence -------------------------------------------------------

    @property
    def path(self) -> Optional[str]:
        if not self.directory:
            return None
        return os.path.join(self.directory,
                            f"chardb_{self.fingerprint}.json")

    def _load(self) -> None:
        try:
            with open(self.path) as fh:
                payload = json.load(fh)
            if isinstance(payload, dict):
                self._store.update(payload.get("corners", {}))
        except (OSError, ValueError):
            pass

    def _save(self) -> None:
        if not self.path:
            return
        os.makedirs(self.directory, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"fingerprint": self.fingerprint, "desc": self.desc,
                       "corners": self._store}, fh)
        os.replace(tmp, self.path)

    # -- corners -----------------------------------------------------------

    @staticmethod
    def corner_key(**fields) -> str:
        """Deterministic key over the corner coordinates.  Callers pass
        the *workload* coordinates (grid/l_max/K/dtype/backend/direction/
        layout/pipeline...) -- never the dispatch mode, so plans built
        with different modes share corners."""
        blob = json.dumps(fields, sort_keys=True, default=str)
        return hashlib.sha1(blob.encode()).hexdigest()[:24]

    def lookup(self, **fields):
        """The stored record for a corner (None if missing or stale)."""
        rec = self._store.get(self.corner_key(**fields))
        if rec is None or rec.get("schema") != SCHEMA:
            return None
        return rec

    def get_or_measure(self, measure_fn: Callable[[], float], **fields):
        """Return ``(us, status)`` for a corner.

        status: ``"reused"`` (fresh record found), ``"measured"`` (ran
        ``measure_fn`` and stored the result; stale records re-measure),
        or ``"skipped"`` (smoke mode and no fresh record: ``us`` is None
        and the caller should fall back to the cost model).
        """
        key = self.corner_key(**fields)
        with _lock:
            rec = self._store.get(key)
            if rec is not None and rec.get("schema") == SCHEMA:
                self.counters["reused"] += 1
                return rec.get("us"), "reused"
            if rec is not None:
                self.counters["stale"] += 1
        if smoke_mode():
            with _lock:
                self.counters["skipped"] += 1
            return None, "skipped"
        us = float(measure_fn())
        with _lock:
            self.counters["measured"] += 1
            self._store[key] = {"schema": SCHEMA, "us": us,
                                "fields": fields}
            self._save()
        return us, "measured"

    def characterize(self, corners, measure_fn) -> dict:
        """Sweep ``corners`` (iterable of field dicts), measuring any that
        are missing or stale via ``measure_fn(fields) -> us``.  Returns
        ``{status: count}``."""
        out = {"measured": 0, "reused": 0, "skipped": 0}
        for fields in corners:
            _, status = self.get_or_measure(
                lambda f=fields: measure_fn(f), **fields)
            out[status] += 1
        return out

    def stats(self) -> dict:
        return {"fingerprint": self.fingerprint, "corners": len(self._store),
                "path": self.path, **self.counters}


def get_db(directory: Optional[str] = None, *,
           interpret: Optional[bool] = None) -> CharDB:
    """The process-wide CharDB for the current hardware (memoized per
    ``(fingerprint, directory)``).  Pass the plan's disk-cache directory
    to persist corners across processes; None keeps them in memory."""
    fp, desc = hardware_fingerprint(interpret=interpret)
    key = f"{fp}:{directory or ''}"
    with _lock:
        db = _DBS.get(key)
        if db is None:
            db = _DBS[key] = CharDB(fp, desc, directory)
        return db


def stats() -> dict:
    """Aggregate counters over every CharDB opened by this process."""
    agg = {"measured": 0, "reused": 0, "skipped": 0, "stale": 0,
           "corners": 0, "dbs": 0}
    with _lock:
        for db in _DBS.values():
            for k in ("measured", "reused", "skipped", "stale"):
                agg[k] += db.counters[k]
            agg["corners"] += len(db._store)
            agg["dbs"] += 1
    return agg


def reset_stats() -> None:
    with _lock:
        for db in _DBS.values():
            db.counters = {k: 0 for k in db.counters}


def clear() -> None:
    """Drop every in-memory DB (disk files are left alone)."""
    with _lock:
        _DBS.clear()
