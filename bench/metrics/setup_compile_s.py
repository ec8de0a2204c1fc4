"""Seconds of backend compiles and persistent-cache loads in the run,
from the program's own counter (``repro.compile_cache.stats()``, counting
from ``enable()`` at start-up).  Read after the window; the harness logs
the compiles inside the window, which should be none, and nothing after
the window compiles, so this is the set-up's share."""

from common import log

_logged = []


def read(record):
    try:
        from repro.compile_cache import stats
    except ImportError:
        return None
    s = stats()
    if not _logged:
        _logged.append(s)
        log("compile", **{k: repr(v) for k, v in s.items()})
    return s["compile_s"] + s["load_s"]
