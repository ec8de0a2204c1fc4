"""Device seconds under the distributed plan's own scopes.

`program_trace.stage_seconds` splits a call by the stage scopes every
plan has (``repro.tracing.STAGES``).  The distributed plan adds two,
``repro.tracing.DIST_STAGES``: the exchange (``sht.exchange``: the
all_to_all and the packing of its channels) and the reshard
(``sht.reshard``: the plan's reorders around its sharded core).  An op
counts for the innermost of them on its scope path; the seconds are
averaged over the devices, as `program_trace` averages.  Against a
program without these names, or a trace whose window the op cap cut,
:func:`seconds_per_call` returns None.
"""

from __future__ import annotations

import program_trace as pt
import trace_reduce as tr
from common import log

__all__ = ["seconds_per_call"]

_logged: set = set()


def _window(t):
    w = [(s, s + d) for s, d, name, *_ in t["spans"] if name == "window"]
    return w[0] if w else None


def seconds_per_call(record, reader_file: str) -> dict | None:
    """``{scope: device seconds per call}`` of the window's calls, for each
    of the distributed plan's scopes, from the trace of the run (found
    from a reader's own path, as `program_trace.of_reader` does)."""
    stages = getattr(pt.names(), "DIST_STAGES", None)
    t = pt.of_reader(reader_file)
    calls = record["window"].get("calls")
    win = _window(t) if t else None
    if not stages or win is None or not t["ops"] or not calls:
        return None
    lo, hi = win
    if any(c is not None and c < hi for c in t["cut_at"].values()):
        return None
    sums = dict.fromkeys(stages, 0.0)
    for events in t["ops"].values():
        for s, d, name, tf in events:
            d_in = min(s + d, hi) - max(s, lo)
            if d_in <= 0 or tr.group_name(name) in tr.CONTAINERS:
                continue
            path = tf.rpartition(":")[0] if ":" in tf else tf
            inner = [p for p in path.split("/") if p in stages]
            if inner:
                sums[inner[-1]] += d_in
    per = {k: v * 1e-9 / len(t["ops"]) / calls for k, v in sums.items()}
    if t["path"] not in _logged:
        _logged.add(t["path"])
        log("dist_scopes", calls=calls,
            **{f"{k}_s": repr(v) for k, v in per.items()})
    return per
