"""The program's own spans and scopes in a run's profiler trace.

`trace_reduce` keeps the benchmark's spans and the device's op kinds.
This module keeps what the program under test names (`repro.tracing`):

* each device op with its ``tf_op``, the op's ``op_name`` metadata, whose
  path holds the program's stage scopes (``sht.phase``, ``sht.legendre``
  with ``recurrence`` / ``accumulate`` inside the jnp scan, ``sht.fold``);
  the trace carries it in the op's event metadata, which
  ``ProfileData`` does not show, so the file is read with `xspace`;
* the host spans whose names start with ``sht.`` or ``engine.``, with the
  ``batch`` argument the engine's spans carry and the host line they
  were on, beside the benchmark's own spans (`trace_reduce.SPANS`).

The per-layer readers under ``bench/metrics/`` call :func:`of_reader`
for the trace their run wrote (``<root>/.bench_out/trace``); it is read
once per process.  Against a program without `repro.tracing`, or a trace
without its spans, every function here returns None: the metric is then
left out of the result line.  Ops are read up to :data:`MAX_OPS` per
device; where that cap cut the window, the scope seconds are None, never
a part.  Each analysis logs one line
the first time it runs: the scope seconds per call with the scoped share
of the op time, and the engine's batches with the longest idle gaps
named ``<benchmark span>/<program span>``.
"""

from __future__ import annotations

import glob
import os
import statistics

import trace_reduce as tr
import xspace
from common import log

__all__ = ["names", "load", "of_reader", "stage_seconds", "scopes_per_call",
           "engine_batches", "engine_host_s_per_batch", "engine_idle_s",
           "named_gaps"]

#: host spans of the program under test start with one of these
PROGRAM = ("sht.", "engine.")
#: device ops read per device.  `trace_reduce.MAX_OPS` caps its own read
#: at 300 000 because ``ProfileData`` costs ~0.5 ms of host time per
#: event; read here an event costs ~3 us, and the library cell's 30 s
#: window holds more than 300 000 ops.
MAX_OPS = 2_000_000

_cache: dict = {}
_logged: set = set()


def names():
    """`repro.tracing` of the program under test, or None where it has
    none (an older program)."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def _start(line, ev) -> float:
    return line.timestamp_ns + ev.offset_ps * 1e-3


def load(path: str) -> dict:
    """``{"ops": {plane: [[start_ns, dur_ns, name, tf_op], ...]},
    "modules": {plane: [[start_ns, dur_ns], ...]},
    "cut_at": {plane: start_ns of the first op not read, or None},
    "spans": [[start_ns, dur_ns, name, batch, line], ...]}``."""
    ops, modules, cut_at, spans = {}, {}, {}, []
    for plane in xspace.parse(path).planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name.startswith("/device:"):
            meta = {}
            for e in plane.event_metadata:
                tf = next((str(xspace.stat_value(s, stat_names))
                           for s in e.value.stats
                           if stat_names.get(s.metadata_id) == "tf_op"), "")
                meta[e.key] = (e.value.name, tf)
            for line in plane.lines:
                if line.name == "XLA Modules" and len(line.events):
                    modules[plane.name] = [[_start(line, ev),
                                            ev.duration_ps * 1e-3]
                                           for ev in line.events]
                elif line.name == "XLA Ops" and len(line.events):
                    evs = line.events
                    ops[plane.name] = [
                        [_start(line, ev), ev.duration_ps * 1e-3]
                        + list(meta.get(ev.metadata_id, ("", "")))
                        for ev in evs[:MAX_OPS]]
                    cut_at[plane.name] = (_start(line, evs[MAX_OPS])
                                          if len(evs) > MAX_OPS else None)
        elif plane.name.startswith("/host:"):
            meta = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    name = meta.get(ev.metadata_id, "")
                    if name not in tr.SPANS and not name.startswith(PROGRAM):
                        continue
                    batch = next((int(xspace.stat_value(s, stat_names))
                                  for s in ev.stats
                                  if stat_names.get(s.metadata_id)
                                  == "batch"), None)
                    spans.append([_start(line, ev), ev.duration_ps * 1e-3,
                                  name, batch, line.name])
    return {"ops": ops, "modules": modules, "cut_at": cut_at,
            "spans": spans, "path": path}


def of_reader(reader_file: str):
    """The trace the run wrote, found from a reader's own path
    (``<root>/bench/metrics/<metric>.py``); None where there is none."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
    files = glob.glob(os.path.join(root, ".bench_out", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = load(path)
    return _cache[key]


def _log_once(tag: str, t, **fields) -> None:
    """One ``[tag]`` line per trace, for the first reader that asks."""
    if (tag, t["path"]) not in _logged:
        _logged.add((tag, t["path"]))
        log(tag, **fields)


def _window(t):
    w = [(s, s + d) for s, d, name, *_ in t["spans"] if name == "window"]
    return w[0] if w else None


def _scope_of(tf_op: str, nm) -> tuple:
    """(innermost stage scope, sub-scope inside it) of an op's path."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    parts = path.split("/")
    stage = sub = None
    for i, p in enumerate(parts):
        if p in nm.STAGES:
            stage = p
            sub = next((q for q in reversed(parts[i + 1:])
                        if q in nm.SUB_STAGES), None)
    return stage, sub


def stage_seconds(t) -> dict | None:
    """Device-op seconds inside the window, averaged over the devices:
    ``ops`` (all but loop containers, as `trace_reduce` counts them),
    each stage scope, ``<stage>/<sub-scope>``, and ``unscoped``.  None
    without the program's names, device ops or scoped ops, or where the
    op cap cut the window."""
    nm = names()
    win = _window(t) if t else None
    if nm is None or win is None or not t["ops"]:
        return None
    lo, hi = win
    if any(c is not None and c < hi for c in t["cut_at"].values()):
        _log_once("cut", t, ops_read=MAX_OPS, cut_at_s=repr(
            (min(c for c in t["cut_at"].values() if c) - lo) * 1e-9))
        return None
    sums: dict = {}
    for events in t["ops"].values():
        for s, d, name, tf in events:
            d_in = min(s + d, hi) - max(s, lo)
            if d_in <= 0 or tr.group_name(name) in tr.CONTAINERS:
                continue
            stage, sub = _scope_of(tf, nm)
            for k in ("ops", stage or "unscoped",
                      f"{stage}/{sub}" if sub else None):
                if k:
                    sums[k] = sums.get(k, 0.0) + d_in
    if not any(sums.get(s) for s in nm.STAGES):
        return None
    n = len(t["ops"])
    return {k: v * 1e-9 / n for k, v in sums.items()}


def scopes_per_call(t, calls) -> dict | None:
    """:func:`stage_seconds` per call of the window (library cells)."""
    sec = stage_seconds(t)
    if sec is None or not calls:
        return None
    per = {k: v / calls for k, v in sec.items()}
    _log_once("scopes", t, calls=calls,
              ops_read=sum(map(len, t["ops"].values())),
              scoped_share=repr(1.0 - sec.get("unscoped", 0.0) / sec["ops"]),
              **{f"{k}_s": repr(v) for k, v in sorted(per.items())})
    return per


def _engine_spans(t, nm):
    return [sp for sp in t["spans"] if sp[2] in nm.ENGINE_SPANS]


def engine_batches(t) -> dict | None:
    """``{batch: {"execute": count, "host_s": seconds, "formed": bool,
    <span name>: seconds}}`` for the batches whose ``engine.execute`` span
    lies in the window; ``host_s`` sums the batch's host stages
    (`repro.tracing.ENGINE_HOST`).  None without the program's names or
    engine spans."""
    nm = names()
    win = _window(t) if t else None
    if nm is None or win is None:
        return None
    spans = _engine_spans(t, nm)
    if not spans:
        return None
    lo, hi = win
    out = {b: {"execute": 0, "host_s": 0.0, "formed": False}
           for s, d, name, b, _ in spans
           if name == nm.ENGINE_EXECUTE and s >= lo and s + d <= hi}
    for s, d, name, b, _ in spans:
        if b not in out:
            continue
        out[b][name] = out[b].get(name, 0.0) + d * 1e-9
        if name == nm.ENGINE_EXECUTE:
            out[b]["execute"] += 1
        elif name in nm.ENGINE_HOST:
            out[b]["host_s"] += d * 1e-9
            out[b]["formed"] |= name == nm.ENGINE_FORM
    return out


def engine_host_s_per_batch(t) -> float | None:
    """Median over the window's batches of their host-stage seconds
    (batches whose formation began before the trace are left out)."""
    batches = engine_batches(t)
    formed = [b for b in (batches or {}).values() if b["formed"]]
    if not formed:
        return None
    host = [b["host_s"] for b in formed]
    nm = names()
    _log_once("engine", t, batches=len(batches), formed=len(host),
              execute_spans=sorted({b["execute"]
                                    for b in batches.values()}),
              host_s_median=repr(statistics.median(host)),
              host_s_max=repr(max(host)),
              **{f"{n.split('.')[-1]}_s_median": repr(statistics.median(
                  b.get(n, 0.0) for b in formed))
                 for n in nm.ENGINE_HOST + (nm.ENGINE_EXECUTE,)},
              gaps=named_gaps(t))
    return statistics.median(host)


def _busy(t, plane, lo, hi) -> list:
    mods = t["modules"].get(plane)
    iv = [(s, s + d) for s, d in mods] if mods else \
        [(s, s + d) for s, d, *_ in t["ops"].get(plane, [])]
    return tr._merged(iv, lo, hi)


def _idle(busy, lo, hi) -> list:
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def _overlap_s(a, b) -> float:
    """Seconds in both of two sorted, merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def engine_idle_s(t) -> float | None:
    """Seconds of the window in which the device was idle while an engine
    thread was in a span of its own work (any engine span but the waits
    for work and for the execute thread), averaged over the devices.
    None without device planes, the program's names or engine spans."""
    nm = names()
    win = _window(t) if t else None
    planes = sorted(set(t["modules"]) | set(t["ops"])) if t else []
    if nm is None or win is None or not planes:
        return None
    lo, hi = win
    working = [(s, s + d) for s, d, name, *_ in _engine_spans(t, nm)
               if name not in nm.ENGINE_WAITS]
    if not _engine_spans(t, nm):
        return None
    working = tr._merged(working, lo, hi)
    return sum(_overlap_s(_idle(_busy(t, p, lo, hi), lo, hi), working)
               for p in planes) / len(planes)


def _program_span_at(spans, nm, at: float) -> str | None:
    """The innermost program span covering ``at``, a span of work before
    a wait."""
    best = None
    for s, d, name, *_ in spans:
        if not name.startswith(PROGRAM) or not s <= at <= s + d:
            continue
        rank = (name in nm.ENGINE_WAITS, d)
        if best is None or rank < best[0]:
            best = (rank, name)
    return best[1] if best else None


def named_gaps(t, top: int = 10) -> list | None:
    """The longest idle gaps of the device in the window, longest first,
    as ``[name, seconds]``: the benchmark span at the gap's middle, and
    ``/<program span>`` where one was open there."""
    nm = names()
    win = _window(t) if t else None
    planes = sorted(set(t["modules"]) | set(t["ops"])) if t else []
    if nm is None or win is None or not planes:
        return None
    lo, hi = win
    bench = [sp[:3] for sp in t["spans"] if sp[2] in tr.SPANS]
    gaps = sorted(((e - s, s) for p in planes
                   for s, e in _idle(_busy(t, p, lo, hi), lo, hi)),
                  reverse=True)[:top]
    out = []
    for d, s in gaps:
        mid = s + d / 2
        name = tr._span_at(bench, mid)
        prog = _program_span_at(t["spans"], nm, mid)
        out.append([f"{name}/{prog}" if prog else name, d * 1e-9])
    return out
