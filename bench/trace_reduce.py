"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps three things: the device's program executions (the ``XLA Modules``
line of each ``/device:`` plane), its operations (the ``XLA Ops`` line,
the first :data:`MAX_OPS` of them: a 30 s window of small programs holds
millions, and reading each costs the host ~0.1 ms) and the benchmark's
own host spans (``jax.profiler.TraceAnnotation`` names in
:data:`SPANS`).  ``reduce`` turns them into

* ``window_s``: the length of the benchmark's ``window`` span;
* ``busy_s``: the union of the device's program intervals inside the
  window (of its operation intervals where a trace has no modules),
  averaged over the devices (``idle`` is 1 - busy / window);
* ``ops``: device seconds per operation group (the operation's name
  without its numeric suffix, ``fusion.12`` -> ``fusion``), with the
  group's HLO category where the trace gives one, over the operations
  read; loops that contain other ops (``while``) are left out;
* ``gaps``: the idle intervals inside the window, longest first, each
  named by the innermost benchmark span the host was in at its middle.
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["SPANS", "MAX_OPS", "load", "load_dir", "reduce", "union_s"]

#: Host spans the harness opens (``TraceAnnotation``), outermost first.
SPANS = ("window", "generate", "call", "submit", "wait", "check")

_SUFFIX = re.compile(r"(\.\d+)+$")
#: ops that contain others on the same line (a loop and its body): they
#: count for busy time, not in the per-op seconds
CONTAINERS = ("while", "conditional", "call")
#: device operations read per plane (the per-op seconds cover these)
MAX_OPS = 300_000
#: stats of a device op that name its kind, in order of preference
_KIND_STATS = ("hlo_category", "category", "hlo_op_category")


def group_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``%copy.3 = f32[..] copy(..)`` ->
    ``copy``."""
    return _SUFFIX.sub("", name.lstrip("%").split(" ", 1)[0])


def load(path: str) -> dict:
    """``{"devices": {plane: [[start_ns, dur_ns, name, kind], ...]},
    "modules": {plane: [[start_ns, dur_ns], ...]},
    "spans": [[start_ns, dur_ns, name], ...]}`` from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [[float(ev.start_ns), float(ev.duration_ns)]
                            for ev in line.events]
                if line.name != "XLA Ops":
                    continue
                for i, ev in enumerate(line.events):
                    if i == MAX_OPS:
                        break
                    stats = dict(ev.stats)
                    kind = next((str(stats[k]) for k in _KIND_STATS
                                 if k in stats), "")
                    ops.append([float(ev.start_ns), float(ev.duration_ns),
                                ev.name, kind])
            if ops:
                devices[plane.name] = ops
            if mods:
                modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append([float(ev.start_ns),
                                      float(ev.duration_ns), ev.name])
    return {"devices": devices, "modules": modules, "spans": spans}


def load_dir(trace_dir: str) -> dict:
    """:func:`load` of the newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load(max(files, key=os.path.getmtime))


def _merged(intervals, lo: float, hi: float) -> list:
    """Sorted, merged ``[start, end]`` intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_s(intervals, lo: float, hi: float) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals
    inside [lo, hi]."""
    return sum(e - s for s, e in _merged(intervals, lo, hi)) * 1e-9


def _span_at(spans, t: float) -> str:
    """Innermost benchmark span (other than the window) covering t."""
    best, best_len = "none", float("inf")
    for s, d, name in spans:
        if name != "window" and s <= t <= s + d and d < best_len:
            best, best_len = name, d
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """Device numbers of the traced window (see the module docstring)."""
    windows = [(s, s + d) for s, d, name in trace["spans"]
               if name == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    busy, ops, kinds, gaps = [], {}, {}, []
    for plane, events in sorted(trace["devices"].items()):
        mods = trace.get("modules", {}).get(plane)
        iv = [(s, s + d) for s, d in mods] if mods else \
            [(s, s + d) for s, d, _, _ in events]
        busy.append(union_s(iv, lo, hi))
        for s, d, name, kind in events:
            d_in = min(s + d, hi) - max(s, lo)
            if d_in <= 0:
                continue
            g = group_name(name)
            if g in CONTAINERS:
                continue
            ops[g] = ops.get(g, 0.0) + d_in * 1e-9
            if kind:
                kinds[g] = kind
        merged = _merged(iv, lo, hi)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    n_dev = max(len(busy), 1)
    gaps = [[_span_at(trace["spans"], s + d / 2), d * 1e-9]
            for d, s in sorted(gaps, reverse=True)[:top]]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "n_devices": len(busy),
        "ops": sorted(([g, t / n_dev, kinds.get(g, "")]
                       for g, t in ops.items()), key=lambda o: -o[1]),
        "gaps": gaps,
    }
