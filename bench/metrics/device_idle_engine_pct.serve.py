"""Share of the traced window in which the device was idle while an
engine thread was inside a span of its own work (any engine span but
``engine.idle`` and ``engine.handoff``): idle that the engine caused, not
the traffic.  At most ``device_idle_pct.serve``."""

import program_trace as pt


def read(record):
    t = record.get("trace")
    idle = pt.engine_idle_s(pt.of_reader(__file__))
    if idle is None or not t or t["window_s"] <= 0:
        return None
    return 100.0 * idle / t["window_s"]
