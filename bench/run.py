#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names its
configuration and traffic files; see ``common.py`` for how each part is
found by name, and ``drivers.py`` for what a run does.  Earlier lines of
standard output name the device, the plans' chosen backends and layouts,
the compiles seen inside the window (there should be none), the peak
device bytes in use and how late the generator ran.  The last lines of
standard error are the numbers the check compared, each with its limit;
the last line of standard output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window by the readers under ``bench/metrics/``; a traced run's window is
the mix's ``trace_seconds`` where that is shorter.  Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result.

Caches, all inside the checkout at fixed paths: JAX's persistent
compilation cache in ``.jax_cache/``, the plans' autotune decisions and
the characterization DB in ``.bench_cache/``, the last trace in
``.bench_out/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import ROOT, Cell, log, make_driver, start, warn  # noqa: E402


class CompileCounter:
    """Backend compiles (a persistent-cache hit is not one), from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _work(cell) -> dict:
    from work import sht_work
    c = cell.config
    return sht_work(c["l_max"], c["m_max"], c["n_rings"], c["n_phi"],
                    int(cell.traffic["K"]))


def _peaks(root: str, kind: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True,
         t_start: float = T_START, patch=None) -> int:
    """One run.  ``require_tpu=False`` and ``patch`` (a callable given the
    driver after its set-up) are for the CPU tests of the harness."""
    args = parse(argv)
    cell = Cell(root, args.workload)
    devices = start(root, cell.chips, "bench", require_tpu)
    if devices is None:
        return 3
    import jax
    d0 = devices[0]
    import repro  # noqa: F401  (the program under test)
    from repro import compile_cache
    log("device", platform=d0.platform, kind=d0.device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=compile_cache.enable())
    peaks = _peaks(root, d0.device_kind) if require_tpu else None
    counter = CompileCounter()
    driver = make_driver(cell, root)
    driver.setup()
    if patch is not None:
        patch(driver)
    driver.prepare(args.seed)
    setup_s = time.perf_counter() - t_start
    compiles_before = counter.count
    trace_dir = os.path.join(root, ".bench_out", "trace")
    seconds = args.seconds
    if args.trace:
        # a mix of many small programs traces a shorter window, so that
        # collecting and reading the trace stays well inside a run's time
        seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                      seconds)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            e2e, record = driver.window(seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    log("window", compiles=counter.count - compiles_before,
        **{k: v for k, v in record.items() if not isinstance(v, list)})
    peak = _peak_bytes(devices[:cell.chips])
    log("memory", peak_bytes_in_use=peak)
    driver.collect()
    driver.release()
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {}
    if args.trace:
        import trace_reduce as trace
        reduced = trace.reduce(trace.load_dir(trace_dir))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        rec = {"cell": cell.name, "config": cell.config,
               "traffic": cell.traffic, "trace": reduced, "peaks": peaks,
               "work": _work(cell), "window": record}
        metrics = {}
        for m in cell.per_layer():
            value = cell.reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": [[name, s] for name, s, _ in reduced["ops"][:10]],
            "idle_gaps": reduced["gaps"][:10]}
        log("trace", ops=reduced["ops"][:10])
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
    with jax.profiler.TraceAnnotation("check"):
        numbers, attempted, failed = driver.check()
    limits = cell.checks["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    for k, c in checks.items():
        warn(f"check {k} = {c['value']!r} limit {c['limit']!r}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **out, "checks": checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
