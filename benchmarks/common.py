"""Shared benchmark utilities: timing, CSV emission, smoke-mode gating."""

import os
import subprocess
import sys
import time

import jax
import numpy as np


def enable_float64_oracle() -> None:
    """Turn on JAX's 64-bit mode for the float64 oracle plans a benchmark
    compares against (`import repro` leaves it off).  CPU only: on a TPU
    float64 plans are refused, and 64-bit mode breaks the float32
    programs' compiles."""
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_x64", True)


def smoke() -> bool:
    """True when REPRO_BENCH_SMOKE=1: one small size, one rep per bench
    (the scripts/check.sh CI gate)."""
    return bool(os.environ.get("REPRO_BENCH_SMOKE"))


def time_call(fn, *args, warmup=1, iters=3, **kw):
    """Median wall time of a jitted call (block_until_ready)."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_pair(fn_a, fn_b, warmup=1, iters=3):
    """Paired interleaved wall times -> (median_a, median_b) seconds.

    Interpret-mode pallas wall times drift 30-40% between runs on a noisy
    host, which makes two independent `time_call` measurements useless for
    an A/B ratio.  Alternating A and B inside one loop exposes both to the
    same drift; the per-call medians stay comparable.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append(time.perf_counter() - t0)
    return float(np.median(ta)), float(np.median(tb))


def time_multi(fns, warmup=1, iters=3):
    """Group-interleaved wall times -> {key: median_seconds}.

    `time_pair` for N alternatives: ``fns`` is ``{key: callable}``; every
    iteration runs each callable once, in dict order, so all candidates see
    the same host drift and their ratios stay meaningful.  Used by the
    dist overlap bench, where ``speedup = t[baseline] / min(t.values())``
    is >= 1.0 by construction whenever the baseline is in the candidate
    set.
    """
    for _ in range(warmup):
        for fn in fns.values():
            jax.block_until_ready(fn())
    ts = {k: [] for k in fns}
    for _ in range(iters):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[k].append(time.perf_counter() - t0)
    return {k: float(np.median(v)) for k, v in ts.items()}


#: every emit() row of the current process, collected so benchmarks/run.py
#: can write its machine-readable BENCH_<date>.json summary
ROWS: list = []


def emit(name, us_per_call, derived=""):
    ROWS.append((str(name), float(us_per_call), str(derived)))
    print(f"{name},{us_per_call:.1f},{derived}")


def run_helper(helper: str, timeout: int = 560):
    """Run a multi-device benchmark helper in a subprocess and re-emit its
    ``CSV name,us,derived`` lines through :func:`emit` so they land in
    the BENCH_<date>.json trajectory.

    The helper simulates 8 host devices in a child process, which is
    only possible on the CPU backend: on a TPU this process holds the
    chip, and a child that needs it fails or hangs -- refused."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "multi-device benchmark helpers simulate host devices in a "
            "child process; run them with JAX_PLATFORMS=cpu, not on "
            f"the {jax.default_backend()} backend this process holds")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # src for repro, the repo root for benchmarks.common (time_multi)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    r = subprocess.run([sys.executable, "-c", helper], capture_output=True,
                       text=True, timeout=timeout, env=env)
    for line in r.stdout.splitlines():
        if line.startswith("CSV "):
            name, us, derived = line[4:].split(",", 2)
            emit(name, float(us), derived)
    return r
