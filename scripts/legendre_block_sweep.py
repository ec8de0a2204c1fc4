"""Sweep the row-block count of the jnp Legendre loop on one chip.

For each size and each forced block count C, builds a float32 jnp plan on
the Gauss-Legendre grid, compiles one direction, and prints one JSON line:
seconds per call (median of ``--calls`` calls, each blocked to
completion), the block step's bytes (`legendre.row_step_bytes`), the
compiled program's temp bytes, the row-step share, and the largest
difference from the single full loop (C = 1) relative to its largest
value.  ``BLOCK_STEP_BYTES`` is read off these lines, within what a
profiler trace of the benchmark's window can hold (see its comment).
Forces C by replacing ``legendre._blocks_for``.

    python scripts/legendre_block_sweep.py --lmax 4096 --K 4 --blocks 1,4,8,16
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax
import numpy as np

import repro
from repro import compile_cache
from repro.core import legendre


def rows_for(M: int, C: int) -> int:
    """Rows per block for about C blocks of a multiple of 8 rows (0: one
    full loop)."""
    if C == 1:
        return 0
    return -(-M // (8 * C)) * 8


def run(l_max, K, direction, fold, blocks, calls, seed):
    M = l_max + 1
    ref = None
    for C in blocks:
        rows = rows_for(M, C)
        legendre._blocks_for = lambda *a, _r=rows, **k: _r
        jax.clear_caches()
        plan = repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                               mode="jnp", fold=fold, cache="off")
        rng = np.random.default_rng(seed)
        if direction == "anal":
            fn = plan._anal_fn("jnp")
            x = rng.uniform(-1, 1, plan._maps_shape).astype(np.float32)
        else:
            fn = plan._synth_fn("jnp")
            x = (rng.uniform(-1, 1, plan._alm_shape)
                 + 1j * rng.uniform(-1, 1, plan._alm_shape)
                 ).astype(np.complex64)
        x = jax.device_put(x)
        t0 = time.perf_counter()
        exe = fn.lower(x).compile()
        compile_s = time.perf_counter() - t0
        out = jax.block_until_ready(exe(x))
        times = []
        for _ in range(calls):
            t0 = time.perf_counter()
            out = jax.block_until_ready(exe(x))
            times.append(time.perf_counter() - t0)
        out = np.asarray(out)
        if ref is None:
            ref = out
        rings = plan._sht.n_north if fold else plan.grid.n_rings
        row_bytes = legendre.row_step_bytes(direction, fold=fold,
                                            n_rings=rings, K=K,
                                            dtype="float32")
        n = -(-M // rows) if rows else 1
        share = 1.0
        if rows:
            l0 = np.minimum(np.arange(n) * rows, l_max + 1)
            share = float(rows * (l_max + 1 - l0).sum() / (M * (l_max + 1)))
        print(json.dumps({
            "l_max": l_max, "K": K, "direction": direction, "fold": fold,
            "C": n, "rows_per_block": rows or M,
            "block_step_mb": (rows or M) * row_bytes / 1e6,
            "row_step_share": share,
            "s_per_call": statistics.median(times), "times": times,
            "compile_s": compile_s,
            "temp_mb": exe.memory_analysis().temp_size_in_bytes / 1e6,
            "rel_diff_vs_C1": float(np.max(np.abs(out - ref))
                                    / np.max(np.abs(ref))),
            "device": jax.devices()[0].device_kind,
            "C_chosen_by_code": legendre.loop_blocks(
                np.arange(M), l_max=l_max, row_bytes=row_bytes)["blocks"],
        }), flush=True)
        del plan, fn, exe, x, out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--lmax", type=int, required=True)
    p.add_argument("--K", type=int, default=1)
    p.add_argument("--direction", choices=("anal", "synth"), default="anal")
    p.add_argument("--fold", action="store_true")
    p.add_argument("--blocks", default="1,2,4,8")
    p.add_argument("--calls", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args()
    compile_cache.enable()
    run(a.lmax, a.K, a.direction, a.fold,
        [int(c) for c in a.blocks.split(",")], a.calls, a.seed)


if __name__ == "__main__":
    main()
