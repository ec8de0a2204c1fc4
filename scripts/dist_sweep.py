"""Time the distributed plan's analysis across the visible chips, by
stage-1 path and exchange chunk count.

For each variant ``<stage1>:<C>`` builds ``repro.make_plan(..., mode="dist",
comm_chunks=C)`` on the Gauss-Legendre grid in float32 (``pallas`` swaps
the plan's stage-1 engine for one that runs the Pallas kernels), compiles
``Plan.map2alm`` on K maps made from the seed, and prints one JSON line:
compile plus first call seconds, seconds per call (median of ``--calls``
calls, each blocked to completion), the largest difference from the first
variant relative to its largest value, the device's peak bytes per chip,
and the warnings the build and calls raised.  ``auto`` as C is the plan's
own pick.  A last line gives ``Plan.report()`` of the default plan.

    python scripts/dist_sweep.py --lmax 4096 --K 4 --variants jnp:1,pallas:1,jnp:2,jnp:4
"""

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax
import numpy as np

import repro
from repro import compile_cache
from repro.core import transform


def variant(plan, stage1: str) -> int:
    """The plan's analysis chunk count, its engine set to ``stage1``."""
    C = plan.comm_chunks["anal"]
    eng = plan._dist_engine(C)
    if eng.stage1 != stage1:
        plan._dists[C] = dataclasses.replace(eng, stage1=stage1)
    return C


def run(l_max, K, variants, calls, seed):
    ref = None
    x = np.random.default_rng(seed).uniform(
        -1, 1, (l_max + 1, 2 * l_max + 2, K)).astype(np.float32)
    for v in variants:
        stage1, c = v.split(":")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            plan = repro.make_plan(
                "gl", l_max=l_max, K=K, dtype="float32", mode="dist",
                comm_chunks=c if c == "auto" else int(c), cache="off")
            C = variant(plan, stage1)
            xd = jax.device_put(x)
            t0 = time.perf_counter()
            out = jax.block_until_ready(plan.map2alm(xd))
            first_s = time.perf_counter() - t0
            times = []
            for _ in range(calls):
                t0 = time.perf_counter()
                out = jax.block_until_ready(plan.map2alm(xd))
                times.append(time.perf_counter() - t0)
        out = np.asarray(out)
        if ref is None:
            ref = out
        print(json.dumps({
            "l_max": l_max, "K": K, "variant": v, "stage1": stage1, "C": C,
            "s_per_call": statistics.median(times), "times": times,
            "compile_and_first_s": first_s,
            "rel_diff_vs_first": float(np.max(np.abs(out - ref))
                                       / np.max(np.abs(ref))),
            "peak_bytes": [(d.memory_stats() or {}).get("peak_bytes_in_use")
                           for d in jax.devices()],
            "warnings": sorted({f"{w.category.__name__}: "
                                f"{str(w.message)[:80]}" for w in caught}),
            "devices": [jax.devices()[0].device_kind, jax.device_count()],
            "dist": plan.describe()["dist"],
        }), flush=True)
        transform.drop_plan(plan)
        del plan, out, xd
        gc.collect()
        jax.clear_caches()
    plan = repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                           mode="dist", cache="off")
    print(json.dumps({"report": plan.report()}), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--lmax", type=int, required=True)
    p.add_argument("--K", type=int, default=4)
    p.add_argument("--variants", default="jnp:1,pallas:1,jnp:2,jnp:4")
    p.add_argument("--calls", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args()
    compile_cache.enable()
    run(a.lmax, a.K, a.variants.split(","), a.calls, a.seed)


if __name__ == "__main__":
    main()
