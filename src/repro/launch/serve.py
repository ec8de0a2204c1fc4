"""Serving CLI: the SHT request-coalescing engine under synthetic load.

    PYTHONPATH=src python -m repro.launch.serve --requests 8 --smoke
    PYTHONPATH=src python -m repro.launch.serve --p99-target-ms 50

Runs the double-buffered serving threads (batch i+1 stages while batch i
computes), submits a mixed spin-0/spin-2 request stream, waits for every
future, and prints the stats table (p50/p95/p99 latency, coalescing
factor, admission caps, plan-pool hit rate).  ``--p99-target-ms`` turns
on roofline admission control: the coalesced K per signature is capped by
the latency target instead of ``--max-k`` alone.
"""

import argparse

import numpy as np

from repro import compile_cache
from repro.core import sht
from repro.serve import ShtEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lmax", type=int, default=32)
    ap.add_argument("--max-k", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--mode", default="jnp",
                    help="plan dispatch mode for pooled plans "
                         "(jnp | auto | model | pallas_*)")
    ap.add_argument("--p99-target-ms", type=float, default=None,
                    help="roofline admission: cap each group's coalesced "
                         "K to fit this tail-latency target")
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    if a.smoke:
        a.lmax = min(a.lmax, 16)
    compile_cache.enable()

    target_s = None if a.p99_target_ms is None else a.p99_target_ms * 1e-3
    eng = ShtEngine(max_k=a.max_k, mode=a.mode, warm_after=2,
                    p99_target_s=target_s)
    with eng:                          # double-buffered form/exec threads
        futs = []
        for rid in range(a.requests):
            if rid % 2 == 0:
                alm = np.asarray(sht.random_alm(
                    seed=rid, l_max=a.lmax, m_max=a.lmax))[..., 0]
                futs.append(eng.submit(direction="alm2map", payload=alm,
                                       grid="gl", l_max=a.lmax))
            else:
                alm = np.asarray(sht.random_alm_spin(
                    seed=rid, l_max=a.lmax, m_max=a.lmax))[..., 0]
                futs.append(eng.submit(direction="alm2map", payload=alm,
                                       grid="gl", l_max=a.lmax, spin=2))
        results = [f.result(timeout=600) for f in futs]
    assert all(np.isfinite(r).all() for r in results)
    print(eng.report())
    done = eng.stats()["requests"]["completed"]
    print(f"completed {done}/{a.requests} requests")


if __name__ == "__main__":
    main()
