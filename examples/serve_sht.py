"""SHT-as-a-service demo: mixed-signature transform requests coalesced
into the K channel axis, served from a warm plan pool.

Submits a mix of Gauss-Legendre and true-HEALPix, spin-0 and spin-2
(Q/U <-> E/B) requests, drains the engine, checks every result against an
independent per-request Plan call, and prints the serving stats table
(latency percentiles, coalescing factor, plan-pool hit rate).

    PYTHONPATH=src python examples/serve_sht.py --requests 12
    PYTHONPATH=src python examples/serve_sht.py --p99-target-ms 50
    PYTHONPATH=src python examples/serve_sht.py --smoke      # CI one-rep

``--p99-target-ms`` switches coalescing from the fixed ``--max-k`` cap to
roofline admission control: per signature, the widest power-of-two K
whose *predicted* batch time fits the target (the admission verdicts and
predicted-vs-measured calibration show up in the stats table).
"""

import argparse

import jax
import numpy as np

import repro
from repro.core import sht
from repro.serve import ShtEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-k", type=int, default=4)
    ap.add_argument("--lmax", type=int, default=24)
    ap.add_argument("--nside", type=int, default=8)
    ap.add_argument("--p99-target-ms", type=float, default=None,
                    help="tail-latency target: roofline admission caps "
                         "each group's coalesced K so predicted batch "
                         "time fits the target (default: off, max-k "
                         "rules)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, few requests (CI)")
    a = ap.parse_args()
    if a.smoke:
        a.requests, a.lmax, a.nside = min(a.requests, 6), 12, 4
    # the demo serves and checks float64 requests (CPU): 64-bit mode on
    jax.config.update("jax_enable_x64", True)

    target_s = None if a.p99_target_ms is None else a.p99_target_ms * 1e-3
    eng = ShtEngine(max_k=a.max_k, mode="jnp", warm_after=2,
                    p99_target_s=target_s)
    eng.prewarm(grid="gl", l_max=a.lmax, dtype="float64")

    # a traffic mix: GL spin-0, GL spin-2 (polarisation), HEALPix spin-0
    jobs = []
    for rid in range(a.requests):
        kind = rid % 3
        if kind == 0:
            alm = np.asarray(sht.random_alm(seed=rid, l_max=a.lmax,
                                            m_max=a.lmax))[..., 0]
            fut = eng.submit(direction="alm2map", payload=alm, grid="gl",
                             l_max=a.lmax, tag="gl-spin0")
            ref = repro.make_plan("gl", l_max=a.lmax, K=1, dtype="float64",
                                  mode="jnp").alm2map(alm[..., None])
        elif kind == 1:
            alm = np.asarray(sht.random_alm_spin(seed=rid, l_max=a.lmax,
                                                 m_max=a.lmax))[..., 0]
            fut = eng.submit(direction="alm2map", payload=alm, grid="gl",
                             l_max=a.lmax, spin=2, tag="gl-spin2")
            ref = repro.make_plan("gl", l_max=a.lmax, K=1, dtype="float64",
                                  mode="jnp",
                                  spin=2).alm2map(alm[..., None])
        else:
            hp = repro.make_plan("healpix", nside=a.nside, K=1,
                                 dtype="float64", mode="jnp")
            alm = np.asarray(sht.random_alm(seed=rid, l_max=hp.l_max,
                                            m_max=hp.m_max))[..., 0]
            fut = eng.submit(direction="alm2map", payload=alm,
                             grid="healpix", nside=a.nside,
                             tag="healpix-spin0")
            ref = hp.alm2map(alm[..., None])
        jobs.append((fut, np.asarray(ref)[..., 0]))

    eng.drain()
    worst = 0.0
    for fut, ref in jobs:
        worst = max(worst, float(np.max(np.abs(fut.result() - ref))))
    assert worst < 1e-12, f"coalesced result diverged: {worst}"

    print(eng.report())
    print(f"max |coalesced - independent| = {worst:.2e}")
    done = eng.stats()["requests"]["completed"]
    print(f"completed {done}/{a.requests} requests via K-coalesced serving")


if __name__ == "__main__":
    main()
