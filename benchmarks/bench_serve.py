"""Sustained-load SHT serving benchmark: throughput and tail latency.

Drives `repro.serve.ShtEngine` with a mixed-signature request stream
(GL spin-0, GL spin-2, HEALPix spin-0), signatures pre-warmed so the
measurement is the steady serving state, not compile time.  Emits the
serving perf-trajectory rows validated by scripts/check.sh:

  serve/throughput/<mix>  -- mean us per request end-to-end (derived req/s
                             + coalescing factor)
  serve/p99/<mix>         -- p99 total request latency us (derived p50/p95)
  serve/coalesce/<mix>    -- mean K maps per device batch (derived
                             occupancy + plan-pool hit rate)
  serve/derr/<mix>        -- max |coalesced - independent Plan call| over
                             sampled requests (must stay at f64 precision:
                             coalescing is a pure batching transformation)

plus the phase-2 latency/throughput **frontier** over a 10:1
hot:minority tenant mix (GL spin-0 hot, GL spin-2 minority, same l_max),
the same pre-built stream replayed through both serving modes
(min-of-reps walls):

  serve/frontier/single/<mix>  -- us/req, synchronous step() pump
  serve/frontier/double/<mix>  -- us/req, double-buffered form/exec threads
  serve/frontier/speedup       -- wall(single) / wall(double)
  serve/frontier/p99/<mix>     -- p99 total latency us, double-buffered run
  serve/frontier/fair_p99_ratio -- minority-tenant p99 in the 10:1 mix /
                                   minority p99 served solo (WDRR bound)

The speedup ceiling is host-dependent: staging overlaps compute only
where compute leaves host cores free (an accelerator, or XLA CPU on a
multi-core box).  On a single-core host the honest ceiling is 1.0x and
the row demonstrates the pipeline adds no overhead; the derived string
records the visible cpu count so BENCH files are self-describing.

``REPRO_BENCH_SMOKE=1``: small sizes, few requests (the CI gate).
"""

import os
import time

import numpy as np

import repro
from repro.core import sht
from repro.serve import ShtEngine
from benchmarks.common import emit, enable_float64_oracle


def _cfg():
    # n_requests is a multiple of 3*max_k so every signature's queue drains
    # in full-K buckets -- the prewarmed plans -- and the latency rows
    # measure steady serving, not an in-stream remainder-bucket compile
    if os.environ.get("REPRO_BENCH_SMOKE"):
        return dict(l_max=16, nside=4, n_requests=24, max_k=4,
                    frontier_n=22, reps=2)
    return dict(l_max=48, nside=8, n_requests=120, max_k=8,
                frontier_n=110, reps=3)


def _frontier(cfg):
    """Single-threaded vs double-buffered serving over a 10:1
    hot:minority tenant mix -- the phase-2 frontier rows."""
    l_max, max_k, n, reps = (cfg["l_max"], cfg["max_k"], cfg["frontier_n"],
                             cfg["reps"])
    label = f"hotcold10to1-lmax{l_max}-{n}req"
    hot = dict(grid="gl", l_max=l_max, dtype="float64")
    cold = dict(grid="gl", l_max=l_max, dtype="float64", spin=2)

    # every 11th request is the minority (spin-2) tenant
    stream = []
    for rid in range(n):
        if rid % 11 == 10:
            alm = np.asarray(sht.random_alm_spin(seed=rid, l_max=l_max,
                                                 m_max=l_max))[..., 0]
            stream.append(dict(direction="alm2map", payload=alm, grid="gl",
                               l_max=l_max, spin=2))
        else:
            alm = np.asarray(sht.random_alm(seed=rid, l_max=l_max,
                                            m_max=l_max))[..., 0]
            stream.append(dict(direction="alm2map", payload=alm, grid="gl",
                               l_max=l_max))
    solo = [r for r in stream if r.get("spin")]
    assert solo, "stream carries no minority requests"

    def _engine():
        eng = ShtEngine(max_k=max_k, max_queue=4 * n, mode="jnp",
                        p99_target_s=60.0)       # bounded-but-generous
        eng.prewarm(**hot)
        eng.prewarm(**cold)
        return eng

    def _replay(requests, background):
        eng = _engine()
        t0 = time.perf_counter()
        if background:
            with eng:                            # form/exec thread pair
                futs = [eng.submit(**r) for r in requests]
                eng.drain()
        else:
            futs = [eng.submit(**r) for r in requests]
            eng.drain()                          # inline step() pump
        wall = time.perf_counter() - t0
        s = eng.stats()
        assert s["requests"]["completed"] == len(requests), s["requests"]
        mino = [f.timing["total_s"] for r, f in zip(requests, futs)
                if r.get("spin")]
        return dict(wall=wall, p99=s["latency"]["total"]["p99_s"],
                    p50=s["latency"]["total"]["p50_s"],
                    mino_max=max(mino) if mino else float("nan"))

    # min-of-reps: same stream, fresh engine per rep (warm global plans)
    single = min((_replay(stream, background=False) for _ in range(reps)),
                 key=lambda r: r["wall"])
    double = min((_replay(stream, background=True) for _ in range(reps)),
                 key=lambda r: r["wall"])
    solo_run = min((_replay(solo, background=True) for _ in range(reps)),
                   key=lambda r: r["wall"])

    emit(f"serve/frontier/single/{label}", single["wall"] / n * 1e6,
         f"{n / single['wall']:.1f} req/s p99={single['p99'] * 1e6:.0f}us")
    emit(f"serve/frontier/double/{label}", double["wall"] / n * 1e6,
         f"{n / double['wall']:.1f} req/s p99={double['p99'] * 1e6:.0f}us")
    emit("serve/frontier/speedup", single["wall"] / double["wall"],
         f"double-buffered wall {double['wall'] * 1e3:.1f}ms vs "
         f"single {single['wall'] * 1e3:.1f}ms ({os.cpu_count()} cpu)")
    emit(f"serve/frontier/p99/{label}", double["p99"] * 1e6,
         f"p50={double['p50'] * 1e6:.0f}us")
    # fairness: the minority tenant's worst latency in the 10:1 mix vs
    # served alone (WDRR keeps the ratio bounded; oldest-head-wins put
    # the whole hot backlog in front of it)
    ratio = double["mino_max"] / solo_run["mino_max"]
    emit("serve/frontier/fair_p99_ratio", ratio,
         f"mixed {double['mino_max'] * 1e6:.0f}us vs solo "
         f"{solo_run['mino_max'] * 1e6:.0f}us")


def main():
    enable_float64_oracle()
    cfg = _cfg()
    l_max, nside = cfg["l_max"], cfg["nside"]
    n, max_k = cfg["n_requests"], cfg["max_k"]
    label = f"mixed-lmax{l_max}-{n}req"

    eng = ShtEngine(max_k=max_k, max_queue=4 * n, mode="jnp")
    eng.prewarm(grid="gl", l_max=l_max, dtype="float64")
    eng.prewarm(grid="gl", l_max=l_max, dtype="float64", spin=2)
    eng.prewarm(grid="healpix", nside=nside, dtype="float64")

    # pre-generate the request stream (payload build must not pollute the
    # serving measurement) + the independent-plan references for a sample
    hp = repro.make_plan("healpix", nside=nside, K=1, dtype="float64",
                         mode="jnp")
    stream, refs = [], {}
    for rid in range(n):
        kind = rid % 3
        if kind == 0:
            alm = np.asarray(sht.random_alm(seed=rid, l_max=l_max,
                                            m_max=l_max))[..., 0]
            stream.append(dict(direction="alm2map", payload=alm, grid="gl",
                               l_max=l_max))
        elif kind == 1:
            alm = np.asarray(sht.random_alm_spin(seed=rid, l_max=l_max,
                                                 m_max=l_max))[..., 0]
            stream.append(dict(direction="alm2map", payload=alm, grid="gl",
                               l_max=l_max, spin=2))
        else:
            alm = np.asarray(sht.random_alm(seed=rid, l_max=hp.l_max,
                                            m_max=hp.m_max))[..., 0]
            stream.append(dict(direction="alm2map", payload=alm,
                               grid="healpix", nside=nside))
        if rid < 3:                       # one reference per signature kind
            plan = repro.make_plan(
                stream[-1]["grid"], stream[-1].get("l_max"),
                nside=stream[-1].get("nside"), K=1, dtype="float64",
                mode="jnp", spin=stream[-1].get("spin", 0))
            refs[rid] = np.asarray(plan.alm2map(alm[..., None]))[..., 0]

    t0 = time.perf_counter()
    futs = [eng.submit(**req) for req in stream]
    eng.drain()
    wall = time.perf_counter() - t0

    done = eng.stats()
    assert done["requests"]["completed"] == n, done["requests"]
    worst = max(float(np.max(np.abs(futs[rid].result() - ref)))
                for rid, ref in refs.items())
    assert worst < 1e-12, f"coalesced serving diverged: {worst}"

    lat, co, pool = (done["latency"]["total"], done["coalescing"],
                     done["pool"])
    emit(f"serve/throughput/{label}", wall / n * 1e6,
         f"{done['throughput_rps']:.1f} req/s coalesce "
         f"x{co['requests_per_batch']:.2f}")
    emit(f"serve/p99/{label}", lat["p99_s"] * 1e6,
         f"p50={lat['p50_s'] * 1e6:.0f}us p95={lat['p95_s'] * 1e6:.0f}us")
    emit(f"serve/coalesce/{label}", co["k_per_batch"],
         f"occupancy {co['k_occupancy']:.2f} pool_hit_rate "
         f"{pool['hit_rate']:.2f}")
    emit(f"serve/derr/{label}", 0.0, f"{worst:.2e}")

    _frontier(cfg)


if __name__ == "__main__":
    main()
