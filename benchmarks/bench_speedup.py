"""Paper Fig. 15: relative speed-up across problem sizes -- via make_plan.

The paper reports MPI/CUDA vs MPI/OpenMP speed-up per process count.  Our
measurable analogue on this container: the float32 plan vs the float64
jnp baseline for the full transform (both directions), plus the batched-K
amortisation.  Every engine is reached through the unified Plan API.

Columns: name, us_per_call (optimised path), derived = speedup vs baseline.
Every ratio comes from ONE paired interleaved loop (`common.time_pair`):
independent timings drift 30-40% between runs on a noisy host, which made
the old A/B ratios meaningless.
"""

import jax
import jax.numpy as jnp

import repro
from repro.core import sht
from benchmarks.common import (emit, enable_float64_oracle, smoke,
                               time_pair)

KEY = jax.random.PRNGKey(3)


def main():
    enable_float64_oracle()
    for l_max in ((32,) if smoke() else (64, 128)):
        alm64 = sht.random_alm(KEY, l_max, l_max)
        base = repro.make_plan("gl", l_max=l_max, K=1, dtype="float64",
                               mode="jnp")
        maps64 = base.alm2map(alm64)

        alm32 = alm64.astype(jnp.complex64)
        maps32 = jnp.asarray(maps64, jnp.float32)
        p = repro.make_plan("gl", l_max=l_max, K=1, dtype="float32",
                            mode="jnp")
        tb_s, ts = time_pair(lambda: base.alm2map(alm64),
                             lambda: p.alm2map(alm32), iters=2)
        tb_a, ta = time_pair(lambda: base.map2alm(maps64),
                             lambda: p.map2alm(maps32), iters=2)
        emit(f"speedup/jnp-f32-synth/lmax{l_max}", ts * 1e6,
             f"x{tb_s / ts:.2f} vs f64 jnp")
        emit(f"speedup/jnp-f32-anal/lmax{l_max}", ta * 1e6,
             f"x{tb_a / ta:.2f} vs f64 jnp")

        # fold optimisation through the plan layer (synthesis only)
        pf = repro.make_plan("gl", l_max=l_max, K=1, dtype="float64",
                             mode="jnp", fold=True)
        tb_s, tf_s = time_pair(lambda: base.alm2map(alm64),
                               lambda: pf.alm2map(alm64), iters=2)
        emit(f"speedup/fold-vs-unfold/lmax{l_max}", tf_s * 1e6,
             f"x{tb_s / tf_s:.2f}")

    # batched-K amortisation: per-map time shrinks as K grows because
    # P_lm generation is shared across the Monte-Carlo batch.
    l_max = 32 if smoke() else 128
    alm1 = sht.random_alm(KEY, l_max, l_max, K=1)
    p1 = repro.make_plan("gl", l_max=l_max, K=1, dtype="float64", mode="jnp")
    for K in ((1, 4) if smoke() else (1, 4, 16)):
        alm = sht.random_alm(KEY, l_max, l_max, K=K)
        p = repro.make_plan("gl", l_max=l_max, K=K, dtype="float64",
                            mode="jnp")
        t1, t = time_pair(lambda: p1.alm2map(alm1),
                          lambda: p.alm2map(alm), iters=2)
        if K == 1:
            t1 = t          # same plan: the ratio is 1.0 by definition
        emit(f"speedup/batched-K{K}/lmax{l_max}", t / K * 1e6,
             f"per-map x{t1 / (t / K):.2f} vs K=1")


if __name__ == "__main__":
    main()
