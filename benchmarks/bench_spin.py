"""Scalar vs spin-2 throughput: the 2x Legendre-panel cost, measured.

A spin-2 transform runs two Wigner-d recurrences per m (the lambda^{+/-}
panel pair) and moves two components (E/B alm, Q/U maps) through the same
phase stage, so the model predicts a wall-clock ratio around 2x on the
recurrence-bound sizes (docs/performance.md).  Columns: us_per_call of
each direction; derived = spin2/scalar ratio at the same signature.

Every transform goes through ``repro.make_plan(..., spin=...)``.
"""

import repro
from repro.core import sht
from benchmarks.common import (emit, enable_float64_oracle, smoke,
                               time_call)


def main():
    enable_float64_oracle()
    sizes = ((32, 4),) if smoke() else ((64, 4), (128, 8))
    iters = 1 if smoke() else 3
    for l_max, K in sizes:
        p0 = repro.make_plan("gl", l_max=l_max, K=K, dtype="float64",
                             mode="jnp")
        p2 = repro.make_plan("gl", l_max=l_max, K=K, dtype="float64",
                             mode="jnp", spin=2)
        a0 = sht.random_alm(seed=0, l_max=l_max, m_max=l_max, K=K)
        a2 = sht.random_alm_spin(seed=0, l_max=l_max, m_max=l_max, K=K)
        t0 = time_call(p0.alm2map, a0, iters=iters)
        t2 = time_call(p2.alm2map, a2, iters=iters)
        tag = f"spin/jnp/lmax{l_max}/K{K}"
        emit(f"{tag}/synth/scalar", t0 * 1e6)
        emit(f"{tag}/synth/spin2", t2 * 1e6, f"ratio={t2 / t0:.2f}")
        m0 = p0.alm2map(a0)
        m2 = p2.alm2map(a2)
        ta0 = time_call(p0.map2alm, m0, iters=iters)
        ta2 = time_call(p2.map2alm, m2, iters=iters)
        emit(f"{tag}/anal/scalar", ta0 * 1e6)
        emit(f"{tag}/anal/spin2", ta2 * 1e6, f"ratio={ta2 / ta0:.2f}")


if __name__ == "__main__":
    main()
