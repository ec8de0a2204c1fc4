"""Paper Fig. 14: FFT strategy comparison -- the phase stage head-to-head.

The paper compared CUFFT (GPU) vs MKL (CPU) and kept FFTs on the CPU.  Our
TPU-shaped analogue, through the unified plan layer: the batched
uniform-length engine (ring-uniform HEALPix grid) vs the ring-bucket
engine (true ragged HEALPix), both device-resident and jitted
(`repro.core.phase`).  Also reports the bucket structure and the padding
waste the bucketing trades for its bucket count.

Columns: name, us_per_call, derived = strategy / bucket info.
"""

import jax

import repro
from repro.core import sht
from benchmarks.common import (emit, enable_float64_oracle, smoke,
                               time_call)

KEY = jax.random.PRNGKey(2)


def main():
    enable_float64_oracle()
    nsides = (16,) if smoke() else (32, 64, 128)
    for nside in nsides:
        l_max = 2 * nside
        alm = sht.random_alm(KEY, l_max, l_max)

        plans = {
            "batched-uniform": repro.make_plan(
                "healpix_ring", nside=nside, l_max=l_max, dtype="float64",
                mode="jnp"),
            "bucketed-ragged": repro.make_plan(
                "healpix", nside=nside, l_max=l_max, dtype="float64",
                mode="jnp"),
        }
        delta = plans["batched-uniform"]._sht._delta_from_alm(alm)

        for name, plan in plans.items():
            ph = plan.phase
            d = ph.describe()
            note = (f"n_phi={plan.grid.max_n_phi} rings={plan.grid.n_rings}"
                    if d["kind"] == "uniform" else
                    f"{d['n_buckets']} buckets "
                    f"(+{d['padded_frac'] * 100:.1f}% padding)")
            f_s = jax.jit(ph.synth)
            dt = time_call(f_s, delta, iters=1 if smoke() else 3)
            emit(f"fft/{name}-synth/nside{nside}", dt * 1e6, note)
            maps = f_s(delta)
            f_a = jax.jit(ph.anal)
            dt = time_call(f_a, maps, iters=1 if smoke() else 3)
            emit(f"fft/{name}-anal/nside{nside}", dt * 1e6, note)


if __name__ == "__main__":
    main()
