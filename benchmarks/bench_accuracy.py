"""Paper Fig. 8: round-trip relative error D_err vs (l_max, grid, dtype).

Columns: name, us_per_call (map2alm(alm2map) wall), derived = D_err.
The GL grid isolates implementation error (machine precision); the
HEALPix-family grids reproduce the paper's aliasing-driven error growth as
l_max approaches the 2*nside sampling limit.  True (ragged) HEALPix runs
through the same plan path as everything else -- the ring-bucket phase
stage -- including ``iters=1`` Jacobi refinement rows.

Every transform goes through ``repro.make_plan``; no engine hand-wiring.
"""

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import sht, spectra
from benchmarks.common import (emit, enable_float64_oracle, smoke,
                               time_call)

KEY = jax.random.PRNGKey(0)  # explicit: random_alm no longer defaults


def _roundtrip(plan, alm, iters=0):
    rt = lambda a: plan.map2alm(plan.alm2map(a), iters=iters)
    dt = time_call(rt, alm, iters=1)
    return dt, spectra.d_err(alm, rt(alm))


def main():
    enable_float64_oracle()
    gl_sizes = (32,) if smoke() else (32, 64, 128, 256)
    for l_max in gl_sizes:
        plan = repro.make_plan("gl", l_max=l_max, dtype="float64", mode="jnp")
        alm = sht.random_alm(KEY, l_max, l_max)
        dt, err = _roundtrip(plan, alm)
        emit(f"accuracy/gl/f64/lmax{l_max}", dt * 1e6, f"{err:.3e}")

    nsides = (8,) if smoke() else (16, 32, 64)
    for nside in nsides:
        # at the sampling limit (l_max = 2 nside) and well-resolved (nside)
        for l_max in (2 * nside, nside):
            for kind in ("healpix_ring", "healpix"):
                plan = repro.make_plan(kind, nside=nside, l_max=l_max,
                                       dtype="float64", mode="jnp")
                alm = sht.random_alm(KEY, l_max, l_max)
                dt, err = _roundtrip(plan, alm)
                emit(f"accuracy/{kind}/nside{nside}/lmax{l_max}",
                     dt * 1e6, f"{err:.3e}")
        # Jacobi refinement on the approximate-quadrature (ragged) grid
        plan = repro.make_plan("healpix", nside=nside, dtype="float64",
                               mode="jnp")
        alm = sht.random_alm(KEY, plan.l_max, plan.m_max)
        dt, err = _roundtrip(plan, alm, iters=1)
        emit(f"accuracy/healpix/nside{nside}/iters1", dt * 1e6, f"{err:.3e}")

    # f32 engine (kernel-precision) error at fixed size
    l_max = 32 if smoke() else 128
    plan = repro.make_plan("gl", l_max=l_max, dtype="float32", mode="jnp")
    alm = sht.random_alm(KEY, l_max, l_max).astype(np.complex64)
    dt, err = _roundtrip(plan, alm)
    emit(f"accuracy/gl/f32/lmax{l_max}", dt * 1e6, f"{err:.3e}")

    # spin-2 (E/B <-> Q/U) accuracy per backend, alongside the scalar table
    l_max = 16 if smoke() else 64
    for backend, dtype in (("jnp", "float64"), ("pallas_vpu", "float32"),
                           ("pallas_mxu", "float32")):
        plan = repro.make_plan("gl", l_max=l_max, dtype=dtype, mode=backend,
                               spin=2)
        alm = sht.random_alm_spin(KEY, l_max, l_max)
        if dtype == "float32":
            alm = alm.astype(np.complex64)
        dt, err = _roundtrip(plan, alm)
        emit(f"accuracy/gl/spin2/{backend}/lmax{l_max}", dt * 1e6,
             f"{err:.3e}")
    nside = 8 if smoke() else 16
    plan = repro.make_plan("healpix", nside=nside, l_max=nside,
                           dtype="float64", mode="jnp", spin=2)
    alm = sht.random_alm_spin(KEY, plan.l_max, plan.m_max)
    dt, err = _roundtrip(plan, alm, iters=1)
    emit(f"accuracy/healpix/spin2/nside{nside}/iters1", dt * 1e6,
         f"{err:.3e}")


if __name__ == "__main__":
    main()
