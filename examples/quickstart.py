"""Quickstart: the paper's validation experiment through the Plan API.

Builds a transform plan (backend dispatch + cached precompute),
synthesises a map from random a_lm (inverse SHT), analyses it back (direct
SHT), and reports the round-trip error D_err (paper eq. 19) -- on the
exact Gauss-Legendre grid this sits at machine precision.

    PYTHONPATH=src python examples/quickstart.py [--lmax 128] [--dtype float32]
"""

import argparse

import jax

import repro
from repro.core import sht, spectra


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lmax", type=int, default=128)
    ap.add_argument("--grid", default="gl", choices=["gl", "healpix_ring"])
    ap.add_argument("--K", type=int, default=2, help="simultaneous maps")
    ap.add_argument("--dtype", default=None,
                    choices=["float64", "float32"],
                    help="default: float64 on a CPU, float32 on a TPU")
    ap.add_argument("--mode", default="auto",
                    help="auto | model | jnp | dist")
    a = ap.parse_args()
    if a.dtype is None:
        a.dtype = "float32" if jax.default_backend() == "tpu" else "float64"
    if a.dtype == "float64":    # the float64 oracle needs JAX's 64-bit mode
        jax.config.update("jax_enable_x64", True)

    # One entry point: the plan owns precompute and backend choice.
    # A second make_plan with this signature returns the same (cached) plan.
    plan = repro.make_plan(a.grid, l_max=a.lmax,
                           nside=max(a.lmax // 2, 1),
                           K=a.K, dtype=a.dtype, mode=a.mode)

    alm = sht.random_alm(jax.random.PRNGKey(0), plan.l_max, plan.m_max,
                         K=a.K)                  # uniform (-1,1), paper §5
    if a.dtype == "float32":
        alm = alm.astype("complex64")
    maps = plan.alm2map(alm)       # inverse SHT (synthesis)
    alm_back = plan.map2alm(maps)  # direct SHT (analysis)

    err = spectra.d_err(alm, alm_back)
    g = plan.grid
    print(f"grid={g.name} rings={g.n_rings} n_pix={g.n_pix} "
          f"l_max={plan.l_max} K={a.K} dtype={a.dtype}")
    print(f"round-trip D_err = {err:.3e}"
          + ("  (exact quadrature: machine precision)"
             if a.grid == "gl" and a.dtype == "float64"
             else "  (f32/approximate-quadrature regime)"))
    print()
    print(plan.report())


if __name__ == "__main__":
    main()
