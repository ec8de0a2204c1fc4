"""Gradient-based C_l recovery through the differentiable transforms.

The workload the adjoint-based custom VJP rules unlock: fit spherical-
harmonic coefficients to an observed (noisy) map by gradient descent on a
pixel-space chi^2 -- ``jax.grad`` flows through ``Plan.alm2map`` via the
adjoint transform (synthesis VJP = weighted analysis), so either backend
(jnp, dist) is usable inside the optimizer loop --
then read the angular power spectrum off the fitted coefficients.

On the exact Gauss-Legendre grid the normal equations are perfectly
conditioned (A^T A is diagonal in harmonic space up to the quadrature
weights), so plain gradient descent with a per-mode step converges fast;
the point here is the machinery, not the estimator.

    PYTHONPATH=src python examples/grad_cl_estimate.py \
        [--lmax 16] [--steps 25] [--dtype float64] [--mode auto]

``--steps 1`` is the CI smoke configuration (scripts/check.sh).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import sht, spectra


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lmax", type=int, default=16)
    ap.add_argument("--grid", default="gl", choices=["gl", "ecp", "healpix"])
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--noise", type=float, default=0.05)
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

    nside = max(a.lmax // 2, 2) if a.grid == "healpix" else None
    plan = repro.make_plan(a.grid, l_max=a.lmax, nside=nside,
                           dtype=a.dtype, mode=a.mode)
    assert all(plan.grad_ready.values()), plan.grad_ready
    cdt = "complex64" if a.dtype == "float32" else "complex128"

    # --- simulated observation: CMB-like alm + white pixel noise ----------
    cl_true = spectra.cmb_like_cl(plan.l_max, amp=1.0)
    alm_true = spectra.alm_from_cl(jax.random.PRNGKey(0), cl_true,
                                   m_max=plan.m_max).astype(cdt)
    noise = a.noise * jax.random.normal(jax.random.PRNGKey(1),
                                        plan._maps_shape, plan.dtype)
    observed = plan.alm2map(alm_true) + noise

    # --- chi^2 in pixel space, gradient through the synthesis -------------
    w = jnp.asarray(plan.grid.weights, plan.dtype)[:, None, None]

    def loss(alm):
        r = plan.alm2map(alm) - observed
        return 0.5 * jnp.sum(w * r * r)     # quadrature-weighted chi^2

    loss_grad = jax.jit(jax.value_and_grad(loss))

    # Per-mode preconditioner: on exact grids the weighted normal matrix
    # is diagonal with entry fac_m per real degree of freedom (adjointness:
    # sum_pix w |dS/dRe a_lm|^2 = fac_m^2 * 1/fac_m), so lr = 1/fac_m is
    # an exact Newton step there and a good preconditioner elsewhere.
    m = np.arange(plan.m_max + 1)
    fac = jnp.asarray(np.where(m == 0, 1.0, 2.0),
                      plan.dtype)[:, None, None]
    lr = 1.0 / fac

    alm = jnp.zeros_like(alm_true)
    for step in range(a.steps):
        val, g = loss_grad(alm)
        # JAX complex grad is d/dRe - i d/dIm: conjugate for the descent step
        alm = alm - lr * jnp.conj(g)
        if step % 5 == 0 or step == a.steps - 1:
            print(f"step {step:3d}  chi2 = {float(val):.6e}")

    # --- read off the spectrum --------------------------------------------
    cl_hat = np.asarray(spectra.cl_from_alm(alm))[:, 0]
    cl_ref = np.asarray(spectra.cl_from_alm(alm_true))[:, 0]
    sel = slice(2, plan.l_max + 1)
    rel = np.abs(cl_hat[sel] - cl_ref[sel]) / np.maximum(cl_ref[sel], 1e-30)
    print(f"\nC_l recovery vs the realisation's pseudo-C_l "
          f"(l = 2..{plan.l_max}):")
    print(f"  median rel err = {np.median(rel):.3e}   "
          f"max rel err = {np.max(rel):.3e}")
    err = spectra.d_err(alm_true, alm)
    print(f"  alm D_err = {err:.3e}  (noise floor ~ {a.noise})")
    if a.steps >= 10 and a.grid == "gl":
        assert err < 5.0 * a.noise + 1e-6, "gradient descent failed to fit"
    print(f"\nbackends: {plan.backends}  differentiable: "
          f"{plan.describe()['differentiable']}")


if __name__ == "__main__":
    main()
