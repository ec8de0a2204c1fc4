"""Property-based adjointness and gradient tests for the differentiable
transforms.

The direct and inverse SHT are (up to quadrature weights) adjoints of each
other -- the identity the custom JVP/VJP rules are built on.  This suite
checks it at every layer and through every backend:

* the *plan-level* dot-product identity, exact in exact arithmetic on any
  grid (including ragged HEALPix with alias-folded short rings):

      <alm2map(a), t>_pix  ==  sum_{m,l} fac_m Re(a_lm conj(ahat_lm)),
      ahat = map2alm(t / w),  fac_m = 1 (m = 0) | 2 (m > 0)

* JVP-vs-VJP consistency of the custom rules (the transpose is checked
  against the forward linearisation, which the forward tests pin down);

* finite-difference gradient checks of ``jax.grad`` through
  ``Plan.alm2map`` and ``Plan.map2alm`` on the jnp backend, float64 and
  float32 (the dtype the benchmark cells run), spin 0 and 2, folded and
  not.  The dist backend's checks run on simulated devices
  (tests/helpers/dist_sht_check.py).

Hypothesis runs through the `_hypothesis_compat` fallback runner, so the
property tests execute (seeded + boundary examples) even without the real
hypothesis package.  The @settings counts below sum to > 200 generated
cases (the acceptance bar for this suite).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, strategies as st

import repro
from repro.core import grids as gridlib
from repro.core import legendre
from repro.core import sht as shtlib
from repro.core.sht import random_alm, random_alm_spin

# every plan here is memoised by signature, so repeated draws are cheap
GRIDS = ("gl", "ecp", "healpix")


def _make_plan(grid_kind, l_max, dtype, mode, spin=0, K=1, fold=None):
    nside = None
    if grid_kind == "healpix":
        nside = max(4, (l_max + 1) // 2)
        l_max = min(l_max, 2 * nside)
    return repro.make_plan(grid_kind, l_max=l_max, nside=nside, K=K,
                           dtype=dtype, mode=mode, spin=spin, fold=fold)


def _rand_alm(plan, seed):
    f = random_alm_spin if plan.spin else random_alm
    a = f(seed=seed, l_max=plan.l_max, m_max=plan.m_max, K=plan.K)
    return a.astype(jnp.complex64 if plan.dtype == "float32"
                    else jnp.complex128)


def _rand_maps(plan, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=plan._maps_shape), plan.dtype)


def _fac(plan):
    m = np.arange(plan.m_max + 1)
    return jnp.asarray(np.where(m == 0, 1.0, 2.0))[:, None, None]


def _harmonic_dot(plan, a, ahat):
    """sum_{m,l} fac_m Re(a conj(ahat)), summed over components and K."""
    p = jnp.real(a * jnp.conj(ahat))
    fac = _fac(plan)
    if plan.spin:
        fac = fac[None]
    return float(jnp.sum(fac * p))


def _adjoint_identity_err(plan, seed):
    """Relative error of the plan-level adjointness identity."""
    a = _rand_alm(plan, seed)
    t = _rand_maps(plan, seed + 1)
    w = jnp.asarray(plan.grid.weights, plan.dtype)[:, None, None]
    t_over_w = t / (w if plan.spin == 0 else w[None])
    synth = plan._synth_fn(plan.backends["synth"])
    anal = plan._anal_fn(plan.backends["anal"])
    lhs = float(jnp.sum(synth(a) * t))
    ahat = anal(t_over_w)
    rhs = _harmonic_dot(plan, a, ahat)
    scale = max(abs(lhs), abs(rhs), 1e-30)
    return abs(lhs - rhs) / scale


# ---------------------------------------------------------------------------
# plan-level adjointness: <A x, y> == <x, A* y> across grids/backends/spins
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(grid=st.sampled_from(GRIDS), l_max=st.integers(4, 12),
       k=st.sampled_from([1, 2]), seed=st.integers(0, 10**6))
def test_adjointness_jnp_f64(grid, l_max, k, seed):
    plan = _make_plan(grid, l_max, "float64", "jnp", K=k)
    assert _adjoint_identity_err(plan, seed) < 1e-11


@settings(max_examples=40, deadline=None)
@given(grid=st.sampled_from(GRIDS), l_max=st.integers(4, 10),
       seed=st.integers(0, 10**6))
def test_adjointness_spin2_jnp(grid, l_max, seed):
    plan = _make_plan(grid, max(l_max, 4), "float64", "jnp", spin=2)
    assert _adjoint_identity_err(plan, seed) < 1e-11


#: (grid, spin, fold) of the float32 jnp plans: every grid at spin 0, folded
#: and not (HEALPix allows the fold, though it is not its default), and at
#: spin 2, which does not fold
F32_CASES = [(g, 0, f) for g in GRIDS for f in (False, True)] + \
    [(g, 2, False) for g in GRIDS]
F32_IDS = [f"{g}-spin{s}-{'fold' if f else 'nofold'}" for g, s, f in F32_CASES]


@pytest.mark.parametrize("grid,spin,fold", F32_CASES, ids=F32_IDS)
def test_adjointness_jnp_f32(grid, spin, fold):
    plan = _make_plan(grid, 8, "float32", "jnp", spin=spin, K=2, fold=fold)
    assert plan.fold is fold
    for seed in (0, 1):
        assert _adjoint_identity_err(plan, seed) < 1e-5


# ---------------------------------------------------------------------------
# phase-stage custom rules: VJP transpose consistent with the JVP (forward
# linearisation), both engines
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grid_kind", ["gl", "ecp", "healpix"])
def test_phase_vjp_jvp_consistency(grid_kind, seed=0):
    nside = 4
    g = gridlib.make_grid(grid_kind, l_max=6, nside=nside)
    m_max = 6 if g.uniform else 2 * nside
    t = shtlib.SHT(g, l_max=m_max, m_max=m_max)
    ph = t.phase
    rng = np.random.default_rng(seed)
    M, R = m_max + 1, g.n_rings
    d = jnp.asarray(rng.normal(size=(M, R, 1)) + 1j * rng.normal(size=(M, R, 1)))
    v = jnp.asarray(rng.normal(size=(M, R, 1)) + 1j * rng.normal(size=(M, R, 1)))
    # synth: <J v, t> == Re(sum(vjp(t) * v))  (JAX bilinear pairing)
    maps, vjp = jax.vjp(ph.synth, d)
    tmap = jnp.asarray(rng.normal(size=maps.shape))
    (ct,) = vjp(tmap)
    _, jv = jax.jvp(ph.synth, (d,), (v,))
    lhs = float(jnp.sum(jv * tmap))
    rhs = float(jnp.real(jnp.sum(ct * v)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-10)
    # anal: same consistency on the reverse direction
    mreal = jnp.asarray(rng.normal(size=maps.shape))
    vm = jnp.asarray(rng.normal(size=maps.shape))
    dw, vjp2 = jax.vjp(ph.anal, mreal)
    ct_d = jnp.asarray(rng.normal(size=dw.shape) + 1j * rng.normal(size=dw.shape))
    (ctm,) = vjp2(ct_d)
    _, jv2 = jax.jvp(ph.anal, (mreal,), (vm,))
    lhs2 = float(jnp.real(jnp.sum(jv2 * ct_d)))
    rhs2 = float(jnp.sum(ctm * vm))
    np.testing.assert_allclose(lhs2, rhs2, rtol=1e-10)


# ---------------------------------------------------------------------------
# finite-difference gradient checks through the Plan API
# ---------------------------------------------------------------------------


def _grad_dir(g, v):
    """Directional derivative from a jax.grad result: Re(sum(g * v)).

    JAX's complex-gradient convention is grad = d/dRe - i * d/dIm, so the
    bilinear (non-conjugating) pairing reproduces the derivative.
    """
    return float(jnp.real(jnp.sum(g * v)))


def _check_grad_synth(plan, seed, rtol):
    a = _rand_alm(plan, seed)
    t = _rand_maps(plan, seed + 1)
    v = _rand_alm(plan, seed + 2)
    synth = plan._synth_fn(plan.backends["synth"])

    def loss(x):
        return jnp.sum(synth(x) * t)

    g = jax.grad(loss)(a)
    eps = 1e-6 if plan.dtype == "float64" else 1e-2
    fd = float((loss(a + eps * v) - loss(a - eps * v)) / (2 * eps))
    np.testing.assert_allclose(_grad_dir(g, v), fd, rtol=rtol,
                               atol=rtol * max(abs(fd), 1.0))


def _check_grad_anal(plan, seed, rtol, iters=0):
    a = _rand_alm(plan, seed)
    maps0 = plan.alm2map(a)
    vm = _rand_maps(plan, seed + 3)
    anal = plan._anal_fn(plan.backends["anal"])

    def loss(mp):
        alm = anal(mp)
        for _ in range(iters):
            alm = alm + anal(mp - plan.alm2map(alm))
        return jnp.sum(jnp.abs(alm) ** 2)

    g = jax.grad(loss)(maps0)
    eps = 1e-6 if plan.dtype == "float64" else 1e-2
    fd = float((loss(maps0 + eps * vm) - loss(maps0 - eps * vm)) / (2 * eps))
    np.testing.assert_allclose(float(jnp.sum(g * vm)), fd, rtol=rtol,
                               atol=rtol * max(abs(fd), 1.0))


@settings(max_examples=24, deadline=None)
@given(grid=st.sampled_from(GRIDS), spin=st.sampled_from([0, 2]),
       seed=st.integers(0, 10**6))
def test_gradcheck_jnp_f64(grid, spin, seed):
    plan = _make_plan(grid, 8, "float64", "jnp", spin=spin)
    _check_grad_synth(plan, seed, rtol=1e-6)
    _check_grad_anal(plan, seed, rtol=1e-6)


@pytest.mark.parametrize("grid,spin,fold", F32_CASES, ids=F32_IDS)
def test_gradcheck_jnp_f32(grid, spin, fold):
    plan = _make_plan(grid, 8, "float32", "jnp", spin=spin, K=2, fold=fold)
    _check_grad_synth(plan, 7, rtol=1e-3)
    _check_grad_anal(plan, 11, rtol=1e-3)


def test_gradcheck_through_jacobi_iters():
    """map2alm(iters=1) (residual refinement) stays differentiable."""
    plan = _make_plan("healpix", 8, "float64", "jnp")
    _check_grad_anal(plan, 3, rtol=1e-6, iters=1)


def test_jvp_linearity_and_consistency():
    """JVP of a linear transform is the transform itself; VJP pairs with it."""
    plan = _make_plan("gl", 10, "float64", "jnp")
    a = _rand_alm(plan, 0)
    v = _rand_alm(plan, 1)
    y, dy = jax.jvp(plan.alm2map, (a,), (v,))
    np.testing.assert_allclose(np.asarray(dy), np.asarray(plan.alm2map(v)),
                               atol=1e-12)


def test_residual_gradients_raise_not_silently_zero():
    """d/d(weights, geometry, ...) is undefined under the adjoint rules;
    asking for it must raise, not return an all-zero gradient."""
    g = gridlib.gauss_legendre_grid(6)
    lm = legendre.log_mu(6)
    m_vals = np.arange(7)
    rng = np.random.default_rng(0)
    d_re = jnp.asarray(rng.normal(size=(7, g.n_rings, 1)))
    d_im = jnp.zeros_like(d_re)

    def loss_w(w):
        a_re, _ = legendre.alm_from_delta(d_re, d_im, m_vals, g.cos_theta,
                                          g.sin_theta, w, lm, l_max=6)
        return jnp.sum(a_re)

    with pytest.raises(ValueError, match="residual"):
        jax.grad(loss_w)(jnp.asarray(g.weights))


def test_grad_ready_surface():
    plan = _make_plan("gl", 8, "float64", "jnp")
    assert plan.grad_ready == {"synth": True, "anal": True}
    d = plan.describe()["differentiable"]
    assert d["synth"] and d["anal"] and d["higher_order"] is False


def test_grad_through_power_spectrum_loss():
    """The motivating workload: grad of a C_l-space loss wrt alm."""
    from repro.core import spectra
    plan = _make_plan("gl", 8, "float64", "jnp")
    a0 = _rand_alm(plan, 5)
    target = spectra.cl_from_alm(a0)

    def loss(a):
        cl = spectra.cl_from_alm(plan.map2alm(plan.alm2map(a)))
        return jnp.sum((cl - target) ** 2)

    g = jax.grad(loss)(a0 * 0.5)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.abs(g).max()) > 0.0
