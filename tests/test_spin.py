"""Spin-2 (polarisation) transform correctness.

Layers under test:
  * the generalised Wigner-d recurrence (`legendre.delta_from_alm_general`)
    against an explicit textbook Wigner-d sum oracle -- this is the only
    test class that can catch per-(l, m) normalisation/sign errors (the
    round-trip is blind to them: synthesis and analysis share the lambda
    code, so any row scaling cancels);
  * spin-2 round-trips at machine precision on the exact grid, the pure-E
    -> zero-B null test, per-backend error thresholds vs the same
    backend's scalar error (the 10x acceptance band), iters-monotone on
    HEALPix;
  * the spin plan plumbing (signature, describe, cost model) and the
    random_alm key/seed hardening.

The distributed spin path is covered by tests/helpers/dist_sht_check.py
(subprocess, 8 host devices) via tests/test_distributed.py.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import grids, legendre, sht, spectra

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# Wigner-d oracle
# ---------------------------------------------------------------------------


def wigner_d(j, m, mp, beta):
    """Explicit Wigner d^j_{m,mp}(beta) sum formula (z-y-z convention,
    matching the standard d^2 table)."""
    f = math.factorial
    m, mp = mp, m          # the sum below is the transposed-index variant
    pref = math.sqrt(f(j + m) * f(j - m) * f(j + mp) * f(j - mp))
    c, s = math.cos(beta / 2.0), math.sin(beta / 2.0)
    tot = 0.0
    for k in range(max(0, m - mp), min(j + m, j - mp) + 1):
        denom = f(j + m - k) * f(k) * f(j - k - mp) * f(k - m + mp)
        tot += ((-1) ** (k - m + mp) / denom
                * c ** (2 * j - 2 * k + m - mp) * s ** (2 * k - m + mp))
    return pref * tot


def test_wigner_sum_matches_d2_table():
    for beta in (0.3, 1.1, 2.5):
        x = math.cos(beta)
        assert abs(wigner_d(2, 2, 2, beta) - ((1 + x) / 2) ** 2) < 1e-12
        assert abs(wigner_d(2, 2, 1, beta)
                   + 0.5 * math.sin(beta) * (1 + x)) < 1e-12
        assert abs(wigner_d(2, 0, 2, beta)
                   - math.sqrt(6) / 4 * math.sin(beta) ** 2) < 1e-12
        assert abs(wigner_d(2, 0, 0, beta) - 0.5 * (3 * x * x - 1)) < 1e-12


@pytest.mark.parametrize("m", [0, 1, 2, 3, 6])
@pytest.mark.parametrize("mp", [-2, 2, 0])
def test_lambda_recurrence_matches_wigner_oracle(m, mp):
    """lam^{(m')}_lm = (-1)^m sqrt((2l+1)/4pi) d^l_{m,m'} for every l, ring."""
    l_max = 8
    g = grids.make_grid("gl", l_max=l_max)
    thetas = np.arccos(g.cos_theta)
    ls = list(range(l_max + 1))
    a_re = np.zeros((1, l_max + 1, len(ls)))
    for j, l in enumerate(ls):
        a_re[0, l, j] = 1.0      # impulse per l in the K channel
    d_re, _ = legendre.delta_from_alm_general(
        a_re, np.zeros_like(a_re), [m], [mp], g.cos_theta, g.sin_theta,
        l_max=l_max, dtype=jnp.float64)
    got = np.asarray(d_re)[0]    # (R, K): got[r, j] = lam_{l_j, m}(theta_r)
    for j, l in enumerate(ls):
        for r, th in enumerate(thetas):
            if l < max(m, abs(mp)):
                want = 0.0
            else:
                want = ((-1) ** m * math.sqrt((2 * l + 1) / (4 * math.pi))
                        * wigner_d(l, m, mp, th))
            assert abs(got[r, j] - want) < 1e-11 * max(1.0, abs(want)), \
                (l, m, mp, r)


# ---------------------------------------------------------------------------
# round-trips / null tests (serial engine)
# ---------------------------------------------------------------------------


def test_spin2_gl_roundtrip_machine_precision():
    l_max, K = 32, 2
    g = grids.make_grid("gl", l_max=l_max)
    t = sht.SHT(g, l_max=l_max, m_max=l_max)
    alm = sht.random_alm_spin(KEY, l_max, l_max, K=K)
    out = t.map2alm_spin(t.alm2map_spin(alm))
    assert spectra.d_err(alm, out) < 1e-12


def test_pure_e_zero_b_null():
    """Pure-E alm synthesise Q/U that analyse back with zero B leakage."""
    l_max = 24
    g = grids.make_grid("gl", l_max=l_max)
    t = sht.SHT(g, l_max=l_max, m_max=l_max)
    alm = sht.random_alm_spin(KEY, l_max, l_max).at[1].set(0.0)
    back = t.map2alm_spin(t.alm2map_spin(alm))
    e_scale = float(np.max(np.abs(np.asarray(alm[0]))))
    assert float(np.max(np.abs(np.asarray(back[1])))) < 1e-13 * e_scale
    # and the E channel itself is recovered
    assert spectra.d_err(alm[0], back[0]) < 1e-12


def test_spin2_fold_rejected():
    g = grids.make_grid("gl", l_max=8)
    t = sht.SHT(g, l_max=8, m_max=8, fold=True)
    alm = sht.random_alm_spin(KEY, 8, 8)
    with pytest.raises(AssertionError):
        t.alm2map_spin(alm)
    with pytest.raises(ValueError):
        repro.make_plan("gl", l_max=8, fold=True, spin=2)
    with pytest.raises(ValueError):
        repro.make_plan("gl", l_max=8, spin=1)


# ---------------------------------------------------------------------------
# plan-level: every backend within 10x of its own scalar error
# ---------------------------------------------------------------------------


def _plan_roundtrip_err(grid_kw, backend, dtype, spin, key):
    p = repro.make_plan(dtype=dtype, mode=backend, K=2, spin=spin, **grid_kw)
    if spin == 0:
        alm = sht.random_alm(key, p.l_max, p.m_max, K=2)
    else:
        alm = sht.random_alm_spin(key, p.l_max, p.m_max, K=2)
    if dtype == "float32":
        alm = alm.astype(jnp.complex64)
    return spectra.d_err(alm, p.map2alm(p.alm2map(alm)))


@pytest.mark.parametrize("grid_kw", [
    {"grid": "gl", "l_max": 24},
    {"grid": "healpix", "nside": 8, "l_max": 16},
], ids=["gl", "healpix"])
@pytest.mark.parametrize("backend,dtype", [
    ("jnp", "float64"), ("jnp", "float32"),
])
def test_spin_backends_within_10x_of_scalar(grid_kw, backend, dtype):
    err_s = _plan_roundtrip_err(grid_kw, backend, dtype, 2, KEY)
    err_0 = _plan_roundtrip_err(grid_kw, backend, dtype, 0, KEY)
    assert err_s < 10 * err_0 + 1e-12, (err_s, err_0)


def test_spin_iters_monotone_on_healpix():
    p = repro.make_plan("healpix", nside=8, dtype="float64", mode="jnp",
                        spin=2)
    alm = sht.random_alm_spin(KEY, p.l_max, p.m_max, K=1)
    maps = p.alm2map(alm)
    errs = [spectra.d_err(alm, p.map2alm(maps, iters=i)) for i in range(3)]
    assert errs[1] < errs[0] / 3
    assert errs[2] < errs[1]


# ---------------------------------------------------------------------------
# plan plumbing / cost model / spectra helpers / random_alm hardening
# ---------------------------------------------------------------------------


def test_spin_plan_signature_and_describe():
    p0 = repro.make_plan("gl", l_max=16, dtype="float64", mode="jnp")
    p2 = repro.make_plan("gl", l_max=16, dtype="float64", mode="jnp", spin=2)
    assert p0 is not p2
    d = p2.describe()
    assert d["signature"]["spin"] == 2
    w0, w2 = p0.describe()["work"], d["work"]
    assert w2["recurrence_flops"] == 2 * w0["recurrence_flops"]
    assert w2["accum_flops"] == 2 * w0["accum_flops"]
    assert "spin=2" in p2.report()
    # shape validation is pair-aware
    with pytest.raises(AssertionError):
        p2.alm2map(jnp.zeros((17, 17, 1), jnp.complex128))


def test_spectra_pol_helpers():
    l_max = 24
    cls = spectra.cmb_like_cl_pol(l_max)
    assert np.all(np.abs(cls["te"]) <= np.sqrt(cls["tt"] * cls["ee"]) + 1e-15)
    alm = spectra.alm_from_cl_pol(KEY, cls, K=256)
    for i, name in enumerate(("tt", "ee", "bb")):
        est = np.asarray(spectra.cl_from_alm(alm[i])).mean(-1)
        good = cls[name][2:] > 0
        rel = np.abs(est[2:][good] - cls[name][2:][good]) / cls[name][2:][good]
        assert np.median(rel) < 0.2, name
    te = np.asarray(spectra.cl_cross_from_alm(alm[0], alm[1])).mean(-1)
    scale = np.sqrt(cls["tt"][2:] * cls["ee"][2:])
    assert np.median(np.abs(te[2:] - cls["te"][2:]) / scale) < 0.2
    # E/B start at l = 2
    assert np.all(np.asarray(alm[1])[:, :2] == 0)


def test_random_alm_requires_key_or_seed():
    with pytest.raises(ValueError):
        sht.random_alm(None, 4, 4)
    with pytest.raises(ValueError):
        sht.random_alm(KEY, 4, 4, seed=0)
    with pytest.raises(ValueError):
        sht.random_alm_spin(l_max=4, m_max=4)
    a1 = sht.random_alm(seed=7, l_max=4, m_max=4)
    a2 = sht.random_alm(seed=7, l_max=4, m_max=4)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
