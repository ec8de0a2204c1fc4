"""The float64 reference against the library's float64 path, its own
invariants at full band limit, and its control: the reference in bfloat16
fails every cell's limit."""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

import reference as ref

CHECKS = sorted(glob.glob(os.path.join(os.path.dirname(
    os.path.dirname(__file__)), "checks", "*.json")))


def _alm(rng, L, K):
    a = rng.uniform(-1, 1, (L + 1, L + 1, K)) \
        + 1j * rng.uniform(-1, 1, (L + 1, L + 1, K))
    a[0] = a[0].real
    m, l = np.indices((L + 1, L + 1))
    a[l < m] = 0
    return a


@pytest.fixture(scope="module")
def x64():
    import jax
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def test_nodes_match_numpy_leggauss():
    for L in (8, 200):
        g = ref.gl_grid(L)
        x, w = np.polynomial.legendre.leggauss(L + 1)
        assert np.allclose(g["x"], x[::-1], rtol=0, atol=1e-14)
        assert np.allclose(g["w"], w[::-1] * 2 * np.pi / (2 * L + 2),
                           rtol=1e-10, atol=0)


def test_agrees_with_library_float64(x64):
    import repro
    L = 20
    plan = repro.make_plan("gl", l_max=L, K=2, dtype="float64", mode="jnp")
    a = _alm(np.random.default_rng(0), L, 2)
    g = ref.gl_grid(L)
    maps = np.asarray(plan.alm2map(a))
    rings = np.array([0, 4, 10, 20])
    assert ref.row_rel_max(maps[rings], ref.synth_rings(a, rings, g)) < 1e-12
    back = np.asarray(plan.map2alm(maps))
    rows = np.array([0, 3, 20])
    assert ref.row_rel_max(back[rows], ref.anal_rows(maps, rows, g)) < 1e-12
    assert ref.row_rel_max(a[rows], ref.anal_rows(maps, rows, g)) < 1e-12


def test_orthonormal_at_l_max_4096():
    """2 pi sum_r w_r lambda_lm lambda_l'm = delta_ll' by quadrature,
    where lambda_mm underflows float64 and the scaled start matters."""
    L = 4096
    g = ref.gl_grid(L)
    m = np.array([0, 600, 3000, 4096])
    want = {4095, 4096}
    vals = {l: v.copy() for l, n, v in ref.legendre_rows(L, m, g["x"],
                                                         g["sin"])
            if l in want}
    w = g["w"] * g["n_phi"]
    for i in range(3):
        assert np.sum(w * vals[4095][i] ** 2) == pytest.approx(1, abs=1e-10)
        assert abs(np.sum(w * vals[4095][i] * vals[4096][i])) < 1e-10
    assert np.sum(w * vals[4096][3] ** 2) == pytest.approx(1, abs=1e-10)


@pytest.mark.parametrize("path", CHECKS, ids=os.path.basename)
def test_bfloat16_control_fails_the_limit(path):
    """The control of each cell at l_max 256: the bfloat16 reference in
    the program's place reads above the cell's limit."""
    limits = json.load(open(path))["limits"]
    rng = np.random.default_rng(1)
    L = 256
    g = ref.gl_grid(L)
    a = _alm(rng, L, 1)
    rings = np.unique(np.r_[0, L, rng.choice(L + 1, 32, replace=False)])
    want = ref.synth_rings(a, rings, g)
    got = ref.synth_rings(a, rings, g, "bfloat16")
    maps = rng.uniform(-1, 1, (L + 1, 2 * L + 2, 2))
    rows = np.unique(np.r_[0, L, rng.choice(L + 1, 16, replace=False)])
    want_a = ref.anal_rows(maps, rows, g)
    got_a = ref.anal_rows(maps, rows, g, "bfloat16")
    for k, limit in limits.items():
        fn = getattr(ref, k)
        assert fn(got, want) > limit
        assert fn(got_a, want_a) > limit
