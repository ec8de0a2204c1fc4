import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

import repro  # noqa: F401
from repro.core import grids, legendre


def _p_matrix(m, l_max, grid):
    """P_lm(x_r) for all l via unit-vector synthesis."""
    lm = legendre.log_mu(l_max)
    P = []
    for l in range(l_max + 1):
        a = np.zeros((1, l_max + 1, 1))
        a[0, l, 0] = 1.0
        d, _ = legendre.delta_from_alm(a, np.zeros_like(a), [m],
                                       grid.cos_theta, grid.sin_theta, lm,
                                       l_max=l_max)
        P.append(np.asarray(d)[0, :, 0])
    return np.stack(P)                   # (L, R)


@pytest.mark.parametrize("m", [0, 1, 7, 16])
def test_orthonormality_on_gl(m):
    l_max = 16
    g = grids.make_grid("gl", l_max=l_max)
    P = _p_matrix(m, l_max, g)
    wring = g.weights * g.n_phi
    G = (P * wring) @ P.T
    sub = G[m:, m:]
    assert np.max(np.abs(sub - np.eye(sub.shape[0]))) < 1e-13


def test_known_values():
    l_max = 4
    g = grids.make_grid("gl", l_max=l_max)
    x = g.cos_theta
    P0 = _p_matrix(0, l_max, g)
    assert np.allclose(P0[0], np.sqrt(1 / (4 * np.pi)))
    assert np.allclose(P0[1], np.sqrt(3 / (4 * np.pi)) * x)
    assert np.allclose(P0[2], np.sqrt(5 / (16 * np.pi)) * (3 * x * x - 1))
    P1 = _p_matrix(1, l_max, g)
    assert np.allclose(P1[1], np.sqrt(3 / (8 * np.pi)) * g.sin_theta)


def test_high_m_underflow_stability():
    """P_mm underflows f64 around m ~ 150 at polar rings without rescaling;
    the scaled recurrence must stay finite and correct through turn-on."""
    l_max = 1400
    g = grids.make_grid("gl", l_max=l_max)
    lm = legendre.log_mu(l_max)
    m = 1200
    a = np.zeros((1, l_max + 1, 1))
    a[0, l_max, 0] = 1.0
    d, _ = legendre.delta_from_alm(a, np.zeros_like(a), [m], g.cos_theta,
                                   g.sin_theta, lm, l_max=l_max)
    d = np.asarray(d)[0, :, 0]
    assert np.all(np.isfinite(d))
    # normalised P values are O(1) near the equator
    assert 0.1 < np.abs(d).max() < 10.0


def test_padding_m_is_inert():
    l_max = 12
    g = grids.make_grid("gl", l_max=l_max)
    lm = legendre.log_mu(l_max)
    a = np.random.default_rng(0).normal(size=(2, l_max + 1, 1))
    d, _ = legendre.delta_from_alm(a, np.zeros_like(a), [3, -1], g.cos_theta,
                                   g.sin_theta, lm, l_max=l_max)
    d = np.asarray(d)
    assert np.all(np.isfinite(d))
    assert np.all(d[1] == 0.0)            # padded slot contributes nothing


def test_folded_matches_unfolded():
    l_max = 24
    g = grids.make_grid("gl", l_max=l_max)
    lm = legendre.log_mu(l_max)
    rng = np.random.default_rng(1)
    a_re = rng.normal(size=(l_max + 1, l_max + 1, 2))
    a_im = rng.normal(size=a_re.shape)
    for m in range(l_max + 1):            # zero sub-diagonal
        a_re[m, :m] = 0
        a_im[m, :m] = 0
    m_vals = np.arange(l_max + 1)
    d_re, d_im = legendre.delta_from_alm(a_re, a_im, m_vals, g.cos_theta,
                                         g.sin_theta, lm, l_max=l_max)
    nh = (g.n_rings + 1) // 2
    ere, eim, ore_, oim = legendre.delta_from_alm_folded(
        a_re, a_im, m_vals, g.cos_theta[:nh], g.sin_theta[:nh], lm,
        l_max=l_max)
    north = np.asarray(ere + ore_)
    south = np.asarray(ere - ore_)[:, : g.n_rings - nh][:, ::-1]
    full = np.concatenate([north, south], axis=1)
    assert np.max(np.abs(full - np.asarray(d_re))) < 1e-12


@settings(max_examples=20, deadline=None)
@given(l=st.integers(2, 40), dm=st.integers(0, 40))
def test_recurrence_vs_direct_formula(l, dm):
    """Property: the scaled recurrence matches the explicit normalised
    Legendre polynomial computed via numpy's unnormalised recurrence."""
    m = max(0, l - dm)
    l_max = l
    g = grids.make_grid("gl", l_max=max(l_max, 4))
    P = _p_matrix(m, l_max, g)[l]
    # direct: P~_lm = N_lm * P_lm with numpy's lpmv-free manual recurrence
    from math import lgamma
    x = g.cos_theta
    # unnormalised P_mm = (-1)^m (2m-1)!! (1-x^2)^(m/2) ... use logs
    dfact = sum(np.log(2 * k - 1) for k in range(1, m + 1))
    pmm = np.exp(dfact + 0.5 * m * np.log(1 - x ** 2))
    p_prev, p_curr = np.zeros_like(x), pmm
    for ll in range(m + 1, l + 1):
        p_next = ((2 * ll - 1) * x * p_curr - (ll - 1 + m) * p_prev) / (ll - m)
        p_prev, p_curr = p_curr, p_next
    lognorm = 0.5 * (np.log(2 * l + 1) - np.log(4 * np.pi)
                     + lgamma(l - m + 1) - lgamma(l + m + 1))
    ref = p_curr * np.exp(lognorm)
    assert np.max(np.abs(P - ref)) < 1e-8 * max(1.0, np.abs(ref).max())


# -- row blocks of the jnp loop ----------------------------------------------

_L_BLOCKS = 29
_ROW_SETS = {
    "m_max_eq_l_max": np.arange(_L_BLOCKS + 1),           # M = 30
    "m_max_lt_l_max": np.arange(20),                      # M = 20
    "padded": np.r_[np.arange(19), -1, -1],               # M = 21
}
_BLOCK_ROWS = {
    "C1": lambda M: M,                  # one block through the block path
    "C2": lambda M: -(-M // 2),         # two blocks
    "uneven": lambda M: 8,              # last block shorter than the rest
}


def _loop_case(direction, fold, m_vals, seed=0):
    """(impl, operands, static kwargs) of one direction's jnp loop over the
    rows ``m_vals`` on the GL grid at ``_L_BLOCKS``, with random data."""
    g = grids.make_grid("gl", l_max=_L_BLOCKS)
    R = (g.n_rings + 1) // 2 if fold else g.n_rings
    sb = legendre.scale_bits_for(jnp.float64)
    m, x, pm, ps = legendre._prep(m_vals, g.cos_theta[:R], g.sin_theta[:R],
                                  legendre.log_mu(_L_BLOCKS), jnp.float64, sb)
    rng = np.random.default_rng(seed)
    M, K = m.shape[0], 3
    if direction == "synth":
        ops = [rng.normal(size=(M, _L_BLOCKS + 1, K)) for _ in range(2)]
    else:
        ops = [rng.normal(size=(M, R, K)) for _ in range(4 if fold else 2)]
    impl = {("synth", False): legendre._delta_from_alm_impl,
            ("synth", True): legendre._delta_from_alm_folded_impl,
            ("anal", False): legendre._alm_from_delta_impl,
            ("anal", True): legendre._alm_from_delta_folded_impl}[
                direction, fold]
    args = (*ops, m, x, pm, ps)
    if (direction, fold) == ("anal", False):
        args += (jnp.asarray(g.weights),)
    return impl, args, dict(l_max=_L_BLOCKS, scale_bits=sb,
                            dtype_name="float64")


@pytest.mark.parametrize("blocks", sorted(_BLOCK_ROWS))
@pytest.mark.parametrize("rows", sorted(_ROW_SETS))
@pytest.mark.parametrize("fold", [False, True])
@pytest.mark.parametrize("direction", ["anal", "synth"])
def test_blocked_loop_matches_full_loop(direction, fold, rows, blocks):
    """Row blocks run from their first non-zero l give the single full
    loop's result: the same steps per row, in the same l order."""
    m_vals = _ROW_SETS[rows]
    impl, args, kw = _loop_case(direction, fold, m_vals)
    full = impl(*args, **kw)
    blocked = impl(*args, **kw, block_rows=_BLOCK_ROWS[blocks](len(m_vals)))
    for f, b in zip(full, blocked):
        f, b = np.asarray(f), np.asarray(b)
        assert f.shape == b.shape
        assert np.max(np.abs(b - f)) <= 1e-12 * np.max(np.abs(f))
    if rows == "padded":
        pad = m_vals < 0
        assert all(np.all(np.asarray(b)[pad] == 0.0) for b in blocked)


@pytest.mark.parametrize("fold", [False, True])
@pytest.mark.parametrize("direction", ["anal", "synth"])
def test_traced_rows_take_the_full_loop(direction, fold, monkeypatch):
    """Traced rows (the dist stage 1 inside shard_map) run in the blocks
    that concrete rows of the same count run, to the same result: the
    block count comes from the row count alone."""
    monkeypatch.setattr(legendre, "BLOCK_STEP_BYTES", 1 << 12)
    g = grids.make_grid("gl", l_max=_L_BLOCKS)
    nh = (g.n_rings + 1) // 2
    x, s = (g.cos_theta[:nh], g.sin_theta[:nh]) if fold else \
        (g.cos_theta, g.sin_theta)
    lm = legendre.log_mu(_L_BLOCKS)
    m_vals = np.arange(_L_BLOCKS + 1)
    rng = np.random.default_rng(3)
    shape = (len(m_vals), _L_BLOCKS + 1 if direction == "synth" else len(x),
             2)
    ops = [rng.normal(size=shape) for _ in range(
        4 if (direction, fold) == ("anal", True) else 2)]
    if direction == "synth":
        fn = legendre.delta_from_alm_folded if fold else \
            legendre.delta_from_alm
        call = lambda mv: fn(*ops, mv, x, s, lm, l_max=_L_BLOCKS)
    elif fold:
        call = lambda mv: legendre.alm_from_delta_folded(
            *ops, mv, x, s, lm, l_max=_L_BLOCKS)
    else:
        call = lambda mv: legendre.alm_from_delta(
            *ops, mv, x, s, g.weights, lm, l_max=_L_BLOCKS)
    row_bytes = legendre.row_step_bytes(direction, fold=fold,
                                        n_rings=len(x), K=2,
                                        dtype=jnp.float64)
    assert legendre.row_blocks(m_vals, row_bytes) > 0

    def traced(mv):
        assert legendre.row_blocks(mv, row_bytes) == \
            legendre.row_blocks(m_vals, row_bytes)
        return call(mv)

    for b, f in zip(call(m_vals), jax.jit(traced)(m_vals)):
        f, b = np.asarray(f), np.asarray(b)
        assert np.max(np.abs(b - f)) <= 1e-12 * np.max(np.abs(f))


@pytest.mark.parametrize("M", [1, 7, 8, 33, 1025])
def test_row_blocks_traced_equals_concrete(M):
    """`row_blocks` of traced rows is that of concrete rows of the same
    count, at every row budget (the count alone decides)."""
    m_vals = np.arange(M)
    B = legendre.BLOCK_STEP_BYTES
    for row_bytes in (1, B // 64, B // 8, B):
        seen = []
        jax.jit(lambda mv: seen.append(legendre.row_blocks(mv, row_bytes))
                or mv).lower(m_vals)
        assert seen == [legendre.row_blocks(m_vals, row_bytes)]


@pytest.mark.parametrize("fold", [False, True])
@pytest.mark.parametrize("C", [2, 4])
def test_describe_reports_jnp_row_blocks(C, fold, monkeypatch):
    """``Plan.describe()`` gives the jnp loop's block count and the share
    of the M x L row-steps it runs: (C + 1) / 2C for C blocks of the rows
    m = 0..l_max, 1.0 for one full loop."""
    import repro
    l_max, K = 31, 2                     # M = L = 32 rows, blocks of 8
    plan = repro.make_plan("gl", l_max=l_max, K=K, dtype="float64",
                           mode="jnp", fold=fold, cache="off")
    got = plan.describe()["legendre"]["jnp_blocks"]
    assert set(got) == {"synth", "anal"}
    for d in got.values():
        assert d["blocks"] == 1 and d["row_step_share"] == 1.0
    rings = (plan.grid.n_rings + 1) // 2 if fold else plan.grid.n_rings
    row_bytes = legendre.row_step_bytes("anal", fold=fold, n_rings=rings,
                                        K=K, dtype="float64")
    monkeypatch.setattr(legendre, "BLOCK_STEP_BYTES",
                        (l_max + 1) * row_bytes // C)
    anal = plan.describe()["legendre"]["jnp_blocks"]["anal"]
    assert anal["blocks"] == C
    assert anal["rows_per_block"] == (l_max + 1) // C
    assert anal["row_step_share"] == pytest.approx((C + 1) / (2 * C))
    assert f"jnp loop anal: {C} row blocks" in plan.report()


# -- the folded loops against the unfolded ones -------------------------------


def _mirror_rings(n_rings):
    """(cos, sin) of ``n_rings`` rings mirror-symmetric about the equator,
    north first, with the equator ring (cos = 0 exactly) when the count is
    odd.  The nodes need not be a quadrature: both loops take the same."""
    nh = (n_rings + 1) // 2
    xn = np.cos(np.linspace(0.15, np.pi / 2, nh, endpoint=n_rings % 2 == 1))
    if n_rings % 2:
        xn[-1] = 0.0
    x = np.concatenate([xn, -xn[: n_rings - nh][::-1]])
    return x, np.sqrt(1.0 - x * x)


@pytest.mark.parametrize("block_rows", [0, 5])
@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("equator", [False, True])
@pytest.mark.parametrize("l_max", [23, 24])
@pytest.mark.parametrize("direction", ["anal", "synth"])
def test_folded_loops_match_unfolded(direction, l_max, equator, K,
                                     block_rows):
    """The folded loops (one parity plane a step) give the unfolded loop's
    result over every ring, with and without an equator ring, at odd and
    even l_max, m_max < l_max, and in blocks of 5 rows (blocks that start
    at odd l)."""
    n_rings = 2 * 9 + int(equator)
    nh, ns = (n_rings + 1) // 2, n_rings // 2
    x, s = _mirror_rings(n_rings)
    m_vals = np.arange(l_max - 4)                     # m_max < l_max
    sb = legendre.scale_bits_for(jnp.float64)
    lm = legendre.log_mu(l_max)
    full = legendre._prep(m_vals, x, s, lm, jnp.float64, sb)
    north = legendre._prep(m_vals, x[:nh], s[:nh], lm, jnp.float64, sb)
    kw = dict(l_max=l_max, scale_bits=sb, dtype_name="float64")
    rng = np.random.default_rng(l_max + 2 * K)
    M = len(m_vals)
    if direction == "synth":
        a = [rng.normal(size=(M, l_max + 1, K)) for _ in range(2)]
        for op in a:                                  # zero below l = m
            op[np.arange(l_max + 1)[None, :] < m_vals[:, None]] = 0.0
        want = legendre._delta_from_alm_impl(*a, *full, **kw)
        e_re, e_im, o_re, o_im = legendre._delta_from_alm_folded_impl(
            *a, *north, **kw, block_rows=block_rows)
        got = [np.concatenate([e + o, (e - o)[:, :ns][:, ::-1]], axis=1)
               for e, o in ((e_re, o_re), (e_im, o_im))]
    else:
        d = [rng.normal(size=(M, n_rings, K)) for _ in range(2)]
        w = rng.uniform(0.5, 1.5, n_rings)
        want = legendre._alm_from_delta_impl(*d, *full, jnp.asarray(w), **kw)
        sums = []
        for op in d:
            dw = op * w[None, :, None]
            south = np.zeros((M, nh, K))
            south[:, :ns] = dw[:, nh:][:, ::-1]
            sums.append((dw[:, :nh] + south, dw[:, :nh] - south))
        (se_re, so_re), (se_im, so_im) = sums
        got = legendre._alm_from_delta_folded_impl(
            se_re, se_im, so_re, so_im, *north, **kw, block_rows=block_rows)
    for g_, w_ in zip(got, want):
        g_, w_ = np.asarray(g_), np.asarray(w_)
        assert g_.shape == w_.shape
        assert np.max(np.abs(g_ - w_)) <= 1e-12 * np.max(np.abs(w_))


def test_folded_synthesis_carry_is_k_major():
    """The folded synthesis loop carries its accumulators K-major, as
    (2, K*M, R), one plane per parity of l: a K-minor (M, R, K) carry is
    padded to 128 lanes on a TPU (~17 GB at l_max 4096)."""
    l_max, M, R, K = 11, 12, 5, 4
    x, s = _mirror_rings(2 * R - 1)
    sb = legendre.scale_bits_for(jnp.float64)
    args = legendre._prep(np.arange(M), x[:R], s[:R], legendre.log_mu(l_max),
                          jnp.float64, sb)
    a = jnp.zeros((M, l_max + 1, K))
    jaxpr = jax.make_jaxpr(lambda a_re, a_im: legendre
                           ._delta_from_alm_folded_impl(
                               a_re, a_im, *args, l_max=l_max,
                               scale_bits=sb, dtype_name="float64"))(a, a)
    loops = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in ("while", "scan"):
                loops.append([v.aval.shape for v in eqn.outvars])
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert loops
    carry = [shape for shapes in loops for shape in shapes]
    assert carry.count((2, K * M, R)) == 2            # re, im
    assert (M, R, K) not in carry
