import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

import repro  # noqa: F401
from repro.core import grids
from repro.core.plan import SHTPlan, minmax_m_order


def test_minmax_order_basic():
    assert list(minmax_m_order(5)) == [0, 5, 1, 4, 2, 3]
    assert list(minmax_m_order(4)) == [0, 4, 1, 3, 2]


@settings(max_examples=30, deadline=None)
@given(m_max=st.integers(1, 600))
def test_minmax_order_is_permutation(m_max):
    o = minmax_m_order(m_max)
    assert sorted(o) == list(range(m_max + 1))
    # consecutive pairs sum to m_max (the paper's balance invariant)
    for i in range(0, m_max - 1, 2):
        assert o[i] + o[i + 1] == m_max


@settings(max_examples=15, deadline=None)
@given(l_max=st.integers(8, 128),
       n_shards=st.sampled_from([2, 4, 8, 16]))
def test_plan_balance_and_coverage(l_max, n_shards):
    g = grids.make_grid("gl", l_max=l_max)
    p = SHTPlan(g, l_max, l_max, n_shards)
    a = p.m_assignment
    vals = a[a >= 0]
    assert sorted(vals.tolist()) == list(range(l_max + 1))   # coverage
    # paper invariant: per-shard recurrence steps within one pair's work
    steps = p.recurrence_steps_per_shard
    pair_work = 2 * (l_max + 1) - l_max + 2
    assert steps.max() - steps.min() <= 2 * pair_work
    # rings: every real ring appears exactly once
    ro = p.ring_order
    real = ro[ro >= 0]
    assert sorted(real.tolist()) == list(range(g.n_rings))
    assert p.r_pad % n_shards == 0
    assert p.r_local % 2 == 0             # whole mirror pairs per shard


@settings(max_examples=10, deadline=None)
@given(l_max=st.integers(4, 64), n_shards=st.sampled_from([2, 4, 8]),
       K=st.integers(1, 3))
def test_pack_unpack_roundtrip(l_max, n_shards, K):
    g = grids.make_grid("gl", l_max=l_max)
    p = SHTPlan(g, l_max, l_max, n_shards)
    rng = np.random.default_rng(0)
    alm = rng.normal(size=(l_max + 1, l_max + 1, K)) \
        + 1j * rng.normal(size=(l_max + 1, l_max + 1, K))
    packed = p.pack_alm(alm)
    back = p.unpack_alm(packed)
    assert np.allclose(back, alm)


@settings(max_examples=10, deadline=None)
@given(l_max=st.integers(4, 64), n_shards=st.sampled_from([2, 4, 8]))
def test_map_gather_scatter_roundtrip(l_max, n_shards):
    g = grids.make_grid("gl", l_max=l_max)
    p = SHTPlan(g, l_max, l_max, n_shards)
    rng = np.random.default_rng(1)
    maps = rng.normal(size=(g.n_rings, g.max_n_phi, 2))
    assert np.allclose(p.scatter_map(p.gather_map(maps)), maps)


@settings(max_examples=8, deadline=None)
@given(nside=st.sampled_from([4, 8, 16]), n_shards=st.sampled_from([2, 4, 8]))
def test_ragged_plan_bucket_aware_dealing(nside, n_shards):
    """Ragged grids: every ring dealt once, and every shard owns the SAME
    local slot->bucket structure with balanced per-bucket ring counts
    (shard_map's single-program requirement + paper §4.1 FFT balance)."""
    g = grids.make_grid("healpix", nside=nside)
    p = SHTPlan(g, 2 * nside, 2 * nside, n_shards)
    ro = p.ring_order
    real = ro[ro >= 0]
    assert sorted(real.tolist()) == list(range(g.n_rings))   # coverage
    assert p.r_pad % n_shards == 0 and p.r_local % 2 == 0
    lay = p.local_fft_layout
    assert sum(len(sl) for sl in lay.slots) == p.r_local
    for s in range(n_shards):
        loc = ro[s * p.r_local:(s + 1) * p.r_local]
        for B, sl in zip(lay.lengths, lay.slots):
            rings = loc[np.asarray(sl)]
            rings = rings[rings >= 0]
            # exact divisor embedding holds on every shard's every slot
            assert np.all(B % g.n_phi[rings] == 0), (s, B)
    # bin maps are consistent with slot geometry
    pos, neg = p.fft_bin_maps
    assert pos.shape == (p.r_pad, p.m_flat.shape[0])
    blen = p.slot_fft_len
    assert np.all(pos < blen[:, None]) and np.all(neg < blen[:, None])


def test_mirror_pairs_adjacent():
    g = grids.make_grid("healpix_ring", nside=8)   # odd ring count
    p = SHTPlan(g, 16, 16, 4)
    ro = p.ring_order
    R = g.n_rings
    for i in range(R // 2):
        assert ro[2 * i] == i
        assert ro[2 * i + 1] == R - 1 - i
    assert ro[2 * (R // 2)] == R // 2      # equator north slot
    assert ro[2 * (R // 2) + 1] == -1      # equator's dummy south


@pytest.mark.parametrize("l_max,n_shards", [(4096, 4), (40, 4), (31, 8),
                                            (16, 2)])
def test_shard_rows_ascend_and_pack_unpack_roundtrip(l_max, n_shards):
    """Each shard's dealt m rows ascend, padding last, and pack_alm /
    unpack_alm invert each other in that order, in numpy and in a jitted
    jnp program (the dist plan's reshards)."""
    import jax
    import jax.numpy as jnp
    g = grids.make_grid("gl", l_max=l_max)
    p = SHTPlan(g, l_max, l_max, n_shards)
    for rows in p.m_assignment:
        real = rows[rows >= 0]
        assert np.all(np.diff(real) > 0)
        assert np.all(rows[len(real):] == -1)
    if l_max > 64:
        return
    rng = np.random.default_rng(2)
    alm = rng.normal(size=(l_max + 1, l_max + 1, 2)).astype(np.complex64)
    packed = p.pack_alm(alm)
    assert np.all(packed[p.m_flat < 0] == 0)
    assert np.array_equal(packed[p.m_flat >= 0], alm[p.m_flat[p.m_flat >= 0]])
    assert np.array_equal(p.unpack_alm(packed), alm)
    back = jax.jit(lambda a: p.unpack_alm(p.pack_alm(a)))(jnp.asarray(alm))
    assert np.array_equal(np.asarray(back), alm)
