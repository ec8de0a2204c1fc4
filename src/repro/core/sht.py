"""Serial/batched spherical harmonic transforms (the pure-jnp engine).

Implements paper Algorithms 1 & 2 for iso-latitude grids:

  alm2map (inverse / synthesis, paper eq. 11-12):
      Delta^A_m(r) = sum_l a_lm P_lm(cos theta_r)        (Legendre stage)
      s(r, phi_j)  = sum_m e^{i m phi_j} Delta^A_m(r)    (FFT stage)

  map2alm (direct / analysis, paper eq. 13-14):
      Delta^S_m(r) = sum_j w_r s(r, phi_j) e^{-i m phi_j}  (FFT stage)
      a_lm         = sum_r Delta^S_m(r) P_lm(cos theta_r)  (Legendre stage)

This module is the *oracle*: float64 by default, used by every test, and
in float32 the one-device backend of every plan.  The distributed
transforms (repro.core.dist_sht) are validated against it.

The FFT stage is NOT implemented here: it lives in the pluggable phase
layer (`repro.core.phase`), which picks the batched-uniform engine or the
ring-bucket engine (true ragged HEALPix) per grid.  This engine and the
distributed transform share that one implementation.

Conventions
-----------
* Fields are real; only m >= 0 coefficients are stored (a_{l,-m} = (-1)^m
  conj(a_lm)).
* alm layout: dense rectangle ``(m_max+1, l_max+1, K)`` complex ("MLK"),
  entries with l < m must be zero.  ``K`` is the number of simultaneous maps
  (the batched/multi-map transform -- the paper's Monte-Carlo target
  workload and our MXU lever).
* maps layout: ``(R, n_phi_max, K)`` real; ragged grids are padded with
  zeros beyond each ring's n_phi.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import legendre
from repro.core.grids import RingGrid
from repro.tracing import FOLD

__all__ = ["SHT", "alm_rect_zeros", "random_alm", "random_alm_spin",
           "alm_mask"]


def alm_mask(l_max: int, m_max: int, spin: int = 0) -> np.ndarray:
    """(m_max+1, l_max+1) bool mask of valid (m, l) entries.

    Valid means ``l >= m`` and ``l >= spin`` (spin-s harmonics start at
    l = s; for polarisation E/B that is l = 2).
    """
    m = np.arange(m_max + 1)[:, None]
    l = np.arange(l_max + 1)[None, :]
    return (l >= m) & (l >= spin)


def alm_rect_zeros(l_max: int, m_max: int, K: int = 1,
                   dtype=np.complex128) -> np.ndarray:
    return np.zeros((m_max + 1, l_max + 1, K), dtype=dtype)


def _resolve_key(key, seed, caller: str):
    if (key is None) == (seed is None):
        raise ValueError(
            f"{caller} requires exactly one of `key` or `seed=` -- the old "
            "silent key=None -> PRNGKey(0) fallback has been removed; pass "
            "jax.random.PRNGKey(...) explicitly or use seed=<int>")
    return jax.random.PRNGKey(seed) if key is None else key


def random_alm(key=None, l_max: int = None, m_max: int = None, K: int = 1,
               dtype=jnp.float64, *, spin: int = 0,
               seed=None) -> jnp.ndarray:
    """Random a_lm, uniform in (-1, 1) (paper §5 experimental setup).

    Exactly one of ``key`` (a jax PRNG key) or ``seed=`` (an int, documented
    deterministic shorthand) must be given.  m = 0 entries are real
    (required for a real field); ``spin`` zeroes the l < spin rows.
    ``dtype`` float64 gives float32 draws when JAX's 64-bit mode is off.
    """
    key = _resolve_key(key, seed, "random_alm")
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    kr, ki = jax.random.split(key)
    shape = (m_max + 1, l_max + 1, K)
    re = jax.random.uniform(kr, shape, dtype, -1.0, 1.0)
    im = jax.random.uniform(ki, shape, dtype, -1.0, 1.0)
    im = im.at[0].set(0.0)  # m = 0 is real
    mask = jnp.asarray(alm_mask(l_max, m_max, spin))[..., None]
    return jnp.where(mask, re + 1j * im, 0.0)


def random_alm_spin(key=None, l_max: int = None, m_max: int = None,
                    K: int = 1, dtype=jnp.float64, *,
                    seed=None) -> jnp.ndarray:
    """Random (E, B) alm pair for spin-2 transforms, shape (2, M, L1, K).

    Same key/seed contract as :func:`random_alm`; rows with l < 2 are zero
    (no spin-2 harmonics below the spin)."""
    key = _resolve_key(key, seed, "random_alm_spin")
    ke, kb = jax.random.split(key)
    e = random_alm(ke, l_max, m_max, K, dtype, spin=2)
    b = random_alm(kb, l_max, m_max, K, dtype, spin=2)
    return jnp.stack([e, b], axis=0)


@dataclasses.dataclass(frozen=True)
class SHT:
    """Batched serial SHT engine on an iso-latitude grid.

    Parameters
    ----------
    grid : RingGrid
    l_max, m_max : band limits (m_max <= l_max; default m_max = l_max)
    dtype : recurrence/accumulation dtype (float64 oracle, float32 perf)
    fold : use the equator-fold optimisation (grid must be symmetric)
    """

    grid: RingGrid
    l_max: int
    m_max: int
    dtype: str = "float64"
    fold: bool = False
    #: cache policy for the phase stage's precomputed index maps
    #: ("off" | "memory" | "disk"), and the disk-tier directory override.
    phase_cache: str = "memory"
    phase_cache_dir: Optional[str] = None

    def __post_init__(self):
        assert self.m_max <= self.l_max
        if self.fold:
            assert self.grid.equator_symmetric, "fold requires a symmetric grid"

    # -- geometry helpers ---------------------------------------------------

    @property
    def n_north(self) -> int:
        """Number of northern rings incl. the equator ring if present."""
        return (self.grid.n_rings + 1) // 2

    @property
    def has_equator(self) -> bool:
        return self.grid.n_rings % 2 == 1

    @functools.cached_property
    def _log_mu(self) -> np.ndarray:
        return legendre.log_mu(self.m_max)

    @functools.cached_property
    def _m_all(self) -> np.ndarray:
        return np.arange(self.m_max + 1)

    # -- FFT/phase stage (pluggable, shared with the dist path) ------------

    @functools.cached_property
    def phase(self):
        """The grid's phase stage: batched-uniform or ring-bucket engine
        (`repro.core.phase.make_phase`), device-resident either way."""
        from repro.core.phase import make_phase
        return make_phase(self.grid, self.m_max, self.dtype,
                          cache=self.phase_cache,
                          cache_dir=self.phase_cache_dir)

    # -- Legendre stage (spin-aware harmonic core) --------------------------

    def _harmonic_core(self, spin: int) -> "legendre.HarmonicCore":
        """The spin-aware recurrence layer bound to this grid/band-limit."""
        cache = self.__dict__.setdefault("_cores", {})
        if spin not in cache:
            g = self.grid
            cache[spin] = legendre.HarmonicCore(
                m_vals=self._m_all, grid_x=g.cos_theta, grid_sin=g.sin_theta,
                log_mu_all=self._log_mu, l_max=self.l_max, spin=spin,
                dtype=self.dtype)
        return cache[spin]

    def _delta_from_alm(self, alm: jnp.ndarray) -> jnp.ndarray:
        """(M, L, K) complex alm -> (M, R, K) complex Delta^A."""
        g = self.grid
        dt = jnp.dtype(self.dtype)
        if not self.fold:
            return self._harmonic_core(0).delta_from_alm(alm)
        nh = self.n_north
        with jax.named_scope(FOLD):
            a_re, a_im = jnp.real(alm), jnp.imag(alm)
        ere, eim, ore_, oim = legendre.delta_from_alm_folded(
            a_re, a_im, self._m_all, g.cos_theta[:nh], g.sin_theta[:nh],
            self._log_mu, l_max=self.l_max, dtype=dt)
        with jax.named_scope(FOLD):
            north = (ere + ore_) + 1j * (eim + oim)           # (M, nh, K)
            ns = nh - 1 if self.has_equator else nh
            south = (ere - ore_)[:, :ns] + 1j * (eim - oim)[:, :ns]
            return jnp.concatenate([north, south[:, ::-1]], axis=1)

    def _alm_from_delta(self, delta_w: jnp.ndarray) -> jnp.ndarray:
        """(M, R, K) weighted Delta^S -> (M, L, K) complex alm.

        ``delta_w`` must already include the quadrature weights (the FFT
        stage applies them)."""
        g = self.grid
        dt = jnp.dtype(self.dtype)
        if not self.fold:
            return self._harmonic_core(0).alm_from_delta(delta_w)
        nh = self.n_north
        with jax.named_scope(FOLD):
            north = delta_w[:, :nh]
            ns = nh - 1 if self.has_equator else nh
            south = delta_w[:, nh:][:, ::-1]                  # mirror order
            pad = north[:, ns:nh] * 0.0                       # equator slot
            south_p = jnp.concatenate([south, pad], axis=1) \
                if self.has_equator else south
            s_e = north + south_p
            s_o = north - south_p
            # (equator ring: P_lm(0) = 0 for odd l+m: its s_o is inert)
            parts = (jnp.real(s_e), jnp.imag(s_e), jnp.real(s_o),
                     jnp.imag(s_o))
        a_re, a_im = legendre.alm_from_delta_folded(
            *parts, self._m_all, g.cos_theta[:nh], g.sin_theta[:nh],
            self._log_mu, l_max=self.l_max, dtype=dt)
        with jax.named_scope(FOLD):
            return a_re + 1j * a_im

    # -- public API ----------------------------------------------------------

    def alm2map(self, alm: jnp.ndarray) -> jnp.ndarray:
        """Inverse SHT (synthesis).  alm (M, L, K) -> maps (R, n_phi, K).

        For ragged grids the output is padded; samples beyond n_phi(r) are 0.
        """
        assert alm.shape[:2] == (self.m_max + 1, self.l_max + 1), alm.shape
        delta = self._delta_from_alm(alm)
        return self.phase.synth(delta)

    def map2alm(self, maps: jnp.ndarray, iters: int = 0) -> jnp.ndarray:
        """Direct SHT (analysis).  maps (R, n_phi, K) -> alm (M, L, K).

        ``iters`` > 0 applies Jacobi residual refinement (the HEALPix
        map2alm_iter technique):  a_{n+1} = a_n + A(m - S(a_n)).  Each
        iteration costs one synthesis + one analysis and drives the
        approximate-quadrature error of the HEALPix-family grids down by
        roughly an order of magnitude per pass (exact grids gain nothing).
        """
        assert maps.shape[0] == self.grid.n_rings, maps.shape
        delta_w = self.phase.anal(jnp.asarray(maps))
        alm = self._alm_from_delta(delta_w)
        for _ in range(iters):
            resid = maps - self.alm2map(alm)
            alm = alm + self.map2alm(resid, iters=0)
        return alm

    # -- spin-2 transforms (polarisation: E/B <-> Q/U) -----------------------
    #
    # The phase stage is spin-blind (e^{im phi} factors are identical), so
    # the (Q, U) component pair rides the trailing K channel axis through
    # the same engine; only the Legendre stage switches to the spin-2
    # harmonic core (two stacked Wigner-d recurrences, lambda^{+/-} mixing).

    def alm2map_spin(self, alm_eb: jnp.ndarray) -> jnp.ndarray:
        """Spin-2 synthesis: (E, B) alm (2, M, L, K) -> (Q, U) maps
        (2, R, n_phi, K)."""
        assert not self.fold, "fold is not supported for spin transforms"
        assert alm_eb.shape[:3] == (2, self.m_max + 1, self.l_max + 1), \
            alm_eb.shape
        K = alm_eb.shape[-1]
        delta = self._harmonic_core(2).delta_from_alm(alm_eb)  # (2, M, R, K)
        with jax.named_scope(FOLD):
            d2 = jnp.concatenate([delta[0], delta[1]], axis=-1)  # (M, R, 2K)
        s = self.phase.synth(d2)                               # (R, nphi, 2K)
        with jax.named_scope(FOLD):
            return jnp.stack([s[..., :K], s[..., K:]], axis=0)

    def map2alm_spin(self, maps_qu: jnp.ndarray, iters: int = 0) -> jnp.ndarray:
        """Spin-2 analysis: (Q, U) maps (2, R, n_phi, K) -> (E, B) alm
        (2, M, L, K); ``iters`` as in :meth:`map2alm`."""
        assert not self.fold, "fold is not supported for spin transforms"
        assert maps_qu.shape[0] == 2 and \
            maps_qu.shape[1] == self.grid.n_rings, maps_qu.shape
        maps_qu = jnp.asarray(maps_qu)
        K = maps_qu.shape[-1]
        with jax.named_scope(FOLD):
            m2 = jnp.concatenate([maps_qu[0], maps_qu[1]], axis=-1)
        dw = self.phase.anal(m2)                               # (M, R, 2K)
        with jax.named_scope(FOLD):
            delta_w = jnp.stack([dw[..., :K], dw[..., K:]], axis=0)
        alm = self._harmonic_core(2).alm_from_delta(delta_w)
        for _ in range(iters):
            resid = maps_qu - self.alm2map_spin(alm)
            alm = alm + self.map2alm_spin(resid, iters=0)
        return alm
