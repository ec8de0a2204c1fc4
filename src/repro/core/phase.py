"""Pluggable FFT/phase stage of the spherical harmonic transforms.

Every SHT backend shares the same two-stage structure (paper Alg. 1-2):
a Legendre stage producing/consuming per-ring Fourier coefficients
Delta_m(r), and a *phase stage* turning them into ring samples (synthesis,
eq. 11) or back (analysis, eq. 14).  This module is the single home of
that phase stage, with two device-resident engines:

``uniform``
    One batched real FFT over all rings (rfft/irfft of the shared n_phi),
    with alias folding of m into the half-spectrum.  The production path
    for Gauss-Legendre and ring-uniform HEALPix grids.

``bucket``
    The ragged-grid (true HEALPix) engine: rings are grouped by rounded-up
    FFT length into buckets (`repro.core.grids.ring_buckets`, libsharp
    style) and each bucket runs ONE batched complex FFT.  Exactness under
    padding comes from the divisor embedding: ring r with n = n_phi(r)
    samples lives in a bucket of length B with n | B, so

      synthesis  -- its alias-folded length-n spectrum is scattered at
                    stride B/n into the length-B spectrum; the length-B
                    inverse FFT then *periodically repeats* the ring's n
                    samples, and a mask keeps the first n;
      analysis   -- its n samples are zero-padded to B; the length-B
                    forward FFT evaluated at bins (m mod n) * (B/n) equals
                    the length-n DFT at bins (m mod n) exactly.

    The scatter/gather index maps are pure geometry, precomputed at plan
    time (`bucket_bin_maps`) and served from the signature-keyed cache.

Both engines are expressed as trace-friendly functions taking the ring
geometry (phi0, weights, n_phi) and the index maps as *arguments*, so the
same code serves two callers:

  * the serial engine (`core.sht.SHT`) via the `UniformPhase`/`BucketPhase`
    classes built by :func:`make_phase` (geometry closed over as numpy
    constants -- free under jit);
  * the distributed transform (`core.dist_sht`), which passes *sharded*
    geometry/index-map operands inside shard_map (every shard runs the
    same bucket structure by construction -- see SHTPlan.local_fft_layout).

Conventions match `core.sht`: delta rows follow ``m_vals`` (entries with
m < 0 are padding and contribute nothing), maps are ``(R, n_phi_max, K)``
real with samples beyond a ring's n_phi zeroed, and analysis output has
the quadrature weights already applied.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import cache as plancache
from repro.core.autodiff import linear_pair
from repro.core.grids import BucketLayout, RingGrid
from repro.tracing import PHASE, scoped

__all__ = [
    "uniform_synth", "uniform_anal", "bucket_synth", "bucket_anal",
    "bucket_bin_maps", "uniform_bin_maps", "phase_factors",
    "PhaseStage", "UniformPhase", "BucketPhase", "make_phase",
]


def _complex_dtype(dtype):
    return jnp.complex128 if jnp.dtype(dtype) == jnp.float64 else jnp.complex64


def phase_factors(m_vals, phi0, sign: float, dtype) -> jnp.ndarray:
    """e^{sign * i * m * phi0(r)} as (M, R) complex; rows with m < 0 are 0.

    ``phi0`` may be a numpy constant (serial path) or a traced shard-local
    operand (dist path).
    """
    m = np.asarray(m_vals)
    msafe = np.maximum(m, 0).astype(np.float64)
    ph = jnp.exp(sign * 1j * msafe[:, None] * jnp.asarray(phi0)[None, :])
    ph = ph.astype(_complex_dtype(dtype))
    if np.any(m < 0):
        ph = jnp.where(jnp.asarray(m >= 0)[:, None], ph, 0.0)
    return ph


# ---------------------------------------------------------------------------
# uniform engine: one batched real FFT over all rings
# ---------------------------------------------------------------------------
#
# Differentiation: both engines carry adjoint-based custom JVP/VJP rules
# (repro.core.autodiff.linear_pair).  The forward maps are real-linear in
# delta/maps; their exact transposes are the opposite-direction phase stage
# with the quadrature weights stripped and a per-m factor
#
#     fac_m = 1 (m == 0) | 2 (m > 0)
#
# compensating the implicit negative-m (conjugate) half of the spectrum:
# the synthesis of each m > 0 row contributes both e^{+im phi} and its
# conjugate, so <synth(delta), t> picks up each positive-m row twice.
# The transposes below are verified against dot-product identities and
# native AD in tests/test_adjoint.py.


def _fac_rows(m_vals, dtype):
    """(M, 1, 1) adjoint compensation factors: 1 for m == 0, else 2
    (padding rows m < 0 are irrelevant -- their phase factors are zero).
    Pure numpy: these are closed over by transpose rules that run in a
    *different* trace than the forward call, so they must not be device
    arrays created under the forward trace (leaked-tracer hazard)."""
    m = np.asarray(m_vals)
    return np.where(m == 0, 1.0, 2.0).astype(
        jnp.dtype(dtype))[:, None, None]


def uniform_bin_maps(m_vals, n):
    """Alias-fold bin maps for the uniform engine, all numpy.

    Returns ``(bins, hi, nyq)``: the rfft half-spectrum bin each m row
    lands in, whether it wraps onto the conjugate half (``hi``: scatter /
    gather the conjugate), and whether it sits on the Nyquist bin (real
    part doubles on synthesis)."""
    m = np.asarray(m_vals)
    b = np.maximum(m, 0) % n
    hi = b > n // 2                                # conjugate wrap
    bins = np.where(hi, n - b, b)
    nyq = 2 * b == n                               # Nyquist: real part doubles
    return bins, hi, nyq


def _uniform_synth_body(d_re, d_im, phi0, scale_rows, m, n, dtype):
    cdt = _complex_dtype(dtype)
    delta = (d_re + 1j * d_im).astype(cdt)
    dp = delta * phase_factors(m, phi0, +1.0, dtype)[..., None]
    bins, hi, nyq = uniform_bin_maps(m, n)
    half = n // 2 + 1
    vals = jnp.where(jnp.asarray(hi)[:, None, None], jnp.conj(dp), dp)
    vals = jnp.where(jnp.asarray(nyq)[:, None, None],
                     2.0 * jnp.real(vals).astype(cdt), vals)
    H = jnp.zeros((half,) + dp.shape[1:], cdt)
    H = H.at[jnp.asarray(bins)].add(vals)
    H = jnp.moveaxis(H, 0, 1)                      # (R, half, K)
    s = (jnp.fft.irfft(H, n=n, axis=1) * n).astype(dtype)
    if scale_rows is not None:
        s = s * scale_rows[:, None, None]
    return s


def _uniform_anal_core(maps, phi0, m, n, dtype):
    """Weight-free analysis core: maps (R, n, K) -> (A_re, A_im), each
    (M, R, K): the e^{-im phi} projection without the quadrature weights."""
    cdt = _complex_dtype(dtype)
    F = jnp.fft.rfft(maps.astype(dtype), axis=1)   # (R, n//2+1, K)
    bins, hi, _ = uniform_bin_maps(m, n)
    Fm = F[:, jnp.asarray(bins), :]                # (R, M, K)
    Fm = jnp.where(jnp.asarray(hi)[None, :, None], jnp.conj(Fm), Fm)
    Fm = jnp.moveaxis(Fm, 1, 0).astype(cdt)        # (M, R, K)
    A = Fm * phase_factors(m, phi0, -1.0, dtype)[..., None]
    return jnp.real(A).astype(dtype), jnp.imag(A).astype(dtype)


@scoped(PHASE)
def uniform_synth(delta, m_vals, n: int, phi0, *, dtype,
                  scale_rows=None) -> jnp.ndarray:
    """Synthesis phase stage on a uniform grid.

    delta: (M, R, K) complex Delta^A rows following ``m_vals`` ->
    maps (R, n, K) real.  Alias-folds every m into the rfft half-spectrum
    (bins past n/2 wrap to the conjugate half; the Nyquist bin doubles its
    real part).  ``scale_rows`` optionally scales rings on the way out
    (the dist path's dummy-ring mask).

    Differentiable both ways: the VJP is ``fac_m`` times the weight-free
    analysis of the map cotangent.
    """
    dt = jnp.dtype(dtype)
    m = np.asarray(m_vals)
    cdt = _complex_dtype(dtype)
    delta = jnp.asarray(delta).astype(cdt)
    fac = _fac_rows(m, dt)

    def fwd(res, ops):
        phi0_, sr = res
        dr, di = ops
        return _uniform_synth_body(dr, di, phi0_, sr, m, n, dtype)

    def bwd(res, t):
        phi0_, sr = res
        if sr is not None:
            t = t * sr[:, None, None]
        a_re, a_im = _uniform_anal_core(t, phi0_, m, n, dtype)
        return (fac * a_re).astype(dt), (fac * a_im).astype(dt)

    return linear_pair(fwd, bwd, (phi0, scale_rows),
                       (jnp.real(delta), jnp.imag(delta)))


@scoped(PHASE)
def uniform_anal(maps, m_vals, n: int, phi0, weights, *, dtype) -> jnp.ndarray:
    """Analysis phase stage on a uniform grid.

    maps: (R, n, K) real -> weighted Delta^S (M, R, K) complex, rows
    following ``m_vals`` (quadrature ``weights`` applied per ring).

    Differentiable both ways: the VJP is the synthesis of the
    ``fac_m``-normalised, weight-scaled Delta cotangent.
    """
    dt = jnp.dtype(dtype)
    cdt = _complex_dtype(dtype)
    m = np.asarray(m_vals)
    maps = jnp.asarray(maps).astype(dt)
    fac = _fac_rows(m, dt)

    def fwd(res, mp):
        (phi0_,) = res
        return _uniform_anal_core(mp, phi0_, m, n, dtype)

    def bwd(res, cts):
        (phi0_,) = res
        g_re, g_im = cts
        return _uniform_synth_body(g_re / fac, g_im / fac, phi0_, None,
                                   m, n, dtype).astype(dt)

    a_re, a_im = linear_pair(fwd, bwd, (phi0,), maps)
    w = jnp.asarray(weights).astype(dt)
    return (a_re + 1j * a_im).astype(cdt) * w[None, :, None]


# ---------------------------------------------------------------------------
# bucket engine: one batched complex FFT per rounded-up ring-length group
# ---------------------------------------------------------------------------


def bucket_bin_maps(m_vals, n_phi, bucket_len):
    """Alias-fold scatter/gather bin maps for the bucket engine.

    Returns ``(pos, neg)`` int32 arrays of shape (M, R): ring r's +m
    contribution lands in bin ``(m mod n_r) * (B_r / n_r)`` of its bucket's
    length-B_r spectrum, the conjugate -m contribution in
    ``((-m) mod n_r) * (B_r / n_r)``.  Pure numpy -- precomputed at plan
    time and cached by plan signature.
    """
    m = np.maximum(np.asarray(m_vals), 0)[:, None]
    n = np.asarray(n_phi)[None, :]
    stride = np.asarray(bucket_len)[None, :] // n  # exact by bucket invariant
    fold = m % n
    pos = fold * stride
    neg = ((n - fold) % n) * stride
    return pos.astype(np.int32), neg.astype(np.int32)


def _bucket_synth_body(d_re, d_im, pos, neg, n_phi, phi0, scale_rows, m,
                       layout, out_width, dtype):
    """Bucket synthesis body.  ``neg`` may be None: the conjugate-half bin
    map is then derived per bucket as ``(B - pos) % B`` (the adjoint path
    of the analysis direction only carries ``pos``)."""
    cdt = _complex_dtype(dtype)
    delta = (d_re + 1j * d_im).astype(cdt)
    dp = delta * phase_factors(m, phi0, +1.0, dtype)[..., None]
    M, R, K = dp.shape
    # m = 0 must not receive its own conjugate (it would double-count);
    # padding rows (m < 0) are already zeroed by the phase factor.
    neg_ok = jnp.asarray(m > 0)[:, None, None]
    nn = jnp.asarray(n_phi)
    out = jnp.zeros((R, out_width, K), dtype)
    for B, sl in zip(layout.lengths, layout.slots):
        sl = np.asarray(sl)
        Rb = sl.shape[0]
        if Rb == 0:
            continue
        dp_b = dp[:, sl, :]                         # (M, Rb, K)
        pos_b = pos[:, sl]
        neg_b = neg[:, sl] if neg is not None else (B - pos_b) % B
        row = np.arange(Rb, dtype=np.int32)[None, :] * B
        S = jnp.zeros((Rb * B, K), cdt)
        S = S.at[jnp.reshape(row + pos_b, (-1,))].add(
            dp_b.reshape(M * Rb, K))
        S = S.at[jnp.reshape(row + neg_b, (-1,))].add(
            jnp.where(neg_ok, jnp.conj(dp_b), 0.0).reshape(M * Rb, K))
        s = jnp.fft.ifft(S.reshape(Rb, B, K), axis=1) * B
        # the length-B inverse FFT repeats each ring's n samples B/n times;
        # keep the first period, zero the padding
        keep = (jnp.arange(B)[None, :] < nn[sl][:, None]).astype(dtype)
        samp = jnp.real(s).astype(dtype) * keep[:, :, None]
        if B < out_width:
            samp = jnp.pad(samp, ((0, 0), (0, out_width - B), (0, 0)))
        out = out.at[jnp.asarray(sl)].set(samp)
    if scale_rows is not None:
        out = out * scale_rows[:, None, None]
    return out


def _bucket_anal_core(maps, pos, n_phi, phi0, m, layout, dtype):
    """Weight-free bucket analysis core: maps (R, W, K) -> (A_re, A_im)."""
    cdt = _complex_dtype(dtype)
    M = m.shape[0]
    R, W, K = maps.shape
    maps = maps.astype(dtype)
    nn = jnp.asarray(n_phi)
    delta = jnp.zeros((M, R, K), cdt)
    for B, sl in zip(layout.lengths, layout.slots):
        sl = np.asarray(sl)
        if sl.shape[0] == 0:
            continue
        x = maps[sl]                                # (Rb, W, K)
        x = x[:, :B, :] if B <= W else \
            jnp.pad(x, ((0, 0), (0, B - W), (0, 0)))
        keep = (jnp.arange(B)[None, :] < nn[sl][:, None]).astype(dtype)
        F = jnp.fft.fft(x * keep[:, :, None], axis=1)          # (Rb, B, K)
        idx = jnp.moveaxis(jnp.asarray(pos[:, sl]), 0, 1)      # (Rb, M)
        Fm = jnp.take_along_axis(F, idx[..., None], axis=1)    # (Rb, M, K)
        delta = delta.at[:, jnp.asarray(sl), :].set(
            jnp.moveaxis(Fm, 1, 0).astype(cdt))
    A = delta * phase_factors(m, phi0, -1.0, dtype)[..., None]
    return jnp.real(A).astype(dtype), jnp.imag(A).astype(dtype)


@scoped(PHASE)
def bucket_synth(delta, layout: BucketLayout, pos, neg, n_phi, phi0, m_vals,
                 *, out_width: int, dtype, scale_rows=None) -> jnp.ndarray:
    """Synthesis phase stage on a ragged grid, one batched FFT per bucket.

    delta: (M, R, K) complex -> maps (R, out_width, K) real, padded with
    zeros beyond each ring's n_phi.  ``pos``/``neg`` are the (M, R) bin
    maps from :func:`bucket_bin_maps`; ``n_phi``/``phi0`` may be traced
    shard-local operands (dist) or numpy constants (serial).

    Differentiable both ways: the VJP is ``fac_m`` times the weight-free
    bucket analysis of the map cotangent (exact under the divisor
    embedding: the folded length-B gather equals the length-n DFT).
    """
    dt = jnp.dtype(dtype)
    cdt = _complex_dtype(dtype)
    m = np.asarray(m_vals)
    delta = jnp.asarray(delta).astype(cdt)
    fac = _fac_rows(m, dt)

    def fwd(res, ops):
        pos_, neg_, nn_, phi0_, sr = res
        dr, di = ops
        return _bucket_synth_body(dr, di, pos_, neg_, nn_, phi0_, sr, m,
                                  layout, out_width, dtype)

    def bwd(res, t):
        pos_, neg_, nn_, phi0_, sr = res
        if sr is not None:
            t = t * sr[:, None, None]
        a_re, a_im = _bucket_anal_core(t, pos_, nn_, phi0_, m, layout, dtype)
        return (fac * a_re).astype(dt), (fac * a_im).astype(dt)

    return linear_pair(fwd, bwd, (pos, neg, n_phi, phi0, scale_rows),
                       (jnp.real(delta), jnp.imag(delta)))


@scoped(PHASE)
def bucket_anal(maps, layout: BucketLayout, pos, n_phi, phi0, weights,
                m_vals, *, dtype) -> jnp.ndarray:
    """Analysis phase stage on a ragged grid, one batched FFT per bucket.

    maps: (R, W, K) real (padded) -> weighted Delta^S (M, R, K) complex.
    Samples at or beyond each ring's n_phi are masked before the FFT, so
    garbage in the padding region cannot alias into the result.

    Differentiable both ways: the VJP is the bucket synthesis of the
    ``fac_m``-normalised, weight-scaled Delta cotangent (the conjugate-half
    bin map is rebuilt as ``(B - pos) % B`` per bucket).
    """
    dt = jnp.dtype(dtype)
    cdt = _complex_dtype(dtype)
    m = np.asarray(m_vals)
    maps = jnp.asarray(maps).astype(dt)
    W = maps.shape[1]
    fac = _fac_rows(m, dt)

    def fwd(res, mp):
        pos_, nn_, phi0_ = res
        return _bucket_anal_core(mp, pos_, nn_, phi0_, m, layout, dtype)

    def bwd(res, cts):
        pos_, nn_, phi0_ = res
        g_re, g_im = cts
        return _bucket_synth_body(g_re / fac, g_im / fac, pos_, None, nn_,
                                  phi0_, None, m, layout, W,
                                  dtype).astype(dt)

    a_re, a_im = linear_pair(fwd, bwd, (pos, n_phi, phi0), maps)
    w = jnp.asarray(weights).astype(dt)
    return (a_re + 1j * a_im).astype(cdt) * w[None, :, None]


# ---------------------------------------------------------------------------
# grid-bound phase-stage objects (the serial engine's integration point)
# ---------------------------------------------------------------------------


class PhaseStage:
    """Common surface of the grid-bound phase engines.

    ``synth``: (M, R, K) complex Delta -> (R, n_phi_max, K) real maps.
    ``anal``:  (R, n_phi_max, K) real maps -> (M, R, K) weighted Delta.
    """

    kind: str = "?"

    def synth(self, delta) -> jnp.ndarray:
        raise NotImplementedError

    def anal(self, maps) -> jnp.ndarray:
        raise NotImplementedError

    @property
    def fft_lengths(self) -> np.ndarray:
        """(R,) per-ring batched FFT length (the cost model's input)."""
        raise NotImplementedError

    def describe(self) -> dict:
        raise NotImplementedError


class UniformPhase(PhaseStage):
    """Batched-rfft phase stage for uniform grids."""

    kind = "uniform"

    def __init__(self, grid: RingGrid, m_vals, dtype):
        assert grid.uniform
        self.n = grid.max_n_phi
        self._phi0 = grid.phi0
        self._weights = grid.weights
        self._m_vals = np.asarray(m_vals)
        self._dtype = dtype
        self._n_rings = grid.n_rings
        assert self.n >= 2 * int(self._m_vals.max()), \
            "uniform FFT stage requires n_phi >= 2*m_max"

    def synth(self, delta) -> jnp.ndarray:
        return uniform_synth(delta, self._m_vals, self.n, self._phi0,
                             dtype=self._dtype)

    def anal(self, maps) -> jnp.ndarray:
        return uniform_anal(maps, self._m_vals, self.n, self._phi0,
                            self._weights, dtype=self._dtype)

    @property
    def fft_lengths(self) -> np.ndarray:
        return np.full(self._n_rings, self.n, dtype=np.int64)

    def describe(self) -> dict:
        return {"kind": self.kind, "n_buckets": 1,
                "bucket_lengths": [self.n], "padded_frac": 0.0}


class BucketPhase(PhaseStage):
    """Ring-bucket phase stage for ragged grids (index maps from the cache)."""

    kind = "bucket"

    def __init__(self, grid: RingGrid, m_vals, dtype, payload: dict):
        self._grid = grid
        self._m_vals = np.asarray(m_vals)
        self._dtype = dtype
        nb = int(payload["n_buckets"])
        self.layout = BucketLayout(
            tuple(int(v) for v in payload["lengths"]),
            tuple(np.asarray(payload[f"slots_{k}"]) for k in range(nb)))
        self._pos = np.asarray(payload["pos"])
        self._neg = np.asarray(payload["neg"])

    def synth(self, delta) -> jnp.ndarray:
        return bucket_synth(delta, self.layout, self._pos, self._neg,
                            self._grid.n_phi, self._grid.phi0, self._m_vals,
                            out_width=self._grid.max_n_phi,
                            dtype=self._dtype)

    def anal(self, maps) -> jnp.ndarray:
        return bucket_anal(maps, self.layout, self._pos, self._grid.n_phi,
                           self._grid.phi0, self._grid.weights, self._m_vals,
                           dtype=self._dtype)

    @property
    def fft_lengths(self) -> np.ndarray:
        return self.layout.fft_lengths

    def describe(self) -> dict:
        return {"kind": self.kind, "n_buckets": self.layout.n_buckets,
                "bucket_lengths": list(self.layout.lengths),
                "padded_frac": self.layout.padded_frac(self._grid.n_phi)}


def make_phase(grid: RingGrid, m_max: int, dtype, *, cache: str = "memory",
               cache_dir: Optional[str] = None,
               max_stretch: Optional[float] = None) -> PhaseStage:
    """Build the phase stage for a grid: uniform engine for uniform grids,
    ring-bucket engine (index maps through the signature-keyed precompute
    cache) for ragged ones."""
    m_vals = np.arange(m_max + 1)
    if grid.uniform:
        return UniformPhase(grid, m_vals, dtype)

    def build() -> dict:
        layout = BucketLayout.from_buckets(grid.fft_buckets(max_stretch))
        pos, neg = bucket_bin_maps(m_vals, grid.n_phi, layout.fft_lengths)
        payload = {
            "n_buckets": np.array(layout.n_buckets),
            "lengths": np.asarray(layout.lengths, dtype=np.int64),
            "pos": pos, "neg": neg,
        }
        for k, sl in enumerate(layout.slots):
            payload[f"slots_{k}"] = np.asarray(sl)
        return payload

    key = plancache.signature_key(
        "phase", grid_nphi=grid.n_phi, grid_phi0=grid.phi0, m_max=m_max,
        max_stretch=max_stretch)
    payload = plancache.get_or_build(key, build, cache=cache,
                                     directory=cache_dir)
    return BucketPhase(grid, m_vals, dtype, payload)
