"""Pure-jnp oracles for the Pallas Legendre kernels.

Bit-matched algorithm (same float32 scaled recurrence, same seed inputs,
same accumulation order up to reassociation) so the interpret-mode kernels
can be checked with tight tolerances; the float64 core engine
(repro.core.legendre) provides the independent ground truth on top.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import legendre as _legendre
from repro.kernels.legendre_pallas import _f32_step, _f32_step_spin

__all__ = ["synth_ref", "anal_ref", "synth_packed_ref", "anal_packed_ref",
           "prepare_seeds", "prepare_seeds_spin"]


def prepare_seeds(m_vals, sin_theta, log_mu_all, scale_bits: int = 64):
    """Scaled P_mm seeds for the f32 kernels, computed on the host in
    float64 (`legendre.pmm_seed_rows`).

    m_vals: (Mp,) int, concrete or traced (may include -1 padding -> inert
    seeds of 0); sin_theta: (R,) f64.  Returns (pmm (Mp, R) f32,
    pms (Mp, R) i32).
    """
    return _legendre.pmm_seed_rows(m_vals, sin_theta, log_mu_all,
                                   dtype=np.float32, scale_bits=scale_bits)


def prepare_seeds_spin(m_vals, mprime_vals, cos_theta, sin_theta,
                       m_max=None, scale_bits: int = 64):
    """Scaled spin-weighted lambda^{(m')} seeds for the f32 kernels.

    m_vals/mprime_vals: (Ms,) int rows (m < 0 padding -> inert 0 seeds);
    cos_theta/sin_theta: (R,) f64.  ``m_max`` must be given when ``m_vals``
    is traced (the distributed path).  Returns (pmm f32, pms i32), (Ms, R).
    """
    return _legendre.spin_seed_rows(m_vals, mprime_vals, cos_theta,
                                    sin_theta, m_max=m_max,
                                    dtype=np.float32, scale_bits=scale_bits)


def _ref_step(spin, l, m_f, mp_f, xb, pp, pc, sc, pmm, pms):
    if spin:
        return _f32_step_spin(l, m_f, mp_f, xb, pp, pc, sc, pmm, pms)
    return _f32_step(l, m_f, xb, pp, pc, sc, pmm, pms)


@functools.partial(jax.jit, static_argnames=("l_max", "fold"))
def synth_ref(a, m_vals, x, pmm, pms, *, l_max: int, fold: bool = False,
              mp_vals=None):
    """Oracle for synth_{vpu,mxu}.

    a: (Mp, L1p, 2K) f32;  x: (R,) f32;  pmm/pms: (Mp, R).
    ``mp_vals`` (Mp,) selects the spin-weighted recurrence per row
    (None -> scalar P_lm).  Returns (Mp, P, R, 2K) f32 (P = 2 if fold).
    """
    Mp, L1p, K2 = a.shape
    R = x.shape[0]
    m = jnp.asarray(m_vals, jnp.int32)[:, None]
    m_f = m.astype(jnp.float32)
    spin = mp_vals is not None
    mp_f = (jnp.asarray(mp_vals, jnp.int32)[:, None].astype(jnp.float32)
            if spin else jnp.zeros_like(m_f))
    xb = jnp.asarray(x, jnp.float32)[None, :]
    n_par = 2 if fold else 1
    carry0 = (jnp.zeros((Mp, R), jnp.float32), jnp.zeros((Mp, R), jnp.float32),
              jnp.zeros((Mp, R), jnp.int32),
              jnp.zeros((Mp, n_par, R, K2), jnp.float32))

    def body(l, carry):
        pp, pc, sc, acc = carry
        pp, pc, sc, val = _ref_step(spin, l, m_f, mp_f, xb, pp, pc, sc,
                                    pmm, pms)
        av = jax.lax.dynamic_index_in_dim(a, l, axis=1, keepdims=False)
        contrib = val[:, :, None] * av[:, None, :]       # (Mp, R, 2K)
        if fold:
            par = ((l + m) % 2)[..., None]               # (Mp, 1, 1)
            upd = jnp.stack([jnp.where(par == 0, contrib, 0.0),
                             jnp.where(par == 1, contrib, 0.0)], axis=1)
            acc = acc + upd
        else:
            acc = acc + contrib[:, None]
        return pp, pc, sc, acc

    _, _, _, acc = jax.lax.fori_loop(0, min(l_max + 1, L1p), body, carry0)
    return acc


@functools.partial(jax.jit, static_argnames=("l_max", "l1p", "fold"))
def anal_ref(dw, m_vals, x, pmm, pms, *, l_max: int, l1p: int,
             fold: bool = False, mp_vals=None):
    """Oracle for anal_{vpu,mxu}.

    dw: (Mp, P, R, 2K) f32 weighted Delta;  returns (Mp, L1p, 2K) f32.
    """
    Mp, n_par, R, K2 = dw.shape
    m = jnp.asarray(m_vals, jnp.int32)[:, None]
    m_f = m.astype(jnp.float32)
    spin = mp_vals is not None
    mp_f = (jnp.asarray(mp_vals, jnp.int32)[:, None].astype(jnp.float32)
            if spin else jnp.zeros_like(m_f))
    xb = jnp.asarray(x, jnp.float32)[None, :]
    carry0 = (jnp.zeros((Mp, R), jnp.float32), jnp.zeros((Mp, R), jnp.float32),
              jnp.zeros((Mp, R), jnp.int32))

    def step(carry, l):
        pp, pc, sc = carry
        pp, pc, sc, val = _ref_step(spin, l, m_f, mp_f, xb, pp, pc, sc,
                                    pmm, pms)
        if fold:
            par = ((l + m) % 2)[..., None]               # (Mp, 1, 1)
            d = jnp.where(par == 0, dw[:, 0], dw[:, 1])
        else:
            d = dw[:, 0]
        row = jnp.einsum("mr,mrk->mk", val, d,
                         precision=jax.lax.Precision.HIGHEST)
        return (pp, pc, sc), row

    _, rows = jax.lax.scan(step, carry0, jnp.arange(l1p))
    out = jnp.swapaxes(rows, 0, 1)                        # (Mp, L1p, 2K)
    lmask = (jnp.arange(l1p) <= l_max)[None, :, None]
    return jnp.where(lmask, out, 0.0)


# ---------------------------------------------------------------------------
# Packed (triangular m-pair) schedule oracles -- bit-matched to the packed
# kernels: same per-step (segment, m, m', l) selection, same seed-at-seam
# behaviour, same accumulation order.  See kernels.pack for the layout.
# ---------------------------------------------------------------------------


def _packed_maps_ref(layout):
    m0 = jnp.asarray(layout.slot_m[:, 0], jnp.int32)[:, None]
    m1 = jnp.asarray(layout.slot_m[:, 1], jnp.int32)[:, None]
    mp0 = jnp.asarray(layout.slot_mp[:, 0], jnp.int32)[:, None]
    mp1 = jnp.asarray(layout.slot_mp[:, 1], jnp.int32)[:, None]
    seed = jnp.asarray(layout.slot_seed, jnp.int32)[:, None]
    return m0, m1, mp0, mp1, seed


def _packed_step_ref(g, layout_maps, spin, x, pmm_pk, pms_pk, pp, pc, sc):
    """One packed-schedule step at intra-slot index ``g`` for every slot."""
    m0, m1, mp0, mp1, seed = layout_maps
    hi = (g >= seed).astype(jnp.int32)                 # (n_slots, 1)
    m = jnp.where(hi == 1, m1, m0)
    mp_v = jnp.where(hi == 1, mp1, mp0)
    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))
    l = jnp.where(hi == 1, l01 + g - seed, l00 + g)
    pmm = jnp.where(hi == 1, pmm_pk[:, 1], pmm_pk[:, 0])
    pms = jnp.where(hi == 1, pms_pk[:, 1], pms_pk[:, 0])
    pp, pc, sc, val = _ref_step(spin, l, m.astype(jnp.float32),
                                mp_v.astype(jnp.float32), x[None, :],
                                pp, pc, sc, pmm, pms)
    return pp, pc, sc, val, hi, m, l


def synth_packed_ref(a_pk, layout, x, pmm_pk, pms_pk, *, fold: bool = False):
    """Oracle for synth_{vpu,mxu}_packed.

    a_pk: (n_slots, S, 2K) f32;  x: (R,) f32;  pmm_pk/pms_pk: (n_slots, 2, R).
    Returns (n_slots, Q, R, 2K) f32 with Q = 2 segments x (2 if fold).
    """
    n_slots, S, K2 = a_pk.shape
    R = x.shape[0]
    spin = layout.spin
    n_par = 2 if fold else 1
    n_q = 2 * n_par
    maps = _packed_maps_ref(layout)
    x32 = jnp.asarray(x, jnp.float32)
    carry0 = (jnp.zeros((n_slots, R), jnp.float32),
              jnp.zeros((n_slots, R), jnp.float32),
              jnp.zeros((n_slots, R), jnp.int32),
              jnp.zeros((n_slots, n_q, R, K2), jnp.float32))

    def body(g, carry):
        pp, pc, sc, acc = carry
        pp, pc, sc, val, hi, m, l = _packed_step_ref(
            g, maps, spin, x32, pmm_pk, pms_pk, pp, pc, sc)
        av = jax.lax.dynamic_index_in_dim(a_pk, g, axis=1, keepdims=False)
        contrib = val[:, :, None] * av[:, None, :]     # (n_slots, R, 2K)
        q = hi * n_par + ((l + m) % 2 if fold else 0)  # (n_slots, 1)
        sel = jnp.arange(n_q, dtype=jnp.int32)[None, :] == q
        acc = acc + jnp.where(sel[:, :, None, None], contrib[:, None], 0.0)
        return pp, pc, sc, acc

    _, _, _, acc = jax.lax.fori_loop(0, S, body, carry0)
    return acc


def anal_packed_ref(dw_pk, layout, x, pmm_pk, pms_pk, *, fold: bool = False):
    """Oracle for anal_{vpu,mxu}_packed.

    dw_pk: (n_slots, Q, R, 2K) f32 weighted Delta per fused component.
    Returns (n_slots, S, 2K) f32 packed l-stream rows.
    """
    n_slots, n_q, R, K2 = dw_pk.shape
    spin = layout.spin
    n_par = 2 if fold else 1
    assert n_q == 2 * n_par
    maps = _packed_maps_ref(layout)
    x32 = jnp.asarray(x, jnp.float32)
    carry0 = (jnp.zeros((n_slots, R), jnp.float32),
              jnp.zeros((n_slots, R), jnp.float32),
              jnp.zeros((n_slots, R), jnp.int32))

    def step(carry, g):
        pp, pc, sc = carry
        pp, pc, sc, val, hi, m, l = _packed_step_ref(
            g, maps, spin, x32, pmm_pk, pms_pk, pp, pc, sc)
        # positions past the real stream (l > l_max) are padding the host
        # unpack discards; the vpu kernel stops its loops there, so the
        # oracle zeroes them to stay bit-matched
        val = jnp.where(l <= layout.l_max, val, 0.0)
        q = hi * n_par + ((l + m) % 2 if fold else 0)  # (n_slots, 1)
        d = jnp.take_along_axis(dw_pk, q[:, :, None, None], axis=1)[:, 0]
        row = jnp.einsum("sr,srk->sk", val, d,
                         precision=jax.lax.Precision.HIGHEST)
        return (pp, pc, sc), row

    _, rows = jax.lax.scan(step, carry0, jnp.arange(layout.S))
    return jnp.swapaxes(rows, 0, 1)                    # (n_slots, S, 2K)
