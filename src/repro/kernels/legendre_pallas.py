"""Pallas TPU kernels for the Legendre-recurrence hot spot (paper §4.2.2).

The paper's GPU algorithm assigns one *ring* per CUDA thread so every thread
executes the identical l-recurrence (SIMD-uniform), and recomputes beta_lm
instead of storing it.  The TPU translation (DESIGN.md §2):

  * rings live on the VPU lane/sublane dimensions (8x128 vectors instead of
    threads);
  * the l loop is the sequential inner `fori_loop`, with the (mantissa,
    scale) pair of the rescaled recurrence carried in VMEM scratch across
    l-panel grid steps;
  * beta is recomputed from l, m on the fly (2 mults + 1 rsqrt per step) --
    never materialised in HBM;
  * m and ring-blocks form the (sequential) Pallas grid; panels fully below
    the diagonal (l < m) are skipped, preserving the triangular work count;
  * the direct-transform (analysis) reduction that costs the paper its GPU
    performance (atomics / host-side reduction, Algorithm 5) is here an
    accumulation into the output block across sequential grid steps --
    race-free by construction because the TPU grid is sequential per core.

Two variants per direction:

  * ``vpu``  -- broadcast-FMA accumulation; the faithful analogue of the
    paper's scalar-per-thread inner loop.  Right for small K (few maps).
  * ``mxu``  -- P panels are materialised in VMEM (l on the sublane axis)
    and contracted against a (l, 2K) coefficient panel on the MXU.  This is
    the beyond-paper optimisation: the paper's Monte-Carlo workload
    transforms many maps with identical geometry, which becomes a matmul.

Inputs are pre-scaled seeds (pmm mantissa + scale) computed host-side in
float64; everything inside the kernels is float32.

All kernels are validated in interpret mode against repro.kernels.ref
(bit-matched algorithm) and against the float64 core engine in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "synth_vpu", "synth_mxu", "anal_vpu", "anal_mxu",
    "synth_vpu_packed", "synth_mxu_packed",
    "anal_vpu_packed", "anal_mxu_packed",
    "SCALE_BITS_F32",
]

SCALE_BITS_F32 = 64
_BIG = float(2.0 ** (SCALE_BITS_F32 // 2))        # 2^32
_INV_BIG2 = float(2.0 ** (-SCALE_BITS_F32))       # 2^-64
_BIG2 = float(2.0 ** SCALE_BITS_F32)              # 2^64

#: Contraction precision of the kernels' float32 dots.  Mosaic's default
#: contracts float32 operands in bfloat16 passes; the transform is a
#: float32 computation, so its dots ask for full float32.
F32_DOT = jax.lax.Precision.HIGHEST


def _pad_rows(blk, rf):
    """Zero-pad a ring-shrunk operand block back to the full (..., 8, 128)
    VPU tile.  Interpret-mode input-block fetches are slow per byte, so
    operands whose rings fit one row block ship only their ``rf`` real
    128-lane rows; the padding rows (pure ring padding, zero by
    construction) are rebuilt here as cheap vector zeros."""
    if rf == 8:
        return blk
    pad = blk.shape[:-2] + (8 - rf, 128)
    return jnp.concatenate([blk, jnp.zeros(pad, blk.dtype)], axis=-2)


def _ring_rows(arr):
    """(..., R1, 128) -> (..., R1, 1, 128) for the MXU kernels, which step
    one 128-ring row block per grid step.  Mosaic requires a block's last
    two dims to be multiples of (8, 128) or the array's own; a (1, 128)
    block of an (R1, 128) array is neither once R1 > 1.  With the row axis
    moved out of the minor pair, the block is (squeezed, 1, 128) and the
    kernels still see (1, 128) rows; XLA lays the unit dim out unpadded."""
    return arr.reshape(arr.shape[:-1] + (1, 128))


def _f32_step(l, m_f, x, pp, pc, sc, pmm, pms):
    """One scaled-recurrence step, float32, branch-free.

    l: traced scalar (current multipole); m_f: scalar f32 (this grid step's
    m); x, pp, pc, pmm: f32 tiles; sc, pms: i32 tiles.
    Returns (pp', pc', sc', value) with `value` the descaled P_{l,m}.
    """
    lf = l.astype(jnp.float32) if hasattr(l, "astype") else jnp.float32(l)
    # beta recomputed on the fly (paper's GPU choice): guard l<=m+1 lanes.
    lb = jnp.maximum(lf, m_f + 2.0)
    bl = jax.lax.rsqrt((lb * lb - m_f * m_f) / (4.0 * lb * lb - 1.0))
    lb1 = jnp.maximum(lf - 1.0, m_f + 1.0)
    bl1 = jax.lax.rsqrt((lb1 * lb1 - m_f * m_f) / (4.0 * lb1 * lb1 - 1.0))
    ratio = bl / bl1
    p_rec = bl * x * pc - ratio * pp
    p_first = jnp.sqrt(jnp.maximum(2.0 * m_f + 3.0, 0.0)) * x * pc

    is_seed = lf == m_f
    is_first = lf == m_f + 1.0
    before = lf < m_f
    new_c = jnp.where(before, 0.0,
            jnp.where(is_seed, pmm,
            jnp.where(is_first, p_first, p_rec)))
    new_p = jnp.where(before | is_seed, 0.0, pc)
    new_s = jnp.where(is_seed, pms, sc)

    grow = (jnp.abs(new_c) > _BIG) & (new_s < 0)
    new_c = jnp.where(grow, new_c * _INV_BIG2, new_c)
    new_p = jnp.where(grow, new_p * _INV_BIG2, new_p)
    new_s = jnp.where(grow, new_s + 1, new_s)
    shrink = (jnp.abs(new_c) < 1.0 / _BIG) & (jnp.abs(new_p) < 1.0 / _BIG) \
        & ~before & ~is_seed
    new_c2 = jnp.where(shrink, new_c * _BIG2, new_c)
    new_p2 = jnp.where(shrink, new_p * _BIG2, new_p)
    new_s2 = jnp.where(shrink, new_s - 1, new_s)

    value = jnp.where((new_s2 == 0) & ~before, new_c2, 0.0)
    return new_p2, new_c2, new_s2, value


def _f32_step_spin(l, m_f, mp_f, x, pp, pc, sc, pmm, pms):
    """One step of the generalised (Wigner-d) scaled recurrence, float32.

    The spin-weighted lambda^{(m')} functions satisfy
    lam_l = (a_l x + b_l) lam_{l-1} - c_l lam_{l-2} seeded at
    l0 = max(m, |m'|) (see core/legendre.py); coefficients are recomputed
    on the fly like the scalar beta.  ``mp_f`` is this row's m' (scalar
    f32); everything else as in `_f32_step`.
    """
    lf = l.astype(jnp.float32) if hasattr(l, "astype") else jnp.float32(l)
    l0 = jnp.maximum(m_f, jnp.abs(mp_f))
    ls = jnp.maximum(lf, l0 + 1.0)
    d2 = jnp.maximum((ls * ls - m_f * m_f) * (ls * ls - mp_f * mp_f), 1e-30)
    lm1 = ls - 1.0
    d2m1 = jnp.maximum((lm1 * lm1 - m_f * m_f) * (lm1 * lm1 - mp_f * mp_f),
                       0.0)
    s2l = jnp.sqrt(4.0 * ls * ls - 1.0)
    inv_d = jax.lax.rsqrt(d2)
    inv_lm1 = 1.0 / jnp.maximum(lm1, 1.0)
    a = ls * s2l * inv_d
    b = -(m_f * mp_f) * s2l * inv_d * inv_lm1
    c = (jnp.sqrt((2.0 * ls + 1.0) / jnp.maximum(2.0 * ls - 3.0, 1.0))
         * ls * jnp.sqrt(d2m1) * inv_d * inv_lm1)

    p_rec = (a * x + b) * pc - c * pp
    is_seed = lf == l0
    before = lf < l0
    new_c = jnp.where(before, 0.0, jnp.where(is_seed, pmm, p_rec))
    new_p = jnp.where(before | is_seed, 0.0, pc)
    new_s = jnp.where(is_seed, pms, sc)

    grow = (jnp.abs(new_c) > _BIG) & (new_s < 0)
    new_c = jnp.where(grow, new_c * _INV_BIG2, new_c)
    new_p = jnp.where(grow, new_p * _INV_BIG2, new_p)
    new_s = jnp.where(grow, new_s + 1, new_s)
    shrink = (jnp.abs(new_c) < 1.0 / _BIG) & (jnp.abs(new_p) < 1.0 / _BIG) \
        & ~before & ~is_seed
    new_c2 = jnp.where(shrink, new_c * _BIG2, new_c)
    new_p2 = jnp.where(shrink, new_p * _BIG2, new_p)
    new_s2 = jnp.where(shrink, new_s - 1, new_s)

    value = jnp.where((new_s2 == 0) & ~before, new_c2, 0.0)
    return new_p2, new_c2, new_s2, value


def _step(spin, l, m_f, mp_f, x, pp, pc, sc, pmm, pms):
    """Static dispatch between the scalar and spin recurrence steps."""
    if spin:
        return _f32_step_spin(l, m_f, mp_f, x, pp, pc, sc, pmm, pms)
    return _f32_step(l, m_f, x, pp, pc, sc, pmm, pms)


# =============================================================================
# Synthesis (inverse transform stage 1): Delta_m(r) = sum_l a_lm P_lm(r)
# =============================================================================


def _synth_vpu_kernel(m_vals_ref, mp_vals_ref, x_ref, pmm_ref, pms_ref,
                      a_ref, out_ref, pp_ref, pc_ref, sc_ref, *, lp_size,
                      n_k2, fold, spin):
    mi = pl.program_id(0)
    lp = pl.program_id(2)
    m = m_vals_ref[mi]
    m_f = m.astype(jnp.float32)
    mp_f = mp_vals_ref[mi].astype(jnp.float32)
    l0 = lp * lp_size

    @pl.when(lp == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    @pl.when(l0 + lp_size > m)   # skip panels fully below the diagonal
    def _work():
        x = x_ref[...]                       # (8, 128)
        pmm = pmm_ref[0]                     # (8, 128)
        pms = pms_ref[0]
        acc = out_ref[0]                     # (P?, 2K, 8, 128) P=1|2 (fold)

        def body(j, carry):
            acc, pp, pc, sc = carry
            l = l0 + j
            pp, pc, sc, val = _step(spin, l, m_f, mp_f, x, pp, pc, sc,
                                    pmm, pms)
            av = a_ref[0, j, :]              # (2K,)
            contrib = av[:, None, None] * val[None, :, :]   # (2K, 8, 128)
            if fold:
                par = (l + m) % 2            # 0 even, 1 odd
                sel = (jnp.arange(2, dtype=jnp.int32) == par)
                acc = acc + jnp.where(sel[:, None, None, None],
                                      contrib[None], 0.0)
            else:
                acc = acc + contrib[None]
            return acc, pp, pc, sc

        acc, pp, pc, sc = jax.lax.fori_loop(
            0, lp_size, body,
            (acc, pp_ref[...], pc_ref[...], sc_ref[...]))
        out_ref[0] = acc
        pp_ref[...] = pp
        pc_ref[...] = pc
        sc_ref[...] = sc


def synth_vpu(a, m_vals, x2d, pmm, pms, *, l_max, fold=False, mp_vals=None,
              lp_size=128, interpret=True):
    """VPU synthesis kernel.

    a      : (Mp, L1p, 2K) f32, L1p a multiple of lp_size, rows l<m zero
    m_vals : (Mp,) i32 (plan m per slot; -1 padding rows never seed)
    mp_vals: (Mp,) i32 Wigner m' per row (None -> scalar P_lm path)
    x2d    : (R1, 128) f32 cos(theta), R1 a multiple of 8
    pmm    : (Mp, R1, 128) f32 seed mantissas;  pms likewise i32 scales
    returns: (Mp, P, 2K, R1, 128) f32 with P = 2 (even, odd) if fold else 1
    """
    Mp, L1p, K2 = a.shape
    R1 = x2d.shape[0]
    assert L1p % lp_size == 0 and R1 % 8 == 0
    spin = mp_vals is not None
    assert not (spin and fold), "fold is not supported on the spin path"
    mp = jnp.zeros(Mp, jnp.int32) if mp_vals is None \
        else jnp.asarray(mp_vals, jnp.int32)
    n_par = 2 if fold else 1
    grid = (Mp, R1 // 8, L1p // lp_size)
    kernel = functools.partial(_synth_vpu_kernel, lp_size=lp_size,
                               n_k2=K2, fold=fold, spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((8, 128), lambda m, rb, lp, *_refs: (rb, 0)),
                pl.BlockSpec((1, 8, 128), lambda m, rb, lp, *_refs: (m, rb, 0)),
                pl.BlockSpec((1, 8, 128), lambda m, rb, lp, *_refs: (m, rb, 0)),
                pl.BlockSpec((1, lp_size, K2), lambda m, rb, lp, *_refs: (m, lp, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_par, K2, 8, 128),
                                   lambda m, rb, lp, *_refs: (m, 0, 0, rb, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, n_par, K2, R1, 128), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(m_vals, mp, x2d, pmm, pms, a)


def _synth_mxu_kernel(m_vals_ref, mp_vals_ref, x_ref, pmm_ref, pms_ref,
                      a_ref, out_ref, pp_ref, pc_ref, sc_ref, panel_ref, *,
                      lp_size, fold, spin):
    mi = pl.program_id(0)
    lp = pl.program_id(2)
    m = m_vals_ref[mi]
    m_f = m.astype(jnp.float32)
    mp_f = mp_vals_ref[mi].astype(jnp.float32)
    l0 = lp * lp_size

    @pl.when(lp == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    @pl.when(l0 + lp_size > m)
    def _work():
        x = x_ref[...]                        # (1, 128)
        pmm = pmm_ref[0]                      # (1, 128)
        pms = pms_ref[0]

        def gen(j, carry):
            pp, pc, sc = carry
            pp, pc, sc, val = _step(spin, l0 + j, m_f, mp_f, x, pp, pc, sc,
                                    pmm, pms)
            panel_ref[pl.ds(j, 1), :] = val   # P panel row (l on sublanes)
            return pp, pc, sc

        pp, pc, sc = jax.lax.fori_loop(
            0, lp_size, gen, (pp_ref[...], pc_ref[...], sc_ref[...]))
        pp_ref[...] = pp
        pc_ref[...] = pc
        sc_ref[...] = sc

        panel = panel_ref[...]                # (LP, 128)
        a_blk = a_ref[0]                      # (2K, LP)
        dims = (((1,), (0,)), ((), ()))       # contract over l
        if fold:
            ls = l0 + jax.lax.broadcasted_iota(jnp.int32, (1, lp_size), 1)
            even = ((ls + m) % 2) == 0
            a_e = jnp.where(even, a_blk, 0.0)
            a_o = jnp.where(even, 0.0, a_blk)
            ce = jax.lax.dot_general(a_e, panel, dims,
                                     precision=F32_DOT,
                                     preferred_element_type=jnp.float32)
            co = jax.lax.dot_general(a_o, panel, dims,
                                     precision=F32_DOT,
                                     preferred_element_type=jnp.float32)
            out_ref[0, 0] += ce               # (2K, 128)
            out_ref[0, 1] += co
        else:
            c = jax.lax.dot_general(a_blk, panel, dims,
                                    precision=F32_DOT,
                                    preferred_element_type=jnp.float32)
            out_ref[0, 0] += c


def synth_mxu(a, m_vals, x2d, pmm, pms, *, l_max, fold=False, mp_vals=None,
              lp_size=128, interpret=True):
    """MXU synthesis kernel (multi-map panel matmul).

    a: (Mp, 2K, L1p) f32 (the l axis minor: lane-dense for any K); rings
    advance 128 at a time; returns (Mp, P, 2K, R) with R = R1 * 128.
    """
    Mp, K2, L1p = a.shape
    R1 = x2d.shape[0]
    R = R1 * 128
    assert L1p % lp_size == 0
    spin = mp_vals is not None
    assert not (spin and fold), "fold is not supported on the spin path"
    mp = jnp.zeros(Mp, jnp.int32) if mp_vals is None \
        else jnp.asarray(mp_vals, jnp.int32)
    n_par = 2 if fold else 1
    grid = (Mp, R1, L1p // lp_size)
    x_flat = _ring_rows(x2d.reshape(R1, 128))
    pmm_f = _ring_rows(pmm.reshape(Mp, R1, 128))
    pms_f = _ring_rows(pms.reshape(Mp, R1, 128))
    kernel = functools.partial(_synth_mxu_kernel, lp_size=lp_size, fold=fold,
                               spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, 128),
                             lambda m, rb, lp, *_refs: (rb, 0, 0)),
                pl.BlockSpec((1, None, 1, 128),
                             lambda m, rb, lp, *_refs: (m, rb, 0, 0)),
                pl.BlockSpec((1, None, 1, 128),
                             lambda m, rb, lp, *_refs: (m, rb, 0, 0)),
                pl.BlockSpec((1, K2, lp_size),
                             lambda m, rb, lp, *_refs: (m, 0, lp)),
            ],
            out_specs=pl.BlockSpec((1, n_par, K2, 128),
                                   lambda m, rb, lp, *_refs: (m, 0, 0, rb)),
            scratch_shapes=[
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.int32),
                pltpu.VMEM((lp_size, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, n_par, K2, R), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(m_vals, mp, x_flat, pmm_f, pms_f, a)


# =============================================================================
# Analysis (direct transform stage): a_lm = sum_r Delta_m(r) P_lm(r)
# =============================================================================


def _anal_vpu_kernel(m_vals_ref, mp_vals_ref, x_ref, pmm_ref, pms_ref,
                     dw_ref, out_ref, pp_ref, pc_ref, sc_ref, acc_ref, *,
                     lp_size, fold, spin):
    """Analysis VPU kernel.  A separate VMEM accumulator (acc_ref) holds the
    current panel's rows; it is added into out_ref at the end of the grid
    step so the out block accumulates across ring blocks (@rb==0 init)."""
    mi = pl.program_id(0)
    rb = pl.program_id(1)
    lp = pl.program_id(2)
    m = m_vals_ref[mi]
    m_f = m.astype(jnp.float32)
    mp_f = mp_vals_ref[mi].astype(jnp.float32)
    l0 = lp * lp_size

    @pl.when(lp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    @pl.when(rb == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l0 + lp_size > m)
    def _work():
        x = x_ref[...]
        pmm = pmm_ref[0]
        pms = pms_ref[0]
        dw = dw_ref[0]                          # (P, 2K, 8, 128)
        acc_ref[...] = jnp.zeros_like(acc_ref)  # (LP, 2K)

        def body(j, carry):
            pp, pc, sc = carry
            l = l0 + j
            pp, pc, sc, val = _step(spin, l, m_f, mp_f, x, pp, pc, sc,
                                    pmm, pms)
            if fold:
                par = (l + m) % 2
                sel = (jnp.arange(2, dtype=jnp.int32) == par)
                d = jnp.sum(jnp.where(sel[:, None, None, None], dw, 0.0),
                            axis=0)
            else:
                d = dw[0]
            row = jnp.sum(d * val[None, :, :], axis=(1, 2))   # (2K,)
            acc_ref[pl.ds(j, 1), :] = row[None, :]
            return pp, pc, sc

        pp, pc, sc = jax.lax.fori_loop(
            0, lp_size, body, (pp_ref[...], pc_ref[...], sc_ref[...]))
        out_ref[0] += acc_ref[...]
        pp_ref[...] = pp
        pc_ref[...] = pc
        sc_ref[...] = sc


def anal_vpu(dw, m_vals, x2d, pmm, pms, *, l_max, l1p, fold=False,
             mp_vals=None, lp_size=128, interpret=True):
    """VPU analysis kernel.

    dw     : (Mp, P, 2K, R1, 128) weighted Delta (P = 2 (e,o) if fold else 1)
    returns: (Mp, L1p, 2K) f32
    """
    Mp, n_par, K2 = dw.shape[0], dw.shape[1], dw.shape[2]
    R1 = dw.shape[3]
    assert l1p % lp_size == 0 and R1 % 8 == 0
    spin = mp_vals is not None
    assert not (spin and fold), "fold is not supported on the spin path"
    mp = jnp.zeros(Mp, jnp.int32) if mp_vals is None \
        else jnp.asarray(mp_vals, jnp.int32)
    grid = (Mp, R1 // 8, l1p // lp_size)
    kernel = functools.partial(_anal_vpu_kernel, lp_size=lp_size,
                               fold=fold, spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((8, 128), lambda m, rb, lp, *_refs: (rb, 0)),
                pl.BlockSpec((1, 8, 128), lambda m, rb, lp, *_refs: (m, rb, 0)),
                pl.BlockSpec((1, 8, 128), lambda m, rb, lp, *_refs: (m, rb, 0)),
                pl.BlockSpec((1, n_par, K2, 8, 128),
                             lambda m, rb, lp, *_refs: (m, 0, 0, rb, 0)),
            ],
            out_specs=pl.BlockSpec((1, lp_size, K2),
                                   lambda m, rb, lp, *_refs: (m, lp, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
                pltpu.VMEM((lp_size, K2), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, l1p, K2), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(m_vals, mp, x2d, pmm, pms, dw)


# =============================================================================
# Packed (triangular m-pair) kernels.
#
# The plain kernels above launch a dense rectangular (Mp, L1p/lp_size)
# grid and mask sub-diagonal panels with `pl.when` -- ~2x wasted grid
# steps at m_max = l_max.  The packed kernels run the min-max paired grid
# built by `kernels.pack.build_layout`: each *slot* fuses two m rows whose
# concatenated l-ranges have near-constant total length, streamed
# back-to-back through (n_sp) full panels with NO `pl.when` diagonal test.
# Five per-slot scalar-prefetch maps (m/m' per segment + the intra-slot
# seam step `seed`) tell every grid step which (m, l) window it serves;
# the (pp, pc, sc) carry re-seeds itself at the seam because the step
# functions seed whenever l == l0, and the packed schedule lands the
# seam step exactly there.
#
# The slot grid dimension is marked "parallel": slots touch disjoint
# output blocks and their carry chains are self-contained (re-initialised
# at panel 0), so Mosaic may partition slots across TensorCores.
# =============================================================================


def _packed_row_masks(base, jsw, m0, m1, mp0, mp1, lp_size, n_par, fold):
    """Per-stream-position (1, lp_size) bool masks selecting each fused
    output component q = segment * n_par + parity (the MXU kernels' l
    splits; the l stream is the lane axis of their operands)."""
    iot = jax.lax.broadcasted_iota(jnp.int32, (1, lp_size), 1)
    g_row = base + iot
    hi_row = g_row >= jsw
    masks = []
    for q in range(2 * n_par):
        seg = q // n_par
        mask = hi_row if seg == 1 else ~hi_row
        if fold:
            l00 = jnp.maximum(m0, jnp.abs(mp0))
            l01 = jnp.maximum(m1, jnp.abs(mp1))
            l_row = jnp.where(hi_row, l01 + g_row - jsw, l00 + g_row)
            m_row = jnp.where(hi_row, m1, m0)
            even = ((l_row + m_row) % 2) == 0
            mask = mask & (even if q % n_par == 0 else ~even)
        masks.append(mask)
    return masks


def _synth_vpu_packed_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                             x_ref, pmm_ref, pms_ref, a_ref, out_ref,
                             pp_ref, pc_ref, sc_ref, *, lp_size, n_par,
                             fold, spin):
    si = pl.program_id(0)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size

    @pl.when(sp == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    x = x_ref[...]                           # (8, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))
    # Split the panel at the intra-slot seam: steps below j0 serve segment
    # 0, steps at/after j0 serve segment 1.  Each half runs a select-free
    # body (constant m / seed operands, static output slot) instead of the
    # per-step `where` chains over both fused rows -- those selects were
    # eating the packed grid-step win on analysis.  The (pp, pc, sc) carry
    # still re-seeds itself at the seam because segment 1's first step
    # lands exactly on l == l01 (duplicate slots have jsw == S, so their
    # segment-1 loop is empty).
    j0 = jnp.clip(jsw - base, 0, lp_size)

    def seg_body(seg, m, mp_v, l_base, pmm, pms):
        m_f = m.astype(jnp.float32)
        mp_f = mp_v.astype(jnp.float32)
        lo = seg * n_par

        def body(j, carry):
            acc, pp, pc, sc = carry
            l = l_base + j
            pp, pc, sc, val = _step(spin, l, m_f, mp_f, x, pp, pc, sc,
                                    pmm, pms)
            av = a_ref[0, j, :]              # (2K,)
            contrib = av[:, None, None] * val[None, :, :]   # (2K, 8, 128)
            if fold:
                par = (l + m) % 2
                sel = (jnp.arange(n_par, dtype=jnp.int32) == par)
                upd = jnp.where(sel[:, None, None, None], contrib[None], 0.0)
            else:
                upd = contrib[None]
            acc = acc.at[lo:lo + n_par].add(upd)
            return acc, pp, pc, sc

        return body

    carry = (out_ref[0], pp_ref[...], pc_ref[...], sc_ref[...])
    carry = jax.lax.fori_loop(
        0, j0, seg_body(0, m0, mp0, l00 + base, pmm0, pms0), carry)
    acc, pp, pc, sc = jax.lax.fori_loop(
        j0, lp_size, seg_body(1, m1, mp1, l01 + base - jsw, pmm1, pms1),
        carry)
    out_ref[0] = acc
    pp_ref[...] = pp
    pc_ref[...] = pc
    sc_ref[...] = sc


def synth_vpu_packed(a_pk, maps, x2d, pmm_pk, pms_pk, *, l_max, fold=False,
                     spin=False, lp_size=128, interpret=True):
    """VPU synthesis on the packed (slot, panel) grid.

    a_pk   : (n_slots, S, 2K) f32 packed coefficient streams
    maps   : (m0, m1, mp0, mp1, seed) i32 per-slot scalar-prefetch arrays
    x2d    : (R1, 128) f32;  pmm_pk/pms_pk: (n_slots, 2, R1, 128)
    returns: (n_slots, Q, 2K, R1, 128) f32, Q = 2 segments x (2 if fold)
    """
    n_slots, S, K2 = a_pk.shape
    R1 = x2d.shape[0]
    assert S % lp_size == 0 and R1 % 8 == 0
    n_par = 2 if fold else 1
    assert not (spin and fold), "fold is not supported on the spin path"
    n_q = 2 * n_par
    grid = (n_slots, R1 // 8, S // lp_size)
    kernel = functools.partial(_synth_vpu_packed_kernel, lp_size=lp_size,
                               n_par=n_par, fold=fold, spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((8, 128), lambda s, rb, sp, *_refs: (rb, 0)),
                pl.BlockSpec((1, 2, 8, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0)),
                pl.BlockSpec((1, 2, 8, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0)),
                pl.BlockSpec((1, lp_size, K2),
                             lambda s, rb, sp, *_refs: (s, sp, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_q, K2, 8, 128),
                                   lambda s, rb, sp, *_refs: (s, 0, 0, rb, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, n_q, K2, R1, 128),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*maps, x2d, pmm_pk, pms_pk, a_pk)


def _synth_mxu_packed_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                             x_ref, pmm_ref, pms_ref, a_ref, out_ref,
                             pp_ref, pc_ref, sc_ref, panel_ref, *, lp_size,
                             n_par, fold, spin):
    si = pl.program_id(0)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size

    @pl.when(sp == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    x = x_ref[...]                           # (1, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))
    j0 = jnp.clip(jsw - base, 0, lp_size)    # seam split (see VPU kernel)

    def seg_gen(m, mp_v, l_base, pmm, pms):
        m_f = m.astype(jnp.float32)
        mp_f = mp_v.astype(jnp.float32)

        def gen(j, carry):
            pp, pc, sc = carry
            pp, pc, sc, val = _step(spin, l_base + j, m_f, mp_f, x,
                                    pp, pc, sc, pmm, pms)
            panel_ref[pl.ds(j, 1), :] = val
            return pp, pc, sc

        return gen

    carry = (pp_ref[...], pc_ref[...], sc_ref[...])
    carry = jax.lax.fori_loop(
        0, j0, seg_gen(m0, mp0, l00 + base, pmm0, pms0), carry)
    pp, pc, sc = jax.lax.fori_loop(
        j0, lp_size, seg_gen(m1, mp1, l01 + base - jsw, pmm1, pms1), carry)
    pp_ref[...] = pp
    pc_ref[...] = pc
    sc_ref[...] = sc

    panel = panel_ref[...]                   # (LP, 128)
    a_blk = a_ref[0]                         # (2K, LP)
    dims = (((1,), (0,)), ((), ()))          # contract over the l stream
    masks = _packed_row_masks(base, jsw, m0, m1, mp0, mp1, lp_size, n_par,
                              fold)
    for q, mask in enumerate(masks):
        a_q = jnp.where(mask, a_blk, 0.0)
        c = jax.lax.dot_general(a_q, panel, dims,
                                precision=F32_DOT,
                                preferred_element_type=jnp.float32)
        out_ref[0, q] += c                   # (2K, 128)


def synth_mxu_packed(a_pk, maps, x2d, pmm_pk, pms_pk, *, l_max, fold=False,
                     spin=False, lp_size=128, interpret=True):
    """MXU synthesis on the packed grid (multi-map panel matmul).

    a_pk: (n_slots, 2K, S) (the stream axis minor: lane-dense for any K);
    rings advance 128 at a time; returns (n_slots, Q, 2K, R) with
    R = R1 * 128.
    """
    n_slots, K2, S = a_pk.shape
    R1 = x2d.shape[0]
    R = R1 * 128
    assert S % lp_size == 0
    n_par = 2 if fold else 1
    assert not (spin and fold), "fold is not supported on the spin path"
    n_q = 2 * n_par
    grid = (n_slots, R1, S // lp_size)
    kernel = functools.partial(_synth_mxu_packed_kernel, lp_size=lp_size,
                               n_par=n_par, fold=fold, spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, 128),
                             lambda s, rb, sp, *_refs: (rb, 0, 0)),
                pl.BlockSpec((1, 2, None, 1, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
                pl.BlockSpec((1, 2, None, 1, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
                pl.BlockSpec((1, K2, lp_size),
                             lambda s, rb, sp, *_refs: (s, 0, sp)),
            ],
            out_specs=pl.BlockSpec((1, n_q, K2, 128),
                                   lambda s, rb, sp, *_refs: (s, 0, 0, rb)),
            scratch_shapes=[
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.int32),
                pltpu.VMEM((lp_size, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, n_q, K2, R), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*maps, _ring_rows(x2d),
      _ring_rows(pmm_pk.reshape(n_slots, 2, R1, 128)),
      _ring_rows(pms_pk.reshape(n_slots, 2, R1, 128)), a_pk)


def _anal_vpu_packed_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                            x_ref, pmm_ref, pms_ref, dw_ref, out_ref,
                            pp_ref, pc_ref, sc_ref, acc_ref, *, lp_size,
                            n_par, fold, spin, rf, l_max):
    si = pl.program_id(0)
    rb = pl.program_id(1)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size

    @pl.when(sp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    @pl.when(rb == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    pmm = _pad_rows(pmm_ref[0], rf)          # (2, 8, 128)
    pms = _pad_rows(pms_ref[0], rf)
    dw = _pad_rows(dw_ref[0], rf)            # (Q, 2K, 8, 128)
    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))

    # ONE static-bound loop with a branch-free where-selected seam (the
    # ref oracle's schedule): a pair of dynamic-bound loops split at the
    # seam lowers to while_loops whose per-step overhead roughly doubles
    # the panel cost vs the plain kernel's scan; the per-step selects are
    # a handful of (8, 128) ops and _step reseeds itself at l == l0.
    def body(j, carry):
        pp, pc, sc = carry
        g = base + j
        hi = g >= jsw
        m = jnp.where(hi, m1, m0)
        mp_v = jnp.where(hi, mp1, mp0)
        l = jnp.where(hi, l01 + g - jsw, l00 + g)
        pmm_s = jnp.where(hi, pmm[1], pmm[0])
        pms_s = jnp.where(hi, pms[1], pms[0])
        pp, pc, sc, val = _step(spin, l, m.astype(jnp.float32),
                                mp_v.astype(jnp.float32), x, pp, pc, sc,
                                pmm_s, pms_s)
        # positions past the real stream (l > l_max) are padding the host
        # unpack discards; zero them so the packed rows match the oracle
        val = jnp.where(l <= l_max, val, 0.0)
        if fold:
            q = hi.astype(jnp.int32) * n_par + (l + m) % 2
            sel = (jnp.arange(2 * n_par, dtype=jnp.int32) == q)
            d = jnp.sum(jnp.where(sel[:, None, None, None], dw, 0.0),
                        axis=0)
        else:
            d = jnp.where(hi, dw[1], dw[0])
        row = jnp.sum(d * val[None, :, :], axis=(1, 2))   # (2K,)
        acc_ref[pl.ds(j, 1), :] = row[None, :]
        return pp, pc, sc

    pp, pc, sc = jax.lax.fori_loop(
        0, lp_size, body, (pp_ref[...], pc_ref[...], sc_ref[...]))
    out_ref[0] += acc_ref[...]
    pp_ref[...] = pp
    pc_ref[...] = pc
    sc_ref[...] = sc


def anal_vpu_packed(dw_pk, maps, x2d, pmm_pk, pms_pk, *, l_max, s_len,
                    fold=False, spin=False, lp_size=128, interpret=True):
    """VPU analysis on the packed grid.

    dw_pk  : (n_slots, Q, 2K, Rw, 128) weighted Delta per fused component.
             ``Rw`` is either the full ``R1`` row count of ``x2d``, or --
             when the ring axis fits one 8-row grid block (R1 == 8) -- the
             ring-shrunk ``ceil(R/128)`` real rows; the kernel rebuilds the
             zero padding rows in-register (`_pad_rows`), so the slow
             interpret-mode input fetch only ships real data.  The
             ``pmm_pk``/``pms_pk`` seed tables (n_slots, 2, Rw, 128) shrink
             with it (their padding entries are zero by construction).
    s_len  : packed l-stream length per slot (layout.S)
    returns: (n_slots, S, 2K) f32 packed l-stream rows
    """
    n_slots, n_q, K2, n_rows = dw_pk.shape[:4]
    R1 = x2d.shape[0]
    rf = n_rows if (R1 == 8 and n_rows < 8) else 8
    n_par = 2 if fold else 1
    assert n_q == 2 * n_par and R1 % 8 == 0
    assert n_rows == (rf if rf < 8 else R1), (n_rows, R1, rf)
    assert pmm_pk.shape[2] == pms_pk.shape[2] == n_rows, \
        (pmm_pk.shape, n_rows)
    assert not (spin and fold), "fold is not supported on the spin path"
    S = int(s_len)
    assert S % lp_size == 0
    grid = (n_slots, R1 // 8, S // lp_size)
    kernel = functools.partial(_anal_vpu_packed_kernel, lp_size=lp_size,
                               n_par=n_par, fold=fold, spin=spin, rf=rf,
                               l_max=l_max)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((8, 128), lambda s, rb, sp, *_refs: (rb, 0)),
                pl.BlockSpec((1, 2, rf, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0)),
                pl.BlockSpec((1, 2, rf, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0)),
                pl.BlockSpec((1, n_q, K2, rf, 128),
                             lambda s, rb, sp, *_refs: (s, 0, 0, rb, 0)),
            ],
            out_specs=pl.BlockSpec((1, lp_size, K2),
                                   lambda s, rb, sp, *_refs: (s, sp, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
                pltpu.VMEM((lp_size, K2), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, S, K2), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(*maps, x2d, pmm_pk, pms_pk, dw_pk)


def _anal_mxu_packed_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                            x_ref, pmm_ref, pms_ref, dw_ref, out_ref,
                            pp_ref, pc_ref, sc_ref, panel_ref, *, lp_size,
                            n_par, fold, spin):
    si = pl.program_id(0)
    rb = pl.program_id(1)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size

    @pl.when(sp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    # the slot's whole output stays resident across its (ring block, panel)
    # steps: every ring block adds into every panel's rows, and an output
    # block is only kept in VMEM between consecutive grid steps
    @pl.when((rb == 0) & (sp == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                           # (1, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))
    j0 = jnp.clip(jsw - base, 0, lp_size)    # seam split (see VPU kernel)

    def seg_gen(m, mp_v, l_base, pmm, pms):
        m_f = m.astype(jnp.float32)
        mp_f = mp_v.astype(jnp.float32)

        def gen(j, carry):
            pp, pc, sc = carry
            pp, pc, sc, val = _step(spin, l_base + j, m_f, mp_f, x,
                                    pp, pc, sc, pmm, pms)
            panel_ref[pl.ds(j, 1), :] = val
            return pp, pc, sc

        return gen

    carry = (pp_ref[...], pc_ref[...], sc_ref[...])
    carry = jax.lax.fori_loop(
        0, j0, seg_gen(m0, mp0, l00 + base, pmm0, pms0), carry)
    pp, pc, sc = jax.lax.fori_loop(
        j0, lp_size, seg_gen(m1, mp1, l01 + base - jsw, pmm1, pms1), carry)
    pp_ref[...] = pp
    pc_ref[...] = pc
    sc_ref[...] = sc

    panel = panel_ref[...]                   # (LP, 128)
    dims = (((1,), (1,)), ((), ()))          # contract over rings(128)
    masks = _packed_row_masks(base, jsw, m0, m1, mp0, mp1, lp_size, n_par,
                              fold)
    acc = jnp.zeros(out_ref.shape[2:], jnp.float32)
    for q, mask in enumerate(masks):
        c = jax.lax.dot_general(dw_ref[0, q], panel, dims,
                                precision=F32_DOT,
                                preferred_element_type=jnp.float32)
        acc = acc + jnp.where(mask, c, 0.0)  # (2K, LP)
    out_ref[0, sp] += acc


def anal_mxu_packed(dw_pk, maps, x2d, pmm_pk, pms_pk, *, l_max, s_len,
                    fold=False, spin=False, lp_size=128, interpret=True):
    """MXU analysis on the packed grid.

    dw_pk  : (n_slots, Q, 2K, R) weighted Delta, R = R1 * 128
    s_len  : packed l-stream length per slot (layout.S)
    returns: (n_slots, S // LP, 2K, LP) f32 packed l-stream rows, one
             lane-dense (2K, LP) block per panel
    """
    n_slots, n_q, K2, R = dw_pk.shape
    R1 = R // 128
    n_par = 2 if fold else 1
    assert n_q == 2 * n_par and R % 128 == 0
    assert not (spin and fold), "fold is not supported on the spin path"
    S = int(s_len)
    assert S % lp_size == 0
    grid = (n_slots, R1, S // lp_size)
    kernel = functools.partial(_anal_mxu_packed_kernel, lp_size=lp_size,
                               n_par=n_par, fold=fold, spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, 128),
                             lambda s, rb, sp, *_refs: (rb, 0, 0)),
                pl.BlockSpec((1, 2, None, 1, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
                pl.BlockSpec((1, 2, None, 1, 128),
                             lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
                pl.BlockSpec((1, n_q, K2, 128),
                             lambda s, rb, sp, *_refs: (s, 0, 0, rb)),
            ],
            out_specs=pl.BlockSpec((1, S // lp_size, K2, lp_size),
                                   lambda s, rb, sp, *_refs: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.int32),
                pltpu.VMEM((lp_size, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, S // lp_size, K2, lp_size),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(*maps, _ring_rows(x2d),
      _ring_rows(pmm_pk.reshape(n_slots, 2, R1, 128)),
      _ring_rows(pms_pk.reshape(n_slots, 2, R1, 128)), dw_pk)


def _anal_mxu_kernel(m_vals_ref, mp_vals_ref, x_ref, pmm_ref, pms_ref,
                     dw_ref, out_ref, pp_ref, pc_ref, sc_ref, panel_ref, *,
                     lp_size, fold, spin):
    mi = pl.program_id(0)
    rb = pl.program_id(1)
    lp = pl.program_id(2)
    m = m_vals_ref[mi]
    m_f = m.astype(jnp.float32)
    mp_f = mp_vals_ref[mi].astype(jnp.float32)
    l0 = lp * lp_size

    @pl.when(lp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    # the row's whole output stays resident across its (ring block, panel)
    # steps (see the packed analysis kernel)
    @pl.when((rb == 0) & (lp == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l0 + lp_size > m)
    def _work():
        x = x_ref[...]                          # (1, 128)
        pmm = pmm_ref[0]
        pms = pms_ref[0]

        def gen(j, carry):
            pp, pc, sc = carry
            pp, pc, sc, val = _step(spin, l0 + j, m_f, mp_f, x, pp, pc, sc,
                                    pmm, pms)
            panel_ref[pl.ds(j, 1), :] = val
            return pp, pc, sc

        pp, pc, sc = jax.lax.fori_loop(
            0, lp_size, gen, (pp_ref[...], pc_ref[...], sc_ref[...]))
        pp_ref[...] = pp
        pc_ref[...] = pc
        sc_ref[...] = sc

        panel = panel_ref[...]                  # (LP, 128)
        dims = (((1,), (1,)), ((), ()))         # contract over rings(128)
        if fold:
            ls = l0 + jax.lax.broadcasted_iota(jnp.int32, (1, lp_size), 1)
            even = ((ls + m) % 2) == 0
            ce = jax.lax.dot_general(dw_ref[0, 0], panel, dims,
                                     precision=F32_DOT,
                                     preferred_element_type=jnp.float32)
            co = jax.lax.dot_general(dw_ref[0, 1], panel, dims,
                                     precision=F32_DOT,
                                     preferred_element_type=jnp.float32)
            out_ref[0, lp] += jnp.where(even, ce, co)     # (2K, LP)
        else:
            c = jax.lax.dot_general(dw_ref[0, 0], panel, dims,
                                    precision=F32_DOT,
                                    preferred_element_type=jnp.float32)
            out_ref[0, lp] += c


def anal_mxu(dw, m_vals, x2d, pmm, pms, *, l_max, l1p, fold=False,
             mp_vals=None, lp_size=128, interpret=True):
    """MXU analysis kernel.

    dw     : (Mp, P, 2K, R) weighted Delta, R = R1 * 128
    returns: (Mp, L1p // LP, 2K, LP) f32, one lane-dense block per panel
    """
    Mp, n_par, K2, R = dw.shape
    R1 = R // 128
    assert l1p % lp_size == 0 and R % 128 == 0
    spin = mp_vals is not None
    assert not (spin and fold), "fold is not supported on the spin path"
    mp = jnp.zeros(Mp, jnp.int32) if mp_vals is None \
        else jnp.asarray(mp_vals, jnp.int32)
    grid = (Mp, R1, l1p // lp_size)
    kernel = functools.partial(_anal_mxu_kernel, lp_size=lp_size, fold=fold,
                               spin=spin)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, 128),
                             lambda m, rb, lp, *_refs: (rb, 0, 0)),
                pl.BlockSpec((1, None, 1, 128),
                             lambda m, rb, lp, *_refs: (m, rb, 0, 0)),
                pl.BlockSpec((1, None, 1, 128),
                             lambda m, rb, lp, *_refs: (m, rb, 0, 0)),
                pl.BlockSpec((1, n_par, K2, 128),
                             lambda m, rb, lp, *_refs: (m, 0, 0, rb)),
            ],
            out_specs=pl.BlockSpec((1, l1p // lp_size, K2, lp_size),
                                   lambda m, rb, lp, *_refs: (m, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.int32),
                pltpu.VMEM((lp_size, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, l1p // lp_size, K2, lp_size),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(m_vals, mp, _ring_rows(x2d), _ring_rows(pmm.reshape(Mp, R1, 128)),
      _ring_rows(pms.reshape(Mp, R1, 128)), dw)
