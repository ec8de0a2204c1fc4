"""Fused Legendre+phase Pallas pipeline (single-kernel inverse/direct SHT
stage pair).

The staged pipeline (kernels/ops.py + core/phase.py) materialises the
intermediate ``delta_m(r)`` rows in HBM between the Legendre kernel and the
host phase stage -- the exact traffic the paper identifies as the GPU
bottleneck of the inverse transform, and what libsharp's fused ring-major
loop avoids.  The kernels here keep the per-ring accumulation on-chip:

  * synthesis: the packed-slot Legendre accumulate is contracted per panel
    and immediately rotated by a per-(row, ring) *phase table*
    (core.phase.uniform_rotation_tables / bucket_rotation_tables -- cos/sin
    of m*phi0 with the engine's conjugate-wrap and Nyquist handling baked
    in), so the kernel's only output is the rotated spectrum-row block.
    The unrotated Delta never exists as a pallas output ref (asserted on
    the jaxpr in tests/test_fused.py).
  * analysis: the gathered FFT rows are rotated into Delta in-kernel (once
    per (slot, ring-block), hoisted out of the panel loop into a VMEM
    scratch) and contracted against the recurrence panel; only packed a_lm
    l-streams leave the kernel.

Every plan shape the staged path serves dispatches through here:

  * **spin-2**: the packed row set carries both lambda^{+-} recurrences
    (``m_vals``/``mp_vals`` from legendre._spin_rows, coefficients from
    spin_pack_alm), the kernels run the generalised Wigner-d step
    (`_step(spin=2, ...)`), and the host epilogue/prologue converts between
    the +-pair and Q/U through the channel axis.  The e^{+-i m phi0}
    rotation is complex-linear and both pair rows share one m, so rotating
    in-kernel commutes with the pair (un)packing exactly.
  * **equator fold**: the kernels carry a plane axis (north | south).  The
    parity split of the coefficient rows happens in-register -- for stream
    position j of a panel, (l + m) mod 2 == (base + j - seam) mod 2, an
    m-independent mask -- and the north/south symmetry combine
    (north = even + odd, south = even - odd) runs in-kernel on the
    contracted planes, replacing the staged path's host reshapes.
  * **bucket (ragged HEALPix)**: the rotation tables are plain
    e^{+-i m phi0(r)} (`phase.bucket_rotation_tables`); the alias-fold
    scatter/gather through `phase.bucket_bin_maps` wraps the kernel on the
    host side (`_bucket_scatter`/`_bucket_gather`), so the Delta rows skip
    the staged path's HBM round-trip between the Legendre kernel and the
    bucket FFT engine.

Beyond the fusion itself the kernels carry raw-speed upgrades over the
staged ones:

  * panel-contraction accumulate: recurrence values stream into a VMEM
    value panel (via the exact shared `_step`, so fused synthesis is
    bit-identical to staged) and are contracted against the coefficient
    block once per panel (one dot) instead of a broadcast-FMA per l-step.
  * ring-shrunk data operands: on the VPU layout the ring axis is padded
    to 1024 lanes but only ``ceil(R/128)`` row blocks carry data, so the
    ``f``/phase-table operands are shipped at that reduced row count and
    the zero padding rows are rebuilt in-register (`_pad_rows`).
  * the MXU synthesis accumulates the panel contraction into a VMEM
    scratch and rotates **once** per ring block (at the last panel),
    not per panel -- undoing per-step rotation+flush traffic was the
    root-cause fix of the historical fused-MXU < 1x regression.

The synthesis VPU kernel double-buffers its per-panel output flush
(`hbuf` two-slot scratch): panel p's contracted+rotated block is written
to HBM while panel p+1's recurrence values stream into the value panel.

The MXU variants take ``bf16=True`` to run the panel contraction in
bfloat16 with float32 accumulation (`preferred_element_type`); the
measured error band rides in benchmarks/bench_recurrence.py (`bf16_err`
rows).

The only shapes still staged: equator fold on a bucket phase stage, and
spin-2 on a uniform grid at the Nyquist alias point (n_phi == 2*m_max)
-- see Plan._fusion_eligibility / Plan.describe()["fusion"].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autodiff import linear_pair
from repro.tracing import FOLD, LEGENDRE, PHASE, scoped
from repro.kernels.legendre_pallas import (F32_DOT, _pad_rows, _ring_rows,
                                           _step)

__all__ = [
    "synth_fused_vpu", "synth_fused_mxu",
    "anal_fused_vpu", "anal_fused_mxu",
    "fused_synth", "fused_anal",
    "fused_synth_bucket", "fused_anal_bucket", "rotation_tables",
]

def _fill_panel(panel_ref, x, m0, m1, mp0, mp1, jsw, base, lp_size, spin,
                pmm0, pms0, pmm1, pms1, carry, l_max=None):
    """Stream the split-seam recurrence values of one panel into the VMEM
    value panel via the exact shared `_step`.  Returns the (pp, pc, sc)
    carry.  Segment l0 == max(m, |m'|) (== m on the scalar path).

    With ``l_max`` given, each segment's loop stops at its true stream
    end (l == l_max) instead of running to the panel edge: positions past
    the end keep whatever the scratch panel last held, which is safe only
    for consumers that zero those rows on the other dot operand (the
    packed ``a`` rows there are zero by construction).  The min-max slot
    pairing leaves ~(S - l_max - 2) dead positions per slot, so the MXU
    kernels skip that fraction of the serial recurrence."""
    j0 = jnp.clip(jsw - base, 0, lp_size)

    def seg_gen(m, mp_v, l_base, pmm, pms):
        m_f = m.astype(jnp.float32)
        mp_f = mp_v.astype(jnp.float32)

        def gen(j, carry):
            pp, pc, sc = carry
            pp, pc, sc, val = _step(spin, l_base + j, m_f, mp_f, x, pp, pc,
                                    sc, pmm, pms)
            panel_ref[pl.ds(j, 1)] = val.reshape((1,) + panel_ref.shape[1:])
            return pp, pc, sc

        return gen

    l00 = jnp.maximum(m0, jnp.abs(mp0))
    l01 = jnp.maximum(m1, jnp.abs(mp1))
    if l_max is None:
        end0, end1 = j0, lp_size
    else:
        end0 = jnp.clip(l_max + 1 - l00 - base, 0, j0)
        end1 = jnp.clip(jsw + l_max + 1 - l01 - base, j0, lp_size)
    carry = jax.lax.fori_loop(
        0, end0, seg_gen(m0, mp0, l00 + base, pmm0, pms0), carry)
    return jax.lax.fori_loop(
        j0, end1, seg_gen(m1, mp1, l01 + base - jsw, pmm1, pms1), carry)


def _hi_row_mask(base, jsw, lp_size):
    """(1, LP): stream positions of the panel that belong to segment 1."""
    iot = jax.lax.broadcasted_iota(jnp.int32, (1, lp_size), 1)
    return (base + iot) >= jsw


def _parity_masks(base, jsw, lp_size):
    """(l + m) even per packed stream position, per segment -- the fold
    plane split.  2m is even so only the panel-local l offset counts:
    seg0 l = l0 + base + j, seg1 l = l0 + base + j - seam.  (1, LP)."""
    iot = jax.lax.broadcasted_iota(jnp.int32, (1, lp_size), 1)
    par0 = ((base + iot) % 2) == 0
    par1 = ((base + iot - jsw) % 2) == 0
    return par0, par1


# =============================================================================
# Fused synthesis: packed a_lm -> rotated spectrum rows, one kernel
# =============================================================================


def _synth_fused_vpu_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                            x_ref, pmm_ref, pms_ref, tab_ref, a_ref,
                            out_ref, pp_ref, pc_ref, sc_ref, panel_ref,
                            hbuf_ref, *, lp_size, n_k, n_sp, rf, spin, n_pl):
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

    # double-buffered flush: panel sp-1's contracted+rotated block drains
    # to the output ref while this panel's recurrence values stream in
    @pl.when(sp > 0)
    def _flush_prev():
        out_ref[0] += hbuf_ref[pl.ds((sp - 1) % 2, 1)][0]

    x = x_ref[...]                            # (8, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    carry = _fill_panel(panel_ref, x, m0, m1, mp0, mp1, jsw, base, lp_size,
                        spin, pmm0, pms0, pmm1, pms1,
                        (pp_ref[...], pc_ref[...], sc_ref[...]))
    pp_ref[...], pc_ref[...], sc_ref[...] = carry

    panel = panel_ref[...].reshape(lp_size, -1)       # (LP, 8*128)
    a_blk = a_ref[0]                          # (2K, LP)
    hi_row = _hi_row_mask(base, jsw, lp_size)
    if n_pl == 2:
        par0, par1 = _parity_masks(base, jsw, lp_size)
    hs = []
    for seg in (0, 1):
        a_seg = jnp.where(hi_row if seg else ~hi_row, a_blk, 0.0)
        if n_pl == 2:
            par = par1 if seg else par0
            a_seg = jnp.concatenate([jnp.where(par, a_seg, 0.0),
                                     jnp.where(par, 0.0, a_seg)], axis=0)
        d = jax.lax.dot_general(a_seg, panel, (((1,), (0,)), ((), ())),
                                precision=F32_DOT,
                                preferred_element_type=jnp.float32)
        d = d.reshape(n_pl * 2 * n_k, 8, 128)
        if n_pl == 2:
            e, o = d[:2 * n_k], d[2 * n_k:]
            planes = (e + o, e - o)           # north | south
        else:
            planes = (d,)
        hp = []
        for pi, dpl in enumerate(planes):
            d_re, d_im = dpl[:n_k], dpl[n_k:]         # (K, 8, 128) each
            t = _pad_rows(tab_ref[0, seg, pi], rf)    # (4, 8, 128)
            h_re = t[0] * d_re + t[1] * d_im
            h_im = t[2] * d_re + t[3] * d_im
            hp.append(jnp.concatenate([h_re, h_im], axis=0))
        hs.append(jnp.stack(hp, axis=0))      # (n_pl, 2K, 8, 128)
    hbuf_ref[pl.ds(sp % 2, 1)] = jnp.stack(hs, axis=0)[None]

    @pl.when(sp == n_sp - 1)
    def _flush_last():
        out_ref[0] += hbuf_ref[pl.ds(sp % 2, 1)][0]


def synth_fused_vpu(a_pk, maps, x2d, pmm_pk, pms_pk, tab_pk, *, l_max,
                    spin=0, lp_size=128, interpret=True):
    """VPU fused synthesis on the packed (slot, panel) grid.

    a_pk   : (n_slots, 2K, S) f32 packed coefficient streams (the stream
             axis minor: lane-dense for any K)
    maps   : (m0, m1, mp0, mp1, seed) i32 per-slot scalar-prefetch arrays
    x2d    : (R1, 128) f32;  pmm_pk/pms_pk: (n_slots, 2, R1, 128)
    tab_pk : (n_slots, 2, n_pl, 4, Rf1, 128) f32 per-(segment, plane) phase
             tables, ring-shrunk to ``Rf1`` real row blocks (= R1 on
             multi-row grids); n_pl == 2 on the equator-fold path
    returns: (n_slots, 2, n_pl, 2K, R1, 128) f32 rotated spectrum rows
    """
    n_slots, K2, S = a_pk.shape
    n_pl = tab_pk.shape[2]
    R1 = x2d.shape[0]
    assert S % lp_size == 0 and R1 % 8 == 0 and K2 % 2 == 0
    n_sp = S // lp_size
    rf = tab_pk.shape[4] if R1 == 8 else 8
    assert tab_pk.shape[4] == (rf if R1 == 8 else R1)
    tab_spec = pl.BlockSpec((1, 2, n_pl, 4, rf, 128),
                            (lambda s, rb, sp, *_refs: (s, 0, 0, 0, 0, 0))
                            if R1 == 8 else
                            (lambda s, rb, sp, *_refs: (s, 0, 0, 0, rb, 0)))
    grid = (n_slots, R1 // 8, n_sp)
    kernel = functools.partial(_synth_fused_vpu_kernel, lp_size=lp_size,
                               n_k=K2 // 2, n_sp=n_sp, rf=rf, spin=spin,
                               n_pl=n_pl)
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
                tab_spec,
                pl.BlockSpec((1, K2, lp_size),
                             lambda s, rb, sp, *_refs: (s, 0, sp)),
            ],
            out_specs=pl.BlockSpec((1, 2, n_pl, K2, 8, 128),
                                   lambda s, rb, sp, *_refs:
                                   (s, 0, 0, 0, rb, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
                pltpu.VMEM((lp_size, 8, 128), jnp.float32),
                pltpu.VMEM((2, 2, n_pl, K2, 8, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, 2, n_pl, K2, R1, 128),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*maps, x2d, pmm_pk, pms_pk, tab_pk, a_pk)


def _tables_identity(tabs):
    """True iff the (host-side) rotation tables are exactly the identity
    rotation on every plane and ring -- any uniform grid with phi0 == 0
    (the Gauss-Legendre/ECP default).  The MXU kernels then drop the
    table operand and the rotate epilogue entirely; ``1*re + 0*im == re``
    exactly in f32, so the skip is bit-identical -- it just stops
    fetching and applying a dead block every grid step.  Fold tables
    never qualify: their south plane zeroes the rows past the mirror
    count, and that masking must stay."""
    t = np.asarray(tabs)
    return bool(np.all(t[:, :, 0] == 1.0) and np.all(t[:, :, 3] == 1.0)
                and np.all(t[:, :, 1] == 0.0) and np.all(t[:, :, 2] == 0.0))


def _synth_fused_mxu_kernel(*refs, lp_size, n_k, n_sp, l_max, bf16, spin,
                            n_pl, rot):
    (m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref, x_ref, pmm_ref, pms_ref,
     *rest) = refs
    rest = list(rest)
    tab_ref = rest.pop(0) if rot else None
    a_ref, out_ref, pp_ref, pc_ref, sc_ref, panel_ref = rest[:6]
    acc_ref = rest[6] if n_sp > 1 else None
    si = pl.program_id(0)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size
    K2 = 2 * n_k

    @pl.when(sp == 0)
    def _init():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)
        # the truncated fill leaves the dead stream tail unwritten; one
        # vectorized zero write keeps those rows from reading scratch
        # garbage (they still multiply all-zero a rows, so any finite
        # value is correct -- NaN/Inf garbage is not)
        panel_ref[...] = jnp.zeros_like(panel_ref)
        if n_sp > 1:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                            # (1, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    # truncated fill: stop at each segment's true stream end; the stale
    # rows past it hit all-zero packed-a rows, so the dot is unchanged
    carry = _fill_panel(panel_ref, x, m0, m1, mp0, mp1, jsw, base, lp_size,
                        spin, pmm0, pms0, pmm1, pms1,
                        (pp_ref[...], pc_ref[...], sc_ref[...]),
                        l_max=l_max)
    pp_ref[...], pc_ref[...], sc_ref[...] = carry

    panel = panel_ref[...]                    # (LP, 128)
    if bf16:
        panel = panel.astype(jnp.bfloat16)
    a_blk = a_ref[0]                          # (2K, LP)
    hi_row = _hi_row_mask(base, jsw, lp_size)
    if n_pl == 2:
        par0, par1 = _parity_masks(base, jsw, lp_size)

    # two narrow masked dots, as in the staged kernel: a single wide
    # [seg0 | seg1] contraction is measurably slower than the narrow pair
    def contract(seg):
        a_seg = jnp.where(hi_row if seg else ~hi_row, a_blk, 0.0)
        if n_pl == 2:
            par = par1 if seg else par0
            a_seg = jnp.concatenate([jnp.where(par, a_seg, 0.0),
                                     jnp.where(par, 0.0, a_seg)], axis=0)
        return jax.lax.dot_general(a_seg, panel, (((1,), (0,)), ((), ())),
                                   precision=None if bf16 else F32_DOT,
                                   preferred_element_type=jnp.float32)

    def flush(seg, cs):                       # (n_pl*2K, 128)
        if n_pl == 2:
            e, o = cs[:K2], cs[K2:]
            planes = (e + o, e - o)           # north | south
        else:
            planes = (cs,)
        for pi, cp in enumerate(planes):
            if rot:
                c_re, c_im = cp[:n_k], cp[n_k:]
                t = tab_ref[0, seg, pi][:, 0, :]  # (4, 128)
                cp = jnp.concatenate(
                    [t[0][None, :] * c_re + t[1][None, :] * c_im,
                     t[2][None, :] * c_re + t[3][None, :] * c_im],
                    axis=0)
            out_ref[0, seg, pi] = cp

    if n_sp == 1:
        for seg in (0, 1):
            flush(seg, contract(seg))
    else:
        for seg in (0, 1):
            acc_ref[seg] += contract(seg)

        @pl.when(sp == n_sp - 1)
        def _rotate_flush():
            for seg in (0, 1):
                flush(seg, acc_ref[seg])


def synth_fused_mxu(a_pk, maps, x2d, pmm_pk, pms_pk, tab_pk, *, l_max,
                    spin=0, bf16=False, lp_size=128, interpret=True,
                    rot=True):
    """MXU fused synthesis (panel matmul + per-ring-block rotation).

    Layouts as :func:`synth_fused_vpu` except rings advance 128 at a time;
    tab_pk is (n_slots, 2, n_pl, 4, R1, 128); returns
    (n_slots, 2, n_pl, 2K, R) with R = R1 * 128.  ``bf16=True`` contracts
    the recurrence panel in bfloat16 with f32 accumulation.  ``rot=False``
    (identity tables, see :func:`_tables_identity`) drops the table
    operand and the rotate epilogue.
    """
    n_slots, K2, S = a_pk.shape
    n_pl = tab_pk.shape[2]
    R1 = x2d.shape[0]
    R = R1 * 128
    assert S % lp_size == 0 and K2 % 2 == 0
    n_sp = S // lp_size
    if bf16:
        a_pk = a_pk.astype(jnp.bfloat16)
    grid = (n_slots, R1, n_sp)
    kernel = functools.partial(_synth_fused_mxu_kernel, lp_size=lp_size,
                               n_k=K2 // 2, n_sp=n_sp, l_max=l_max,
                               bf16=bf16, spin=spin, n_pl=n_pl, rot=rot)
    in_specs = [
        pl.BlockSpec((None, 1, 128), lambda s, rb, sp, *_refs: (rb, 0, 0)),
        pl.BlockSpec((1, 2, None, 1, 128),
                     lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
        pl.BlockSpec((1, 2, None, 1, 128),
                     lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
    ]
    operands = [_ring_rows(x2d),
                _ring_rows(pmm_pk.reshape(n_slots, 2, R1, 128)),
                _ring_rows(pms_pk.reshape(n_slots, 2, R1, 128))]
    if rot:
        in_specs.append(
            pl.BlockSpec((1, 2, n_pl, 4, None, 1, 128),
                         lambda s, rb, sp, *_refs: (s, 0, 0, 0, rb, 0, 0)))
        operands.append(_ring_rows(tab_pk))
    in_specs.append(pl.BlockSpec((1, K2, lp_size),
                                 lambda s, rb, sp, *_refs: (s, 0, sp)))
    operands.append(a_pk)
    scratch = [
        pltpu.VMEM((1, 128), jnp.float32),
        pltpu.VMEM((1, 128), jnp.float32),
        pltpu.VMEM((1, 128), jnp.int32),
        pltpu.VMEM((lp_size, 128), jnp.float32),
    ]
    if n_sp > 1:
        scratch.append(pltpu.VMEM((2, n_pl * K2, 128), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 2, n_pl, K2, 128),
                                   lambda s, rb, sp, *_refs:
                                   (s, 0, 0, 0, rb)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, 2, n_pl, K2, R),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*maps, *operands)


# =============================================================================
# Fused analysis: gathered FFT rows -> packed a_lm l-streams, one kernel
# =============================================================================


def _anal_fused_vpu_kernel(m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref,
                           x_ref, pmm_ref, pms_ref, tab_ref, f_ref,
                           out_ref, pp_ref, pc_ref, sc_ref, panel_ref,
                           dbuf_ref, *, lp_size, n_k, rf, spin, n_pl):
    si = pl.program_id(0)
    rb = pl.program_id(1)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size
    K2 = 2 * n_k

    @pl.when(sp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    # the whole slot's output stays resident across its (ring block,
    # panel) steps: every ring block adds into every panel's rows, and
    # an output block is only kept in VMEM between consecutive steps
    @pl.when((rb == 0) & (sp == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    # rotate the gathered spectrum rows into Delta once per (slot, ring
    # block) -- l-independent, so hoisted out of the panel loop into a
    # VMEM scratch instead of recomputed every grid step
    @pl.when(sp == 0)
    def _rotate():
        f = _pad_rows(f_ref[0], rf)           # (2, n_pl, 2K, 8, 128)
        for seg in (0, 1):
            dp = []
            for pi in range(n_pl):
                f_re, f_im = f[seg, pi, :n_k], f[seg, pi, n_k:]
                t = _pad_rows(tab_ref[0, seg, pi], rf)    # (4, 8, 128)
                d_re = t[0] * f_re + t[1] * f_im
                d_im = t[2] * f_re + t[3] * f_im
                dp.append(jnp.concatenate([d_re, d_im], axis=0))
            if n_pl == 2:
                # even/odd planes: the l-parity selection happens on the
                # contracted rows below
                dcat = jnp.concatenate([dp[0] + dp[1], dp[0] - dp[1]],
                                       axis=0)
            else:
                dcat = dp[0]
            dbuf_ref[seg] = dcat              # (n_pl*2K, 8, 128)

    x = x_ref[...]
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    carry = _fill_panel(panel_ref, x, m0, m1, mp0, mp1, jsw, base, lp_size,
                        spin, pmm0, pms0, pmm1, pms1,
                        (pp_ref[...], pc_ref[...], sc_ref[...]))
    pp_ref[...], pc_ref[...], sc_ref[...] = carry

    panel = panel_ref[...].reshape(lp_size, -1)       # (LP, 8*128)
    dims = (((1,), (1,)), ((), ()))           # NT gemm over the ring tile
    c0 = jax.lax.dot_general(dbuf_ref[0].reshape(n_pl * K2, -1), panel,
                             dims, precision=F32_DOT,
                             preferred_element_type=jnp.float32)
    c1 = jax.lax.dot_general(dbuf_ref[1].reshape(n_pl * K2, -1), panel,
                             dims, precision=F32_DOT,
                             preferred_element_type=jnp.float32)
    hi_row = _hi_row_mask(base, jsw, lp_size)
    if n_pl == 2:
        par0, par1 = _parity_masks(base, jsw, lp_size)
        c0 = jnp.where(par0, c0[:K2], c0[K2:])
        c1 = jnp.where(par1, c1[:K2], c1[K2:])
    out_ref[0, sp] += jnp.where(hi_row, c1, c0)   # (2K, LP)


def anal_fused_vpu(f_pk, maps, x2d, pmm_pk, pms_pk, tab_pk, *, l_max, s_len,
                   spin=0, lp_size=128, interpret=True):
    """VPU fused analysis on the packed grid.

    f_pk   : (n_slots, 2, n_pl, 2K, Rf1, 128) gathered per-plane FFT rows
             per segment, ring-shrunk like ``tab_pk``
    tab_pk : (n_slots, 2, n_pl, 4, Rf1, 128) f32 anal-direction tables
    returns: (n_slots, S // LP, 2K, LP) f32 packed l-stream rows, one
             lane-dense (2K, LP) block per panel
    """
    n_slots, n_seg, n_pl, K2 = f_pk.shape[:4]
    R1 = x2d.shape[0]
    assert n_seg == 2 and R1 % 8 == 0 and K2 % 2 == 0
    rf = f_pk.shape[4] if R1 == 8 else 8
    assert f_pk.shape[4] == tab_pk.shape[4] == (rf if R1 == 8 else R1)
    idx = ((lambda s, rb, sp, *_refs: (s, 0, 0, 0, 0, 0)) if R1 == 8 else
           (lambda s, rb, sp, *_refs: (s, 0, 0, 0, rb, 0)))
    S = int(s_len)
    assert S % lp_size == 0
    grid = (n_slots, R1 // 8, S // lp_size)
    kernel = functools.partial(_anal_fused_vpu_kernel, lp_size=lp_size,
                               n_k=K2 // 2, rf=rf, spin=spin, n_pl=n_pl)
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
                pl.BlockSpec((1, 2, n_pl, 4, rf, 128), idx),
                pl.BlockSpec((1, 2, n_pl, K2, rf, 128), idx),
            ],
            out_specs=pl.BlockSpec((1, S // lp_size, K2, lp_size),
                                   lambda s, rb, sp, *_refs: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.int32),
                pltpu.VMEM((lp_size, 8, 128), jnp.float32),
                pltpu.VMEM((2, n_pl * K2, 8, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, S // lp_size, K2, lp_size),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(*maps, x2d, pmm_pk, pms_pk, tab_pk, f_pk)


def _anal_fused_mxu_kernel(*refs, lp_size, n_k, l_max, bf16, spin, n_pl,
                           rot):
    (m0_ref, m1_ref, mp0_ref, mp1_ref, seed_ref, x_ref, pmm_ref, pms_ref,
     *rest) = refs
    rest = list(rest)
    tab_ref = rest.pop(0) if rot else None
    f_ref, out_ref, pp_ref, pc_ref, sc_ref, panel_ref, dbuf_ref = rest
    si = pl.program_id(0)
    rb = pl.program_id(1)
    sp = pl.program_id(2)
    m0, m1 = m0_ref[si], m1_ref[si]
    mp0, mp1 = mp0_ref[si], mp1_ref[si]
    jsw = seed_ref[si]
    base = sp * lp_size
    K2 = 2 * n_k

    @pl.when(sp == 0)
    def _init_carry():
        pp_ref[...] = jnp.zeros_like(pp_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)
        sc_ref[...] = jnp.zeros_like(sc_ref)

    # resident per-slot output, as in the VPU analysis kernel
    @pl.when((rb == 0) & (sp == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    # keep the truncated fill's unwritten tail rows finite (their
    # contracted output lands on packed positions the unpack never
    # gathers, but NaN scratch garbage would otherwise propagate)
    @pl.when(sp == 0)
    def _init_panel():
        panel_ref[...] = jnp.zeros_like(panel_ref)

    # rotate the gathered spectrum rows into Delta once per (slot, ring
    # block) -- l-independent, so hoisted out of the panel loop into a
    # VMEM scratch instead of recomputed every grid step
    @pl.when(sp == 0)
    def _rotate():
        f = f_ref[0]                          # (2, n_pl, 2K, 128)
        for seg in (0, 1):
            dp = []
            for pi in range(n_pl):
                fs = f[seg, pi]
                if rot:
                    f_re, f_im = fs[:n_k], fs[n_k:]
                    t = tab_ref[0, seg, pi][:, 0, :]  # (4, 128)
                    fs = jnp.concatenate(
                        [t[0][None, :] * f_re + t[1][None, :] * f_im,
                         t[2][None, :] * f_re + t[3][None, :] * f_im],
                        axis=0)
                dp.append(fs)
            if n_pl == 2:
                dbuf_ref[seg] = jnp.concatenate([dp[0] + dp[1],
                                                 dp[0] - dp[1]], axis=0)
            else:
                dbuf_ref[seg] = dp[0]

    x = x_ref[...]                            # (1, 128)
    pmm0, pmm1 = pmm_ref[0, 0], pmm_ref[0, 1]
    pms0, pms1 = pms_ref[0, 0], pms_ref[0, 1]
    # truncated fill: rows past each segment's stream end stay stale, so
    # their contracted output rows are garbage -- but those packed
    # positions are never gathered by the unpack (alm_src == -1 there)
    carry = _fill_panel(panel_ref, x, m0, m1, mp0, mp1, jsw, base, lp_size,
                        spin, pmm0, pms0, pmm1, pms1,
                        (pp_ref[...], pc_ref[...], sc_ref[...]),
                        l_max=l_max)
    pp_ref[...], pc_ref[...], sc_ref[...] = carry

    panel = panel_ref[...]                    # (LP, 128)
    d = dbuf_ref[...]                         # (2, W, 128)
    if bf16:
        panel = panel.astype(jnp.bfloat16)
        d = d.astype(jnp.bfloat16)
    # two narrow ring contractions (one per segment), as in the staged
    # kernel -- a single wide [seg0 | seg1] dot is measurably slower
    dims = (((1,), (1,)), ((), ()))           # NT gemm over the ring block
    prec = None if bf16 else F32_DOT
    c0 = jax.lax.dot_general(d[0], panel, dims, precision=prec,
                             preferred_element_type=jnp.float32)
    c1 = jax.lax.dot_general(d[1], panel, dims, precision=prec,
                             preferred_element_type=jnp.float32)
    hi_row = _hi_row_mask(base, jsw, lp_size)
    if n_pl == 2:
        par0, par1 = _parity_masks(base, jsw, lp_size)
        c0 = jnp.where(par0, c0[:K2], c0[K2:])
        c1 = jnp.where(par1, c1[:K2], c1[K2:])
    out_ref[0, sp] += jnp.where(hi_row, c1, c0)   # (2K, LP)


def anal_fused_mxu(f_pk, maps, x2d, pmm_pk, pms_pk, tab_pk, *, l_max, s_len,
                   spin=0, bf16=False, lp_size=128, interpret=True,
                   rot=True):
    """MXU fused analysis (ring-contraction matmul + hoisted rotation).

    f_pk   : (n_slots, 2, n_pl, 2K, R) gathered per-plane FFT rows
    returns: (n_slots, S // LP, 2K, LP) f32 packed l-stream rows (as
             :func:`anal_fused_vpu`)
    ``rot=False`` (identity tables) drops the table operand and the
    rotate half of the per-ring-block prologue.
    """
    n_slots, n_seg, n_pl, K2, R = f_pk.shape
    R1 = R // 128
    assert n_seg == 2 and R % 128 == 0 and K2 % 2 == 0
    S = int(s_len)
    assert S % lp_size == 0
    grid = (n_slots, R1, S // lp_size)
    kernel = functools.partial(_anal_fused_mxu_kernel, lp_size=lp_size,
                               n_k=K2 // 2, l_max=l_max, bf16=bf16,
                               spin=spin, n_pl=n_pl, rot=rot)
    in_specs = [
        pl.BlockSpec((None, 1, 128), lambda s, rb, sp, *_refs: (rb, 0, 0)),
        pl.BlockSpec((1, 2, None, 1, 128),
                     lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
        pl.BlockSpec((1, 2, None, 1, 128),
                     lambda s, rb, sp, *_refs: (s, 0, rb, 0, 0)),
    ]
    operands = [_ring_rows(x2d),
                _ring_rows(pmm_pk.reshape(n_slots, 2, R1, 128)),
                _ring_rows(pms_pk.reshape(n_slots, 2, R1, 128))]
    if rot:
        in_specs.append(
            pl.BlockSpec((1, 2, n_pl, 4, None, 1, 128),
                         lambda s, rb, sp, *_refs: (s, 0, 0, 0, rb, 0, 0)))
        operands.append(_ring_rows(tab_pk))
    in_specs.append(pl.BlockSpec((1, 2, n_pl, K2, 128),
                                 lambda s, rb, sp, *_refs: (s, 0, 0, 0, rb)))
    operands.append(f_pk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, S // lp_size, K2, lp_size),
                                   lambda s, rb, sp, *_refs: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.float32),
                pltpu.VMEM((1, 128), jnp.int32),
                pltpu.VMEM((lp_size, 128), jnp.float32),
                pltpu.VMEM((2, n_pl * K2, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, S // lp_size, K2, lp_size),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(*maps, *operands)


# =============================================================================
# Host chains: packing + FFT/scatter around the kernels, adjoint-paired
# =============================================================================


def _prep(lo, x, pmm, pms, var):
    """Ring padding + per-slot packing shared by both directions.

    ``Rf1`` is the ring-shrunk row-block count for data operands (f rows,
    phase tables): on a single-row-block VPU grid only the rows holding
    real rings ship to the kernel (interpret-mode block fetches are slow
    per byte); the zero padding rows are rebuilt in-kernel (`_pad_rows`).
    """
    from repro.kernels import ops as kops
    R = x.shape[0]
    Rp = kops._pad_to(R, 1024 if var == "vpu" else 128)
    x_p = jnp.pad(jnp.asarray(x, jnp.float32), (0, Rp - R))
    pmm_pk = kops._pack_rows(jnp.pad(pmm, ((0, 0), (0, Rp - R))), lo)
    pms_pk = kops._pack_rows(jnp.pad(pms, ((0, 0), (0, Rp - R))), lo)
    R1 = Rp // 128
    Rf1 = kops._pad_to(R, 128) // 128 if (var == "vpu" and R1 == 8) else R1
    return (Rp, R1, Rf1, x_p.reshape(R1, 128),
            pmm_pk.reshape(lo.n_slots, 2, R1, 128),
            pms_pk.reshape(lo.n_slots, 2, R1, 128))


def _pack_tables(tabs, lo, Rf1):
    """(M, n_pl, 4, R) f32 rotation tables ->
    (n_slots, 2, n_pl, 4, Rf1, 128) f32, ring-shrunk to the kernels'
    data-operand row count."""
    from repro.kernels import ops as kops
    _, n_pl, _, R = tabs.shape
    t = jnp.pad(jnp.asarray(tabs, jnp.float32),
                ((0, 0), (0, 0), (0, 0), (0, Rf1 * 128 - R)))
    return kops._pack_rows(t, lo).reshape(lo.n_slots, 2, n_pl, 4, Rf1, 128)


def _rotation_tables(m_vals, direction, *, phase_kind, n, phi0, fold_rings,
                     n_half):
    """(M, n_pl, 4, R_kernel) f64 tables for every fused phase flavour.

    Uniform unfolded: one plane of uniform_rotation_tables.  Fold: north
    plane = rings [0, nh), south plane row i = full-grid ring R-1-i (the
    staged combine's reversal baked into the table order; rows past the
    southern count stay zero -- the odd-R equator has no mirror).  Bucket:
    one plane of the pure e^{+-i m phi0(r)} tables; the alias fold is the
    host-side scatter/gather."""
    from repro.core import phase
    if phase_kind == "bucket":
        return phase.bucket_rotation_tables(m_vals, phi0, direction)[:, None]
    full = phase.uniform_rotation_tables(m_vals, phi0, n, direction)
    if fold_rings is None:
        return full[:, None]
    nh = n_half
    ns = fold_rings - nh
    north = full[:, :, :nh]
    south = np.zeros_like(north)
    south[:, :, :ns] = full[:, :, nh:][:, :, ::-1]
    return np.stack([north, south], axis=1)


def rotation_tables(m_vals, *, phase_kind, phi0, n=None, fold_rings=None,
                    n_half=None):
    """Host-side rotation tables of both fused directions.

    Returns ``((synth, anal), (rot_synth, rot_anal))``: float32 tables
    (Mr, n_pl, 4, R_kernel) computed in float64 and cast at the end, and
    per direction whether the table rotates at all (False: the exact
    identity, see :func:`_tables_identity`).  Plans build these once and
    pass them to the fused entry points as jit arguments (``tabs=``/
    ``rot=``), so they are never baked into a program as constants."""
    tabs, rot = [], []
    for direction in ("synth", "anal"):
        t = _rotation_tables(m_vals, direction, phase_kind=phase_kind, n=n,
                             phi0=phi0, fold_rings=fold_rings, n_half=n_half)
        tabs.append(t.astype(np.float32))
        rot.append(not _tables_identity(t))
    return tuple(tabs), tuple(rot)


@scoped(LEGENDRE)
def _kernel_synth(a, tabs, x, pmm, pms, *, l_max, var, bf16, lo, lp_size,
                  interpret, spin, rot):
    """Packed fused kernel leg: a (Mr, L1, 2K) + (Mr, n_pl, 4, R) tables ->
    rotated per-plane rows h (Mr, n_pl, R, 2K)."""
    from repro.kernels import ops as kops
    Mr = a.shape[0]
    K2 = a.shape[-1]
    n_pl = tabs.shape[1]
    R = x.shape[0]
    a_pk = jnp.swapaxes(kops._pack_a(a, lo), 1, 2)     # (n_slots, 2K, S)
    Rp, R1, Rf1, x2d, pmm2, pms2 = _prep(lo, x, pmm, pms, var)
    tab_pk = _pack_tables(tabs, lo, Rf1)
    pmaps = kops._pack_maps(lo)
    if var == "vpu":
        out = synth_fused_vpu(a_pk, pmaps, x2d, pmm2, pms2, tab_pk,
                              l_max=l_max, spin=spin, lp_size=lp_size,
                              interpret=interpret)
        out = out.reshape(lo.n_slots, 2, n_pl, K2, Rp)
    else:
        out = synth_fused_mxu(a_pk, pmaps, x2d, pmm2, pms2, tab_pk,
                              l_max=l_max, spin=spin, bf16=bf16,
                              lp_size=lp_size, interpret=interpret,
                              rot=rot)
    seg = jnp.moveaxis(out, 3, -1).reshape(lo.n_slots * 2, n_pl, Rp, K2)
    return kops._unpack_rows(seg, lo, Mr)[:, :, :R, :]


@scoped(LEGENDRE)
def _kernel_anal(fp, tabs, x, pmm, pms, *, l_max, var, bf16, lo, lp_size,
                 interpret, spin, rot):
    """Packed fused kernel leg: per-plane unrotated-input rows fp
    (Mr, n_pl, R, 2K) + anal tables -> packed a (Mr, L1, 2K)."""
    from repro.kernels import ops as kops
    Mr, n_pl, R, K2 = fp.shape
    Rp, R1, Rf1, x2d, pmm2, pms2 = _prep(lo, x, pmm, pms, var)
    tab_pk = _pack_tables(tabs, lo, Rf1)
    pmaps = kops._pack_maps(lo)
    f_pk = kops._pack_rows(
        jnp.pad(fp, ((0, 0), (0, 0), (0, Rf1 * 128 - R), (0, 0))), lo)
    f_pk = f_pk.reshape(lo.n_slots, 2, n_pl, Rf1, 128, K2)
    fk = jnp.moveaxis(f_pk, -1, 3)            # (n_slots, 2, n_pl, 2K, Rf1, 128)
    if var == "vpu":
        out = anal_fused_vpu(fk, pmaps, x2d, pmm2, pms2, tab_pk,
                             l_max=l_max, s_len=lo.S, spin=spin,
                             lp_size=lp_size, interpret=interpret)
    else:
        out = anal_fused_mxu(fk.reshape(lo.n_slots, 2, n_pl, K2, Rp),
                             pmaps, x2d, pmm2, pms2, tab_pk, l_max=l_max,
                             s_len=lo.S, spin=spin, bf16=bf16,
                             lp_size=lp_size, interpret=interpret,
                             rot=rot)
    out = jnp.moveaxis(out, 2, -1).reshape(lo.n_slots, lo.S, K2)
    return kops._unpack_alm(out, lo)


@scoped(PHASE)
def _bucket_scatter(hc, m_vals, layout, pos, neg, n_phi, out_width):
    """Host epilogue of the fused bucket synthesis: rotated rows hc
    (M, R, C) complex64 -> ring samples (R, out_width, C) f32.  The
    alias-fold scatter of core.phase._bucket_synth_body with the phase
    rotation already applied in-kernel."""
    m = np.asarray(m_vals)
    M, R, C = hc.shape
    neg_ok = jnp.asarray(m > 0)[:, None, None]
    nn = jnp.asarray(n_phi)
    out = jnp.zeros((R, out_width, C), jnp.float32)
    for B, sl in zip(layout.lengths, layout.slots):
        sl = np.asarray(sl)
        Rb = sl.shape[0]
        if Rb == 0:
            continue
        dp_b = hc[:, sl, :]                   # (M, Rb, C)
        pos_b, neg_b = pos[:, sl], neg[:, sl]
        row = np.arange(Rb, dtype=np.int32)[None, :] * B
        S = jnp.zeros((Rb * B, C), jnp.complex64)
        S = S.at[jnp.reshape(row + pos_b, (-1,))].add(
            dp_b.reshape(M * Rb, C))
        S = S.at[jnp.reshape(row + neg_b, (-1,))].add(
            jnp.where(neg_ok, jnp.conj(dp_b), 0.0).reshape(M * Rb, C))
        s = jnp.fft.ifft(S.reshape(Rb, B, C), axis=1) * B
        keep = (jnp.arange(B)[None, :] < nn[jnp.asarray(sl)][:, None]
                ).astype(jnp.float32)
        samp = jnp.real(s).astype(jnp.float32) * keep[:, :, None]
        if B < out_width:
            samp = jnp.pad(samp, ((0, 0), (0, out_width - B), (0, 0)))
        out = out.at[jnp.asarray(sl)].set(samp)
    return out


@scoped(PHASE)
def _bucket_gather(maps_w, m_vals, layout, pos, n_phi):
    """Host prologue of the fused bucket analysis: ring samples (R, W, C)
    -> gathered UNrotated spectrum rows (M, R, C) complex64 (the in-kernel
    anal tables apply e^{-i m phi0}).  Mirrors
    core.phase._bucket_anal_core minus the phase factor."""
    M = np.asarray(m_vals).shape[0]
    R, W, C = maps_w.shape
    maps_w = maps_w.astype(jnp.float32)
    nn = jnp.asarray(n_phi)
    delta = jnp.zeros((M, R, C), jnp.complex64)
    for B, sl in zip(layout.lengths, layout.slots):
        sl = np.asarray(sl)
        if sl.shape[0] == 0:
            continue
        xb = maps_w[jnp.asarray(sl)]          # (Rb, W, C)
        xb = xb[:, :B, :] if B <= W else \
            jnp.pad(xb, ((0, 0), (0, B - W), (0, 0)))
        keep = (jnp.arange(B)[None, :] < nn[jnp.asarray(sl)][:, None]
                ).astype(jnp.float32)
        F = jnp.fft.fft(xb * keep[:, :, None], axis=1)         # (Rb, B, C)
        idx = jnp.moveaxis(jnp.asarray(pos[:, sl]), 0, 1)      # (Rb, M)
        Fm = jnp.take_along_axis(F, idx[..., None], axis=1)    # (Rb, M, C)
        delta = delta.at[:, jnp.asarray(sl), :].set(
            jnp.moveaxis(Fm, 1, 0).astype(jnp.complex64))
    return delta


def _synth_chain(a, m_vals, x, pmm, pms, tabs, *, l_max, var, bf16, lo,
                 lp_size, interpret, spin, rot, phase_kind, n=None,
                 fold_rings=None, bucket=None):
    """Weight-free fused synthesis for every fused plan shape:
    a (Mr, L1, 2K) f32 -> maps (R_out, width, C) f32.  ``Mr`` is the
    kernel row count (2M lambda^{+-} rows on the spin path, C = 2K Q|U
    channels out); ``tabs``/``rot`` are the (synth, anal) pairs from
    :func:`rotation_tables`."""
    from repro.core import legendre as leg
    from repro.core import phase
    K2 = a.shape[-1]
    n_k = K2 // 2
    h = _kernel_synth(a, tabs[0], x, pmm, pms, l_max=l_max, var=var,
                      bf16=bf16, lo=lo, lp_size=lp_size, interpret=interpret,
                      spin=spin, rot=rot[0])
    with jax.named_scope(FOLD):
        if fold_rings is not None:
            # in-kernel combine already produced (north | south) planes;
            # the south rows come out in fold order (equator-out),
            # reverse + trim
            ns = fold_rings - x.shape[0]
            flat = jnp.concatenate([h[:, 0], h[:, 1, :ns][:, ::-1]], axis=1)
        else:
            flat = h[:, 0]                    # (Mr, R, 2K)
        if spin:
            dq_re, dq_im, du_re, du_im = leg.spin_unpack_delta(
                flat[..., :n_k], flat[..., n_k:])
            hc = jnp.concatenate([dq_re + 1j * dq_im, du_re + 1j * du_im],
                                 axis=-1).astype(jnp.complex64)  # (M, R, 2K)
            mv = np.asarray(m_vals)[:a.shape[0] // 2]
        else:
            hc = (flat[..., :n_k] + 1j * flat[..., n_k:]).astype(
                jnp.complex64)
            mv = np.asarray(m_vals)
    if phase_kind == "bucket":
        return _bucket_scatter(hc, mv, bucket["layout"], bucket["pos"],
                               bucket["neg"], bucket["n_phi"],
                               bucket["out_width"])
    with jax.named_scope(PHASE):
        R_out, C = hc.shape[1], hc.shape[-1]
        bins, _, _ = phase.uniform_bin_maps(mv, n)
        half = n // 2 + 1
        H = jnp.zeros((R_out, half, C), jnp.complex64)
        H = H.at[:, jnp.asarray(bins)].add(jnp.moveaxis(hc, 0, 1))
        return (jnp.fft.irfft(H, n=n, axis=1) * n).astype(jnp.float32)


def _anal_chain(maps_w, m_vals, x, pmm, pms, tabs, *, l_max, var, bf16, lo,
                lp_size, interpret, spin, rot, phase_kind, n=None,
                fold_rings=None, bucket=None):
    """Weight-free fused analysis core: (already ring-weighted) maps
    (R_full, W, C) f32 -> a (Mr, L1, 2K) f32."""
    from repro.core import legendre as leg
    from repro.core import phase
    mall = np.asarray(m_vals)
    mv = mall[:mall.shape[0] // 2] if spin else mall
    R_full = maps_w.shape[0]
    if phase_kind == "bucket":
        Fm = _bucket_gather(maps_w, mv, bucket["layout"], bucket["pos"],
                            bucket["n_phi"])
    else:
        with jax.named_scope(PHASE):
            F = jnp.fft.rfft(maps_w.astype(jnp.float32), axis=1)  # (R, h, C)
            bins, _, _ = phase.uniform_bin_maps(mv, n)
            Fm = jnp.moveaxis(F[:, jnp.asarray(bins), :], 1, 0)   # (M, R, C)
    with jax.named_scope(FOLD):
        if spin:
            n_k = Fm.shape[-1] // 2
            f_re, f_im = leg.spin_pack_delta(
                jnp.real(Fm[..., :n_k]), jnp.imag(Fm[..., :n_k]),
                jnp.real(Fm[..., n_k:]), jnp.imag(Fm[..., n_k:]))
            f = jnp.concatenate([f_re, f_im], axis=-1).astype(jnp.float32)
        else:
            f = jnp.concatenate([jnp.real(Fm), jnp.imag(Fm)],
                                axis=-1).astype(jnp.float32)   # (M, R, 2K)
        if fold_rings is not None:
            nh = x.shape[0]
            ns = R_full - nh
            f_n = f[:, :nh]
            f_s = jnp.zeros_like(f_n).at[:, :ns].set(f[:, nh:][:, ::-1])
            fp = jnp.stack([f_n, f_s], axis=1)    # (Mr, 2, nh, 2K)
        else:
            fp = f[:, None]                       # (Mr, 1, R, 2K)
    return _kernel_anal(fp, tabs[1], x, pmm, pms, l_max=l_max, var=var,
                        bf16=bf16, lo=lo, lp_size=lp_size,
                        interpret=interpret, spin=spin, rot=rot[1])


def _resolve(m_vals, l_max, lp_size, lo, interpret, mp_vals=None):
    from repro.kernels import pack as kpack
    from repro.kernels.ops import should_interpret
    if lo is None:
        lo = kpack.build_layout(
            np.asarray(m_vals), l_max, lp_size=lp_size,
            mp_vals=None if mp_vals is None else np.asarray(mp_vals))
    if interpret is None:
        interpret = should_interpret()
    return lo, interpret


def _tables(tabs, rot, m_vals, **geometry):
    """The caller's precomputed ``(tabs, rot)`` pair, or one built here
    (host numpy, so a traced caller embeds it as a program constant)."""
    if tabs is None:
        return rotation_tables(m_vals, **geometry)
    return tabs, rot


# The whole-chain adjoints below compose the staged pipeline's transposes:
# scalar  synth^T = fac * anal-core      (fac = 1|2 per m, phase.py)
# spin    synth^T = 0.5 * fac * anal-core:  spin_unpack_delta^T is
#         spin_pack_delta / 2 and spin_pack_alm^T is 2 * spin_unpack_alm,
#         so the pair packing contributes a net 1/2 on the synth adjoint
#         (and its inverse 2 on the anal adjoint).  fac commutes with the
#         Legendre stage (block-diagonal per m) and with the pair packing
#         (both +- rows share one m).  The bucket scatter's transpose is
#         fac * the bucket gather (for real cotangents the conjugate-half
#         scatter bin contributes the conjugate of the positive bin), and
#         the fold combine's transpose is exactly the fold split -- both
#         verified in tests/test_fused.py adjoint identities.


def _linear_synth(a, m_vals, x, pmm, pms, tabs, kw):
    """synth chain + its adjoint (``fac``-compensated anal chain)."""
    from repro.core.phase import _fac_rows
    fac = _fac_rows(m_vals, jnp.float32)
    bsc = 0.5 if kw["spin"] else 1.0

    def fwd(res, a_):
        return _synth_chain(a_, m_vals, *res, **kw)

    def bwd(res, t):
        return bsc * fac * _anal_chain(t, m_vals, *res, **kw)

    return linear_pair(fwd, bwd, (x, pmm, pms, tabs), a)


def _linear_anal(maps, weights, m_vals, x, pmm, pms, tabs, kw):
    """Ring weights outside the linear core, then anal chain + adjoint."""
    from repro.core.phase import _fac_rows
    fac = _fac_rows(m_vals, jnp.float32)
    bsc = 0.5 if kw["spin"] else 1.0
    with jax.named_scope(PHASE):
        w = jnp.asarray(weights, jnp.float32)
        maps_w = jnp.asarray(maps, jnp.float32) * w[:, None, None]

    def fwd(res, mw):
        return _anal_chain(mw, m_vals, *res, **kw)

    def bwd(res, g):
        return _synth_chain(g / (bsc * fac), m_vals, *res, **kw)

    return linear_pair(fwd, bwd, (x, pmm, pms, tabs), maps_w)


def _uniform_kw(m_vals, x, *, l_max, n, phi0, variant, bf16, lo, lp_size,
                interpret, mp_vals, fold_rings, tabs, rot):
    lo, interpret = _resolve(m_vals, l_max, lp_size, lo, interpret, mp_vals)
    tabs, rot = _tables(tabs, rot, m_vals, phase_kind="uniform", n=n,
                        phi0=phi0, fold_rings=fold_rings, n_half=x.shape[0])
    kw = dict(l_max=l_max, var=variant, bf16=bf16, lo=lo, lp_size=lp_size,
              interpret=interpret, spin=2 if lo.spin else 0, rot=rot,
              phase_kind="uniform", n=n, fold_rings=fold_rings)
    return tabs, kw


def _bucket_kw(m_vals, *, l_max, layout, pos, neg, n_phi, phi0, out_width,
               variant, bf16, lo, lp_size, interpret, mp_vals, tabs, rot):
    lo, interpret = _resolve(m_vals, l_max, lp_size, lo, interpret, mp_vals)
    tabs, rot = _tables(tabs, rot, m_vals, phase_kind="bucket", phi0=phi0)
    bucket = dict(layout=layout, pos=np.asarray(pos), neg=np.asarray(neg),
                  n_phi=np.asarray(n_phi), out_width=int(out_width))
    kw = dict(l_max=l_max, var=variant, bf16=bf16, lo=lo, lp_size=lp_size,
              interpret=interpret, spin=2 if lo.spin else 0, rot=rot,
              phase_kind="bucket", bucket=bucket)
    return tabs, kw


def fused_synth(a, m_vals, x, pmm, pms, *, l_max, n, phi0, variant="vpu",
                bf16=False, lo=None, lp_size=128, interpret=None,
                mp_vals=None, fold_rings=None, tabs=None, rot=None):
    """Differentiable fused synthesis on a uniform grid:
    a (Mr, L1, 2K) f32 -> maps (R, n, C).

    Spin-2: pass the stacked lambda^{+-} row set (``m_vals``/``mp_vals``
    from legendre._spin_rows, ``a`` channels from spin_pack_alm as
    re|im); the epilogue unpacks Q/U through the channel axis (C = 2K).
    Equator fold: pass ``fold_rings`` = the full ring count; ``x``/
    ``pmm``/``pms`` cover the northern half only and the north/south
    combine runs in-kernel.  ``tabs``/``rot``: the precomputed
    :func:`rotation_tables` of this geometry (built here when omitted)."""
    tabs, kw = _uniform_kw(m_vals, x, l_max=l_max, n=n, phi0=phi0,
                           variant=variant, bf16=bf16, lo=lo,
                           lp_size=lp_size, interpret=interpret,
                           mp_vals=mp_vals, fold_rings=fold_rings,
                           tabs=tabs, rot=rot)
    return _linear_synth(a, m_vals, x, pmm, pms, tabs, kw)


def fused_anal(maps, weights, m_vals, x, pmm, pms, *, l_max, n, phi0,
               variant="vpu", bf16=False, lo=None, lp_size=128,
               interpret=None, mp_vals=None, fold_rings=None, tabs=None,
               rot=None):
    """Differentiable fused analysis on a uniform grid:
    maps (R, n, C) -> a (Mr, L1, 2K) f32.

    Ring quadrature weights are applied to the maps *outside* the linear
    core (they commute with the phi-axis FFT), keeping the core's adjoint
    the weight-free fused synthesis of the fac-normalised cotangent."""
    tabs, kw = _uniform_kw(m_vals, x, l_max=l_max, n=n, phi0=phi0,
                           variant=variant, bf16=bf16, lo=lo,
                           lp_size=lp_size, interpret=interpret,
                           mp_vals=mp_vals, fold_rings=fold_rings,
                           tabs=tabs, rot=rot)
    return _linear_anal(maps, weights, m_vals, x, pmm, pms, tabs, kw)


def fused_synth_bucket(a, m_vals, x, pmm, pms, *, l_max, layout, pos, neg,
                       n_phi, phi0, out_width, variant="vpu", bf16=False,
                       lo=None, lp_size=128, interpret=None, mp_vals=None,
                       tabs=None, rot=None):
    """Differentiable fused synthesis on a ragged (bucketed) grid:
    a (Mr, L1, 2K) f32 -> maps (R, out_width, C) f32.

    The kernel rotates the Delta rows by e^{+i m phi0(r)} in-register
    (`phase.bucket_rotation_tables`); the alias-fold scatter through the
    per-bucket bin maps (``pos``/``neg`` from `phase.bucket_bin_maps`,
    ``layout`` a BucketLayout) runs on the host around the one kernel, so
    the unrotated Delta never round-trips HBM.  Spin-2 rides exactly like
    :func:`fused_synth` (``mp_vals`` + stacked rows)."""
    tabs, kw = _bucket_kw(m_vals, l_max=l_max, layout=layout, pos=pos,
                          neg=neg, n_phi=n_phi, phi0=phi0,
                          out_width=out_width, variant=variant, bf16=bf16,
                          lo=lo, lp_size=lp_size, interpret=interpret,
                          mp_vals=mp_vals, tabs=tabs, rot=rot)
    return _linear_synth(a, m_vals, x, pmm, pms, tabs, kw)


def fused_anal_bucket(maps, weights, m_vals, x, pmm, pms, *, l_max, layout,
                      pos, neg, n_phi, phi0, variant="vpu", bf16=False,
                      lo=None, lp_size=128, interpret=None, mp_vals=None,
                      tabs=None, rot=None):
    """Differentiable fused analysis on a ragged (bucketed) grid:
    maps (R, W, C) -> a (Mr, L1, 2K) f32.  The per-bucket gather feeds
    unrotated spectrum rows to the kernel; the e^{-i m phi0} rotation
    happens in-register via the anal-direction bucket tables."""
    tabs, kw = _bucket_kw(m_vals, l_max=l_max, layout=layout, pos=pos,
                          neg=neg, n_phi=n_phi, phi0=phi0,
                          out_width=int(maps.shape[1]), variant=variant,
                          bf16=bf16, lo=lo, lp_size=lp_size,
                          interpret=interpret, mp_vals=mp_vals, tabs=tabs,
                          rot=rot)
    return _linear_anal(maps, weights, m_vals, x, pmm, pms, tabs, kw)
