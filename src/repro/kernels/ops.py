"""Jit'd wrappers around the Pallas Legendre kernels.

Responsibilities:
  * padding/layout conversion between the engine's (M, R, K) world and the
    kernels' tiled (Mp, R1, 128 / 2K) world;
  * seed precomputation (float64 -> scaled f32 mantissas);
  * variant selection (VPU broadcast-FMA for few maps, MXU panel matmul for
    many) with env/arg overrides;
  * `interpret=True` execution on a CPU backend vs. compiled Mosaic on a
    TPU backend (never interpret mode there).

These wrappers are the integration point used by core.dist_sht's
``stage1="pallas"`` mode and by the benchmarks.
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import legendre
from repro.core.autodiff import linear_pair
from repro.kernels import legendre_pallas as lk
from repro.kernels import pack as kpack
from repro.kernels import ref as kref
from repro.tracing import LEGENDRE, scoped

__all__ = ["synth", "anal", "delta_from_alm_auto", "alm_from_delta_auto",
           "delta_from_alm_spin_auto", "alm_from_delta_spin_auto",
           "spin_rows", "pick_variant", "pick_layout", "should_interpret"]


def should_interpret() -> bool:
    """Pallas interpret mode unless running on a real TPU backend."""
    return jax.default_backend() != "tpu"


#: Why the staged VPU kernels (plain and packed grids, both directions)
#: are ineligible on a TPU: Mosaic refuses each of them (v5e, jax 0.9).
#: The two analysis kernels abort the compiler -- and the process with it
#: -- so they must never reach compilation, not even inside a measurement.
STAGED_VPU_TPU_ERROR = (
    "the staged VPU kernels do not compile for TPU: synth packed -- "
    "'scatter-add' has no Pallas TPU lowering; synth plain -- Mosaic "
    "infer-vector-layout 'unsupported shape cast'; anal plain and packed "
    "-- Mosaic 'layout.h:320 Check failed: arr.size() >= "
    "layout_rank(implicit_dim)' aborts the compiler")


#: canonical problem size for the vpu/mxu autotune measurement
_AUTOTUNE_LMAX = 32


def _measure_variant(K2: int, var: str) -> float:
    """One warm-up + one timed synth call of ``var`` at the canonical size."""
    import time
    from repro.core import grids as _grids
    l_max = _AUTOTUNE_LMAX
    g = _grids.make_grid("gl", l_max=l_max)
    lm = legendre.log_mu(l_max)
    m_vals = np.arange(l_max + 1)
    pmm, pms = kref.prepare_seeds(m_vals, g.sin_theta, lm)
    a = jnp.ones((l_max + 1, l_max + 1, K2), jnp.float32)
    x32 = jnp.asarray(g.cos_theta, jnp.float32)

    def fn():
        return synth(a, m_vals, x32, pmm, pms, l_max=l_max, variant=var)

    jax.block_until_ready(fn())            # warm-up / compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _autotune_variant(K2: int):
    """Measured vpu-vs-mxu decision, cached by (K2, interpret) signature.
    A measurement that fails raises and caches nothing."""
    from repro.core import cache as plancache
    kind = "disk" if os.environ.get("REPRO_CACHE_DIR") else "memory"
    key = plancache.signature_key("legendre_variant", K2=int(K2),
                                  interpret=should_interpret())
    dec = plancache.load_decision(key, cache=kind)
    if dec is not None and dec.get("variant") in ("vpu", "mxu"):
        return dec["variant"]
    meas = {v: _measure_variant(K2, v) for v in ("vpu", "mxu")}
    best = min(meas, key=meas.get)
    plancache.save_decision(key, {"variant": best, "measured": meas},
                            cache=kind)
    return best


def pick_variant(K2: int, variant: str | None = None) -> str:
    """vpu-vs-mxu selection for the staged kernels: explicit arg >
    $REPRO_LEGENDRE_VARIANT > cached autotune measurement (when
    $REPRO_LEGENDRE_AUTOTUNE is set) > the static ``K2 >= 16`` rule.
    On a TPU only ``mxu`` compiles (:data:`STAGED_VPU_TPU_ERROR`): the
    rule picks it, and an explicit ``vpu`` raises."""
    if variant not in ("vpu", "mxu"):
        variant = os.environ.get("REPRO_LEGENDRE_VARIANT")
    if jax.default_backend() == "tpu":
        if variant == "vpu":
            raise ValueError(f"staged variant 'vpu' requested on a TPU: "
                             f"{STAGED_VPU_TPU_ERROR}")
        return "mxu"
    if variant in ("vpu", "mxu"):
        return variant
    if os.environ.get("REPRO_LEGENDRE_AUTOTUNE", "0") \
            not in ("", "0", "false", "False"):
        tuned = _autotune_variant(K2)
        if tuned is not None:
            return tuned
    return "mxu" if K2 >= 16 else "vpu"


def _concrete_rows(v):
    """Static numpy view of a row array, or None when traced."""
    return None if v is None else legendre._concrete(v)


#: one-time traced-row degradation warning (see pick_layout); benches that
#: accidentally jit m_vals as an argument silently timed the plain kernel
#: under a packed label once (the PR-7 "packed anal slowdown") -- never again.
_TRACED_WARNED = False


def pick_layout(m_vals, layout: str | None = None, mp_vals=None) -> str:
    """packed-vs-plain selection.

    Traced row sets (the distributed stage-1 path) can never build a
    static packing and always run the plain rectangular grid, whatever
    the caller asked for -- warned once per process, because a traced
    ``m_vals`` usually means a bench/jit boundary mistake timing the
    wrong kernel.  Otherwise ``$REPRO_LEGENDRE_LAYOUT`` is the global
    debugging override (it outranks the per-call argument, so it also
    forces plans whose autotuner passes an explicit layout), then the
    explicit ``layout`` argument, then packed by default.  The override
    value ``fused`` is rejected here: the fused pipeline dispatches at
    the plan level, not through the staged wrappers."""
    global _TRACED_WARNED
    if _concrete_rows(m_vals) is None or \
            (mp_vals is not None and _concrete_rows(mp_vals) is None):
        if not _TRACED_WARNED:
            _TRACED_WARNED = True
            warnings.warn(
                "ops.synth/ops.anal received traced m_vals/mp_vals and are "
                "degrading to the plain rectangular layout (a static "
                "packing needs concrete rows). If this is a benchmark or a "
                "jit boundary, close over m_vals instead of passing it as "
                "a jit argument -- otherwise the packed/fused kernels are "
                "never the ones being timed.", RuntimeWarning, stacklevel=3)
        return "plain"
    env = os.environ.get("REPRO_LEGENDRE_LAYOUT")
    if env == "fused":
        raise ValueError(
            "$REPRO_LEGENDRE_LAYOUT=fused cannot be served by the staged "
            "kernel wrappers (ops.synth/ops.anal) -- the fused "
            "Legendre+phase pipeline dispatches at the plan level "
            "(repro.make_plan, layout 'fused'). Use a Plan, or set the "
            "override to 'plain' or 'packed'.")
    if env in ("plain", "packed"):
        return env
    if layout in ("plain", "packed"):
        return layout
    return "packed"


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# ---------------------------------------------------------------------------
# packed-layout conversion (kernels.pack <-> the plain (Mp, L1/R) world)
# ---------------------------------------------------------------------------


def _pack_maps(lo):
    """The five per-slot scalar-prefetch arrays for the packed kernels."""
    return (jnp.asarray(lo.slot_m[:, 0], jnp.int32),
            jnp.asarray(lo.slot_m[:, 1], jnp.int32),
            jnp.asarray(lo.slot_mp[:, 0], jnp.int32),
            jnp.asarray(lo.slot_mp[:, 1], jnp.int32),
            jnp.asarray(lo.slot_seed, jnp.int32))


def _pack_a(a, lo):
    """(Mp, L1, 2K) coefficients -> (n_slots, S, 2K) packed l-streams."""
    Mp, L1, K2 = a.shape
    flat = a.reshape(Mp * L1, K2)
    valid = (lo.a_row >= 0) & (lo.a_l < L1)
    idx = np.where(valid, lo.a_row * L1 + np.maximum(lo.a_l, 0), 0)
    out = jnp.take(flat, jnp.asarray(idx.reshape(-1)), axis=0)
    out = jnp.where(jnp.asarray(valid.reshape(-1))[:, None], out, 0.0)
    return out.reshape(lo.n_slots, lo.S, K2)


def _pack_rows(arr, lo):
    """(Mp, ...) per-row operand -> (n_slots, 2, ...) per-segment."""
    safe = np.maximum(lo.slot_row, 0).reshape(-1)
    out = jnp.take(jnp.asarray(arr), jnp.asarray(safe), axis=0)
    mask = (lo.slot_row >= 0).reshape((-1,) + (1,) * (out.ndim - 1))
    out = jnp.where(jnp.asarray(mask), out, 0)
    return out.reshape((lo.n_slots, 2) + tuple(arr.shape[1:]))


def _unpack_rows(seg, lo, n_rows):
    """(n_slots * 2, ...) per-segment results -> (n_rows, ...) plain rows
    (plan-padding rows come back as zeros)."""
    idx = np.maximum(lo.row_dst, 0)
    out = jnp.take(seg, jnp.asarray(idx), axis=0)
    mask = (lo.row_dst >= 0).reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(jnp.asarray(mask), out, 0.0)


def _unpack_alm(packed, lo):
    """(n_slots, S, 2K) packed l-stream rows -> (n_rows, l_max + 1, 2K)."""
    K2 = packed.shape[-1]
    flat = packed.reshape(lo.n_slots * lo.S, K2)
    src = lo.alm_src
    out = jnp.take(flat, jnp.asarray(np.maximum(src, 0).reshape(-1)), axis=0)
    out = jnp.where(jnp.asarray((src >= 0).reshape(-1))[:, None], out, 0.0)
    return out.reshape(lo.n_rows, lo.l_max + 1, K2)


def _synth_packed(a, lo, x, pmm, pms, *, l_max, fold, var, spin, lp_size,
                  interpret):
    Mp, L1, K2 = a.shape
    R = x.shape[0]
    n_par = 2 if fold else 1
    a_pk = _pack_a(a, lo)
    Rp = _pad_to(R, 1024 if var == "vpu" else 128)
    x_p = jnp.pad(jnp.asarray(x, jnp.float32), (0, Rp - R))
    pmm_pk = _pack_rows(jnp.pad(pmm, ((0, 0), (0, Rp - R))), lo)
    pms_pk = _pack_rows(jnp.pad(pms, ((0, 0), (0, Rp - R))), lo)
    R1 = Rp // 128
    x2d = x_p.reshape(R1, 128)
    pmm2 = pmm_pk.reshape(lo.n_slots, 2, R1, 128)
    pms2 = pms_pk.reshape(lo.n_slots, 2, R1, 128)
    maps = _pack_maps(lo)
    if var == "vpu":
        out = lk.synth_vpu_packed(a_pk, maps, x2d, pmm2, pms2, l_max=l_max,
                                  fold=fold, spin=spin, lp_size=lp_size,
                                  interpret=interpret)
        out = jnp.moveaxis(out, 2, -1)       # (n_slots, Q, R1, 128, 2K)
        out = out.reshape(lo.n_slots, 2 * n_par, Rp, K2)
    else:
        out = lk.synth_mxu_packed(jnp.swapaxes(a_pk, 1, 2), maps, x2d, pmm2,
                                  pms2, l_max=l_max, fold=fold, spin=spin,
                                  lp_size=lp_size, interpret=interpret)
        out = jnp.moveaxis(out, 2, -1)       # (n_slots, Q, R, 2K)
    seg = out.reshape(lo.n_slots * 2, n_par, Rp, K2)
    return _unpack_rows(seg, lo, Mp)[:, :, :R, :]


def _anal_packed(dw, lo, x, pmm, pms, *, l_max, fold, var, spin, lp_size,
                 interpret):
    Mp, n_par, R, K2 = dw.shape
    Rp = _pad_to(R, 1024 if var == "vpu" else 128)
    x_p = jnp.pad(jnp.asarray(x, jnp.float32), (0, Rp - R))
    pmm_pk = _pack_rows(jnp.pad(pmm, ((0, 0), (0, Rp - R))), lo)
    pms_pk = _pack_rows(jnp.pad(pms, ((0, 0), (0, Rp - R))), lo)
    R1 = Rp // 128
    x2d = x_p.reshape(R1, 128)
    pmm2 = pmm_pk.reshape(lo.n_slots, 2, R1, 128)
    pms2 = pms_pk.reshape(lo.n_slots, 2, R1, 128)
    maps = _pack_maps(lo)
    if var == "vpu":
        # Ring-shrink the data operands when the ring axis fits one grid
        # row-block: ship only the ceil(R/128) real 128-lane rows of dw
        # and the seed tables and let the kernel rebuild the zero padding
        # rows in-register (the slow interpret-mode input fetch then only
        # moves real data; same technique as kernels/fused.py).
        rn = _pad_to(R, 128) if Rp == 1024 else Rp
        dw_p = jnp.pad(dw, ((0, 0), (0, 0), (0, rn - R), (0, 0)))
        dwk = jnp.moveaxis(
            _pack_rows(dw_p, lo).reshape(
                lo.n_slots, 2 * n_par, rn // 128, 128, K2), -1, 2)
        pmm2s = _pack_rows(jnp.pad(pmm, ((0, 0), (0, rn - R))), lo) \
            .reshape(lo.n_slots, 2, rn // 128, 128)
        pms2s = _pack_rows(jnp.pad(pms, ((0, 0), (0, rn - R))), lo) \
            .reshape(lo.n_slots, 2, rn // 128, 128)
        out = lk.anal_vpu_packed(dwk, maps, x2d, pmm2s, pms2s, l_max=l_max,
                                 s_len=lo.S, fold=fold, spin=spin,
                                 lp_size=lp_size, interpret=interpret)
    else:
        dw_p = jnp.pad(dw, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
        dw_pk = _pack_rows(dw_p, lo).reshape(lo.n_slots, 2 * n_par, Rp, K2)
        out = lk.anal_mxu_packed(jnp.moveaxis(dw_pk, -1, 2), maps, x2d, pmm2,
                                 pms2, l_max=l_max, s_len=lo.S, fold=fold,
                                 spin=spin, lp_size=lp_size,
                                 interpret=interpret)
        out = jnp.moveaxis(out, 2, -1).reshape(lo.n_slots, lo.S, K2)
    return _unpack_alm(out, lo)


def _resolve_layout(m_vals, layout, mp_vals, l_max, lp_size):
    """Trace-time packed-vs-plain resolution: the packed layout object (or
    None for the plain rectangular grid)."""
    if pick_layout(m_vals, layout, mp_vals) != "packed":
        return None
    return kpack.build_layout(_concrete_rows(m_vals), l_max, lp_size=lp_size,
                              mp_vals=_concrete_rows(mp_vals))


def _synth_exec(a, m_vals, x, pmm, pms, mp_vals, *, l_max, fold, var, lo,
                lp_size, interpret):
    """Synthesis body with the layout/variant decision already made
    (``lo`` is the packed layout or None for plain)."""
    Mp, L1, K2 = a.shape
    R = x.shape[0]
    if lo is not None:
        return _synth_packed(a, lo, x, pmm, pms, l_max=l_max, fold=fold,
                             var=var, spin=mp_vals is not None,
                             lp_size=lp_size, interpret=interpret)
    L1p = _pad_to(L1, lp_size)
    Rp = _pad_to(R, 1024 if var == "vpu" else 128)
    a_p = jnp.pad(a, ((0, 0), (0, L1p - L1), (0, 0)))
    x_p = jnp.pad(jnp.asarray(x, jnp.float32), (0, Rp - R))
    pmm_p = jnp.pad(pmm, ((0, 0), (0, Rp - R)))
    pms_p = jnp.pad(pms, ((0, 0), (0, Rp - R)))
    R1 = Rp // 128
    x2d = x_p.reshape(R1, 128)
    pmm2 = pmm_p.reshape(Mp, R1, 128)
    pms2 = pms_p.reshape(Mp, R1, 128)
    if var == "vpu":
        out = lk.synth_vpu(a_p, jnp.asarray(m_vals, jnp.int32), x2d, pmm2,
                           pms2, l_max=l_max, fold=fold, mp_vals=mp_vals,
                           lp_size=lp_size, interpret=interpret)
        n_par = out.shape[1]
        out = jnp.moveaxis(out, 2, -1)            # (Mp, P, R1, 128, 2K)
        out = out.reshape(Mp, n_par, Rp, K2)
    else:
        out = lk.synth_mxu(jnp.swapaxes(a_p, 1, 2),
                           jnp.asarray(m_vals, jnp.int32), x2d, pmm2, pms2,
                           l_max=l_max, fold=fold, mp_vals=mp_vals,
                           lp_size=lp_size, interpret=interpret)
        out = jnp.moveaxis(out, 2, -1)            # (Mp, P, R, 2K)
    return out[:, :, :R, :]


def _anal_exec(dw, m_vals, x, pmm, pms, mp_vals, *, l_max, l1p, fold, var,
               lo, lp_size, interpret):
    """Analysis body with the layout/variant decision already made."""
    Mp, n_par, R, K2 = dw.shape
    L1 = l_max + 1
    if lo is not None:
        return _anal_packed(dw, lo, x, pmm, pms, l_max=l_max, fold=fold,
                            var=var, spin=mp_vals is not None,
                            lp_size=lp_size, interpret=interpret)
    L1p = _pad_to(L1 if l1p is None else l1p, lp_size)
    Rp = _pad_to(R, 1024 if var == "vpu" else 128)
    dw_p = jnp.pad(dw, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    x_p = jnp.pad(jnp.asarray(x, jnp.float32), (0, Rp - R))
    pmm_p = jnp.pad(pmm, ((0, 0), (0, Rp - R)))
    pms_p = jnp.pad(pms, ((0, 0), (0, Rp - R)))
    R1 = Rp // 128
    x2d = x_p.reshape(R1, 128)
    pmm2 = pmm_p.reshape(Mp, R1, 128)
    pms2 = pms_p.reshape(Mp, R1, 128)
    mv = jnp.asarray(m_vals, jnp.int32)
    if var == "vpu":
        dw_k = jnp.moveaxis(dw_p.reshape(Mp, n_par, R1, 128, K2), -1, 2)
        out = lk.anal_vpu(dw_k, mv, x2d, pmm2, pms2, l_max=l_max, l1p=L1p,
                          fold=fold, mp_vals=mp_vals, lp_size=lp_size,
                          interpret=interpret)
    else:
        out = lk.anal_mxu(jnp.moveaxis(dw_p, -1, 2), mv, x2d, pmm2, pms2,
                          l_max=l_max, l1p=L1p, fold=fold, mp_vals=mp_vals,
                          lp_size=lp_size, interpret=interpret)
        out = jnp.moveaxis(out, 2, -1).reshape(Mp, L1p, K2)
    return out[:, :L1, :]


@scoped(LEGENDRE)
def synth(a, m_vals, x, pmm, pms, *, l_max, fold=False, variant=None,
          mp_vals=None, lp_size=128, interpret=None, layout=None):
    """Kernel-backed synthesis with automatic padding.

    a: (Mp, L1, 2K) f32;  x: (R,) f32;  pmm/pms: (Mp, R).
    ``mp_vals`` (Mp,) switches rows to the spin-weighted (Wigner m')
    recurrence -- seeds must then come from ref.prepare_seeds_spin.
    ``layout`` selects the packed triangular m-pair grid vs the plain
    rectangular one (see :func:`pick_layout`).
    Returns (Mp, P, R, 2K) f32 matching ref.synth_ref.

    Differentiable both ways (when ``L1 == l_max + 1``, which every plan
    layout satisfies): Pallas kernels are opaque to JAX AD, so the VJP is
    the adjoint transform -- the *analysis* kernel with the same seeds,
    variant and packed schedule (synthesis and analysis panels are exact
    transposes of each other; no quadrature weights live at this layer).
    """
    if interpret is None:
        interpret = should_interpret()
    Mp, L1, K2 = a.shape
    var = pick_variant(K2, variant)
    lo = _resolve_layout(m_vals, layout, mp_vals, l_max, lp_size)
    kw = dict(l_max=l_max, fold=fold, var=var, lo=lo, lp_size=lp_size,
              interpret=interpret)
    if L1 != l_max + 1:     # non-plan layout: no adjoint contract, run raw
        return _synth_exec(a, m_vals, x, pmm, pms, mp_vals, **kw)

    def fwd(res, a_):
        m_, x_, pmm_, pms_, mp_ = res
        return _synth_exec(a_, m_, x_, pmm_, pms_, mp_, **kw)

    def bwd(res, g):
        m_, x_, pmm_, pms_, mp_ = res
        return _anal_exec(g, m_, x_, pmm_, pms_, mp_, l1p=None, **kw)

    return linear_pair(fwd, bwd, (m_vals, x, pmm, pms, mp_vals), a)


@scoped(LEGENDRE)
def anal(dw, m_vals, x, pmm, pms, *, l_max, l1p=None, fold=False,
         variant=None, mp_vals=None, lp_size=128, interpret=None,
         layout=None):
    """Kernel-backed analysis with automatic padding.

    dw: (Mp, P, R, 2K) f32;  returns (Mp, L1, 2K) f32 (L1 = l_max+1).
    ``mp_vals`` / ``layout`` as in :func:`synth`.

    Differentiable both ways: the VJP is the *synthesis* kernel with the
    same seeds, variant and packed schedule (see :func:`synth`).
    """
    if interpret is None:
        interpret = should_interpret()
    Mp, n_par, R, K2 = dw.shape
    var = pick_variant(K2, variant)
    lo = _resolve_layout(m_vals, layout, mp_vals, l_max, lp_size)
    kw = dict(l_max=l_max, fold=fold, var=var, lo=lo, lp_size=lp_size,
              interpret=interpret)
    if n_par != (2 if fold else 1):  # non-plan panel count: run raw
        return _anal_exec(dw, m_vals, x, pmm, pms, mp_vals, l1p=l1p, **kw)

    def fwd(res, dw_):
        m_, x_, pmm_, pms_, mp_ = res
        return _anal_exec(dw_, m_, x_, pmm_, pms_, mp_, l1p=l1p, **kw)

    def bwd(res, g):
        m_, x_, pmm_, pms_, mp_ = res
        return _synth_exec(g, m_, x_, pmm_, pms_, mp_, **kw)

    return linear_pair(fwd, bwd, (m_vals, x, pmm, pms, mp_vals), dw)


# ---------------------------------------------------------------------------
# dist_sht stage-1 adapters (the `stage1="pallas"` path)
# ---------------------------------------------------------------------------


def delta_from_alm_auto(a_re, a_im, m_vals, geom, log_mu_all, *, l_max,
                        fold=False, dtype=jnp.float32, variant=None,
                        layout=None):
    """Drop-in for legendre.delta_from_alm(+_folded) backed by the kernels.

    a_re/a_im: (M, L1, K); geom: plan.ring_geometry dict (numpy, static).
    Returns (d_re, d_im): (M, R_pad, K) in plan slot order (fold handled
    internally: even/odd parts recombined and re-interleaved).
    Kernel math is float32; inputs/outputs are cast from/to ``dtype``.
    """
    M, L1, K = a_re.shape
    if fold:
        sin = geom["sin_theta"][0::2]
        x = geom["cos_theta"][0::2]
    else:
        sin = geom["sin_theta"]
        x = geom["cos_theta"]
    pmm, pms = kref.prepare_seeds(m_vals, sin, log_mu_all)
    a = jnp.concatenate([a_re, a_im], axis=-1).astype(jnp.float32)
    out = synth(a, m_vals, jnp.asarray(x, jnp.float32), pmm, pms,
                l_max=l_max, fold=fold, variant=variant,
                layout=layout)                             # (M, P, R', 2K)
    if fold:
        e, o = out[:, 0], out[:, 1]                        # (M, R_north, 2K)
        north, south = e + o, e - o
        inter = jnp.stack([north, south], axis=2)          # (M, Rn, 2, 2K)
        out2 = inter.reshape(M, 2 * north.shape[1], 2 * K)
    else:
        out2 = out[:, 0]
    d_re = out2[..., :K].astype(dtype)
    d_im = out2[..., K:].astype(dtype)
    return d_re, d_im


def alm_from_delta_auto(dw_re, dw_im, m_vals, geom, log_mu_all, *, l_max,
                        fold=False, dtype=jnp.float32, variant=None,
                        layout=None):
    """Drop-in for legendre.alm_from_delta(+_folded) backed by the kernels.

    dw_re/dw_im: (M, R_pad, K) weighted Delta in plan slot order.
    Returns (a_re, a_im): (M, L1, K).
    """
    M, R_pad, K = dw_re.shape
    dw = jnp.concatenate([dw_re, dw_im], axis=-1).astype(jnp.float32)
    if fold:
        n, s = dw[:, 0::2], dw[:, 1::2]
        dwk = jnp.stack([n + s, n - s], axis=1)            # (M, 2, Rn, 2K)
        sin = geom["sin_theta"][0::2]
        x = geom["cos_theta"][0::2]
    else:
        dwk = dw[:, None]
        sin = geom["sin_theta"]
        x = geom["cos_theta"]
    pmm, pms = kref.prepare_seeds(m_vals, sin, log_mu_all)
    out = anal(dwk, m_vals, jnp.asarray(x, jnp.float32), pmm, pms,
               l_max=l_max, fold=fold, variant=variant,
               layout=layout)                              # (M, L1, 2K)
    return out[..., :K].astype(dtype), out[..., K:].astype(dtype)


# ---------------------------------------------------------------------------
# spin-2 adapters: two stacked Wigner-d recurrences (m' = -2 | +2 row
# blocks) through the same kernels; component mixing via legendre.spin_*.
# ---------------------------------------------------------------------------


def spin_rows(m_vals):
    """Stack the m rows for the two spin recurrences: (m2, mp2), (2M,)."""
    return legendre._spin_rows(m_vals)


def delta_from_alm_spin_auto(e_re, e_im, b_re, b_im, m_vals, geom, *, l_max,
                             m_max, dtype=jnp.float32, variant=None,
                             layout=None):
    """Spin-2 drop-in for legendre.delta_from_alm_spin backed by the kernels.

    e/b re/im: (M, L1, K); geom: plan.ring_geometry dict (or any dict with
    ``cos_theta``/``sin_theta``).  Returns (dq_re, dq_im, du_re, du_im),
    each (M, R, K) in the geometry's ring order.  Kernel math is float32.
    """
    from repro.kernels import ref as kref_
    M, L1, K = e_re.shape
    x = geom["cos_theta"]
    sin = geom["sin_theta"]
    m2, mp2 = spin_rows(m_vals)
    a2_re, a2_im = legendre.spin_pack_alm(e_re, e_im, b_re, b_im)
    a = jnp.concatenate([a2_re, a2_im], axis=-1).astype(jnp.float32)
    pmm, pms = kref_.prepare_seeds_spin(m2, mp2, x, sin, m_max=m_max)
    out = synth(a, m2, jnp.asarray(x, jnp.float32), pmm, pms, l_max=l_max,
                fold=False, variant=variant, mp_vals=mp2,
                layout=layout)                              # (2M, 1, R, 2K)
    flat = out[:, 0]
    d_re = flat[..., :K].astype(dtype)
    d_im = flat[..., K:].astype(dtype)
    return legendre.spin_unpack_delta(d_re, d_im)


def alm_from_delta_spin_auto(dq_re, dq_im, du_re, du_im, m_vals, geom, *,
                             l_max, m_max, dtype=jnp.float32, variant=None,
                             layout=None):
    """Spin-2 drop-in for legendre.alm_from_delta_spin backed by the kernels.

    dq/du re/im: (M, R, K) weighted Delta_Q/Delta_U.  Returns
    (e_re, e_im, b_re, b_im), each (M, L1, K).
    """
    from repro.kernels import ref as kref_
    M, R, K = dq_re.shape
    x = geom["cos_theta"]
    sin = geom["sin_theta"]
    m2, mp2 = spin_rows(m_vals)
    d2_re, d2_im = legendre.spin_pack_delta(dq_re, dq_im, du_re, du_im)
    dw = jnp.concatenate([d2_re, d2_im], axis=-1).astype(jnp.float32)
    pmm, pms = kref_.prepare_seeds_spin(m2, mp2, x, sin, m_max=m_max)
    out = anal(dw[:, None], m2, jnp.asarray(x, jnp.float32), pmm, pms,
               l_max=l_max, fold=False, variant=variant, mp_vals=mp2,
               layout=layout)
    a_re = out[..., :K].astype(dtype)
    a_im = out[..., K:].astype(dtype)
    return legendre.spin_unpack_alm(a_re, a_im)
