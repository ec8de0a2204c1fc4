"""Normalised associated Legendre functions via the scaled two-term recurrence.

Implements the paper's §2.1 machinery in a vectorised, branch-free form:

  recurrence (paper eq. 7, with the sign corrected -- the published "+" is a
  typo; the standard normalised recurrence is)

      P_{l,m}(x) = beta_{l,m} * x * P_{l-1,m}(x) - (beta_{l,m}/beta_{l-1,m}) * P_{l-2,m}(x)
      beta_{l,m} = sqrt((4 l^2 - 1) / (l^2 - m^2))                (paper eq. 8)

  seeds (paper eqs. 9-10, normalised convention P_mm = mu_m (1-x^2)^{m/2})

      mu_m   = sqrt(1/(4 pi)) * prod_{k=1..m} sqrt((2k+1)/(2k))
      P_{m+1,m} = sqrt(2m+3) * x * P_mm

  and the under/overflow rescaling: instead of the paper's per-value test and
  scale-vector lookup (a scalar-code construct), we carry every P value as a
  (mantissa, scale) pair with P = mant * 2^(scale * SCALE_BITS), scale <= 0,
  and renormalise with vector selects.  Contributions with scale < 0 (i.e.
  |P| < 2^-(SCALE_BITS/2)) are dropped from accumulations; they are below the
  dtype's resolution by construction.  This is the SIMD-uniform TPU adaptation
  of the paper's scheme (DESIGN.md §2).

The recurrence is pure jnp and dtype-parametric: float64 for the
reference/validation engine, float32 for the device.
The seeds are precomputed on the host in numpy float64 and cast at the end,
so no float64 arithmetic runs on the device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autodiff import linear_pair
from repro.tracing import ACCUMULATE, FOLD, LEGENDRE, RECURRENCE, scoped

__all__ = [
    "log_mu",
    "log_factorials",
    "scale_bits_for",
    "pmm_scaled",
    "recurrence_step",
    "row_step_bytes",
    "planes_per_step",
    "row_blocks",
    "loop_blocks",
    "delta_from_alm",
    "alm_from_delta",
    "delta_from_alm_folded",
    "alm_from_delta_folded",
    # spin-aware harmonic core (Wigner-d generalisation)
    "spin_seeds_scaled",
    "spin_seed_rows",
    "pmm_seed_rows",
    "recurrence_step_general",
    "delta_from_alm_general",
    "alm_from_delta_general",
    "spin_pack_alm",
    "spin_unpack_delta",
    "spin_pack_delta",
    "spin_unpack_alm",
    "delta_from_alm_spin",
    "alm_from_delta_spin",
    "HarmonicCore",
]

_LN2 = float(np.log(2.0))
#: The ring contractions are float32 (or float64) sums; a TPU's default
#: matmul precision would round their float32 operands to bfloat16.
_HIGHEST = jax.lax.Precision.HIGHEST


def scale_bits_for(dtype) -> int:
    """SCALE_BITS used by the scaled recurrence for a given dtype."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.float64):
        return 512
    if dtype == jnp.dtype(jnp.float32):
        return 64
    raise ValueError(f"unsupported recurrence dtype {dtype}")


def log_mu(m_max: int) -> np.ndarray:
    """log(mu_m) for m = 0..m_max (host-side, float64).

    mu_m = sqrt(1/(4 pi)) * prod_{k=1..m} sqrt((2k+1)/(2k)); computed as a
    cumulative sum of logs so it is exact to f64 rounding for any m.
    """
    m = np.arange(1, m_max + 1, dtype=np.float64)
    inc = 0.5 * np.log((2.0 * m + 1.0) / (2.0 * m))
    out = np.empty(m_max + 1, dtype=np.float64)
    out[0] = -0.5 * np.log(4.0 * np.pi)
    out[1:] = out[0] + np.cumsum(inc)
    return out


def pmm_scaled(log_mu_m, m, sin_theta, *, dtype, scale_bits: int):
    """Scaled seed P_mm = mu_m * sin(theta)^m as (mantissa, scale).

    log P_mm = log mu_m + m * log(sin theta); split into scale * SCALE_BITS
    octaves + mantissa so the seed is representable for any m, theta.
    Host-side numpy float64 throughout, cast to ``dtype`` at the end: at
    m ~ 4096, log P_mm ~ -3e4, so a float32 log would cost ~0.2% in the
    seeds.  Returns numpy ``(mant dtype, scale int32)``.
    """
    log_p = (np.asarray(log_mu_m, np.float64)
             + np.asarray(m, np.float64) * np.log(np.asarray(sin_theta,
                                                            np.float64)))
    denom = scale_bits * _LN2
    # round (not floor): keeps the mantissa within [2^-B/2, 2^B/2] and maps
    # any representable P (log_p near 0) to scale == 0 exactly.
    scale = np.minimum(np.round(log_p / denom), 0.0)
    mant = np.exp(log_p - scale * denom)
    return mant.astype(dtype), scale.astype(np.int32)


def _concrete(v):
    """``v`` as a numpy array, or None when it is a tracer (the
    distributed stage-1 path deals its m rows inside shard_map)."""
    if isinstance(v, jax.core.Tracer):
        return None
    return np.asarray(v)


def _seed_rows(m_vals, table, m_max: int):
    """Per-row seeds ``(mant, scale)`` (M, R) from a host table builder.

    ``table(m)`` maps concrete rows (numpy int, every m >= 0) to numpy
    seeds.  Concrete ``m_vals`` are evaluated directly; traced ones gather
    from the table over every m in [0, m_max], so the float64 precompute
    never runs inside a trace.  Rows with m < 0 (padding) get zero seeds.
    """
    m = _concrete(m_vals)
    if m is not None:
        ok = (m >= 0)[:, None]
        mant, scale = table(np.maximum(m, 0).astype(np.int64))
        return np.where(ok, mant, 0).astype(mant.dtype), \
            np.where(ok, scale, 0).astype(np.int32)
    mant, scale = table(np.arange(m_max + 1))
    m_vals = jnp.asarray(m_vals, jnp.int32)
    idx = jnp.clip(m_vals, 0, m_max)
    ok = (m_vals >= 0)[:, None]
    return (jnp.where(ok, jnp.take(jnp.asarray(mant), idx, axis=0), 0),
            jnp.where(ok, jnp.take(jnp.asarray(scale), idx, axis=0), 0))


def pmm_seed_rows(m_vals, sin_theta, log_mu_all, *, dtype, scale_bits: int):
    """Scaled P_mm seeds (M, R) for rows ``m_vals`` (concrete or traced),
    computed on the host in float64 (see :func:`pmm_scaled`)."""
    lm = np.asarray(log_mu_all, np.float64)
    sin = np.asarray(sin_theta, np.float64)[None, :]

    def table(m):
        return pmm_scaled(lm[m][:, None], m[:, None], sin, dtype=dtype,
                          scale_bits=scale_bits)

    return _seed_rows(m_vals, table, lm.shape[0] - 1)


def _beta(l, m, dtype):
    """beta_{l,m}; caller guarantees l > m (paper eq. 8)."""
    l = l.astype(dtype) if hasattr(l, "astype") else jnp.asarray(l, dtype)
    m = m.astype(dtype) if hasattr(m, "astype") else jnp.asarray(m, dtype)
    return jnp.sqrt((4.0 * l * l - 1.0) / (l * l - m * m))


def recurrence_step(l, m, x, mant_prev, mant_curr, scale, pmm_mant, pmm_scale,
                    *, scale_bits: int, dtype):
    """One vectorised step of the scaled recurrence at multipole ``l``.

    Shapes: ``m`` is (M, 1), ``x`` is (1, R) (or any broadcastable pair);
    carries are (M, R).  Returns (new_prev, new_curr, new_scale, value) where
    ``value`` is the descaled P_{l,m} (zero wherever scale < 0 or l < m).
    """
    fdt = dtype
    lf = jnp.asarray(l, fdt)
    mf = m.astype(fdt)
    # beta_{l,m} and beta_{l-1,m}: guard the l <= m+1 cases with safe values.
    # (Also guards padded lanes with m = -1 used by the distributed plan:
    # those never seed, so any finite beta keeps them at exactly zero.)
    safe = lambda v: jnp.where(jnp.isfinite(v), v, 0.0)
    bl = safe(_beta(jnp.maximum(lf, mf + 2.0), m, fdt))
    blm1 = safe(_beta(jnp.maximum(lf - 1.0, mf + 1.0), m, fdt))
    ratio = jnp.where(blm1 > 0, bl / jnp.where(blm1 > 0, blm1, 1.0), 0.0)
    two_m_p3 = jnp.sqrt(jnp.maximum(2.0 * mf + 3.0, 0.0))

    p_rec = bl * x * mant_curr - ratio * mant_prev
    p_first = two_m_p3 * x * mant_curr          # l == m+1 (curr holds P_mm)
    is_seed = l == m                             # (M, 1) broadcast
    is_first = l == m + 1
    before = l < m

    new_curr = jnp.where(before, 0.0,
               jnp.where(is_seed, pmm_mant,
               jnp.where(is_first, p_first, p_rec)))
    new_prev = jnp.where(before | is_seed, 0.0, mant_curr)
    new_scale = jnp.where(is_seed, pmm_scale, scale)

    # Renormalise: if the pair has grown past 2^(B/2), push an octave of
    # 2^B back into the scale (only meaningful while scale < 0).
    big = jnp.asarray(2.0, fdt) ** (scale_bits // 2)
    inv_big2 = jnp.asarray(2.0, fdt) ** (-scale_bits)
    grow = (jnp.abs(new_curr) > big) & (new_scale < 0)
    new_curr = jnp.where(grow, new_curr * inv_big2, new_curr)
    new_prev = jnp.where(grow, new_prev * inv_big2, new_prev)
    new_scale = jnp.where(grow, new_scale + 1, new_scale)
    # Shrink guard (pair heading to underflow while still scaled): rare for
    # the synthesis direction (P grows towards the turning point) but present
    # for completeness and required for very high m at near-polar rings.
    small = (jnp.abs(new_curr) < 1.0 / big) & (jnp.abs(new_prev) < 1.0 / big) \
        & (new_scale > jnp.int32(-32000)) & ~before & ~is_seed
    big2 = jnp.asarray(2.0, fdt) ** scale_bits
    new_curr2 = jnp.where(small, new_curr * big2, new_curr)
    new_prev2 = jnp.where(small, new_prev * big2, new_prev)
    new_scale2 = jnp.where(small, new_scale - 1, new_scale)

    value = jnp.where((new_scale2 == 0) & ~before, new_curr2, 0.0)
    return new_prev2, new_curr2, new_scale2, value


def _prep(m_vals, grid_x, grid_sin, log_mu_all, dtype, scale_bits):
    m = jnp.asarray(m_vals, jnp.int32)[:, None]                  # (M, 1)
    x = jnp.asarray(grid_x, dtype)[None, :]                      # (1, R)
    pmm_mant, pmm_scale = pmm_seed_rows(m_vals, grid_sin, log_mu_all,
                                        dtype=dtype, scale_bits=scale_bits)
    return m, x, pmm_mant, pmm_scale


# ---------------------------------------------------------------------------
# Row blocks.  Every l step streams the (m, ring) carries through memory, and
# at step l the rows with m > l only write zeros: summed over the loop that
# is half the rows of an m_max = l_max transform.  The jnp loops therefore
# run contiguous blocks of m rows, each from its first non-zero l.
# ---------------------------------------------------------------------------

#: Bytes one l step of a row block should stream (see `row_blocks`).
#: Each loop iteration costs a fixed time on the device while its bytes
#: shrink with the block, so the m rows are split only as far as every
#: block's step streams about this much.  From a chip sweep of the block
#: count on a TPU v5e (PERF.md §5), at l_max 4096, K=4: 1141 MB a step at
#: C = 1, 7.95 s a call; 287 MB at C = 4, 3.46 s; 74-96 MB at C = 12-16,
#: 1.33-1.39 s, where a block's working set stays in on-chip memory and
#: an iteration of ~15 device ops takes 30-40 us.  Blocks that fine run
#: ~300 000 device ops a second, more than a profiler trace of a 30 s
#: window keeps (the benchmark's trace reader reads 2 000 000), so the
#: per-stage device times could no longer be read; this floor keeps each
#: op above ~20 us, with C = 4 at l_max 4096, K=4.
BLOCK_STEP_BYTES = 256 << 20

#: Arrays of R values per map that one step's accumulate streams for each
#: m row, by (direction, fold): the analysis reads its weighted Delta
#: (re, im), the synthesis reads and writes its accumulators (re, im).
#: The folded loops stream one parity plane a step (plane l % 2).
_ACCUMULATE_ARRAYS = {("anal", False): 2, ("anal", True): 2,
                      ("synth", False): 4, ("synth", True): 4}


def row_step_bytes(direction: str, *, fold: bool, n_rings: int, K: int,
                   dtype) -> int:
    """Bytes one l step of the jnp loop streams per m row over ``n_rings``
    rings (the northern rings when folded): the recurrence reads and
    writes its three carries and reads both seeds, the accumulate reads
    the value row and its operands (``_ACCUMULATE_ARRAYS``) for K maps."""
    arrays = 9 + _ACCUMULATE_ARRAYS[direction, bool(fold)] * int(K)
    return arrays * int(n_rings) * jnp.dtype(dtype).itemsize


def planes_per_step(direction: str, *, fold: bool) -> int:
    """Operand planes a step of the jnp loop streams per map, by the byte
    model: the unfolded loop has one; a folded step reads the even or the
    odd (l + m) plane, not both (2 would mean both)."""
    return (_ACCUMULATE_ARRAYS[direction, bool(fold)]
            // _ACCUMULATE_ARRAYS[direction, False])


def row_blocks(m_vals, row_bytes: int) -> int:
    """Rows per block of the jnp loop, or 0 for one loop over every row.

    The m rows are split into C contiguous blocks of a multiple of 8
    rows, C the count nearest to the step's bytes over
    :data:`BLOCK_STEP_BYTES` (``row_bytes`` per row, from
    :func:`row_step_bytes`), so a block step streams at least 3/4 of it.
    Rounded down instead, a step of 1.6 x that took one block: the folded
    synthesis at l_max 4096, K=1, 2.60 s a call on a v5e against 0.77 s
    in 3 blocks (PERF.md §6).  Only the row count matters, so traced
    ``m_vals`` (the distributed stage 1 inside shard_map, whose rows are
    static in shape) get the blocks of concrete rows of the same count.
    """
    M = int(np.shape(m_vals)[0])
    c = int(M * row_bytes / BLOCK_STEP_BYTES + 0.5)
    if c < 2:
        return 0
    rows = -(-M // c)
    rows = -(-rows // 8) * 8
    return rows if rows < M else 0


def loop_blocks(m_vals, *, l_max: int, row_bytes: int) -> dict:
    """What the jnp loop runs for these rows: its block count, rows per
    block, and the row-steps it runs as a share of the full
    ``M x (l_max + 1)`` rectangle (1.0 for one full loop; about
    (C + 1) / 2C for C blocks of the rows m = 0..l_max)."""
    m = np.asarray(m_vals).reshape(-1)
    M, L = m.shape[0], l_max + 1
    rows = row_blocks(m, row_bytes)
    if not rows:
        return {"blocks": 1, "rows_per_block": M, "row_step_share": 1.0}
    C = -(-M // rows)
    mb = np.concatenate([m, np.full(C * rows - M, -1)]).reshape(C, rows)
    l0 = np.where(mb >= 0, mb, L).min(axis=1)
    return {"blocks": C, "rows_per_block": rows,
            "row_step_share": float(rows * (L - l0).sum() / (M * L))}


def _by_row_blocks(loop, block_rows: int, l_max: int, m, *rows):
    """``loop(l_lo, m, *rows)`` over all m rows, or block by block.

    ``loop`` runs the recurrence from ``l_lo`` to ``l_max`` over the rows
    it is given (axis 0 of ``m`` and of each of ``rows``) and returns
    arrays with those rows on axis 0.  With ``block_rows`` 0 it runs once,
    from l = 0.  Otherwise the rows are padded with m = -1 rows (zero
    seeds: they stay zero) to C blocks of ``block_rows``, and one compiled
    loop is mapped over the blocks, each from l0 = its least m.  Before l0
    the full loop leaves every carry of the block at zero and writes only
    zeros, so each row runs the same steps in the same l order.
    """
    if not block_rows:
        return loop(0, m, *rows)
    M = m.shape[0]
    C = -(-M // block_rows)

    def split(a, fill):
        pad = [(0, C * block_rows - M)] + [(0, 0)] * (a.ndim - 1)
        a = jnp.pad(a, pad, constant_values=fill)
        return a.reshape((C, block_rows) + a.shape[1:])

    m_b = split(m, -1)
    l0 = jnp.min(jnp.where(m_b >= 0, m_b, l_max + 1), axis=(1, 2))
    out = jax.lax.map(lambda blk: loop(*blk),
                      (l0, m_b, *(split(a, 0) for a in rows)))
    return tuple(o.reshape((C * block_rows,) + o.shape[2:])[:M] for o in out)


def _blocks_for(m_vals, direction, *, fold, n_rings, K, dtype) -> int:
    return row_blocks(m_vals, row_step_bytes(direction, fold=fold,
                                             n_rings=n_rings, K=K,
                                             dtype=dtype))


_IMPL_STATICS = ("l_max", "scale_bits", "dtype_name", "block_rows")


@functools.partial(jax.jit, static_argnames=_IMPL_STATICS)
def _delta_from_alm_impl(a_re, a_im, m, x, pmm_mant, pmm_scale, *, l_max,
                         scale_bits, dtype_name, block_rows=0):
    dtype = jnp.dtype(dtype_name)
    R, K = x.shape[1], a_re.shape[-1]

    def loop(lo, m, pmm_mant, pmm_scale, a_re, a_im):
        M = m.shape[0]
        # K-major rows throughout the loop (see `_scan_to_mlk`): the a_lm
        # stream as (L, K*M), the accumulators as (K*M, R).
        rows_re = jnp.transpose(a_re, (1, 2, 0)).reshape(l_max + 1, K * M)
        rows_im = jnp.transpose(a_im, (1, 2, 0)).reshape(l_max + 1, K * M)
        carry0 = (
            jnp.zeros((M, R), dtype),          # P_{l-2} mantissa
            jnp.zeros((M, R), dtype),          # P_{l-1} mantissa
            jnp.zeros((M, R), jnp.int32),      # scale
            jnp.zeros((K * M, R), dtype),      # d_re accumulator
            jnp.zeros((K * M, R), dtype),      # d_im accumulator
        )

        def body(l, carry):
            mp, mc, sc, dre, dim = carry
            with jax.named_scope(RECURRENCE):
                mp, mc, sc, val = recurrence_step(
                    l, m, x, mp, mc, sc, pmm_mant, pmm_scale,
                    scale_bits=scale_bits, dtype=dtype)
            # Delta_m(r) += a_{l,m} * P_{l,m}(r)   (paper eq. 12)
            with jax.named_scope(ACCUMULATE):
                are = jax.lax.dynamic_index_in_dim(rows_re, l, axis=0,
                                                   keepdims=False)
                aim = jax.lax.dynamic_index_in_dim(rows_im, l, axis=0,
                                                   keepdims=False)
                dre = dre + (are.reshape(K, M)[:, :, None]
                             * val).reshape(K * M, R)
                dim = dim + (aim.reshape(K, M)[:, :, None]
                             * val).reshape(K * M, R)
            return mp, mc, sc, dre, dim

        _, _, _, d_re, d_im = jax.lax.fori_loop(lo, l_max + 1, body, carry0)
        unrow = lambda d: jnp.transpose(d.reshape(K, M, R), (1, 2, 0))
        return unrow(d_re), unrow(d_im)

    return _by_row_blocks(loop, block_rows, l_max, m, pmm_mant, pmm_scale,
                          a_re, a_im)


@scoped(LEGENDRE)
def delta_from_alm(a_re, a_im, m_vals, grid_x, grid_sin, log_mu_all, *,
                   l_max: int, dtype=jnp.float64):
    """Synthesis inner step: Delta^A_m(r) = sum_l a_lm P_lm(cos theta_r).

    a_re/a_im: (M, l_max+1, K) with rows l < m zero-padded.
    Returns (d_re, d_im): (M, R, K).  This is paper Algorithm 2 STEP 2 /
    Algorithm 3 STEP 2, vectorised over (m, ring) with the l loop sequential.
    The rows run in blocks from their first non-zero l (`row_blocks`).

    Differentiable both ways via the adjoint identity (VJP = analysis with
    unit weights); ``m_vals`` may be traced (the distributed stage-1 path).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    gx = np.asarray(grid_x)
    gs = np.asarray(grid_sin, np.float64)
    lm_all = np.asarray(log_mu_all)
    a_re = jnp.asarray(a_re, dtype)
    a_im = jnp.asarray(a_im, dtype)
    assert a_re.shape[1] == l_max + 1, (a_re.shape, l_max)
    R = gx.shape[0]
    kw = dict(l_max=l_max, scale_bits=sb, dtype_name=dtype.name,
              block_rows=_blocks_for(m_vals, "synth", fold=False, n_rings=R,
                                     K=a_re.shape[-1], dtype=dtype))

    def fwd(pre, ops):
        ar, ai = ops
        m, x, pm, ps = pre
        return _delta_from_alm_impl(ar, ai, m, x, pm, ps, **kw)

    def bwd(pre, cts):
        gd_re, gd_im = cts
        m, x, pm, ps = pre
        ones = jnp.ones((R,), dtype)
        return _alm_from_delta_impl(gd_re, gd_im, m, x, pm, ps, ones, **kw)

    return linear_pair(fwd, bwd, _prep(m_vals, gx, gs, lm_all, dtype, sb),
                       (a_re, a_im))


def _scan_to_mlk(a, K: int):
    """Per-l loop outputs stacked as (L, K*M) -> (M, L, K).  Each step
    emits its (K, M) row block flattened: a stack with K as its minor
    dimension is padded to 128 lanes on a TPU (32x at K=4: 16 GB at
    l_max=4096), and a rank-3 (L, K, M) stack still gets that layout from
    XLA's layout assignment (it propagates the (M, L, K) result back)."""
    return jnp.transpose(a.reshape(a.shape[0], K, -1), (2, 0, 1))


def _stack_loop(lo, l_max, M, K, carry0, step, dtype):
    """``fori_loop`` from ``lo`` to ``l_max`` of ``step(l, carry) ->
    (carry, (a_re_l, a_im_l))``, the per-l (K*M,) outputs stacked as
    (L, K*M) (rows before ``lo`` stay zero) -> two (M, L, K) arrays."""
    def body(l, c):
        carry, (a_re, a_im) = c
        carry, (a_re_l, a_im_l) = step(l, carry)
        upd = lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, l, 0)
        return carry, (upd(a_re, a_re_l), upd(a_im, a_im_l))

    out0 = (jnp.zeros((l_max + 1, K * M), dtype),) * 2
    _, (a_re, a_im) = jax.lax.fori_loop(lo, l_max + 1, body, (carry0, out0))
    return _scan_to_mlk(a_re, K), _scan_to_mlk(a_im, K)


@functools.partial(jax.jit, static_argnames=_IMPL_STATICS)
def _alm_from_delta_impl(d_re, d_im, m, x, pmm_mant, pmm_scale, w, *, l_max,
                         scale_bits, dtype_name, block_rows=0):
    dtype = jnp.dtype(dtype_name)
    R, K = x.shape[1], d_re.shape[-1]
    dw_re = d_re * w[None, :, None]
    dw_im = d_im * w[None, :, None]

    def loop(lo, m, pmm_mant, pmm_scale, dw_re, dw_im):
        M = m.shape[0]

        def step(l, carry):
            mp, mc, sc = carry
            with jax.named_scope(RECURRENCE):
                mp, mc, sc, val = recurrence_step(
                    l, m, x, mp, mc, sc, pmm_mant, pmm_scale,
                    scale_bits=scale_bits, dtype=dtype)
            # a_{l,m} = sum_r w_r Delta^S_m(r) P_lm(r)   (paper eq. 13)
            with jax.named_scope(ACCUMULATE):
                a_re_l = jnp.einsum("mr,mrk->km", val, dw_re,
                                    precision=_HIGHEST).reshape(-1)
                a_im_l = jnp.einsum("mr,mrk->km", val, dw_im,
                                    precision=_HIGHEST).reshape(-1)
            return (mp, mc, sc), (a_re_l, a_im_l)

        carry0 = (jnp.zeros((M, R), dtype), jnp.zeros((M, R), dtype),
                  jnp.zeros((M, R), jnp.int32))
        return _stack_loop(lo, l_max, M, K, carry0, step, dtype)

    return _by_row_blocks(loop, block_rows, l_max, m, pmm_mant, pmm_scale,
                          dw_re, dw_im)


@scoped(LEGENDRE)
def alm_from_delta(d_re, d_im, m_vals, grid_x, grid_sin, weights, log_mu_all,
                   *, l_max: int, dtype=jnp.float64):
    """Analysis inner step: a_lm = sum_r w_r Delta^S_m(r) P_lm(cos theta_r).

    d_re/d_im: (M, R, K).  Returns (a_re, a_im): (M, l_max+1, K) with rows
    l < m exactly zero.  Paper Algorithm 1 STEP 3.  The rows run in
    blocks from their first non-zero l (`row_blocks`).

    Differentiable both ways via the adjoint identity (VJP = weights times
    synthesis of the cotangent).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    gx = np.asarray(grid_x)
    gs = np.asarray(grid_sin, np.float64)
    lm_all = np.asarray(log_mu_all)
    d_re = jnp.asarray(d_re, dtype)
    d_im = jnp.asarray(d_im, dtype)
    kw = dict(l_max=l_max, scale_bits=sb, dtype_name=dtype.name,
              block_rows=_blocks_for(m_vals, "anal", fold=False,
                                     n_rings=gx.shape[0], K=d_re.shape[-1],
                                     dtype=dtype))

    def fwd(res, ops):
        pre, w = res
        dr, di = ops
        m, x, pm, ps = pre
        return _alm_from_delta_impl(dr, di, m, x, pm, ps, w, **kw)

    def bwd(res, cts):
        pre, w = res
        ga_re, ga_im = cts
        m, x, pm, ps = pre
        gd_re, gd_im = _delta_from_alm_impl(ga_re, ga_im, m, x, pm, ps, **kw)
        return gd_re * w[None, :, None], gd_im * w[None, :, None]

    return linear_pair(fwd, bwd, (_prep(m_vals, gx, gs, lm_all, dtype, sb),
                                  jnp.asarray(weights, dtype)),
                       (d_re, d_im))


# ---------------------------------------------------------------------------
# Equator-folded variants (libpsht/libsharp-style; the default of symmetric
# spin-0 plans, see `transform.make_plan`).
#
# P_lm(-x) = (-1)^(l+m) P_lm(x), so for a grid symmetric about the equator the
# recurrence only needs to run over the northern half of the rings:
#   Delta(north r) = E(r) + O(r),   Delta(mirror r) = E(r) - O(r)
# with E/O the even/odd (l+m) partial sums.  Row m at step l needs the even
# plane when l + m is even, so with each row's two planes stacked by the
# parity of m (plane 0: the one used at even l) the plane a step reads is
# plane l % 2 for every row: one dynamic index, and each step streams one
# plane, not both.
# ---------------------------------------------------------------------------


def _by_m_parity(m, even, odd):
    """Per row: ``even`` where m is even, ``odd`` where it is odd (rows on
    axis 0; ``m`` is (M, 1))."""
    return jnp.where((m % 2 == 0).reshape((-1,) + (1,) * (even.ndim - 1)),
                     even, odd)


@functools.partial(jax.jit, static_argnames=_IMPL_STATICS)
def _delta_from_alm_folded_impl(a_re, a_im, m, x, pmm_mant, pmm_scale, *,
                                l_max, scale_bits, dtype_name, block_rows=0):
    dtype = jnp.dtype(dtype_name)
    R = x.shape[1]                     # R = number of *northern* rings
    K = a_re.shape[-1]

    def loop(lo, m, pmm_mant, pmm_scale, a_re, a_im):
        M = m.shape[0]
        # K-major rows as in `_delta_from_alm_impl`: the a_lm stream as
        # (L, K*M), the accumulators as (2, K*M, R), one plane per parity
        # of l.
        rows_re = jnp.transpose(a_re, (1, 2, 0)).reshape(l_max + 1, K * M)
        rows_im = jnp.transpose(a_im, (1, 2, 0)).reshape(l_max + 1, K * M)
        carry0 = (jnp.zeros((M, R), dtype), jnp.zeros((M, R), dtype),
                  jnp.zeros((M, R), jnp.int32),
                  jnp.zeros((2, K * M, R), dtype),
                  jnp.zeros((2, K * M, R), dtype))

        def body(l, carry):
            mp, mc, sc, dre, dim = carry
            with jax.named_scope(RECURRENCE):
                mp, mc, sc, val = recurrence_step(
                    l, m, x, mp, mc, sc, pmm_mant, pmm_scale,
                    scale_bits=scale_bits, dtype=dtype)
            with jax.named_scope(ACCUMULATE):
                are = jax.lax.dynamic_index_in_dim(rows_re, l, axis=0,
                                                   keepdims=False)
                aim = jax.lax.dynamic_index_in_dim(rows_im, l, axis=0,
                                                   keepdims=False)

                def add(d, a):
                    plane = jax.lax.dynamic_index_in_dim(d, l % 2, axis=0,
                                                         keepdims=False)
                    plane = plane + (a.reshape(K, M)[:, :, None]
                                     * val).reshape(K * M, R)
                    return jax.lax.dynamic_update_index_in_dim(
                        d, plane, l % 2, axis=0)

                dre, dim = add(dre, are), add(dim, aim)
            return mp, mc, sc, dre, dim

        _, _, _, d_re, d_im = jax.lax.fori_loop(lo, l_max + 1, body, carry0)
        unrow = lambda d: jnp.transpose(d.reshape(K, M, R), (1, 2, 0))
        r0, r1, i0, i1 = map(unrow, (d_re[0], d_re[1], d_im[0], d_im[1]))
        # even l holds the even (l + m) partial of even rows, the odd one
        # of odd rows
        return (_by_m_parity(m, r0, r1), _by_m_parity(m, i0, i1),
                _by_m_parity(m, r1, r0), _by_m_parity(m, i1, i0))

    return _by_row_blocks(loop, block_rows, l_max, m, pmm_mant, pmm_scale,
                          a_re, a_im)


@scoped(LEGENDRE)
def delta_from_alm_folded(a_re, a_im, m_vals, north_x, north_sin, log_mu_all,
                          *, l_max: int, dtype=jnp.float64):
    """Folded synthesis: returns even/odd partials over the northern rings.

    (d_even_re, d_even_im, d_odd_re, d_odd_im), each (M, R_north, K).
    North ring r: even + odd; its mirror: even - odd.  The rows run in
    blocks from their first non-zero l (`row_blocks`), and each step adds
    to the accumulators of its parity of l.

    Differentiable both ways: the VJP is the folded analysis of the even/odd
    cotangent partials (the parity split is its own transpose).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    gx = np.asarray(north_x)
    gs = np.asarray(north_sin, np.float64)
    lm_all = np.asarray(log_mu_all)
    a_re = jnp.asarray(a_re, dtype)
    a_im = jnp.asarray(a_im, dtype)
    assert a_re.shape[1] == l_max + 1, (a_re.shape, l_max)
    kw = dict(l_max=l_max, scale_bits=sb, dtype_name=dtype.name,
              block_rows=_blocks_for(m_vals, "synth", fold=True,
                                     n_rings=gx.shape[0], K=a_re.shape[-1],
                                     dtype=dtype))

    def fwd(pre, ops):
        ar, ai = ops
        m, x, pm, ps = pre
        return _delta_from_alm_folded_impl(ar, ai, m, x, pm, ps, **kw)

    def bwd(pre, cts):
        ge_re, ge_im, go_re, go_im = cts
        m, x, pm, ps = pre
        return _alm_from_delta_folded_impl(ge_re, ge_im, go_re, go_im, m, x,
                                           pm, ps, **kw)

    return linear_pair(fwd, bwd, _prep(m_vals, gx, gs, lm_all, dtype, sb),
                       (a_re, a_im))


@functools.partial(jax.jit, static_argnames=_IMPL_STATICS)
def _alm_from_delta_folded_impl(s_e_re, s_e_im, s_o_re, s_o_im, m, x,
                                pmm_mant, pmm_scale, *, l_max, scale_bits,
                                dtype_name, block_rows=0):
    dtype = jnp.dtype(dtype_name)
    R, K = x.shape[1], s_e_re.shape[-1]
    # each row's planes (M, 2, R, K) by the parity of l that reads them:
    # plane 0 (even l) is the even sum of even rows, the odd sum of odd
    with jax.named_scope(FOLD):
        planes_re = jnp.stack([_by_m_parity(m, s_e_re, s_o_re),
                               _by_m_parity(m, s_o_re, s_e_re)], axis=1)
        planes_im = jnp.stack([_by_m_parity(m, s_e_im, s_o_im),
                               _by_m_parity(m, s_o_im, s_e_im)], axis=1)

    def loop(lo, m, pmm_mant, pmm_scale, planes_re, planes_im):
        M = m.shape[0]

        def step(l, carry):
            mp, mc, sc = carry
            with jax.named_scope(RECURRENCE):
                mp, mc, sc, val = recurrence_step(
                    l, m, x, mp, mc, sc, pmm_mant, pmm_scale,
                    scale_bits=scale_bits, dtype=dtype)
            with jax.named_scope(ACCUMULATE):
                sre = jax.lax.dynamic_index_in_dim(planes_re, l % 2, axis=1,
                                                   keepdims=False)
                sim = jax.lax.dynamic_index_in_dim(planes_im, l % 2, axis=1,
                                                   keepdims=False)
                a_re_l = jnp.einsum("mr,mrk->km", val, sre,
                                    precision=_HIGHEST).reshape(-1)
                a_im_l = jnp.einsum("mr,mrk->km", val, sim,
                                    precision=_HIGHEST).reshape(-1)
            return (mp, mc, sc), (a_re_l, a_im_l)

        carry0 = (jnp.zeros((M, R), dtype), jnp.zeros((M, R), dtype),
                  jnp.zeros((M, R), jnp.int32))
        return _stack_loop(lo, l_max, M, K, carry0, step, dtype)

    return _by_row_blocks(loop, block_rows, l_max, m, pmm_mant, pmm_scale,
                          planes_re, planes_im)


@scoped(LEGENDRE)
def alm_from_delta_folded(sum_e_re, sum_e_im, sum_o_re, sum_o_im, m_vals,
                          north_x, north_sin, log_mu_all, *, l_max: int,
                          dtype=jnp.float64):
    """Folded analysis.  Inputs are the pre-folded weighted sums over ring
    pairs: sum_e = w_n*Delta(north) + w_s*Delta(south mirror), sum_o = the
    difference; an equator ring, if any, enters sum_e alone (the caller
    pairs it with zero; P_lm(0) = 0 for odd l + m).  Each (M, R_north, K).
    The rows run in blocks from their first non-zero l (`row_blocks`), and
    each step reads the one sum its rows need.

    Differentiable both ways: the VJP is the folded synthesis of the alm
    cotangent (even/odd partials of the gradient).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    gx = np.asarray(north_x)
    gs = np.asarray(north_sin, np.float64)
    lm_all = np.asarray(log_mu_all)
    ops = tuple(jnp.asarray(v, dtype)
                for v in (sum_e_re, sum_e_im, sum_o_re, sum_o_im))
    kw = dict(l_max=l_max, scale_bits=sb, dtype_name=dtype.name,
              block_rows=_blocks_for(m_vals, "anal", fold=True,
                                     n_rings=gx.shape[0], K=ops[0].shape[-1],
                                     dtype=dtype))

    def fwd(pre, ops_):
        se_re, se_im, so_re, so_im = ops_
        m, x, pm, ps = pre
        return _alm_from_delta_folded_impl(se_re, se_im, so_re, so_im, m, x,
                                           pm, ps, **kw)

    def bwd(pre, cts):
        ga_re, ga_im = cts
        m, x, pm, ps = pre
        return _delta_from_alm_folded_impl(ga_re, ga_im, m, x, pm, ps, **kw)

    return linear_pair(fwd, bwd, _prep(m_vals, gx, gs, lm_all, dtype, sb),
                       ops)


# ===========================================================================
# Spin-aware harmonic core (the Wigner-d generalisation of the above).
#
# The scalar P_lm are the m' = 0 slice of the normalised Wigner-d functions
#
#     lam^{(m')}_lm(theta) = (-1)^m sqrt((2l+1)/4pi) d^l_{m,m'}(theta),
#
# and spin-s transforms need the m' = -s / m' = +s slices: for polarisation
# (spin 2, Stokes Q/U <-> E/B) the spin-(+2) harmonics are built from
# lam^{(-2)} and the spin-(-2) ones from lam^{(+2)} (the lambda^+/- pair of
# libsharp is just their half-sum/half-difference).  All slices satisfy ONE
# three-term recurrence in l (fixed m, m'), the standard Wigner-d recursion
#
#     lam_l = (a_l x + b_l) lam_{l-1} - c_l lam_{l-2},      l > l0,
#     l0   = max(m, |m'|),
#     D_l  = sqrt((l^2 - m^2)(l^2 - m'^2)),
#     a_l  = l sqrt(4l^2 - 1) / D_l,
#     b_l  = -m m' sqrt(4l^2 - 1) / ((l-1) D_l),
#     c_l  = sqrt((2l+1)/(2l-3)) l D_{l-1} / ((l-1) D_l),
#
# which reduces exactly to the scalar recurrence at m' = 0 (b_l = 0,
# a_l = beta_{l,m}, c_l = beta_{l,m}/beta_{l-1,m}) and needs no special
# "first step" case: c_{l0+1} contains D_{l0} = 0, so the lam_{l0-1} term
# vanishes by construction.  The (mantissa, scale) rescaling of the scalar
# engine carries over unchanged.
#
# Seeds at l0 (derived from d^j_{j,m'} and the d^2 table via the standard
# Wigner-d symmetries; signs folded with the (-1)^m of the lam definition):
#
#   m >= |m'|:  lam^{(m')}_{m,m} = sqrt((2m+1)/4pi)
#                 * sqrt((2m)! / ((m+m')!(m-m')!))
#                 * cos(t/2)^{m+m'} sin(t/2)^{m-m'}          (positive)
#   m' = +-2, m = 0:  lam^{(+-2)}_{2,0} =  sqrt(5/4pi) sqrt(6)/4 sin^2 t
#   m' = -2,  m = 1:  lam^{(-2)}_{2,1} =  sqrt(5/4pi) (sin t / 2) (1 - x)
#   m' = +2,  m = 1:  lam^{(+2)}_{2,1} = -sqrt(5/4pi) (sin t / 2) (1 + x)
#
# Spin-2 synthesis / analysis then reuse the whole scalar pipeline through
# the "+/-" component packing (a^+- = -(E +- iB), Delta_Q +- i Delta_U):
# two independent recurrences (m' = -2 and m' = +2) stacked along the m-row
# axis, each accumulating exactly like a scalar transform.
# ===========================================================================


def log_factorials(n_max: int) -> np.ndarray:
    """log(n!) for n = 0..n_max (host-side float64 cumulative log-sum)."""
    out = np.zeros(n_max + 1, dtype=np.float64)
    if n_max >= 1:
        out[1:] = np.cumsum(np.log(np.arange(1, n_max + 1, dtype=np.float64)))
    return out


def spin_seeds_scaled(m_vals, mprime_vals, grid_x, grid_sin, logfact, *,
                      dtype, scale_bits: int):
    """Scaled seeds lam^{(m')}_{l0,m} as (mantissa, scale), l0 = max(m,|m'|).

    ``m_vals``/``mprime_vals``: (Ms,) concrete int rows (m < 0 rows are
    padding -> zero seeds); ``grid_x``/``grid_sin``: (R,) float64;
    ``logfact``: host table from :func:`log_factorials`, length >=
    2*max(m)+1.  Host-side numpy float64 throughout, cast to ``dtype`` at
    the end (see :func:`pmm_scaled`); traced rows go through
    :func:`spin_seed_rows`.  Currently |m'| must be 0 or 2.
    """
    m = np.asarray(m_vals, np.int64)[:, None]                    # (Ms, 1)
    mp = np.asarray(mprime_vals, np.int64)[:, None]
    x = np.asarray(grid_x, np.float64)[None, :]                  # (1, R)
    sin_t = np.asarray(grid_sin, np.float64)[None, :]
    lf = np.asarray(logfact, np.float64)
    mf = m.astype(np.float64)
    mpf = mp.astype(np.float64)

    # log cos(t/2), log sin(t/2) from x = cos t (grids never hit the poles)
    log_c = 0.5 * np.log(np.maximum((1.0 + x) / 2.0, 1e-300))
    log_s = 0.5 * np.log(np.maximum((1.0 - x) / 2.0, 1e-300))

    # --- general m >= |m'| branch (log domain; also the scalar m' = 0 seed)
    msafe = np.maximum(m, 0)
    idx = lambda v: np.clip(v, 0, lf.shape[0] - 1)
    log_norm = 0.5 * (np.log(2.0 * np.maximum(mf, 0.0) + 1.0)
                      - np.log(4.0 * np.pi))
    log_ratio = 0.5 * (lf[idx(2 * msafe)] - lf[idx(msafe + mp)]
                       - lf[idx(msafe - mp)])
    log_p = (log_norm + log_ratio
             + (mf + mpf) * log_c + (mf - mpf) * log_s)
    denom = scale_bits * _LN2
    scale_g = np.minimum(np.round(log_p / denom), 0.0)
    mant_g = np.exp(log_p - scale_g * denom)

    # --- |m'| = 2, m < 2 branches (O(1) values, unscaled)
    c5 = float(np.sqrt(5.0 / (4.0 * np.pi)))
    v_m0 = c5 * (np.sqrt(6.0) / 4.0) * sin_t * sin_t
    v_m1 = np.where(mp < 0,
                    c5 * 0.5 * sin_t * (1.0 - x),       # m' = -2
                    -c5 * 0.5 * sin_t * (1.0 + x))      # m' = +2
    low = (m < np.abs(mp)) & (m >= 0)
    mant = np.where(low, np.where(m == 0, v_m0, v_m1), mant_g)
    scale = np.where(low, 0.0, scale_g)
    mant = np.where(m >= 0, mant, 0.0)
    scale = np.where(m >= 0, scale, 0.0)
    return mant.astype(dtype), scale.astype(np.int32)


def spin_seed_rows(m_vals, mprime_vals, grid_x, grid_sin, *, m_max, dtype,
                   scale_bits: int):
    """Spin seeds (Ms, R) for rows (m, m'), with ``m_vals`` concrete or
    traced (``m_max`` bounds the traced rows) and ``mprime_vals``
    concrete.  Traced rows gather from host float64 tables over every m in
    [0, m_max] for each distinct m' (see :func:`_seed_rows`)."""
    mp = np.asarray(mprime_vals)
    m = _concrete(m_vals)
    if m_max is None:
        m_max = int(np.max(m))
    logfact = log_factorials(2 * max(int(m_max), 2) + 1)

    def seeds(rows, mprime):
        return spin_seeds_scaled(rows, mprime, grid_x, grid_sin, logfact,
                                 dtype=dtype, scale_bits=scale_bits)

    if m is not None:
        return seeds(m, mp)
    mps = np.unique(mp)
    which = jnp.asarray(np.searchsorted(mps, mp), jnp.int32)
    m_all = np.arange(m_max + 1)
    tabs = [seeds(m_all, np.full_like(m_all, v)) for v in mps]
    mant = jnp.asarray(np.stack([t[0] for t in tabs]))       # (n_mp, M, R)
    scale = jnp.asarray(np.stack([t[1] for t in tabs]))
    m_vals = jnp.asarray(m_vals, jnp.int32)
    idx = jnp.clip(m_vals, 0, m_max)
    ok = (m_vals >= 0)[:, None]
    return (jnp.where(ok, mant[which, idx], 0),
            jnp.where(ok, scale[which, idx], 0))


def recurrence_step_general(l, m, mp, x, mant_prev, mant_curr, scale,
                            seed_mant, seed_scale, *, scale_bits: int, dtype):
    """One step of the generalised (spin-aware) scaled recurrence.

    Identical contract to :func:`recurrence_step` but seeded at
    ``l0 = max(m, |m'|)`` and using the Wigner-d coefficients; reduces to
    the scalar recurrence at ``m' = 0``.  ``mp`` is (Ms, 1) like ``m``.
    """
    fdt = dtype
    lf = jnp.asarray(l, fdt)
    mf = m.astype(fdt)
    mpf = mp.astype(fdt)
    l0 = jnp.maximum(mf, jnp.abs(mpf))
    ls = jnp.maximum(lf, l0 + 1.0)                    # safe l for coefficients
    d2 = jnp.maximum((ls * ls - mf * mf) * (ls * ls - mpf * mpf), 1e-30)
    lm1 = ls - 1.0
    d2m1 = jnp.maximum((lm1 * lm1 - mf * mf) * (lm1 * lm1 - mpf * mpf), 0.0)
    s2l = jnp.sqrt(4.0 * ls * ls - 1.0)
    inv_d = 1.0 / jnp.sqrt(d2)
    inv_lm1 = 1.0 / jnp.maximum(lm1, 1.0)
    a = ls * s2l * inv_d
    b = -(mf * mpf) * s2l * inv_d * inv_lm1
    c = (jnp.sqrt((2.0 * ls + 1.0) / jnp.maximum(2.0 * ls - 3.0, 1.0))
         * ls * jnp.sqrt(d2m1) * inv_d * inv_lm1)

    p_rec = (a * x + b) * mant_curr - c * mant_prev
    is_seed = lf == l0
    before = lf < l0

    new_curr = jnp.where(before, 0.0,
               jnp.where(is_seed, seed_mant, p_rec))
    new_prev = jnp.where(before | is_seed, 0.0, mant_curr)
    new_scale = jnp.where(is_seed, seed_scale, scale)

    big = jnp.asarray(2.0, fdt) ** (scale_bits // 2)
    inv_big2 = jnp.asarray(2.0, fdt) ** (-scale_bits)
    grow = (jnp.abs(new_curr) > big) & (new_scale < 0)
    new_curr = jnp.where(grow, new_curr * inv_big2, new_curr)
    new_prev = jnp.where(grow, new_prev * inv_big2, new_prev)
    new_scale = jnp.where(grow, new_scale + 1, new_scale)
    small = (jnp.abs(new_curr) < 1.0 / big) & (jnp.abs(new_prev) < 1.0 / big) \
        & (new_scale > jnp.int32(-32000)) & ~before & ~is_seed
    big2 = jnp.asarray(2.0, fdt) ** scale_bits
    new_curr2 = jnp.where(small, new_curr * big2, new_curr)
    new_prev2 = jnp.where(small, new_prev * big2, new_prev)
    new_scale2 = jnp.where(small, new_scale - 1, new_scale)

    value = jnp.where((new_scale2 == 0) & ~before, new_curr2, 0.0)
    return new_prev2, new_curr2, new_scale2, value


def _prep_general(m_vals, mprime_vals, grid_x, dtype):
    m = jnp.asarray(m_vals, jnp.int32)[:, None]
    mp = jnp.asarray(mprime_vals, jnp.int32)[:, None]
    x = jnp.asarray(grid_x, dtype)[None, :]
    return m, mp, x


@functools.partial(jax.jit, static_argnames=("l_max", "scale_bits",
                                             "dtype_name"))
def _delta_from_alm_general_impl(a_re, a_im, m, mp, x, seed_mant, seed_scale,
                                 *, l_max, scale_bits, dtype_name):
    dtype = jnp.dtype(dtype_name)
    M, R = m.shape[0], x.shape[1]
    K = a_re.shape[-1]
    carry0 = (
        jnp.zeros((M, R), dtype),
        jnp.zeros((M, R), dtype),
        jnp.zeros((M, R), jnp.int32),
        jnp.zeros((M, R, K), dtype),
        jnp.zeros((M, R, K), dtype),
    )

    def body(l, carry):
        mprev, mcurr, sc, dre, dim = carry
        with jax.named_scope(RECURRENCE):
            mprev, mcurr, sc, val = recurrence_step_general(
                l, m, mp, x, mprev, mcurr, sc, seed_mant, seed_scale,
                scale_bits=scale_bits, dtype=dtype)
        with jax.named_scope(ACCUMULATE):
            are = jax.lax.dynamic_index_in_dim(a_re, l, axis=1,
                                               keepdims=False)
            aim = jax.lax.dynamic_index_in_dim(a_im, l, axis=1,
                                               keepdims=False)
            dre = dre + val[..., None] * are[:, None, :]
            dim = dim + val[..., None] * aim[:, None, :]
        return mprev, mcurr, sc, dre, dim

    _, _, _, d_re, d_im = jax.lax.fori_loop(0, l_max + 1, body, carry0)
    return d_re, d_im


@functools.partial(jax.jit, static_argnames=("l_max", "scale_bits",
                                             "dtype_name"))
def _alm_from_delta_general_impl(d_re, d_im, m, mp, x, seed_mant, seed_scale,
                                 *, l_max, scale_bits, dtype_name):
    dtype = jnp.dtype(dtype_name)
    M, R, K = m.shape[0], x.shape[1], d_re.shape[-1]
    carry0 = (jnp.zeros((M, R), dtype), jnp.zeros((M, R), dtype),
              jnp.zeros((M, R), jnp.int32))

    def step(carry, l):
        mprev, mcurr, sc = carry
        with jax.named_scope(RECURRENCE):
            mprev, mcurr, sc, val = recurrence_step_general(
                l, m, mp, x, mprev, mcurr, sc, seed_mant, seed_scale,
                scale_bits=scale_bits, dtype=dtype)
        with jax.named_scope(ACCUMULATE):
            a_re_l = jnp.einsum("mr,mrk->km", val, d_re,
                                precision=_HIGHEST).reshape(-1)
            a_im_l = jnp.einsum("mr,mrk->km", val, d_im,
                                precision=_HIGHEST).reshape(-1)
        return (mprev, mcurr, sc), (a_re_l, a_im_l)

    _, (a_re, a_im) = jax.lax.scan(step, carry0, jnp.arange(l_max + 1))
    return _scan_to_mlk(a_re, K), _scan_to_mlk(a_im, K)


@scoped(LEGENDRE)
def delta_from_alm_general(a_re, a_im, m_vals, mprime_vals, grid_x, grid_sin,
                           *, l_max: int, m_max: Optional[int] = None,
                           dtype=jnp.float64):
    """Generalised synthesis inner step over lam^{(m')} rows.

    Like :func:`delta_from_alm` but each row carries its own (m, m') pair
    (m' = 0 rows reproduce the scalar transform through the generalised
    recurrence).  a_re/a_im: (Ms, l_max+1, K) -> (Ms, R, K).
    ``m_max`` must be given when ``m_vals`` is traced (distributed path).

    Differentiable both ways (VJP = generalised analysis of the cotangent,
    same Wigner-d rows, unit weights).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    seed_mant, seed_scale = spin_seed_rows(m_vals, mprime_vals, grid_x,
                                           grid_sin, m_max=m_max, dtype=dtype,
                                           scale_bits=sb)
    a_re = jnp.asarray(a_re, dtype)
    a_im = jnp.asarray(a_im, dtype)
    assert a_re.shape[1] == l_max + 1, (a_re.shape, l_max)

    def fwd(res, ops):
        m, mp, x, sm, ss = res
        ar, ai = ops
        return _delta_from_alm_general_impl(ar, ai, m, mp, x, sm, ss,
                                            l_max=l_max, scale_bits=sb,
                                            dtype_name=dtype.name)

    def bwd(res, cts):
        m, mp, x, sm, ss = res
        gd_re, gd_im = cts
        return _alm_from_delta_general_impl(gd_re, gd_im, m, mp, x, sm, ss,
                                            l_max=l_max, scale_bits=sb,
                                            dtype_name=dtype.name)

    m, mp, x = _prep_general(m_vals, mprime_vals, grid_x, dtype)
    return linear_pair(fwd, bwd, (m, mp, x, seed_mant, seed_scale),
                       (a_re, a_im))


@scoped(LEGENDRE)
def alm_from_delta_general(d_re, d_im, m_vals, mprime_vals, grid_x, grid_sin,
                           *, l_max: int, m_max: Optional[int] = None,
                           dtype=jnp.float64):
    """Generalised analysis inner step (adjoint of the above).

    d_re/d_im: (Ms, R, K) *weighted* Delta -> (Ms, l_max+1, K); rows with
    l < max(m, |m'|) come out exactly zero.

    Differentiable both ways (VJP = generalised synthesis of the alm
    cotangent).
    """
    dtype = jnp.dtype(dtype)
    sb = scale_bits_for(dtype)
    seed_mant, seed_scale = spin_seed_rows(m_vals, mprime_vals, grid_x,
                                           grid_sin, m_max=m_max, dtype=dtype,
                                           scale_bits=sb)
    d_re = jnp.asarray(d_re, dtype)
    d_im = jnp.asarray(d_im, dtype)

    def fwd(res, ops):
        m, mp, x, sm, ss = res
        dr, di = ops
        return _alm_from_delta_general_impl(dr, di, m, mp, x, sm, ss,
                                            l_max=l_max, scale_bits=sb,
                                            dtype_name=dtype.name)

    def bwd(res, cts):
        m, mp, x, sm, ss = res
        ga_re, ga_im = cts
        return _delta_from_alm_general_impl(ga_re, ga_im, m, mp, x, sm, ss,
                                            l_max=l_max, scale_bits=sb,
                                            dtype_name=dtype.name)

    m, mp, x = _prep_general(m_vals, mprime_vals, grid_x, dtype)
    return linear_pair(fwd, bwd, (m, mp, x, seed_mant, seed_scale),
                       (d_re, d_im))


# ---------------------------------------------------------------------------
# Spin-2 component packing: (E, B) <-> a^+- = -(E +- iB), stacked along the
# row axis as [m' = -2 rows | m' = +2 rows], and (Delta_Q, Delta_U) <->
# Delta^+- = Delta_Q +- i Delta_U.  Shared by the serial engine and the
# distributed transform (all dtypes, any trailing dims).
# ---------------------------------------------------------------------------


def spin_pack_alm(e_re, e_im, b_re, b_im):
    """(E, B) -> stacked a^+- rows: a2 = [-(E+iB) | -(E-iB)], (2M, ...)."""
    a_p_re = -(e_re - b_im)
    a_p_im = -(e_im + b_re)
    a_m_re = -(e_re + b_im)
    a_m_im = -(e_im - b_re)
    return (jnp.concatenate([a_p_re, a_m_re], axis=0),
            jnp.concatenate([a_p_im, a_m_im], axis=0))


def spin_unpack_delta(d_re, d_im):
    """Stacked Delta^+- rows (2M, ...) -> (dq_re, dq_im, du_re, du_im).

    Delta_Q = (Delta^+ + Delta^-)/2,  Delta_U = -i (Delta^+ - Delta^-)/2.
    """
    M = d_re.shape[0] // 2
    dp_re, dm_re = d_re[:M], d_re[M:]
    dp_im, dm_im = d_im[:M], d_im[M:]
    dq_re = 0.5 * (dp_re + dm_re)
    dq_im = 0.5 * (dp_im + dm_im)
    du_re = 0.5 * (dp_im - dm_im)
    du_im = -0.5 * (dp_re - dm_re)
    return dq_re, dq_im, du_re, du_im


def spin_pack_delta(dq_re, dq_im, du_re, du_im):
    """(Delta_Q, Delta_U) -> stacked Delta^+- = Delta_Q +- i Delta_U rows."""
    dp_re = dq_re - du_im
    dp_im = dq_im + du_re
    dm_re = dq_re + du_im
    dm_im = dq_im - du_re
    return (jnp.concatenate([dp_re, dm_re], axis=0),
            jnp.concatenate([dp_im, dm_im], axis=0))


def spin_unpack_alm(a_re, a_im):
    """Stacked a^+- rows (2M, ...) -> (e_re, e_im, b_re, b_im).

    E = -(a^+ + a^-)/2,  B = i (a^+ - a^-)/2.
    """
    M = a_re.shape[0] // 2
    ap_re, am_re = a_re[:M], a_re[M:]
    ap_im, am_im = a_im[:M], a_im[M:]
    e_re = -0.5 * (ap_re + am_re)
    e_im = -0.5 * (ap_im + am_im)
    b_re = -0.5 * (ap_im - am_im)
    b_im = 0.5 * (ap_re - am_re)
    return e_re, e_im, b_re, b_im


def _spin_rows(m_vals):
    """Stack m rows for the two spin recurrences -> (m2, mp2), each (2M,).

    Stays numpy for concrete inputs (so plan layers can treat the result
    as static); traced ``m_vals`` (the distributed path) stay jnp.
    """
    if isinstance(m_vals, (np.ndarray, list, tuple)):
        m2 = np.concatenate([np.asarray(m_vals, np.int32)] * 2, axis=0)
        M = m2.shape[0] // 2
    else:
        m2 = jnp.concatenate([jnp.asarray(m_vals, jnp.int32)] * 2, axis=0)
        M = m2.shape[0] // 2
    mp2 = np.concatenate([np.full(M, -2, np.int32), np.full(M, 2, np.int32)])
    return m2, mp2


def delta_from_alm_spin(e_re, e_im, b_re, b_im, m_vals, grid_x, grid_sin, *,
                        l_max: int, m_max: Optional[int] = None,
                        dtype=jnp.float64):
    """Spin-2 synthesis inner step: (E, B) alm -> (Delta_Q, Delta_U).

    Inputs (M, l_max+1, K) real/imag parts; returns
    (dq_re, dq_im, du_re, du_im), each (M, R, K).
    """
    with jax.named_scope(FOLD):
        a2_re, a2_im = spin_pack_alm(e_re, e_im, b_re, b_im)
    m2, mp2 = _spin_rows(m_vals)
    d_re, d_im = delta_from_alm_general(
        a2_re, a2_im, m2, mp2, grid_x, grid_sin, l_max=l_max, m_max=m_max,
        dtype=dtype)
    with jax.named_scope(FOLD):
        return spin_unpack_delta(d_re, d_im)


def alm_from_delta_spin(dq_re, dq_im, du_re, du_im, m_vals, grid_x, grid_sin,
                        *, l_max: int, m_max: Optional[int] = None,
                        dtype=jnp.float64):
    """Spin-2 analysis inner step: weighted (Delta_Q, Delta_U) -> (E, B).

    Inputs (M, R, K); returns (e_re, e_im, b_re, b_im), each (M, L1, K).
    """
    with jax.named_scope(FOLD):
        d2_re, d2_im = spin_pack_delta(dq_re, dq_im, du_re, du_im)
    m2, mp2 = _spin_rows(m_vals)
    a_re, a_im = alm_from_delta_general(
        d2_re, d2_im, m2, mp2, grid_x, grid_sin, l_max=l_max, m_max=m_max,
        dtype=dtype)
    with jax.named_scope(FOLD):
        return spin_unpack_alm(a_re, a_im)


import dataclasses as _dataclasses


@_dataclasses.dataclass(frozen=True)
class HarmonicCore:
    """Spin-aware recurrence layer: one surface over the scalar P_lm panels
    (spin 0) and the spin-weighted lambda pairs (spin 2).

    The serial engine (`core.sht.SHT`), and through it every plan backend,
    produces/consumes per-ring Fourier coefficients via this object:

      ``delta_from_alm``: complex alm (M, L, K)            [spin 0]
                          or (2, M, L, K) = (E, B)          [spin 2]
                       -> Delta (M, R, K) / (2, M, R, K) = (Q, U) rows.
      ``alm_from_delta``: the adjoint (weighted Delta in).

    Spin 2 runs two generalised Wigner-d recurrences (m' = -2, +2) stacked
    along the row axis -- exactly 2x the scalar panel work -- and mixes the
    components host-side (`spin_pack_alm` and friends).
    """

    m_vals: tuple
    grid_x: np.ndarray
    grid_sin: np.ndarray
    log_mu_all: np.ndarray
    l_max: int
    spin: int = 0
    dtype: str = "float64"

    def __post_init__(self):
        assert self.spin in (0, 2), f"unsupported spin {self.spin}"

    @property
    def n_components(self) -> int:
        return 1 if self.spin == 0 else 2

    def delta_from_alm(self, alm):
        dt = jnp.dtype(self.dtype)
        if self.spin == 0:
            with jax.named_scope(FOLD):
                a_re, a_im = jnp.real(alm), jnp.imag(alm)
            d_re, d_im = delta_from_alm(
                a_re, a_im, self.m_vals, self.grid_x, self.grid_sin,
                self.log_mu_all, l_max=self.l_max, dtype=dt)
            with jax.named_scope(FOLD):
                return d_re + 1j * d_im
        with jax.named_scope(FOLD):
            e, b = alm[0], alm[1]
            parts = (jnp.real(e), jnp.imag(e), jnp.real(b), jnp.imag(b))
        dq_re, dq_im, du_re, du_im = delta_from_alm_spin(
            *parts, self.m_vals, self.grid_x, self.grid_sin,
            l_max=self.l_max, dtype=dt)
        with jax.named_scope(FOLD):
            return jnp.stack([dq_re + 1j * dq_im, du_re + 1j * du_im],
                             axis=0)

    def alm_from_delta(self, delta_w):
        dt = jnp.dtype(self.dtype)
        if self.spin == 0:
            ones = np.ones(np.asarray(self.grid_x).shape[0])
            with jax.named_scope(FOLD):
                d_re, d_im = jnp.real(delta_w), jnp.imag(delta_w)
            a_re, a_im = alm_from_delta(
                d_re, d_im, self.m_vals, self.grid_x, self.grid_sin, ones,
                self.log_mu_all, l_max=self.l_max, dtype=dt)
            with jax.named_scope(FOLD):
                return a_re + 1j * a_im
        with jax.named_scope(FOLD):
            dq, du = delta_w[0], delta_w[1]
            parts = (jnp.real(dq), jnp.imag(dq), jnp.real(du), jnp.imag(du))
        e_re, e_im, b_re, b_im = alm_from_delta_spin(
            *parts, self.m_vals, self.grid_x, self.grid_sin,
            l_max=self.l_max, dtype=dt)
        with jax.named_scope(FOLD):
            return jnp.stack([e_re + 1j * e_im, b_re + 1j * b_im], axis=0)
