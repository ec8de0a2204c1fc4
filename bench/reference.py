"""Plain float64 transforms on the Gauss-Legendre grid, for the check.

Written for the benchmark alone: it imports nothing of the library under
test and takes nothing the library made (no nodes, weights or tables).
It evaluates a sample of the output, so that the check at l_max=4096 costs
seconds on the host:

* ``synth_rings``: the map on a sample of rings, every pixel, every m;
* ``anal_rows``: the a_lm of a sample of m, every l, every ring.

Conventions (those of the transforms under test): alm is ``(M, L, K)``
complex with m on the first axis and zero below the diagonal l < m; maps
are ``(R, n_phi, K)`` real, R = l_max + 1 rings at the Gauss-Legendre
nodes from north to south, n_phi = 2 l_max + 2 pixels from phi = 0;
lambda_lm are orthonormal (2 pi int lambda^2 dx = 1) without the
Condon-Shortley phase, and f(theta, phi) = Re sum_m c_m e^{i m phi}
sum_l a_lm lambda_lm(cos theta), c_0 = 1, c_m = 2.

``precision="bfloat16"`` rounds the Legendre values and the operand they
multiply (a_lm in synthesis, the weighted ring coefficients in analysis)
to bfloat16 before an exact product and a float64 sum: what a contraction
on bfloat16 operands with a wide accumulator gives.  That is the check's
control, the nearest precision below the float32 the configurations state.
"""

from __future__ import annotations

import numpy as np

__all__ = ["gl_grid", "legendre_rows", "synth_rings", "anal_rows",
           "row_errors", "row_rel_max"]

#: Rescaling step of the recurrence: values above 2**_STEP are scaled down
#: and the step is carried in a per-entry binary exponent, so that
#: lambda_mm = O(sin^m theta), far below the float64 range at l_max=4096,
#: still starts the recurrence.
_STEP = 256


def gl_grid(l_max: int) -> dict:
    """The Gauss-Legendre grid of ``l_max``: ``x`` (cos theta, north to
    south), ``sin``, ``w`` (per-pixel weights: Gauss-Legendre weight times
    2 pi / n_phi) and ``n_phi``.  Nodes by Newton's iteration on P_n."""
    n = l_max + 1
    i = np.arange(1, n + 1, dtype=np.float64)
    x = np.cos(np.pi * (i - 0.25) / (n + 0.5))
    for it in range(100):
        p_prev, p = np.ones_like(x), x.copy()
        for k in range(2, n + 1):
            p_prev, p = p, ((2 * k - 1) * x * p - (k - 1) * p_prev) / k
        dp = n * (x * p - p_prev) / (x * x - 1.0)
        dx = p / dp
        x = x - dx
        if np.max(np.abs(dx)) < 1e-15:
            break
    p_prev, p = np.ones_like(x), x.copy()
    for k in range(2, n + 1):
        p_prev, p = p, ((2 * k - 1) * x * p - (k - 1) * p_prev) / k
    dp = n * (x * p - p_prev) / (x * x - 1.0)
    n_phi = 2 * l_max + 2
    w = 2.0 / ((1.0 - x * x) * dp * dp)
    return {"x": x, "sin": np.sqrt((1.0 - x) * (1.0 + x)),
            "w": w * (2.0 * np.pi / n_phi), "n_phi": n_phi}


def legendre_rows(l_max: int, m, x, sin):
    """Yield ``(l, n, values)`` for l = 0..l_max: ``values`` is the
    ``(n, len(x))`` float64 array of lambda_lm(x) for the first ``n``
    entries of ``m`` (ascending, distinct), those with m <= l.

    Three-term recurrence in l from lambda_mm = mu_m sin^m, with
    mu_m = sqrt((2m+1)!! / (4 pi (2m)!!)); the start is carried as a
    mantissa and a binary exponent, so that it does not underflow."""
    m = np.asarray(m, dtype=np.int64)
    assert np.all(np.diff(m) > 0), "m must be ascending and distinct"
    x = np.asarray(x, np.float64)[None, :]
    mf = m.astype(np.float64)[:, None]
    k = np.arange(1, max(int(m[-1]), 1) + 1, dtype=np.float64)
    log2_mu = -0.5 * np.log2(4.0 * np.pi) + np.concatenate(
        [[0.0], np.cumsum(0.5 * np.log2((2 * k + 1) / (2 * k)))])
    with np.errstate(divide="ignore"):
        lg = log2_mu[m][:, None] + mf * np.log2(np.asarray(sin))[None, :]
    lg = np.maximum(lg, -1e6)               # sin = 0: nothing but m = 0
    exp0 = np.floor(lg)
    mant0 = np.exp2(lg - exp0)
    rows, R = m.size, x.shape[1]
    prev = np.zeros((rows, R))
    curr = np.zeros((rows, R))
    expo = np.zeros((rows, R), np.int64)
    big = 2.0 ** _STEP
    n = 0
    for l in range(l_max + 1):
        # rows already running (m < l): one step of the recurrence
        if n:
            mm = mf[:n]
            a = np.sqrt((4.0 * l * l - 1.0) / (l * l - mm * mm))
            lm1 = l - 1
            b_prev = np.sqrt(max(4.0 * lm1 * lm1 - 1.0, 1.0)
                             / np.maximum(lm1 * lm1 - mm * mm, 1.0))
            new = a * (x * curr[:n] - prev[:n] / b_prev)
            prev[:n] = curr[:n]
            curr[:n] = new
            over = np.abs(new) > big
            if over.any():
                prev[:n][over] /= big
                curr[:n][over] /= big
                expo[:n][over] += _STEP
        # the row m = l starts here
        if n < rows and m[n] == l:
            prev[n] = 0.0
            curr[n] = mant0[n]
            expo[n] = exp0[n].astype(np.int64)
            n += 1
        if n:
            yield l, n, np.ldexp(curr[:n], expo[:n])


def _bf16(a):
    import ml_dtypes
    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


def _round(a, precision: str):
    if precision == "float64":
        return a
    assert precision == "bfloat16", precision
    if np.iscomplexobj(a):
        return _bf16(a.real) + 1j * _bf16(a.imag)
    return _bf16(a)


def synth_rings(alm, rings, grid: dict, precision: str = "float64"):
    """Maps ``(len(rings), n_phi, K)`` float64 of alm ``(M, L, K)`` on the
    given rings of ``grid`` (see :func:`gl_grid`)."""
    alm = _round(np.asarray(alm, np.complex128), precision)
    M, L, K = alm.shape
    rings = np.asarray(rings)
    delta = np.zeros((M, rings.size, K), np.complex128)
    for l, n, v in legendre_rows(L - 1, np.arange(M), grid["x"][rings],
                                 grid["sin"][rings]):
        v = _round(v, precision)
        delta[:n] += v[:, :, None] * alm[:n, l, None, :]
    delta[1:] *= 2.0
    n_phi = grid["n_phi"]
    coef = np.zeros((rings.size, n_phi, K), np.complex128)
    coef[:, :M, :] = np.moveaxis(delta, 0, 1)
    return (np.fft.ifft(coef, axis=1) * n_phi).real


def anal_rows(maps, m_rows, grid: dict, precision: str = "float64",
              chunk: int = 256):
    """a_lm of the given m (ascending) for maps ``(R, n_phi, K)``:
    ``(len(m_rows), L, K)`` complex128, by Gauss-Legendre quadrature."""
    m_rows = np.asarray(m_rows, np.int64)
    R, n_phi, K = maps.shape
    L = R
    phi = 2.0 * np.pi * np.arange(n_phi) / n_phi
    ang = np.outer(phi, m_rows)                  # (n_phi, S)
    cos, sin = np.cos(ang), np.sin(ang)
    f = np.empty((m_rows.size, R, K), np.complex128)
    for r0 in range(0, R, chunk):
        blk = np.moveaxis(np.asarray(maps[r0:r0 + chunk], np.float64), 1, 2)
        f[:, r0:r0 + chunk, :] = np.moveaxis(blk @ cos - 1j * (blk @ sin),
                                             2, 0)
    g = _round(f * grid["w"][None, :, None], precision)
    g_re, g_im = np.ascontiguousarray(g.real), np.ascontiguousarray(g.imag)
    out = np.zeros((m_rows.size, L, K), np.complex128)
    for l, n, v in legendre_rows(L - 1, m_rows, grid["x"], grid["sin"]):
        v = _round(v, precision)[:, None, :]     # (n, 1, R)
        out[:n, l, :] = (v @ g_re[:n])[:, 0] + 1j * (v @ g_im[:n])[:, 0]
    return out


def row_errors(got, want) -> np.ndarray:
    """||got_r - want_r|| / ||want_r|| for each row r (first axis: rings
    or m), over every other axis."""
    got = np.asarray(got, np.complex128 if np.iscomplexobj(got)
                     else np.float64)
    want = np.asarray(want)
    axes = tuple(range(1, want.ndim))
    num = np.sqrt(np.sum(np.abs(got - want) ** 2, axis=axes))
    return num / np.sqrt(np.sum(np.abs(want) ** 2, axis=axes))


def row_rel_max(got, want) -> float:
    """The worst row's relative error (see :func:`row_errors`); NaN if any
    is not finite."""
    e = row_errors(got, want)
    return float(np.max(e)) if np.all(np.isfinite(e)) else float("nan")
