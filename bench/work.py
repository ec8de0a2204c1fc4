"""Operations and bytes of one transform, from its shape alone.

A copy of ``sht_work`` from the library's ``roofline/analysis.py`` (the
paper's section 3 complexity), kept here so that the yardstick does not
move when the library's cost model does.  It counts what the algorithm
needs, whatever implements it:

* ``recurrence_flops``: lambda_lm generation, ~10 flops per (l, m, ring);
* ``accum_flops``: the a_lm / Delta_m contraction, 4 K flops per
  (l, m, ring) (one complex multiply-add per map);
* ``fft_flops``: ring FFTs, 5 n log2 n per ring and map;
* ``bytes``: the HBM traffic floor, a_lm + maps + Delta_m once each.
"""

from __future__ import annotations

import math

__all__ = ["sht_work"]


def sht_work(l_max: int, m_max: int, n_rings: int, n_phi: int, K: int,
             spin: int = 0) -> dict:
    """Work of one direction of a transform on a uniform ring grid."""
    ncomp = 1 if spin == 0 else 2
    n_lm = (m_max + 1) * (l_max + 1) - m_max * (m_max + 1) // 2
    rec = 10.0 * n_lm * n_rings * ncomp
    acc = 4.0 * n_lm * n_rings * K * ncomp
    fft = 5.0 * n_rings * n_phi * math.log2(max(n_phi, 2)) * K * ncomp
    maps_elems = float(n_rings * n_phi) * K * ncomp
    byts = (16.0 * (m_max + 1) * (l_max + 1) * K * ncomp
            + 8.0 * maps_elems
            + 16.0 * (m_max + 1) * n_rings * K * ncomp)
    return {"n_lm": n_lm, "recurrence_flops": rec, "accum_flops": acc,
            "fft_flops": fft, "bytes": byts, "total_flops": rec + acc + fft}
