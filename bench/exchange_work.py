"""Bytes of the distributed transform's exchange, from its shape alone.

The two-stage transform (arXiv:1106.0159 section 4.1, Algorithm 3) moves
the Delta block, Delta_m(r) for every m, ring and map, between its
m-sharded Legendre stage and its ring-sharded FFTs with one all-to-all.
Counted here from the shape, whatever the program pads or chunks:

* ``block_bytes``: the whole Delta block, 8 bytes (a float32 complex
  value) per m, ring and map (and spin component);
* ``bytes_per_chip``: what each of ``n_devices`` chips sends: its share of
  the block, less the part it keeps, (n - 1) / n of block / n.
"""

from __future__ import annotations

__all__ = ["exchange_work"]


def exchange_work(m_max: int, n_rings: int, K: int, n_devices: int,
                  spin: int = 0) -> dict:
    """Exchange bytes of one transform over ``n_devices`` chips."""
    ncomp = 1 if spin == 0 else 2
    block = 8.0 * (m_max + 1) * n_rings * K * ncomp
    n = max(int(n_devices), 1)
    return {"block_bytes": block,
            "bytes_per_chip": block / n * (n - 1) / n}
