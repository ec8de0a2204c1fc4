"""SHTPlan: the data-distribution plan for the parallel transforms.

Encodes the paper's §4.1.1 layout decisions as static (numpy, host-side)
arrays consumed by ``dist_sht``:

* **m distribution with min-max pairing** (paper Fig. 5): the global m list
  is reordered as [0, m_max, 1, m_max-1, ...] and pairs are dealt
  round-robin to shards, so every shard's total recurrence length is the
  paper's invariant  sum over pairs of (2 l_max - m_max + 2); each shard
  then holds its rows in ascending m.  Padding slots
  (m = -1) keep every shard's slot count identical -- the TPU analogue of
  `Alltoallv` raggedness (DESIGN.md §2).
* **ring distribution**: rings are dealt to shards as blocks of mirror pairs
  (north_i, south_mirror_i) so each shard can fold about the equator; dummy
  rings (weight 0) pad R to a multiple of the shard count.
* **bucket-aware dealing (ragged grids)**: for variable-n_phi grids the
  mirror pairs are dealt *per FFT bucket* (grids.ring_buckets), each
  bucket's pair list padded to a multiple of the shard count, so every
  shard owns the same number of rings from every bucket.  That gives each
  shard balanced Legendre FLOPs *and* balanced FFT work (paper §4.1), and
  -- crucially for shard_map's single-program model -- an *identical*
  local slot->bucket structure (`local_fft_layout`) on every shard.

The plan is pure geometry: it never touches jax device state and can be
built under `jax.eval_shape` / dry-run tracing.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core import legendre
from repro.core.grids import BucketLayout, RingGrid

__all__ = ["SHTPlan", "minmax_m_order", "Plan", "make_plan", "drop_plan"]


def __getattr__(name):
    """Lazy aliases for the unified transform-plan API.

    ``repro.core.plan.Plan`` / ``make_plan`` / ``drop_plan`` live in
    ``repro.core.transform`` (which imports jax); resolving them lazily
    keeps this module pure host-side geometry, importable under
    ``jax.eval_shape`` dry-runs with no device state.
    """
    if name in ("Plan", "make_plan", "drop_plan"):
        from repro.core import transform
        return getattr(transform, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def minmax_m_order(m_max: int) -> np.ndarray:
    """[0, m_max, 1, m_max-1, ...] -- the min-max pair ordering."""
    out = np.empty(m_max + 1, dtype=np.int64)
    out[0::2] = np.arange((m_max + 2) // 2)
    out[1::2] = m_max - np.arange((m_max + 1) // 2)
    return out


@dataclasses.dataclass(frozen=True)
class SHTPlan:
    """Distribution plan for a (grid, l_max, m_max, n_shards) problem.

    ``comm_chunks`` is the default chunk count of the chunked-exchange
    pipeline (`DistSHT` overrides it per engine): the Delta block is
    split into C chunks so each chunk's all_to_all overlaps the adjacent
    chunk's Legendre/FFT compute.  ``chunk_schedule`` resolves which axis
    the split rides on for a given K.
    """

    grid: RingGrid
    l_max: int
    m_max: int
    n_shards: int
    comm_chunks: int = 1

    # ---- m axis ------------------------------------------------------------

    @functools.cached_property
    def m_assignment(self) -> np.ndarray:
        """(n_shards, m_local) global m value per slot; -1 = padding.

        Pairs from ``minmax_m_order`` are dealt round-robin: pair p goes to
        shard p % n_shards, preserving the paper's balance invariant.  Each
        shard's rows then ascend, padding last, so that a row block of the
        jnp Legendre loop holds neighbouring m and starts at its least m
        (`legendre.row_blocks`).
        """
        order = minmax_m_order(self.m_max)
        # Group into pairs [(0, m_max), (1, m_max-1), ...]; a lone middle
        # element (even m_max+1 count has none) forms a singleton pair.
        pairs = [order[i:i + 2] for i in range(0, len(order), 2)]
        per_shard: list[list[int]] = [[] for _ in range(self.n_shards)]
        for p, pair in enumerate(pairs):
            per_shard[p % self.n_shards].extend(int(v) for v in pair)
        m_local = max(len(s) for s in per_shard)
        out = np.full((self.n_shards, m_local), -1, dtype=np.int64)
        for i, s in enumerate(per_shard):
            out[i, : len(s)] = sorted(s)
        return out

    @property
    def m_local(self) -> int:
        return self.m_assignment.shape[1]

    @functools.cached_property
    def m_flat(self) -> np.ndarray:
        """(n_shards * m_local,) global m per global slot (row-major)."""
        return self.m_assignment.reshape(-1)

    @functools.cached_property
    def recurrence_steps_per_shard(self) -> np.ndarray:
        """Work balance diagnostic: total l-recurrence steps per shard."""
        a = self.m_assignment
        steps = np.where(a >= 0, self.l_max + 1 - np.maximum(a, 0), 0)
        return steps.sum(axis=1)

    def pack_alm(self, alm: np.ndarray) -> np.ndarray:
        """(M, L, K) dense alm -> (n_shards * m_local, L, K) plan layout.

        Padding slots are zero.  Works with numpy or jnp inputs.
        """
        M, L, K = alm.shape
        assert M == self.m_max + 1 and L == self.l_max + 1
        import jax.numpy as jnp
        xp = jnp if not isinstance(alm, np.ndarray) else np
        safe = np.maximum(self.m_flat, 0)
        out = alm[safe]
        mask = (self.m_flat >= 0)[:, None, None]
        return xp.where(xp.asarray(mask), out, xp.zeros_like(out))

    def unpack_alm(self, packed: np.ndarray) -> np.ndarray:
        """Inverse of pack_alm (padding rows dropped)."""
        import jax.numpy as jnp
        xp = jnp if not isinstance(packed, np.ndarray) else np
        M = self.m_max + 1
        out_shape = (M,) + tuple(packed.shape[1:])
        out = xp.zeros(out_shape, packed.dtype)
        src = np.flatnonzero(self.m_flat >= 0)
        idx = self.m_flat[src]
        if xp is np:
            out[idx] = packed[src]
            return out
        return out.at[idx].set(packed[src])

    # ---- chunked-exchange dealing -------------------------------------------

    def chunk_schedule(self, K: int, ncomp: int = 1,
                       chunks: int | None = None) -> tuple[str, tuple]:
        """Resolve the chunked-exchange split for a C-chunk pipeline.

        Returns ``(axis, bounds)`` where ``axis`` is ``"none"`` (C=1,
        monolithic exchange), ``"k"`` (split the K map-batch axis -- the
        ``ncomp`` spin components and the re/im pair ride *inside* each
        chunk, so chunk boundaries never cut a coupled channel group), or
        ``"m"`` (K too small: split the local m rows instead), and
        ``bounds`` is a tuple of half-open ``(start, stop)`` index pairs
        along that axis.  C is clamped to what the chosen axis can carry;
        pure host-side arithmetic (no jax).
        """
        C = int(self.comm_chunks if chunks is None else chunks)
        if C <= 1:
            return "none", ()
        if K >= C:
            axis, n = "k", int(K)
        else:
            axis, n = "m", int(self.m_local)
            C = min(C, n)
            if C <= 1:
                return "none", ()
        edges = np.linspace(0, n, C + 1).astype(np.int64)
        bounds = tuple((int(a), int(b)) for a, b in zip(edges[:-1], edges[1:]))
        assert all(b > a for a, b in bounds), bounds
        return axis, bounds

    # ---- ring axis -----------------------------------------------------------

    @functools.cached_property
    def _pairs(self) -> np.ndarray:
        """(n_pairs, 2) mirror pairs (north, south); equator south = -1."""
        R = self.grid.n_rings
        out = [(i, R - 1 - i) for i in range(R // 2)]
        if R % 2 == 1:
            out.append((R // 2, -1))
        return np.asarray(out, dtype=np.int64)

    @functools.cached_property
    def _bucket_deal(self):
        """Bucket-aware pair dealing for ragged grids.

        Returns ``(bucket_lengths, counts, ring_order)``: pairs are grouped
        by their FFT bucket (a pair's bucket is its north ring's -- mirrors
        share n_phi on symmetric grids, asserted), each bucket's pair list
        is dealt round-robin and padded to ``counts[k]`` pairs per shard,
        and the plan slot order is shard-major with buckets contiguous
        inside each shard -- so every shard sees the identical local
        slot->bucket structure (shard_map runs one program).
        """
        buckets = self.grid.fft_buckets()
        R = self.grid.n_rings
        ring2b = np.empty(R, dtype=np.int64)
        for k, b in enumerate(buckets):
            ring2b[b.rings] = k
        pairs = self._pairs
        pb = ring2b[pairs[:, 0]]
        south = pairs[:, 1]
        assert np.all((south < 0)
                      | (ring2b[np.maximum(south, 0)] == pb)), \
            "mirror pair spans two FFT buckets (grid not symmetric?)"
        n = self.n_shards
        per_bucket = [np.where(pb == k)[0] for k in range(len(buckets))]
        counts = [-(-len(p) // n) for p in per_bucket]
        order = np.full((n, sum(counts), 2), -1, dtype=np.int64)
        for k, p in enumerate(per_bucket):
            off = sum(counts[:k])
            for j, pair_idx in enumerate(p):
                order[j % n, off + j // n] = pairs[pair_idx]
        return [b.length for b in buckets], counts, order.reshape(-1)

    @functools.cached_property
    def n_pairs_pad(self) -> int:
        """Mirror-pair count padded to a multiple of n_shards (ragged
        grids: padded per bucket, see ``_bucket_deal``)."""
        if not self.grid.uniform:
            return self.n_shards * sum(self._bucket_deal[1])
        n_pairs = (self.grid.n_rings + 1) // 2
        return -(-n_pairs // self.n_shards) * self.n_shards

    @functools.cached_property
    def ring_order(self) -> np.ndarray:
        """(R_pad,) grid ring index per plan slot; -1 = dummy padding ring.

        Pair-interleaved: slot 2i is pair i's northern ring, slot 2i+1 its
        southern mirror.  An odd equator ring is a pair with a dummy south;
        padding pairs are (dummy, dummy).  Every shard owns r_local/2
        consecutive *pairs*, which is what the fold optimisation and the
        tiled all_to_all both want.  Ragged grids deal pairs bucket-aware
        (``_bucket_deal``) so FFT work is balanced too.
        """
        if not self.grid.uniform:
            return self._bucket_deal[2]
        R = self.grid.n_rings
        out = np.full(2 * self.n_pairs_pad, -1, dtype=np.int64)
        for i in range(R // 2):
            out[2 * i] = i                 # northern ring
            out[2 * i + 1] = R - 1 - i     # its mirror
        if R % 2 == 1:
            out[2 * (R // 2)] = R // 2     # equator (dummy south partner)
        return out

    @functools.cached_property
    def local_fft_layout(self) -> BucketLayout:
        """Static local-slot -> FFT-bucket structure, identical on every
        shard (uniform grids: one bucket over all local slots)."""
        if self.grid.uniform:
            return BucketLayout((self.grid.max_n_phi,),
                                (np.arange(self.r_local),))
        lengths, counts, _ = self._bucket_deal
        slots, off = [], 0
        for c in counts:
            slots.append(np.arange(2 * off, 2 * (off + c)))
            off += c
        return BucketLayout(tuple(lengths), tuple(slots))

    @functools.cached_property
    def slot_fft_len(self) -> np.ndarray:
        """(R_pad,) batched-FFT length of each plan slot's bucket."""
        return np.tile(self.local_fft_layout.fft_lengths, self.n_shards)

    @functools.cached_property
    def fft_bin_maps(self) -> tuple[np.ndarray, np.ndarray]:
        """(pos, neg) (R_pad, Mp) int32 alias-fold bin maps in plan slot
        order -- `phase.bucket_bin_maps` over ``m_flat`` and the slot
        geometry, shaped rings-first so they shard as stage-2 operands."""
        from repro.core.phase import bucket_bin_maps
        g = self.ring_geometry
        pos, neg = bucket_bin_maps(self.m_flat, g["n_phi"],
                                   self.slot_fft_len)
        return np.ascontiguousarray(pos.T), np.ascontiguousarray(neg.T)

    @property
    def r_pad(self) -> int:
        return self.ring_order.shape[0]

    @property
    def r_local(self) -> int:
        return self.r_pad // self.n_shards

    @functools.cached_property
    def north_order(self) -> np.ndarray:
        """(n_pairs_pad,) grid ring index of each pair's north; -1 padding."""
        return self.ring_order[0::2]

    @functools.cached_property
    def ring_geometry(self) -> dict[str, np.ndarray]:
        """Per-plan-slot ring geometry (R_pad,), dummies weight-0/benign."""
        g = self.grid
        ro = self.ring_order
        safe = np.maximum(ro, 0)
        dummy = ro < 0
        cos = np.where(dummy, 0.123456, g.cos_theta[safe])
        sin = np.sqrt(1.0 - cos * cos)
        w = np.where(dummy, 0.0, g.weights[safe])
        phi0 = np.where(dummy, 0.0, g.phi0[safe])
        # dummy slots adopt their bucket's FFT length so the bucket engine's
        # stride arithmetic stays exact (their output is weight-masked away)
        dummy_n = g.max_n_phi if g.uniform else self.slot_fft_len
        nphi = np.where(dummy, dummy_n, g.n_phi[safe])
        return {"cos_theta": cos, "sin_theta": sin, "weights": w,
                "phi0": phi0, "n_phi": nphi, "valid": ~dummy}

    def scatter_map(self, maps_plan: np.ndarray) -> np.ndarray:
        """(R_pad, n_phi, K) plan-order maps -> (R, n_phi, K) grid order."""
        import jax.numpy as jnp
        xp = jnp if not isinstance(maps_plan, np.ndarray) else np
        R = self.grid.n_rings
        out = xp.zeros((R,) + tuple(maps_plan.shape[1:]), maps_plan.dtype)
        src = np.flatnonzero(self.ring_order >= 0)
        idx = self.ring_order[src]
        if xp is np:
            out[idx] = maps_plan[src]
            return out
        return out.at[idx].set(maps_plan[src])

    def gather_map(self, maps_grid: np.ndarray) -> np.ndarray:
        """(R, n_phi, K) grid-order maps -> (R_pad, n_phi, K) plan order."""
        import jax.numpy as jnp
        xp = jnp if not isinstance(maps_grid, np.ndarray) else np
        safe = np.maximum(self.ring_order, 0)
        out = maps_grid[xp.asarray(safe)] if xp is not np else maps_grid[safe]
        mask = (self.ring_order >= 0)[:, None, None]
        return xp.where(xp.asarray(mask), out, xp.zeros_like(out))

    # ---- logs ---------------------------------------------------------------

    def describe(self) -> str:
        steps = self.recurrence_steps_per_shard
        return (f"SHTPlan(grid={self.grid.name}, l_max={self.l_max}, "
                f"m_max={self.m_max}, shards={self.n_shards}, "
                f"m_local={self.m_local}, r_pad={self.r_pad}, "
                f"r_local={self.r_local}, "
                f"balance={steps.min()}/{steps.max()} steps)")
