"""Distributed spherical harmonic transforms (paper §4.1, Algorithm 3).

The two-stage structure, verbatim from the paper but phrased in shard_map:

  alm2map:  [m-sharded]  Delta^A_m(r) for local m, ALL rings   (Legendre)
            --- one global all_to_all (the paper's MPI_Alltoallv) ---
            [ring-sharded]  per-ring inverse FFTs for local rings, all m

  map2alm:  [ring-sharded]  per-ring forward FFTs (weights applied)
            --- one global all_to_all (reversed) ---
            [m-sharded]  a_lm projection for local m over ALL rings

Design notes (DESIGN.md §2):
* The SHTPlan pads the m list and the ring-pair list so every shard has
  identical slot counts: `lax.all_to_all(tiled=True)` replaces Alltoallv.
* Real/imag (and the K map batch) are packed into one trailing channel axis
  so each transform issues exactly ONE collective, like the paper.  The
  packing, the collective and the unpacking run under the ``sht.exchange``
  scope (`repro.tracing.EXCHANGE`).
* `fold=True` runs the Legendre recurrence on northern rings only
  (equatorial symmetry), the libpsht-style optimisation.
* `comm_dtype` optionally down-casts the Delta exchange (e.g. bfloat16) --
  the paper explicitly leaves lossy-compressed communication to future work
  (§4.1.2); we implement it and measure the accuracy cost in tests.
* `stage1` selects the jnp reference path or the Pallas kernel path.
* `comm_chunks = C > 1` replaces the monolithic exchange with a chunked,
  software-pipelined one: the Delta block is split into C chunks along the
  K map-batch axis (or the local m rows when K is too small, see
  `SHTPlan.chunk_schedule`), and each chunk runs its own stage-1 compute +
  all_to_all.  The chunks are data-independent, so XLA's latency-hiding
  scheduler can keep chunk i's collective in flight while chunk i+1's
  Legendre recurrence (synthesis) or chunk i-1's projection (analysis)
  computes -- the libsharp-style comm/compute overlap the scaling model
  says the distributed path is starved for.  Chunking is a pure
  reordering of independent per-(m, k) work: outputs are bit-identical to
  the monolithic path (tests/helpers/dist_chunk_check.py), and every
  chunk exchange is still `lax.all_to_all`, so the adjoint contract
  (transposed reverse exchange) survives unchanged.
* Both transforms are differentiable inside shard_map: stage 1 and the
  phase stage carry adjoint-based custom VJP/JVP rules (linear_call
  transposes), and `lax.all_to_all` transposes to the reverse exchange --
  so `jax.grad` of a loss through `alm2map`/`map2alm` runs the
  opposite-direction two-stage transform with the same single collective
  (checked by the gradchecks in tests/helpers/dist_sht_check.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import legendre
from repro.core import phase as phaselib
from repro.core.plan import SHTPlan
from repro.tracing import EXCHANGE

__all__ = ["DistSHT"]


def _complex_dtype(real_dtype) -> jnp.dtype:
    return jnp.dtype(jnp.complex128 if jnp.dtype(real_dtype) == jnp.float64
                     else jnp.complex64)


@dataclasses.dataclass(frozen=True)
class DistSHT:
    """Distributed SHT bound to a plan, mesh and axis name(s).

    ``axis_names`` may be a single mesh axis or a tuple (the m/ring shards
    span the flattened product, e.g. ("data", "model") uses all 256 chips of
    a pod as one S^2HAT process ring).
    """

    plan: SHTPlan
    mesh: Mesh
    axis_names: tuple[str, ...]
    dtype: str = "float64"
    fold: bool = False
    comm_dtype: Optional[str] = None      # e.g. "bfloat16" for compressed Delta
    stage1: str = "jnp"                    # "jnp" | "pallas"
    comm_chunks: Optional[int] = None      # None -> plan.comm_chunks; C>1 =
                                           # chunked pipelined exchange

    def __post_init__(self):
        n = int(np.prod([self.mesh.shape[a] for a in self.axis_names]))
        assert n == self.plan.n_shards, (n, self.plan.n_shards)
        if self.fold:
            assert self.plan.grid.equator_symmetric
        assert self._comm_chunks >= 1, self.comm_chunks

    @property
    def _comm_chunks(self) -> int:
        c = self.plan.comm_chunks if self.comm_chunks is None \
            else self.comm_chunks
        return max(1, int(c))

    # -- shardings -------------------------------------------------------------

    @property
    def _axis(self):
        return self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]

    def alm_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis_names))

    def map_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis_names))

    def _spec_sharded(self) -> P:
        return P(self.axis_names)

    # -- static geometry (closed over as constants) ------------------------------

    @functools.cached_property
    def _log_mu(self) -> np.ndarray:
        return legendre.log_mu(self.plan.m_max)

    @functools.cached_property
    def _geom(self):
        return self.plan.ring_geometry

    # -- stage 1: Legendre synthesis (m-sharded) ---------------------------------

    def _stage1_synth(self, a_re, a_im, m_loc):
        """Per-shard: (m_local, L, K) -> Delta (m_local, R_pad, K) x (re, im).

        Closes over the full ring geometry (every shard sees all rings).
        """
        p = self.plan
        dt = jnp.dtype(self.dtype)
        if self.stage1 == "pallas":
            from repro.kernels import ops as kops
            return kops.delta_from_alm_auto(
                a_re, a_im, m_loc, self._geom, self._log_mu,
                l_max=p.l_max, fold=self.fold, dtype=dt)
        g = self._geom
        if not self.fold:
            return legendre.delta_from_alm(
                a_re, a_im, m_loc, g["cos_theta"], g["sin_theta"],
                self._log_mu, l_max=p.l_max, dtype=dt)
        nx = g["cos_theta"][0::2]
        ns = g["sin_theta"][0::2]
        ere, eim, ore_, oim = legendre.delta_from_alm_folded(
            a_re, a_im, m_loc, nx, ns, self._log_mu, l_max=p.l_max, dtype=dt)
        # interleave (E+O, E-O) back to plan slot order
        d_re = jnp.stack([ere + ore_, ere - ore_], axis=2)
        d_im = jnp.stack([eim + oim, eim - oim], axis=2)
        ml, npair, _, K = d_re.shape
        return (d_re.reshape(ml, 2 * npair, K), d_im.reshape(ml, 2 * npair, K))

    def _stage1_anal(self, dw_re, dw_im, m_loc):
        """Per-shard: weighted Delta^S (m_local, R_pad, K) -> alm (m_local, L, K)."""
        p = self.plan
        dt = jnp.dtype(self.dtype)
        g = self._geom
        if self.stage1 == "pallas":
            from repro.kernels import ops as kops
            return kops.alm_from_delta_auto(
                dw_re, dw_im, m_loc, g, self._log_mu,
                l_max=p.l_max, fold=self.fold, dtype=dt)
        if not self.fold:
            ones = np.ones(p.r_pad)
            return legendre.alm_from_delta(
                dw_re, dw_im, m_loc, g["cos_theta"], g["sin_theta"], ones,
                self._log_mu, l_max=p.l_max, dtype=dt)
        nx = g["cos_theta"][0::2]
        ns = g["sin_theta"][0::2]
        n_re, s_re = dw_re[:, 0::2], dw_re[:, 1::2]
        n_im, s_im = dw_im[:, 0::2], dw_im[:, 1::2]
        return legendre.alm_from_delta_folded(
            n_re + s_re, n_im + s_im, n_re - s_re, n_im - s_im,
            m_loc, nx, ns, self._log_mu, l_max=p.l_max, dtype=dt)

    # -- spin-2 stage 1 (two stacked Wigner-d recurrences per shard) -------------

    def _stage1_synth_spin(self, e_re, e_im, b_re, b_im, m_loc):
        """Per-shard spin-2 Legendre synthesis: (E, B) (m_local, L, K) ->
        (dq_re, dq_im, du_re, du_im), each (m_local, R_pad, K)."""
        p = self.plan
        dt = jnp.dtype(self.dtype)
        g = self._geom
        if self.stage1 == "pallas":
            from repro.kernels import ops as kops
            return kops.delta_from_alm_spin_auto(
                e_re, e_im, b_re, b_im, m_loc, g, l_max=p.l_max,
                m_max=p.m_max, dtype=dt)
        return legendre.delta_from_alm_spin(
            e_re, e_im, b_re, b_im, m_loc, g["cos_theta"], g["sin_theta"],
            l_max=p.l_max, m_max=p.m_max, dtype=dt)

    def _stage1_anal_spin(self, dq_re, dq_im, du_re, du_im, m_loc):
        """Per-shard spin-2 Legendre analysis: weighted (Delta_Q, Delta_U)
        (m_local, R_pad, K) -> (e_re, e_im, b_re, b_im) (m_local, L, K)."""
        p = self.plan
        dt = jnp.dtype(self.dtype)
        g = self._geom
        if self.stage1 == "pallas":
            from repro.kernels import ops as kops
            return kops.alm_from_delta_spin_auto(
                dq_re, dq_im, du_re, du_im, m_loc, g, l_max=p.l_max,
                m_max=p.m_max, dtype=dt)
        return legendre.alm_from_delta_spin(
            dq_re, dq_im, du_re, du_im, m_loc, g["cos_theta"],
            g["sin_theta"], l_max=p.l_max, m_max=p.m_max, dtype=dt)

    # -- stage 2: FFTs (ring-sharded), plan-slot m ordering ----------------------
    #
    # Both directions delegate to the pluggable phase layer
    # (repro.core.phase): the batched-rfft engine for uniform grids, the
    # ring-bucket engine for ragged (true HEALPix) ones.  Every shard runs
    # the same static bucket structure (plan.local_fft_layout); the
    # per-slot geometry and alias-fold bin maps arrive as *sharded
    # operands* so one SPMD program serves all shards.

    def _synth_fft(self, d_re, d_im, phi0_loc, w_dummy_loc, fft_ops=()):
        """(Mp, r_local, K) Delta -> (r_local, n_phi, K) samples."""
        p = self.plan
        cdt = _complex_dtype(self.dtype)
        delta = (d_re + 1j * d_im).astype(cdt)
        if p.grid.uniform:
            return phaselib.uniform_synth(
                delta, p.m_flat, p.grid.max_n_phi, phi0_loc,
                dtype=self.dtype, scale_rows=w_dummy_loc)
        n_loc, pos_loc, neg_loc = fft_ops
        return phaselib.bucket_synth(
            delta, p.local_fft_layout, pos_loc.T, neg_loc.T, n_loc,
            phi0_loc, p.m_flat, out_width=p.grid.max_n_phi,
            dtype=self.dtype, scale_rows=w_dummy_loc)

    def _anal_fft(self, maps_loc, phi0_loc, w_loc, fft_ops=()):
        """(r_local, n_phi, K) samples -> weighted Delta^S (Mp, r_local, K)."""
        p = self.plan
        if p.grid.uniform:
            dw = phaselib.uniform_anal(
                maps_loc, p.m_flat, p.grid.max_n_phi, phi0_loc, w_loc,
                dtype=self.dtype)
        else:
            n_loc, pos_loc = fft_ops
            dw = phaselib.bucket_anal(
                maps_loc, p.local_fft_layout, pos_loc.T, n_loc, phi0_loc,
                w_loc, p.m_flat, dtype=self.dtype)
        return jnp.real(dw).astype(self.dtype), jnp.imag(dw).astype(self.dtype)

    # -- collective ---------------------------------------------------------------

    def _exchange(self, x, *, to_rings: bool):
        """The paper's global communication step (one per chunk).

        to_rings:  (m_local, R_pad, C) -> (Mp, r_local, C)
        else:      (Mp, r_local, C)    -> (m_local, R_pad, C)
        """
        with jax.named_scope(EXCHANGE):
            n = self.plan.n_shards
            split_axis = 1 if to_rings else 0
            what = "dealt ring-pair slot" if to_rings else "dealt m-row slot"
            if x.shape[split_axis] % n != 0:
                raise ValueError(
                    f"all_to_all(tiled=True) needs the {what} count to be a "
                    f"multiple of the device count: axis {split_axis} has "
                    f"{x.shape[split_axis]} slots but the mesh "
                    f"{dict(self.mesh.shape)} spans {n} devices over axes "
                    f"{self.axis_names} (shape {x.shape})")
            if self.comm_dtype is not None:
                x = x.astype(self.comm_dtype)
            if to_rings:
                out = jax.lax.all_to_all(x, self._axis, split_axis=1,
                                         concat_axis=0, tiled=True)
            else:
                out = jax.lax.all_to_all(x, self._axis, split_axis=0,
                                         concat_axis=1, tiled=True)
            return out.astype(self.dtype)

    def _exchange_parts(self, parts, *, to_rings: bool, widths=None):
        """:meth:`_exchange` of ``parts`` (one shape but for the trailing
        channel axis) packed into one channel axis, unpacked into arrays
        of ``widths`` channels (by default the parts' own), all under the
        exchange scope: ONE collective for every channel."""
        with jax.named_scope(EXCHANGE):
            out = self._exchange(jnp.concatenate(parts, axis=-1),
                                 to_rings=to_rings)
            if widths is None:
                widths = [p.shape[-1] for p in parts]
            return jnp.split(out, np.cumsum(widths)[:-1].tolist(), axis=-1)

    # -- chunked pipelined exchange helpers ----------------------------------
    #
    # Each chunk is an independent (stage-1 compute, all_to_all) pair: the
    # loops below emit C data-independent collectives interleaved with the
    # adjacent chunks' compute, which is exactly the dependence structure an
    # async/latency-hiding scheduler needs to keep the wire and the ALUs
    # busy at the same time.  Numerically this is a pure reordering of
    # per-(m, k)-independent work, so results match the monolithic path
    # bit-for-bit.

    def _schedule(self, K: int, ncomp: int = 1):
        return self.plan.chunk_schedule(K, ncomp=ncomp,
                                        chunks=self._comm_chunks)

    def _merge_m_chunks(self, parts):
        """Exchanged m-chunks [(n*mc_j, r_local, C)] -> (Mp, r_local, C).

        Each chunk's global rows are shard-major over that chunk's slice
        of the local m rows; re-interleave so the full plan slot order
        (shard-major over m_local) is restored exactly.
        """
        n = self.plan.n_shards
        segs = [p.reshape((n, p.shape[0] // n) + p.shape[1:]) for p in parts]
        cat = jnp.concatenate(segs, axis=1)
        return cat.reshape((n * cat.shape[1],) + cat.shape[2:])

    def _split_m_chunk(self, packed, m0: int, m1: int):
        """(Mp, r_local, C) plan-order rows -> the (n*(m1-m0), r_local, C)
        block holding local rows [m0, m1) of every shard (inverse of one
        `_merge_m_chunks` segment)."""
        n = self.plan.n_shards
        g = packed.reshape((n, packed.shape[0] // n) + packed.shape[1:])
        piece = g[:, m0:m1]
        return piece.reshape((n * (m1 - m0),) + packed.shape[1:])

    # -- public transforms ---------------------------------------------------------

    def _build(self, K: int, spin: int = 0):
        cache = getattr(self, "_built", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_built", cache)
        key = (spin, K)
        if key in cache:
            return cache[key]
        out = self._build_uncached(K) if spin == 0 \
            else self._build_spin_uncached(K)
        cache[key] = out
        return out

    def _consts(self):
        """Static per-slot operands closed over by the shard programs."""
        p = self.plan
        geom = self._geom
        phi0_all = jnp.asarray(geom["phi0"], self.dtype)
        w_all = jnp.asarray(geom["weights"], self.dtype)
        valid_all = jnp.asarray(geom["valid"].astype(np.float64), self.dtype)
        m_flat = jnp.asarray(p.m_flat, jnp.int32)
        # ragged grids: per-slot FFT geometry + precomputed alias-fold bin
        # maps ride along as ring-sharded operands (plan.fft_bin_maps)
        if p.grid.uniform:
            synth_ops = anal_ops = ()
        else:
            pos_all, neg_all = p.fft_bin_maps            # (R_pad, Mp) int32
            n_all = jnp.asarray(geom["n_phi"], jnp.int32)
            synth_ops = (n_all, jnp.asarray(pos_all), jnp.asarray(neg_all))
            anal_ops = (n_all, jnp.asarray(pos_all))
        return dict(phi0=phi0_all, w=w_all, valid=valid_all, m_flat=m_flat,
                    synth_ops=synth_ops, anal_ops=anal_ops)

    def _build_uncached(self, K: int):
        consts = self._consts()
        synth_ops, anal_ops = consts["synth_ops"], consts["anal_ops"]
        axis, bounds = self._schedule(K)

        def synth_shard(a_re, a_im, m_loc, phi0_loc, valid_loc, *fft_ops):
            if axis == "k":
                # chunk i's collective is issued while chunk i+1's Legendre
                # recurrence runs (the chunks share no data)
                parts = []
                for k0, k1 in bounds:
                    d_re, d_im = self._stage1_synth(
                        a_re[..., k0:k1], a_im[..., k0:k1], m_loc)
                    parts.append(self._exchange_parts(
                        [d_re, d_im], to_rings=True))   # (Mp, r_local, kc)
                with jax.named_scope(EXCHANGE):
                    d_re = jnp.concatenate([p[0] for p in parts], axis=-1)
                    d_im = jnp.concatenate([p[1] for p in parts], axis=-1)
            elif axis == "m":
                parts = []
                for m0, m1 in bounds:
                    d_re, d_im = self._stage1_synth(
                        a_re[m0:m1], a_im[m0:m1], m_loc[m0:m1])
                    parts.append(self._exchange_parts(
                        [d_re, d_im], to_rings=True))  # (n*mc, r_local, K)
                with jax.named_scope(EXCHANGE):         # (Mp, r_local, K)
                    d_re = self._merge_m_chunks([p[0] for p in parts])
                    d_im = self._merge_m_chunks([p[1] for p in parts])
            else:
                d_re, d_im = self._stage1_synth(a_re, a_im, m_loc)
                d_re, d_im = self._exchange_parts(
                    [d_re, d_im], to_rings=True)       # (Mp, r_local, K)
            return self._synth_fft(d_re, d_im, phi0_loc, valid_loc, fft_ops)

        def anal_shard(maps_loc, m_loc, phi0_loc, w_loc, *fft_ops):
            if axis == "k":
                # chunk i's collective overlaps chunk i-1's projection and
                # chunk i+1's FFT
                res = []
                for k0, k1 in bounds:
                    dw_re, dw_im = self._anal_fft(
                        maps_loc[..., k0:k1], phi0_loc, w_loc, fft_ops)
                    dw_re, dw_im = self._exchange_parts(
                        [dw_re, dw_im], to_rings=False)  # (m_local, R_pad, kc)
                    res.append(self._stage1_anal(dw_re, dw_im, m_loc))
                return (jnp.concatenate([r[0] for r in res], axis=-1),
                        jnp.concatenate([r[1] for r in res], axis=-1))
            if axis == "m":
                dw_re, dw_im = self._anal_fft(maps_loc, phi0_loc, w_loc,
                                              fft_ops)       # (Mp, r, K)
                res = []
                for m0, m1 in bounds:
                    with jax.named_scope(EXCHANGE):
                        pieces = [self._split_m_chunk(d, m0, m1)
                                  for d in (dw_re, dw_im)]
                    c_re, c_im = self._exchange_parts(
                        pieces, to_rings=False)          # (mc, R_pad, K)
                    res.append(self._stage1_anal(c_re, c_im, m_loc[m0:m1]))
                return (jnp.concatenate([r[0] for r in res], axis=0),
                        jnp.concatenate([r[1] for r in res], axis=0))
            dw_re, dw_im = self._anal_fft(maps_loc, phi0_loc, w_loc, fft_ops)
            dw_re, dw_im = self._exchange_parts(
                [dw_re, dw_im], to_rings=False)          # (m_local, R_pad, K)
            return self._stage1_anal(dw_re, dw_im, m_loc)

        spec = self._spec_sharded()
        # check_vma=False disables the replication/VMA tracker: the
        # Legendre loop carries are seeded from constants (unvarying) and
        # become shard-varying inside the loop; we opt out rather than
        # pcast-ing deep inside the shared recurrence code.
        synth = jax.jit(jax.shard_map(
            synth_shard, mesh=self.mesh,
            in_specs=(spec,) * (5 + len(synth_ops)),
            out_specs=spec, check_vma=False))
        anal = jax.jit(jax.shard_map(
            anal_shard, mesh=self.mesh,
            in_specs=(spec,) * (4 + len(anal_ops)),
            out_specs=(spec, spec), check_vma=False))
        return synth, anal, consts

    def _build_spin_uncached(self, K: int):
        """Spin-2 shard programs.  Identical two-stage structure: the
        (Q, U) / (E, B) component pair is packed into the trailing channel
        axis (2K complex channels through the phase stage, 4K real
        channels through the ONE all_to_all), so the exchange count and
        the bucketed phase stage are untouched."""
        assert not self.fold, "fold is not supported for spin transforms"
        consts = self._consts()
        synth_ops, anal_ops = consts["synth_ops"], consts["anal_ops"]
        # the (Q, U) pair is coupled through the Wigner lambda^{+/-} pair,
        # so chunk boundaries ride the K axis only (ncomp channels stay
        # inside each chunk) -- or fall back to m rows for small K.
        axis, bounds = self._schedule(K, ncomp=2)

        def _synth_one(e_re, e_im, b_re, b_im, m_loc):
            """Stage 1 + exchange for one chunk -> [dq_re, du_re, dq_im,
            du_im], each (Mp, r, kc)."""
            dq_re, dq_im, du_re, du_im = self._stage1_synth_spin(
                e_re, e_im, b_re, b_im, m_loc)
            return self._exchange_parts([dq_re, du_re, dq_im, du_im],
                                  to_rings=True)

        def synth_shard(e_re, e_im, b_re, b_im, m_loc, phi0_loc, valid_loc,
                        *fft_ops):
            if axis == "k":
                parts = [_synth_one(e_re[..., k0:k1], e_im[..., k0:k1],
                                    b_re[..., k0:k1], b_im[..., k0:k1], m_loc)
                         for k0, k1 in bounds]
                quad = [[p[c] for p in parts] for c in range(4)]
            elif axis == "m":
                parts = [_synth_one(e_re[m0:m1], e_im[m0:m1], b_re[m0:m1],
                                    b_im[m0:m1], m_loc[m0:m1])
                         for m0, m1 in bounds]
                with jax.named_scope(EXCHANGE):          # (Mp, r_local, K)
                    quad = [[self._merge_m_chunks([p[c] for p in parts])]
                            for c in range(4)]
            else:
                quad = [[p] for p in _synth_one(e_re, e_im, b_re, b_im,
                                                m_loc)]
            with jax.named_scope(EXCHANGE):
                d_re = jnp.concatenate(quad[0] + quad[1], axis=-1)  # [Q|U] re
                d_im = jnp.concatenate(quad[2] + quad[3], axis=-1)  # [Q|U] im
            return self._synth_fft(d_re, d_im, phi0_loc, valid_loc, fft_ops)

        def _anal_one(maps_c, kc, m_loc, phi0_loc, w_loc, fft_ops):
            """FFT + exchange + stage 1 for one (r_local, n_phi, 2kc) chunk."""
            dw_re, dw_im = self._anal_fft(maps_c, phi0_loc, w_loc, fft_ops)
            dq_re, du_re, dq_im, du_im = self._exchange_parts(
                [dw_re, dw_im], to_rings=False, widths=[kc] * 4)
            return self._stage1_anal_spin(dq_re, dq_im, du_re, du_im, m_loc)

        def anal_shard(maps_loc, m_loc, phi0_loc, w_loc, *fft_ops):
            # maps_loc: (r_local, n_phi, 2K) = [Q | U] channels
            if axis == "k":
                res = []
                for k0, k1 in bounds:
                    maps_c = jnp.concatenate(
                        [maps_loc[..., k0:k1], maps_loc[..., K + k0:K + k1]],
                        axis=-1)
                    res.append(_anal_one(maps_c, k1 - k0, m_loc, phi0_loc,
                                         w_loc, fft_ops))
                return tuple(jnp.concatenate([r[c] for r in res], axis=-1)
                             for c in range(4))
            if axis == "m":
                dw_re, dw_im = self._anal_fft(maps_loc, phi0_loc, w_loc,
                                              fft_ops)     # (Mp, r, 2K)
                res = []
                for m0, m1 in bounds:
                    with jax.named_scope(EXCHANGE):
                        pieces = [self._split_m_chunk(d, m0, m1)
                                  for d in (dw_re, dw_im)]
                    dq_re, du_re, dq_im, du_im = self._exchange_parts(
                        pieces, to_rings=False, widths=[K] * 4)
                    res.append(self._stage1_anal_spin(
                        dq_re, dq_im, du_re, du_im, m_loc[m0:m1]))
                return tuple(jnp.concatenate([r[c] for r in res], axis=0)
                             for c in range(4))
            return _anal_one(maps_loc, K, m_loc, phi0_loc, w_loc, fft_ops)

        spec = self._spec_sharded()
        synth = jax.jit(jax.shard_map(
            synth_shard, mesh=self.mesh,
            in_specs=(spec,) * (7 + len(synth_ops)),
            out_specs=spec, check_vma=False))
        anal = jax.jit(jax.shard_map(
            anal_shard, mesh=self.mesh,
            in_specs=(spec,) * (4 + len(anal_ops)),
            out_specs=(spec,) * 4, check_vma=False))
        return synth, anal, consts

    def alm2map(self, alm_packed):
        """Packed plan-layout alm (Mp, L, K) complex -> maps (R_pad, n_phi, K).

        Input rows follow plan.m_flat; use plan.pack_alm / plan.scatter_map
        for dense-layout conversion.  Output rows follow plan.ring_order.
        """
        K = alm_packed.shape[-1]
        synth, _, c = self._build(K)
        a_re = jnp.real(alm_packed).astype(self.dtype)
        a_im = jnp.imag(alm_packed).astype(self.dtype)
        return synth(a_re, a_im, c["m_flat"], c["phi0"], c["valid"],
                     *c["synth_ops"])

    def map2alm(self, maps_plan):
        """maps (R_pad, n_phi, K) in plan ring order -> packed alm (Mp, L, K)."""
        K = maps_plan.shape[-1]
        _, anal, c = self._build(K)
        a_re, a_im = anal(maps_plan.astype(self.dtype), c["m_flat"],
                          c["phi0"], c["w"], *c["anal_ops"])
        return a_re + 1j * a_im

    def alm2map_spin(self, alm_packed_eb):
        """Spin-2 synthesis: packed (E, B) alm (2, Mp, L, K) complex ->
        (Q, U) maps (2, R_pad, n_phi, K) in plan ring order."""
        K = alm_packed_eb.shape[-1]
        synth, _, c = self._build(K, spin=2)
        e, b = alm_packed_eb[0], alm_packed_eb[1]
        args = [jnp.real(e), jnp.imag(e), jnp.real(b), jnp.imag(b)]
        args = [a.astype(self.dtype) for a in args]
        maps2 = synth(*args, c["m_flat"], c["phi0"], c["valid"],
                      *c["synth_ops"])               # (R_pad, n_phi, 2K)
        return jnp.stack([maps2[..., :K], maps2[..., K:]], axis=0)

    def map2alm_spin(self, maps_plan_qu):
        """Spin-2 analysis: (Q, U) maps (2, R_pad, n_phi, K) in plan ring
        order -> packed (E, B) alm (2, Mp, L, K) complex."""
        K = maps_plan_qu.shape[-1]
        _, anal, c = self._build(K, spin=2)
        maps2 = jnp.concatenate([maps_plan_qu[0], maps_plan_qu[1]],
                                axis=-1).astype(self.dtype)
        e_re, e_im, b_re, b_im = anal(maps2, c["m_flat"], c["phi0"],
                                      c["w"], *c["anal_ops"])
        return jnp.stack([e_re + 1j * e_im, b_re + 1j * b_im], axis=0)

    # -- shape-only entry points for the dry-run -----------------------------------

    def lower_synth(self, K: int):
        """Return (lowered, input ShapeDtypeStructs) for the dry-run."""
        p = self.plan
        synth, _, c = self._build(K)
        sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
        sh = self.alm_sharding()
        Mp = p.n_shards * p.m_local
        args = (
            jax.ShapeDtypeStruct((Mp, p.l_max + 1, K), jnp.dtype(self.dtype), sharding=sh),
            jax.ShapeDtypeStruct((Mp, p.l_max + 1, K), jnp.dtype(self.dtype), sharding=sh),
            c["m_flat"], c["phi0"], c["valid"], *c["synth_ops"],
        )
        return synth.lower(*args), args

    def lower_anal(self, K: int):
        p = self.plan
        _, anal, c = self._build(K)
        sh = self.map_sharding()
        args = (
            jax.ShapeDtypeStruct((p.r_pad, p.grid.max_n_phi, K),
                                 jnp.dtype(self.dtype), sharding=sh),
            c["m_flat"], c["phi0"], c["w"], *c["anal_ops"],
        )
        return anal.lower(*args), args
