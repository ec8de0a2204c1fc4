"""Unified transform plans: one entry point for every SHT execution path.

This is the dispatch seam the paper's "dichotomy" demands (§4-5): the
winning kernel differs between problem sizes *and between the direct and
inverse transforms*, so ``make_plan`` chooses an execution backend per
``(grid, l_max, K, dtype)`` signature and per direction, instead of callers
hand-wiring ``SHT`` / ``legendre_pallas`` / ``DistSHT`` themselves::

    import repro
    plan = repro.make_plan("gl", l_max=256, K=8, dtype="float32")
    maps = plan.alm2map(alm)       # inverse  (synthesis)
    alm2 = plan.map2alm(maps)      # direct   (analysis)
    print(plan.report())           # chosen kernels, predicted vs measured

Backends
--------
``jnp``
    The pure-jnp engine (`repro.core.sht.SHT`): float64 oracle, runs on any
    grid (including ragged HEALPix).  The only candidate when
    ``dtype="float64"`` -- the Pallas kernels compute in float32.
``pallas_vpu`` / ``pallas_mxu``
    The Pallas Legendre kernels (`repro.kernels`) for the recurrence stage,
    with the shared phase stage (`repro.core.phase`) for the FFTs --
    batched-uniform or ring-bucket per grid, so ragged HEALPix runs here
    too.  ``vpu`` is the broadcast-FMA variant (small K); ``mxu`` contracts
    P panels on the matrix unit (large K, the Monte-Carlo batch workload).
``dist``
    The two-stage distributed transform (`repro.core.dist_sht.DistSHT`,
    paper Algorithm 3) across every visible device, with bucket-aware
    ring-pair sharding on ragged grids.  Dense alm/maps in, dense out --
    plan packing/unpacking is handled internally.

Backends that are *not* eligible for a signature are reported with the
reason they were skipped (``describe()["skipped"]`` / the ``report()``
footer), so dispatch decisions stay debuggable.

Dispatch modes
--------------
``mode="model"``  rank backends with the analytic roofline cost model
                  (`repro.roofline.predict_sht_time`) -- free, deterministic.
``mode="auto"``   measure each candidate once per direction (compiled
                  ahead of time, then one timed call; a Legendre layout
                  is skipped when its backend's first layout already runs
                  `PRUNE_FACTOR` times slower than the best candidate so
                  far) and pick the fastest; the decision is
                  cached by plan signature (memory + optional disk), so the
                  autotune pass runs once per signature, ever.  The raw
                  corner timings additionally land in the persistent
                  per-hardware characterization DB (`repro.roofline.chardb`)
                  keyed by workload -- NOT by plan signature or mode -- so
                  even a decision-cache-cold rebuild re-measures zero
                  corners, and ``REPRO_CHARDB_SMOKE=1`` runs skip missing
                  corners entirely (cost-model fallback) instead of timing.
``mode=<backend>`` force one backend for both directions.

Pallas plans additionally dispatch a per-direction Legendre *layout*
(``plan.layouts``): the ``packed``/``plain`` grids of the staged pipeline,
plus ``fused`` -- the single-kernel Legendre+phase pipeline
(`repro.kernels.fused`), which keeps the intermediate ``delta_m`` on-chip
for every plan shape: spin 0 and 2, equator-folded, uniform and bucketed
(ragged HEALPix) grids.  The fused panel length (``lp_size``) is
chardb-autotuned per corner.  ``describe()["fusion"]`` reports
eligibility, the chosen ``lp_size``, and the fallback reason for the two
residual staged shapes (fold on a bucket phase stage; spin-2 at the
uniform Nyquist alias point).

Differentiability
-----------------
``Plan.alm2map`` and ``Plan.map2alm`` carry adjoint-based custom JVP/VJP
rules on every backend (spin 0 and 2, plain and packed layouts, ragged
bucket FFTs, shard_map dist): the synthesis VJP is the weighted analysis
and vice versa, so ``jax.grad`` never traces kernel internals.  See
``Plan.grad_ready``, ``describe()["differentiable"]`` and
docs/architecture.md ("Differentiation via adjoints").

Precompute caching
------------------
Grid geometry (Gauss-Legendre Newton iteration), ``pmm``/``pms`` recurrence
seed tables and autotune decisions are cached by plan signature through
`repro.core.cache` -- in memory always, and on disk under
``$REPRO_CACHE_DIR`` when ``cache="disk"``.  A second ``make_plan`` with an
identical signature returns the *same* plan object without recomputing
anything (asserted by tests/test_transform_plan.py).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.core import cache as plancache
from repro.core import grids as gridlib
from repro.core import legendre
from repro.core.grids import RingGrid
from repro.core.sht import SHT, alm_mask, random_alm, random_alm_spin
from repro.roofline import analysis as roofline
from repro.tracing import ALM2MAP, FOLD, MAP2ALM

__all__ = ["Plan", "make_plan", "available_backends", "backend_eligibility",
           "clear_plan_cache", "drop_plan"]

BACKENDS = ("jnp", "pallas_vpu", "pallas_mxu", "dist")

#: ``mode="auto"`` times a pallas backend's further Legendre layouts only
#: when its first runs within this factor of the best time measured so
#: far in that direction.  The first is ``fused`` where eligible, the
#: fastest layout of its backend wherever it has been measured
#: (docs/performance.md).  At l_max=4096 on a TPU v5e every MXU layout
#: takes ~70 s a call against ~6 s for the best candidate; timing all
#: of them made one auto build take ~20 minutes.
PRUNE_FACTOR = 4.0

#: Order in which a pallas backend's layouts are timed (first = the one
#: that decides whether the rest are timed at all).
_LAYOUT_ORDER = ("fused", "packed", "plain")

#: make_plan memoisation: signature key -> Plan.  This is the "second
#: make_plan is free" tier; the payload caches underneath make a cold
#: rebuild (new process, cache="disk") cheap too.
_PLANS: dict[str, "Plan"] = {}


def clear_plan_cache(*, disk: bool = False,
                     directory: Optional[str] = None) -> None:
    """Drop memoised plans AND the in-memory precompute tier (test hook).

    ``disk=True`` additionally removes the persistent tier under
    ``directory`` (default: ``$REPRO_CACHE_DIR`` / the cache default) --
    without it a clear left stale ``.npz``/``.json`` entries behind that a
    later ``cache="disk"`` plan would silently resurrect.
    """
    _PLANS.clear()
    plancache.clear_memory()
    if disk:
        plancache.clear_disk(directory)


def drop_plan(plan: "Plan") -> bool:
    """Remove one memoised plan so it can be garbage-collected.

    ``clear_plan_cache`` is all-or-nothing; bounded plan holders (the
    serving engine's LRU pool, `repro.serve.PlanPool`) evict a single
    signature through this.  The shared precompute payloads (geometry,
    seed tables) stay cached -- only the live Plan object (compiled
    executables, device seed arrays) is released.  Returns True when the
    plan was actually memoised.
    """
    return _PLANS.pop(plan._signature_key, None) is not None


def _pallas_ops():
    """Import the kernel layer lazily (keeps `import repro` light)."""
    from repro.kernels import ops as kops
    return kops


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_dtype() -> str:
    """The plan dtype used when none is given: ``"float64"`` (the oracle
    precision) where it can run -- JAX's 64-bit mode on, not a TPU --
    else ``"float32"``."""
    if jax.config.jax_enable_x64 and not _on_tpu():
        return "float64"
    return "float32"


def check_dtype(dtype: str) -> None:
    """Refuse a float64 transform where it cannot run as float64: on a TPU
    (no float64 unit; it would run in float32 or in slow emulation) and
    wherever JAX's 64-bit mode is off (it would silently run in float32)."""
    if dtype not in ("float64", "float32"):
        raise ValueError(f"unsupported dtype {dtype!r}: expected 'float32' "
                         "or 'float64'")
    if dtype != "float64":
        return
    if _on_tpu():
        raise ValueError("dtype='float64' is not available on a TPU (no "
                         "float64 hardware); use dtype='float32'")
    if not jax.config.jax_enable_x64:
        raise ValueError("dtype='float64' needs JAX's 64-bit mode: call "
                         "jax.config.update('jax_enable_x64', True) first, "
                         "or use dtype='float32'")


def backend_eligibility(grid: RingGrid, dtype: str,
                        n_devices: Optional[int] = None
                        ) -> dict[str, Optional[str]]:
    """Why-or-why-not per backend: ``{backend: None | skip_reason}``.

    float64 restricts to the jnp oracle (the kernels compute in float32);
    dist needs >= 2 devices.  Grid raggedness is NOT a restriction: the
    phase stage (`repro.core.phase`) serves every backend on every grid.
    On a TPU the staged VPU layouts, which do not compile there, are
    reported as ineligible under ``"pallas_vpu[plain]"`` and
    ``"pallas_vpu[packed]"`` (the VPU backend keeps its fused layout).
    """
    out: dict[str, Optional[str]] = {b: None for b in BACKENDS}
    if dtype != "float32":
        reason = (f"kernels compute in float32 (plan dtype {dtype!r}); "
                  "force mode='pallas_*' to accept the precision drop")
        out["pallas_vpu"] = out["pallas_mxu"] = reason
    if _on_tpu():
        out["pallas_vpu[plain]"] = out["pallas_vpu[packed]"] = \
            _pallas_ops().STAGED_VPU_TPU_ERROR
    n_dev = jax.device_count() if n_devices is None else n_devices
    if n_dev < 2:
        out["dist"] = f"needs >= 2 devices (visible: {n_dev})"
    return out


def available_backends(grid: RingGrid, dtype: str,
                       n_devices: Optional[int] = None) -> list[str]:
    """Backends eligible for this signature (see `backend_eligibility`
    for the skip reasons of the rest)."""
    elig = backend_eligibility(grid, dtype, n_devices)
    return [b for b in BACKENDS if elig[b] is None]


def _complex_dtype(dtype: str):
    return jnp.complex128 if jnp.dtype(dtype) == jnp.float64 else jnp.complex64


def _time_call_us(fn, arg) -> float:
    """Microseconds of one call ``fn(arg)``, compile excluded.  A jitted
    plan function (``jax.jit`` or `_bind`) is compiled ahead of time and
    run once; anything else (the dist wrapper) gets one untimed warm-up
    call first.  On a TPU one call at l_max=4096 can take over a minute,
    so no execution is spent on warming up."""
    jitted, kw = ((fn.func, fn.keywords) if isinstance(fn, functools.partial)
                  else (fn, {}))
    if hasattr(jitted, "lower"):
        compiled = jitted.lower(arg, **kw).compile()
        call = functools.partial(compiled, arg, **kw)
    else:
        jax.block_until_ready(fn(arg))
        call = functools.partial(fn, arg)
    t0 = time.perf_counter()
    jax.block_until_ready(call())
    return (time.perf_counter() - t0) * 1e6


def _bind(fn, consts):
    """``fn(x, consts)`` jitted, with the plan's precomputed tables (seeds,
    ring nodes, rotation tables, masks) passed as arguments.  Closed over
    instead, they would be embedded in each program as constants: hundreds
    of MB of program per kernel at l_max=4096."""
    return functools.partial(jax.jit(fn), consts=consts)


class Plan:
    """An executable SHT plan: precompute + layout + kernel choice.

    Construct through :func:`make_plan` (which memoises by signature); the
    constructor itself does no autotuning and no device work.

    Attributes
    ----------
    grid, l_max, m_max, K, dtype, fold, spin : the plan signature.
    mode : dispatch mode this plan was built with.
    backends : ``{"synth": name, "anal": name}`` -- the chosen execution
        backend per direction (the paper's direct/inverse dichotomy made
        into a data structure).

    A ``spin=2`` plan transforms (E, B) alm pairs ``(2, M, L, K)`` to/from
    (Q, U) map pairs ``(2, R, n_phi, K)`` -- same K batch axis, same
    backends, twice the Legendre-panel work (lambda^{+/-} pair).
    """

    def __init__(self, grid: RingGrid, l_max: int, m_max: int, K: int,
                 dtype: str, *, mode: str, fold: bool, spin: int,
                 cache_kind: str, cache_dir: Optional[str],
                 n_shards: Optional[int], signature_key: str,
                 comm_chunks: Union[int, str] = "auto"):
        self.grid = grid
        self.l_max = int(l_max)
        self.m_max = int(m_max)
        self.K = int(K)
        self.dtype = str(dtype)
        self.mode = mode
        self.fold = bool(fold)
        self.spin = int(spin)
        self._cache_kind = cache_kind
        self._cache_dir = cache_dir
        self._n_shards = n_shards
        self._signature_key = signature_key
        self._sht = SHT(grid, l_max=self.l_max, m_max=self.m_max,
                        dtype=self.dtype, fold=self.fold,
                        phase_cache=cache_kind, phase_cache_dir=cache_dir)
        self._m_vals = np.arange(self.m_max + 1)
        self._seeds_cache: Optional[tuple] = None
        self._seeds_spin_cache: Optional[tuple] = None
        self._dists: dict = {}          # comm_chunks C -> DistSHT engine
        self._dist_splan = None
        self._comm_spec = comm_chunks   # "auto" or a forced chunk count
        self._compiled: dict = {}
        self.backends: dict = {}
        #: Legendre layout per direction (pallas backends only; None
        #: elsewhere): "packed" / "plain" staged grids, or "fused" -- the
        #: single-kernel Legendre+phase pipeline (kernels/fused.py).
        self.layouts: dict = {}
        #: Exchange chunk count per direction (dist backend only; None
        #: elsewhere): C > 1 runs the chunked pipelined all_to_all.
        self.comm_chunks: dict = {}
        self.candidates: list[str] = []
        self.skipped: dict = {}
        self.predicted_s: dict = {}
        self.measured_s: dict = {}
        self.cache_events: dict = {}

    @property
    def phase(self):
        """The plan's FFT/phase stage (`repro.core.phase.PhaseStage`):
        the uniform batched engine or the ring-bucket engine, shared by
        every backend of this plan."""
        return self._sht.phase

    # -- precompute (cached by signature) -----------------------------------

    def _seeds(self):
        """(pmm, pms, x32) float32 seed tables for the Pallas kernels.

        Fold plans seed northern rings only (half the table).  Built once
        per plan, persisted by signature when ``cache="disk"``.
        """
        if self._seeds_cache is not None:
            return self._seeds_cache
        g = self.grid
        nh = (g.n_rings + 1) // 2
        sin = g.sin_theta[:nh] if self.fold else g.sin_theta
        x = g.cos_theta[:nh] if self.fold else g.cos_theta

        def build():
            from repro.kernels import ref as kref
            lm = legendre.log_mu(self.m_max)
            pmm, pms = kref.prepare_seeds(self._m_vals, sin, lm)
            return {"pmm": np.asarray(pmm), "pms": np.asarray(pms)}

        key = plancache.signature_key(
            "seeds", sig=self._signature_key, fold=self.fold)
        payload = plancache.get_or_build(
            key, build, cache=self._cache_kind, directory=self._cache_dir)
        self.cache_events.setdefault("seeds", key)
        self._seeds_cache = (jnp.asarray(payload["pmm"]),
                             jnp.asarray(payload["pms"]),
                             jnp.asarray(x, jnp.float32))
        return self._seeds_cache

    def _seeds_spin(self):
        """Spin-2 float32 seed tables for the Pallas kernels: the stacked
        (m' = -2 | +2) lambda rows, persisted by signature like `_seeds`."""
        if self._seeds_spin_cache is not None:
            return self._seeds_spin_cache
        from repro.core import legendre as leg
        g = self.grid
        m2, mp2 = leg._spin_rows(self._m_vals)

        def build():
            from repro.kernels import ref as kref
            pmm, pms = kref.prepare_seeds_spin(
                m2, mp2, g.cos_theta, g.sin_theta, m_max=self.m_max)
            return {"pmm": np.asarray(pmm), "pms": np.asarray(pms)}

        key = plancache.signature_key("seeds_spin", sig=self._signature_key)
        payload = plancache.get_or_build(
            key, build, cache=self._cache_kind, directory=self._cache_dir)
        self.cache_events.setdefault("seeds_spin", key)
        self._seeds_spin_cache = (jnp.asarray(payload["pmm"]),
                                  jnp.asarray(payload["pms"]),
                                  jnp.asarray(g.cos_theta, jnp.float32),
                                  m2, mp2)
        return self._seeds_spin_cache

    def _dist_engine(self, comm_chunks: int = 1):
        """The distributed engine for one exchange chunk count (engines are
        cached per C; the dealing plan and mesh are shared)."""
        C = max(1, int(comm_chunks))
        if C not in self._dists:
            from repro.core.dist_sht import DistSHT
            from repro.core.plan import SHTPlan
            n = self._n_shards or jax.device_count()
            if self._dist_splan is None:
                # Auto axes: jax.grad through shard_map needs them (the
                # default Explicit axes reject the adjoint's device count)
                mesh = jax.make_mesh((n,), ("sht",),
                                     axis_types=(AxisType.Auto,))
                self._dist_splan = (mesh,
                                    SHTPlan(self.grid, self.l_max,
                                            self.m_max, n))
            mesh, splan = self._dist_splan
            stage1 = "pallas" if self.dtype == "float32" else "jnp"
            self._dists[C] = DistSHT(splan, mesh, ("sht",), dtype=self.dtype,
                                     fold=False, stage1=stage1,
                                     comm_chunks=C)
        return self._dists[C]

    # -- per-backend execution ------------------------------------------------

    def _apply_layout_env(self, backend: str, layout):
        """Honour ``$REPRO_LEGENDRE_LAYOUT=fused`` at the plan level.

        The staged wrappers reject the value (`ops.pick_layout`); here the
        override routes an eligible pallas direction onto the fused
        pipeline, and raises (naming the eligibility reason) instead of
        silently falling back when the plan cannot be fused -- the same
        silent-fallback bug class as the PR-7 packed-anal mistiming.
        """
        if backend not in ("pallas_vpu", "pallas_mxu") or layout == "fused":
            return layout
        if os.environ.get("REPRO_LEGENDRE_LAYOUT") != "fused":
            return layout
        ok, reason = self._fusion_eligibility()
        if not ok:
            raise ValueError(
                "$REPRO_LEGENDRE_LAYOUT=fused requested, but the fused "
                f"pipeline is ineligible for this plan: {reason}")
        return "fused"

    def _synth_fn(self, backend: str, layout: Optional[str] = None):
        """Synthesis callable alm -> maps for ``backend`` (jitted; compiled
        executables are cached on the plan).  ``layout`` overrides the
        plan's packed-vs-plain choice (autotune measures both); for the
        dist backend it carries the exchange chunk count C instead."""
        if layout is None:
            layout = self.layouts.get("synth")
        if backend == "dist" and layout is None:
            layout = self.comm_chunks.get("synth") or 1
        layout = self._apply_layout_env(backend, layout)
        key = ("synth", backend, layout)
        if key in self._compiled:
            return self._compiled[key]
        spin = self.spin != 0
        if backend == "jnp":
            fn = jax.jit(self._sht.alm2map_spin if spin
                         else self._sht.alm2map)
        elif backend in ("pallas_vpu", "pallas_mxu"):
            variant = backend.split("_")[1]
            if layout == "fused":
                ok, reason = self._fusion_eligibility()
                if not ok:
                    raise ValueError(f"fused layout unavailable: {reason}")
                fn = self._make_fused_synth(variant=variant)
            elif spin:
                fn = self._make_pallas_synth_spin(variant=variant,
                                                  layout=layout)
            else:
                fn = self._make_pallas_synth(variant=variant, layout=layout)
        elif backend == "dist":
            d = self._dist_engine(comm_chunks=int(layout or 1))
            splan = d.plan

            if spin:
                def fn(alm_eb):
                    packed = jnp.stack([splan.pack_alm(alm_eb[0]),
                                        splan.pack_alm(alm_eb[1])], axis=0)
                    mp = d.alm2map_spin(packed)        # (2, R_pad, nphi, K)
                    return jnp.stack([splan.scatter_map(mp[0]),
                                      splan.scatter_map(mp[1])], axis=0)
            else:
                def fn(alm):
                    maps_plan = d.alm2map(splan.pack_alm(alm))
                    return splan.scatter_map(maps_plan)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        self._compiled[key] = fn
        return fn

    def _anal_fn(self, backend: str, layout: Optional[str] = None):
        """Analysis callable maps -> alm for ``backend`` (``layout``: see
        :meth:`_synth_fn` -- chunk count C for the dist backend)."""
        if layout is None:
            layout = self.layouts.get("anal")
        if backend == "dist" and layout is None:
            layout = self.comm_chunks.get("anal") or 1
        layout = self._apply_layout_env(backend, layout)
        key = ("anal", backend, layout)
        if key in self._compiled:
            return self._compiled[key]
        spin = self.spin != 0
        if backend == "jnp":
            fn = jax.jit(self._sht.map2alm_spin if spin
                         else self._sht.map2alm)
        elif backend in ("pallas_vpu", "pallas_mxu"):
            variant = backend.split("_")[1]
            if layout == "fused":
                ok, reason = self._fusion_eligibility()
                if not ok:
                    raise ValueError(f"fused layout unavailable: {reason}")
                fn = self._make_fused_anal(variant=variant)
            elif spin:
                fn = self._make_pallas_anal_spin(variant=variant,
                                                 layout=layout)
            else:
                fn = self._make_pallas_anal(variant=variant, layout=layout)
        elif backend == "dist":
            d = self._dist_engine(comm_chunks=int(layout or 1))
            splan = d.plan

            if spin:
                def fn(maps_qu):
                    packed = jnp.stack([splan.gather_map(maps_qu[0]),
                                        splan.gather_map(maps_qu[1])], axis=0)
                    alm_p = d.map2alm_spin(packed)     # (2, Mp, L, K)
                    return jnp.stack([splan.unpack_alm(alm_p[0]),
                                      splan.unpack_alm(alm_p[1])], axis=0)
            else:
                def fn(maps):
                    alm_packed = d.map2alm(splan.gather_map(maps))
                    return splan.unpack_alm(alm_packed)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        self._compiled[key] = fn
        return fn

    def _make_pallas_synth(self, variant: str, layout=None):
        kops = _pallas_ops()
        K, nh = self.K, (self.grid.n_rings + 1) // 2
        ns = nh - 1 if self.grid.n_rings % 2 == 1 else nh
        cdt = _complex_dtype(self.dtype)

        def fn(alm, consts):
            pmm, pms, x32 = consts
            with jax.named_scope(FOLD):
                a32 = jnp.concatenate([jnp.real(alm), jnp.imag(alm)],
                                      axis=-1).astype(jnp.float32)
            out = kops.synth(a32, self._m_vals, x32, pmm, pms,
                             l_max=self.l_max, fold=self.fold,
                             variant=variant, layout=layout)
            with jax.named_scope(FOLD):
                if self.fold:
                    e, o = out[:, 0], out[:, 1]           # (M, nh, 2K)
                    north = e + o
                    south = (e - o)[:, :ns][:, ::-1]
                    flat = jnp.concatenate([north, south], axis=1)
                else:
                    flat = out[:, 0]                      # (M, R, 2K)
                delta = (flat[..., :K] + 1j * flat[..., K:]).astype(cdt)
            return self._sht.phase.synth(delta).astype(self.dtype)

        return _bind(fn, self._seeds())

    def _make_pallas_anal(self, variant: str, layout=None):
        kops = _pallas_ops()
        K, R = self.K, self.grid.n_rings
        nh = (R + 1) // 2
        cdt = _complex_dtype(self.dtype)
        mask = jnp.asarray(alm_mask(self.l_max, self.m_max))[..., None]

        def fn(maps, consts):
            (pmm, pms, x32), mask = consts
            dwc = self._sht.phase.anal(maps)              # (M, R, K) complex
            with jax.named_scope(FOLD):
                dw = jnp.concatenate([jnp.real(dwc), jnp.imag(dwc)],
                                     axis=-1).astype(jnp.float32)
                if self.fold:
                    n_part = dw[:, :nh]
                    s_part = jnp.zeros_like(n_part)
                    s_part = s_part.at[:, : R - nh].set(dw[:, nh:][:, ::-1])
                    dwk = jnp.stack([n_part + s_part, n_part - s_part],
                                    axis=1)
                else:
                    dwk = dw[:, None]                     # (M, 1, R, 2K)
            out = kops.anal(dwk, self._m_vals, x32, pmm, pms,
                            l_max=self.l_max, fold=self.fold, variant=variant,
                            layout=layout)
            with jax.named_scope(FOLD):
                alm = (out[..., :K] + 1j * out[..., K:]).astype(cdt)
                return jnp.where(mask, alm, 0.0)

        return _bind(fn, (self._seeds(), mask))

    def _make_pallas_synth_spin(self, variant: str, layout=None):
        """Spin-2 kernel synthesis: stacked lambda^{(m' = -+2)} rows through
        the same kernels, component mixing host-side, shared phase stage."""
        from repro.core import legendre as leg
        kops = _pallas_ops()
        K = self.K
        cdt = _complex_dtype(self.dtype)
        pmm, pms, x32, m2, mp2 = self._seeds_spin()

        def fn(alm_eb, consts):
            pmm, pms, x32 = consts
            with jax.named_scope(FOLD):
                e, b = alm_eb[0], alm_eb[1]
                a2_re, a2_im = leg.spin_pack_alm(
                    jnp.real(e), jnp.imag(e), jnp.real(b), jnp.imag(b))
                a32 = jnp.concatenate([a2_re, a2_im],
                                      axis=-1).astype(jnp.float32)
            out = kops.synth(a32, m2, x32, pmm, pms, l_max=self.l_max,
                             fold=False, variant=variant, mp_vals=mp2,
                             layout=layout)
            with jax.named_scope(FOLD):
                flat = out[:, 0]                      # (2M, R, 2K)
                dq_re, dq_im, du_re, du_im = leg.spin_unpack_delta(
                    flat[..., :K], flat[..., K:])
                delta = jnp.concatenate(
                    [dq_re + 1j * dq_im, du_re + 1j * du_im],
                    axis=-1).astype(cdt)              # (M, R, 2K)
            s = self._sht.phase.synth(delta).astype(self.dtype)
            with jax.named_scope(FOLD):
                return jnp.stack([s[..., :K], s[..., K:]], axis=0)

        return _bind(fn, (pmm, pms, x32))

    def _make_pallas_anal_spin(self, variant: str, layout=None):
        from repro.core import legendre as leg
        kops = _pallas_ops()
        K = self.K
        cdt = _complex_dtype(self.dtype)
        pmm, pms, x32, m2, mp2 = self._seeds_spin()
        mask = jnp.asarray(
            alm_mask(self.l_max, self.m_max, spin=2))[..., None]

        def fn(maps_qu, consts):
            (pmm, pms, x32), mask = consts
            with jax.named_scope(FOLD):
                m2d = jnp.concatenate([maps_qu[0], maps_qu[1]], axis=-1)
            dwc = self._sht.phase.anal(m2d)           # (M, R, 2K) complex
            with jax.named_scope(FOLD):
                d2_re, d2_im = leg.spin_pack_delta(
                    jnp.real(dwc[..., :K]), jnp.imag(dwc[..., :K]),
                    jnp.real(dwc[..., K:]), jnp.imag(dwc[..., K:]))
                dw32 = jnp.concatenate([d2_re, d2_im],
                                       axis=-1).astype(jnp.float32)[:, None]
            out = kops.anal(dw32, m2, x32, pmm, pms, l_max=self.l_max,
                            fold=False, variant=variant, mp_vals=mp2,
                            layout=layout)
            with jax.named_scope(FOLD):
                e_re, e_im, b_re, b_im = leg.spin_unpack_alm(
                    out[..., :K], out[..., K:])
                alm = jnp.stack([e_re + 1j * e_im, b_re + 1j * b_im],
                                axis=0).astype(cdt)
                return jnp.where(mask[None], alm, 0.0)

        return _bind(fn, ((pmm, pms, x32), mask))

    # -- fused pipeline (layout "fused") --------------------------------------

    def _fusion_eligibility(self) -> tuple:
        """(eligible, reason) for the fused Legendre+phase pipeline.

        The fused kernels now cover spin 0 and 2, equator-folded, uniform
        and bucketed (ragged HEALPix) plans.  Two residual shapes stay
        staged: the equator fold combine is baked into the uniform-engine
        rotation tables (no folded bucket tables), and spin-2 at the
        uniform Nyquist alias point would need the real-part doubling --
        which is not complex-linear and so cannot commute with the
        lambda^{+/-} pair unpacking that follows the in-kernel rotation.
        """
        if self.fold and self.phase.kind != "uniform":
            return False, (f"equator fold on a {self.phase.kind!r} phase "
                           "stage is not fused (staged path)")
        if (self.spin != 0 and self.phase.kind == "uniform"
                and self.grid.max_n_phi == 2 * self.m_max):
            return False, ("spin-2 at the Nyquist alias point "
                           "(n_phi == 2*m_max) is not fused (staged path)")
        return True, None

    def _fused_lp_size(self) -> int:
        """The fused pipeline's panel length, chardb-autotuned per corner.

        Candidate block shapes come from `pack.fused_lp_candidates`; under
        ``mode="auto"`` each candidate is timed once per hardware through
        the characterization DB (a second plan build re-measures zero
        corners), otherwise (model mode, chardb smoke) the roofline model
        ranks them.  Memoized on the plan.
        """
        if getattr(self, "_fused_lp", None) is not None:
            return self._fused_lp
        from repro.kernels import pack as kpack
        from repro.roofline import chardb
        cands = kpack.fused_lp_candidates(self.l_max)
        if len(cands) == 1:
            self._fused_lp = int(cands[0])
            return self._fused_lp
        times: dict = {}
        if self.mode == "auto" and not chardb.smoke_mode():
            db = self._chardb()
            cdt = _complex_dtype(self.dtype)
            arg = jnp.zeros(self._alm_shape, cdt)
            for c in cands:

                def measure(c=c):
                    return _time_call_us(self._make_fused_synth(
                        variant="vpu", lp_size=int(c)), arg)

                # base fields on the *staged* corner and override: the
                # fused fields would recurse into this very chooser.
                fields = self._corner_fields("pallas_vpu", "synth", "packed")
                fields["layout"] = "fused"
                fields["lp_size"] = int(c)
                try:
                    us, _ = db.get_or_measure(measure, **fields)
                except Exception:
                    if _on_tpu():   # a kernel that fails to build is an
                        raise       # error on the chip, not a slow entry
                    us = None
                times[int(c)] = float("inf") if us is None else float(us)
        if not times or not np.isfinite(min(times.values())):
            g = self.grid
            hw = roofline.hardware_for()
            times = {int(c): roofline.predict_sht_time(
                "pallas_vpu", layout="packed", pipeline="fused",
                lp_size=int(c), l_max=self.l_max, m_max=self.m_max,
                n_rings=g.n_rings, n_phi=g.max_n_phi, K=self.K,
                direction="synth", hw=hw,
                fft_lengths=self._sht.phase.fft_lengths, spin=self.spin)
                for c in cands}
        self._fused_lp = int(min(times, key=times.get))
        return self._fused_lp

    def _fused_layout(self, lp_size: Optional[int] = None):
        """The packed slot layout shared by both fused directions (pure
        numpy; one per panel length).  Spin-2 plans pack the stacked
        lambda^{+/-} row set (`legendre._spin_rows`)."""
        if getattr(self, "_fused_los", None) is None:
            self._fused_los = {}
        lp = int(lp_size) if lp_size else self._fused_lp_size()
        if lp not in self._fused_los:
            from repro.kernels import pack as kpack
            if self.spin:
                m2, mp2 = legendre._spin_rows(self._m_vals)
                self._fused_los[lp] = kpack.build_layout(
                    m2, self.l_max, lp_size=lp, mp_vals=mp2)
            else:
                self._fused_los[lp] = kpack.build_layout(
                    self._m_vals, self.l_max, lp_size=lp)
        return self._fused_los[lp]

    def _fused_tables(self, m_vals, x32):
        """Host-built rotation tables of both fused directions, on the
        device once per plan (`kernels.fused.rotation_tables`)."""
        if getattr(self, "_fused_tabs", None) is None:
            from repro.kernels import fused as kfused
            g, ph = self.grid, self.phase
            if ph.kind == "uniform":
                geometry = dict(phase_kind="uniform", n=ph.n, phi0=g.phi0,
                                fold_rings=(g.n_rings if self.fold else None),
                                n_half=x32.shape[0])
            else:
                geometry = dict(phase_kind="bucket", phi0=g.phi0)
            tabs, rot = kfused.rotation_tables(m_vals, **geometry)
            self._fused_tabs = (tuple(jnp.asarray(t) for t in tabs), rot)
        return self._fused_tabs

    def _fused_parts(self, variant: str, bf16: bool, lp_size):
        """Shared fused-dispatch plumbing: the row set, the device operands
        (``consts`` = ring nodes, seeds, rotation tables), the static
        keyword block, and the (synth_fn, anal_fn) kernel-chain pair for
        this plan's shape (scalar/spin x uniform/fold/bucket)."""
        from repro.kernels import fused as kfused
        g, ph = self.grid, self.phase
        lp = int(lp_size) if lp_size else self._fused_lp_size()
        lo = self._fused_layout(lp)
        if self.spin == 0:
            pmm, pms, x32 = self._seeds()
            m_vals, mp2 = self._m_vals, None
        else:
            pmm, pms, x32, m2, mp2 = self._seeds_spin()
            m_vals = m2
        tabs, rot = self._fused_tables(m_vals, x32)
        kw = dict(l_max=self.l_max, variant=variant, bf16=bf16, lo=lo,
                  lp_size=lp, mp_vals=mp2, rot=rot)
        if ph.kind == "uniform":
            kw.update(n=ph.n, phi0=g.phi0,
                      fold_rings=(g.n_rings if self.fold else None))
            pair = (kfused.fused_synth, kfused.fused_anal)
        else:
            kw.update(layout=ph.layout, pos=ph._pos, neg=ph._neg,
                      n_phi=g.n_phi, phi0=g.phi0)
            pair = (kfused.fused_synth_bucket, kfused.fused_anal_bucket)
        return m_vals, (x32, pmm, pms, tabs), kw, pair

    def _make_fused_synth(self, variant: str, bf16: bool = False,
                          lp_size: Optional[int] = None):
        from repro.core import legendre as leg
        K = self.K
        m_vals, consts, kw, (fsynth, _) = \
            self._fused_parts(variant, bf16, lp_size)
        if self.phase.kind == "bucket":
            kw = dict(kw, out_width=self.grid.max_n_phi)

        def run(a32, consts):
            x32, pmm, pms, tabs = consts
            return fsynth(a32, m_vals, x32, pmm, pms, tabs=tabs, **kw)

        if self.spin == 0:
            def fn(alm, consts):
                with jax.named_scope(FOLD):
                    a32 = jnp.concatenate(
                        [jnp.real(alm), jnp.imag(alm)],
                        axis=-1).astype(jnp.float32)
                return run(a32, consts).astype(self.dtype)
        else:
            def fn(alm_eb, consts):
                with jax.named_scope(FOLD):
                    e, b = alm_eb[0], alm_eb[1]
                    a2_re, a2_im = leg.spin_pack_alm(
                        jnp.real(e), jnp.imag(e), jnp.real(b), jnp.imag(b))
                    a32 = jnp.concatenate([a2_re, a2_im],
                                          axis=-1).astype(jnp.float32)
                s = run(a32, consts).astype(self.dtype)
                with jax.named_scope(FOLD):
                    return jnp.stack([s[..., :K], s[..., K:]], axis=0)

        return _bind(fn, consts)

    def _make_fused_anal(self, variant: str, bf16: bool = False,
                         lp_size: Optional[int] = None):
        from repro.core import legendre as leg
        K = self.K
        cdt = _complex_dtype(self.dtype)
        m_vals, consts, kw, (_, fanal) = \
            self._fused_parts(variant, bf16, lp_size)
        w = jnp.asarray(self.grid.weights)
        mask = jnp.asarray(
            alm_mask(self.l_max, self.m_max, spin=self.spin))[..., None]

        def run(maps, consts):
            (x32, pmm, pms, tabs), w, _ = consts
            return fanal(maps, w, m_vals, x32, pmm, pms, tabs=tabs, **kw)

        if self.spin == 0:
            def fn(maps, consts):
                out = run(maps, consts)
                with jax.named_scope(FOLD):
                    alm = (out[..., :K] + 1j * out[..., K:]).astype(cdt)
                    return jnp.where(consts[2], alm, 0.0)
        else:
            def fn(maps_qu, consts):
                with jax.named_scope(FOLD):
                    m2d = jnp.concatenate([maps_qu[0], maps_qu[1]], axis=-1)
                out = run(m2d, consts)
                with jax.named_scope(FOLD):
                    e_re, e_im, b_re, b_im = leg.spin_unpack_alm(
                        out[..., :K], out[..., K:])
                    alm = jnp.stack([e_re + 1j * e_im, b_re + 1j * b_im],
                                    axis=0).astype(cdt)
                    return jnp.where(consts[2][None], alm, 0.0)

        return _bind(fn, (consts, w, mask))

    # -- dispatch -------------------------------------------------------------

    def _pallas_layouts(self, backend: str = "pallas_vpu") -> tuple:
        """Candidate Legendre layouts for a pallas backend: the staged
        grids the platform compiles (see `backend_eligibility`) plus
        ``fused`` where the plan shape allows it."""
        lays = tuple(lay for lay in ("packed", "plain")
                     if f"{backend}[{lay}]" not in self.skipped)
        if self._fusion_eligibility()[0]:
            lays = lays + ("fused",)
        return lays

    def _predict_all(self, hw=None) -> dict:
        """Cost-model prediction per candidate per direction (seconds).

        Pallas candidates are modelled per Legendre *layout* (packed vs
        plain grid); ``out[b][d]`` is the better of the two and
        ``out[b][f"{d}_layout"]`` names it.
        """
        g = self.grid
        if hw is None:
            hw = roofline.hardware_for()
        n_dev = self._n_shards or jax.device_count()
        fl = self._sht.phase.fft_lengths        # per-bucket cost on ragged
        out = {}
        for b in self.candidates:
            out[b] = {}
            for d in ("synth", "anal"):
                kw = dict(l_max=self.l_max, m_max=self.m_max,
                          n_rings=g.n_rings, n_phi=g.max_n_phi, K=self.K,
                          direction=d, hw=hw,
                          n_devices=n_dev if b == "dist" else 1,
                          fft_lengths=fl, spin=self.spin)
                if b in ("pallas_vpu", "pallas_mxu"):
                    per = {lay: roofline.predict_sht_time(
                               b, layout="packed" if lay == "fused" else lay,
                               pipeline="fused" if lay == "fused"
                               else "staged", **kw)
                           for lay in self._pallas_layouts(b)}
                    lay = min(per, key=per.get)
                    out[b][d] = per[lay]
                    out[b][f"{d}_layout"] = lay
                elif b == "dist":
                    # overlapped pipeline model: pick the exchange chunk
                    # count C that minimizes the modelled time.
                    per = {c: roofline.predict_sht_time(
                               b, overlap=True, comm_chunks=c, **kw)
                           for c in self._dist_chunk_variants(d)}
                    c_best = min(per, key=per.get)
                    out[b][d] = per[c_best]
                    out[b][f"{d}_chunks"] = c_best
                else:
                    out[b][d] = roofline.predict_sht_time(b, **kw)
        return out

    def _dist_chunk_variants(self, direction: str) -> tuple:
        """Candidate exchange chunk counts for the dist backend: the
        monolithic baseline plus the overlap model's pick (or just the
        forced count when ``comm_chunks`` was given as an int)."""
        if isinstance(self._comm_spec, (int, np.integer)):
            return (max(1, int(self._comm_spec)),)
        g = self.grid
        n_dev = self._n_shards or jax.device_count()
        hw = roofline.hardware_for()
        c = roofline.predict_comm_chunks(
            l_max=self.l_max, m_max=self.m_max, n_rings=g.n_rings,
            n_phi=g.max_n_phi, K=self.K, direction=direction, hw=hw,
            n_devices=n_dev, fft_lengths=self._sht.phase.fft_lengths,
            spin=self.spin)
        return tuple(sorted({1, int(c)}))

    def _chardb(self):
        """The persistent per-hardware characterization DB this plan's
        corner timings live in (disk-backed iff the plan's cache is)."""
        from repro.roofline import chardb
        directory = None
        if self._cache_kind == "disk":
            directory = plancache.cache_dir(self._cache_dir)
        return chardb.get_db(directory)

    def _corner_fields(self, backend: str, direction: str, layout) -> dict:
        """Workload coordinates of one autotune corner.  Deliberately
        excludes the dispatch mode and the plan signature key: any plan
        exercising the same workload on the same hardware reuses the
        measurement.  For the dist backend the variant slot carries the
        exchange chunk count instead of a Legendre layout."""
        fields = dict(
            grid=self.grid.name, n_rings=self.grid.n_rings,
            n_phi=self.grid.max_n_phi, l_max=self.l_max, m_max=self.m_max,
            K=self.K, dtype=self.dtype, spin=self.spin, fold=self.fold,
            backend=backend, direction=direction, layout=layout or "-",
            n_devices=((self._n_shards or jax.device_count())
                       if backend == "dist" else 1))
        # block-shape coordinate: fused corners are only comparable at one
        # panel length (staged kernels are pinned to 128)
        fields["lp_size"] = (self._fused_lp_size() if layout == "fused"
                             else 128)
        if backend == "dist":
            fields["layout"] = "-"
            fields["comm_chunks"] = max(1, int(layout or 1))
        return fields

    def _measure_all(self) -> dict:
        """Corner timings per candidate per direction, through the chardb:
        already-characterized corners are reused without running anything;
        missing/stale ones are compiled and timed over one call (or are
        skipped entirely under ``REPRO_CHARDB_SMOKE=1``).  A pallas
        backend's layouts after its first are pruned (listed under
        ``"<dir>_pruned"``) when the first is `PRUNE_FACTOR` times slower
        than the best time so far."""
        db = self._chardb()
        cdt = _complex_dtype(self.dtype)
        if self.spin == 0:
            alm = random_alm(jax.random.PRNGKey(0), self.l_max, self.m_max,
                             K=self.K).astype(cdt)
            maps = jnp.zeros((self.grid.n_rings, self.grid.max_n_phi,
                              self.K), jnp.dtype(self.dtype))
        else:
            alm = random_alm_spin(jax.random.PRNGKey(0), self.l_max,
                                  self.m_max, K=self.K).astype(cdt)
            maps = jnp.zeros((2, self.grid.n_rings, self.grid.max_n_phi,
                              self.K), jnp.dtype(self.dtype))
        out: dict = {}
        fastest = {"synth": float("inf"), "anal": float("inf")}
        for b in self.candidates:
            out[b] = {}
            for direction, fn_of, arg in (("synth", self._synth_fn, alm),
                                          ("anal", self._anal_fn, maps)):
                if b in ("pallas_vpu", "pallas_mxu"):
                    layouts = sorted(self._pallas_layouts(b),
                                     key=_LAYOUT_ORDER.index)
                elif b == "dist":
                    layouts = self._dist_chunk_variants(direction)
                else:
                    layouts = (None,)
                best, best_lay, errs = float("inf"), None, {}
                for i, lay in enumerate(layouts):
                    if (i == 1 and b != "dist" and np.isfinite(best)
                            and best > PRUNE_FACTOR * fastest[direction]):
                        out[b][f"{direction}_pruned"] = list(layouts[1:])
                        break

                    def measure(b=b, lay=lay, fn_of=fn_of, arg=arg):
                        fn = fn_of(b, lay) if lay is not None else fn_of(b)
                        return _time_call_us(fn, arg)

                    try:
                        us, status = db.get_or_measure(
                            measure, **self._corner_fields(b, direction, lay))
                        t = float("inf") if us is None else us * 1e-6
                        if status == "skipped":
                            out[b][f"{direction}_skipped"] = True
                    except Exception as e:  # unusable here: rank last
                        if _on_tpu():   # ... but on the chip a candidate
                            raise       # that fails to build is an error
                        t = float("inf")
                        errs[lay] = f"{type(e).__name__}: {e}"
                        if lay is not None:
                            out[b][f"{direction}_{lay}_error"] = errs[lay]
                    if lay is not None:
                        out[b][f"{direction}_{lay}"] = t
                    if t < best:
                        best, best_lay = t, lay
                    fastest[direction] = min(fastest[direction], t)
                out[b][direction] = best
                if not np.isfinite(best):   # every layout failed: backend
                    out[b][f"{direction}_error"] = \
                        "; ".join(errs.values())            # unusable
                if best_lay is not None:
                    slot = "chunks" if b == "dist" else "layout"
                    out[b][f"{direction}_{slot}"] = best_lay
        return out

    def _fill_layouts(self, source: dict) -> None:
        """Set ``self.layouts`` per direction from a per-candidate table
        (``{backend: {"<dir>_layout": ...}}``); model predictions fill any
        gap, non-pallas backends get None."""
        self.layouts = {}
        for d in ("synth", "anal"):
            b = self.backends.get(d)
            if b not in ("pallas_vpu", "pallas_mxu"):
                self.layouts[d] = None
                continue
            lay = source.get(b, {}).get(f"{d}_layout") \
                or self.predicted_s.get(b, {}).get(f"{d}_layout")
            self.layouts[d] = lay or "packed"

    def _fill_comm_chunks(self, source: dict) -> None:
        """Set ``self.comm_chunks`` per direction: the forced count when
        ``comm_chunks`` was an int, else the measured winner from ``source``
        (``{"dist": {"<dir>_chunks": C}}``) with the overlap model's pick
        filling any gap.  Non-dist directions get None."""
        self.comm_chunks = {}
        for d in ("synth", "anal"):
            if self.backends.get(d) != "dist":
                self.comm_chunks[d] = None
                continue
            if isinstance(self._comm_spec, (int, np.integer)):
                self.comm_chunks[d] = max(1, int(self._comm_spec))
                continue
            c = source.get("dist", {}).get(f"{d}_chunks")
            if c is None:
                c = self.predicted_s.get("dist", {}).get(f"{d}_chunks")
            self.comm_chunks[d] = max(1, int(c or 1))

    def _choose_backends(self) -> None:
        """Fill ``self.backends``/``self.layouts`` according to ``mode``."""
        self.predicted_s = self._predict_all()
        if self.mode in BACKENDS:                   # forced backend
            self.backends = {"synth": self.mode, "anal": self.mode}
            self._fill_layouts(self.predicted_s)
            self._fill_comm_chunks(self.predicted_s)
            return
        if self.mode == "model":
            self.backends = {
                d: min(self.candidates, key=lambda b: self.predicted_s[b][d])
                for d in ("synth", "anal")}
            self._fill_layouts(self.predicted_s)
            self._fill_comm_chunks(self.predicted_s)
            return
        assert self.mode == "auto", self.mode
        dkey = plancache.signature_key("decision", sig=self._signature_key)
        cached = plancache.load_decision(dkey, cache=self._cache_kind,
                                         directory=self._cache_dir)
        if cached is not None and all(
                cached.get(d) in self.candidates for d in ("synth", "anal")):
            self.backends = {d: cached[d] for d in ("synth", "anal")}
            self.measured_s = cached.get("measured", {})
            self._fill_layouts(self.measured_s)
            self._fill_comm_chunks(self.measured_s)
            cached_lay = cached.get("layouts")
            if cached_lay:
                self.layouts.update({d: cached_lay.get(d)
                                     for d in ("synth", "anal")
                                     if d in cached_lay})
            cached_cc = cached.get("comm_chunks")
            if cached_cc:
                self.comm_chunks.update(
                    {d: cached_cc.get(d) for d in ("synth", "anal")
                     if d in cached_cc})
            self.cache_events["decision"] = "hit"
            return
        self.measured_s = self._measure_all()
        self.backends, fell_back = {}, False
        for d in ("synth", "anal"):
            finite = [b for b in self.candidates
                      if np.isfinite(self.measured_s[b][d])]
            if finite:
                self.backends[d] = min(
                    finite, key=lambda b: self.measured_s[b][d])
            else:
                # every corner skipped (chardb smoke mode) or unusable:
                # rank by the cost model instead of timing anything.
                self.backends[d] = min(
                    self.candidates, key=lambda b: self.predicted_s[b][d])
                fell_back = True
        self._fill_layouts(self.measured_s)
        self._fill_comm_chunks(self.measured_s)
        if fell_back:
            # an un-measured decision must not shadow a later real autotune
            self.cache_events["decision"] = "model-fallback"
            return
        self.cache_events["decision"] = "autotuned"
        plancache.save_decision(
            dkey, {**self.backends, "measured": self.measured_s,
                   "layouts": dict(self.layouts),
                   "comm_chunks": dict(self.comm_chunks)},
            cache=self._cache_kind, directory=self._cache_dir)

    # -- public API -----------------------------------------------------------

    @property
    def _alm_shape(self) -> tuple:
        base = (self.m_max + 1, self.l_max + 1, self.K)
        return base if self.spin == 0 else (2,) + base

    @property
    def _maps_shape(self) -> tuple:
        base = (self.grid.n_rings, self.grid.max_n_phi, self.K)
        return base if self.spin == 0 else (2,) + base

    def alm2map(self, alm) -> jnp.ndarray:
        """Inverse SHT (synthesis) through the chosen backend.

        spin 0: alm ``(m_max+1, l_max+1, K)`` -> maps ``(R, n_phi, K)``;
        spin 2: (E, B) alm ``(2, M, L, K)`` -> (Q, U) maps
        ``(2, R, n_phi, K)``.
        """
        assert alm.shape == self._alm_shape, \
            (alm.shape, f"plan was built for {self._alm_shape}")
        with jax.profiler.TraceAnnotation(ALM2MAP):
            return self._synth_fn(self.backends["synth"])(jnp.asarray(alm))

    def map2alm(self, maps, iters: int = 0) -> jnp.ndarray:
        """Direct SHT (analysis): maps -> alm through the chosen backend.

        ``iters > 0`` applies Jacobi residual refinement (one extra
        synthesis + analysis per pass) -- worthwhile on approximate-
        quadrature grids (HEALPix family), a no-op improvement on exact
        Gauss-Legendre grids.  Spin-2 plans take/return the stacked
        (Q, U) / (E, B) pair shapes (see :meth:`alm2map`).
        """
        assert maps.shape == self._maps_shape, \
            (maps.shape, f"plan was built for {self._maps_shape}")
        with jax.profiler.TraceAnnotation(MAP2ALM):
            maps = jnp.asarray(maps)
            alm = self._anal_fn(self.backends["anal"])(maps)
            for _ in range(iters):
                resid = maps - self.alm2map(alm)
                alm = alm + self._anal_fn(self.backends["anal"])(resid)
            return alm

    def warmup(self, directions=("synth", "anal")) -> "Plan":
        """Compile and execute each direction once on zero inputs.

        The serving pool's warm-up hook: after ``warmup()`` the first real
        request through this plan pays no trace/compile latency.  Blocks
        until the device work is done; safe to call from a background
        thread (the executables land in ``self._compiled``).
        """
        cdt = _complex_dtype(self.dtype)
        for d in directions:
            if d == "synth":
                out = self._synth_fn(self.backends["synth"])(
                    jnp.zeros(self._alm_shape, cdt))
            else:
                out = self._anal_fn(self.backends["anal"])(
                    jnp.zeros(self._maps_shape, jnp.dtype(self.dtype)))
            jax.block_until_ready(out)
        return self

    @property
    def grad_ready(self) -> dict:
        """Per-direction differentiability of the chosen execution paths.

        ``{"synth": bool, "anal": bool}`` -- True when that direction's
        backend carries the adjoint-based custom JVP/VJP rules, i.e.
        ``jax.grad``/``jax.jvp`` flow through :meth:`alm2map` /
        :meth:`map2alm` without tracing kernel internals.  Every built-in
        backend (jnp, pallas_vpu, pallas_mxu, dist) qualifies; the rules
        are first-order (no reverse-over-reverse).
        """
        return {d: self.backends.get(d) in BACKENDS
                for d in ("synth", "anal")}

    def memory_footprint(self) -> dict:
        """Estimated working-set bytes per buffer class."""
        g = self.grid
        M, L1, K = self.m_max + 1, self.l_max + 1, self.K
        ncomp = 1 if self.spin == 0 else 2
        csize = 16 if self.dtype == "float64" else 8
        rsize = 8 if self.dtype == "float64" else 4
        out = {
            "alm_bytes": M * L1 * K * csize * ncomp,
            "maps_bytes": g.n_rings * g.max_n_phi * K * rsize * ncomp,
            "delta_bytes": M * g.n_rings * K * csize * ncomp,
            "seed_bytes": (2 * M * g.n_rings * 4 * ncomp
                           if any(b.startswith("pallas")
                                  for b in self.backends.values()) else 0),
        }
        out["total_bytes"] = sum(out.values())
        return out

    def _jnp_blocks(self) -> dict:
        """Per direction run by the jnp backend: the row blocks of its
        Legendre loop (`legendre.loop_blocks`: block count and the share
        of the M x L row-steps it runs).  Spin-2 loops run every row."""
        out = {}
        for d in ("synth", "anal"):
            if self.backends.get(d) != "jnp":
                continue
            if self.spin:
                out[d] = {"blocks": 1, "rows_per_block": 2 * (self.m_max + 1),
                          "row_step_share": 1.0}
                continue
            rings = self._sht.n_north if self.fold else self.grid.n_rings
            out[d] = legendre.loop_blocks(
                self._m_vals, l_max=self.l_max,
                row_bytes=legendre.row_step_bytes(
                    d, fold=self.fold, n_rings=rings, K=self.K,
                    dtype=self.dtype))
        return out

    def describe(self) -> dict:
        """Structured report: signature, chosen kernels, predicted vs
        measured seconds per candidate, memory footprint, cache counters.

        Benchmarks and docs consume this dict; ``report()`` pretty-prints
        it.
        """
        w = roofline.sht_work(self.l_max, self.m_max, self.grid.n_rings,
                              self.grid.max_n_phi, self.K,
                              fft_lengths=self._sht.phase.fft_lengths,
                              spin=self.spin)
        from repro.roofline import chardb
        layouts = dict(self.layouts)
        fusion_ok, fusion_reason = self._fusion_eligibility()
        return {
            "signature": {
                "grid": self.grid.name, "n_rings": self.grid.n_rings,
                "n_phi": self.grid.max_n_phi, "l_max": self.l_max,
                "m_max": self.m_max, "K": self.K, "dtype": self.dtype,
                "fold": self.fold, "spin": self.spin,
                "key": self._signature_key,
            },
            "mode": self.mode,
            "backends": dict(self.backends),
            "differentiable": {**self.grad_ready,
                               "rule": "adjoint (custom_jvp + linear_call)",
                               "higher_order": False},
            "layouts": layouts,
            "fusion": {
                "eligible": fusion_ok, "reason": fusion_reason,
                # the eligibility reason again, under the name the env
                # override error uses -- None when nothing was skipped
                "skipped": fusion_reason,
                "lp_size": getattr(self, "_fused_lp", None),
                "active": {d: layouts.get(d) == "fused"
                           for d in ("synth", "anal")},
                "pipelines": {d: ("fused" if layouts.get(d) == "fused"
                                  else "staged")
                              for d in ("synth", "anal")},
            },
            "comm": {
                "spec": self._comm_spec,
                "chunks": dict(self.comm_chunks),
                "pipelined": {d: (self.comm_chunks.get(d) or 1) > 1
                              for d in ("synth", "anal")},
            },
            "candidates": list(self.candidates),
            "skipped": dict(self.skipped),
            # grouped view of the packing decision; panels comes from the
            # sht_work() call above (same legendre_panel_counts dict)
            "legendre": {"layouts": layouts, "panels": w["panels"],
                         "jnp_blocks": self._jnp_blocks()},
            "phase": self._sht.phase.describe(),
            "predicted_s": self.predicted_s,
            "measured_s": self.measured_s,
            "work": w,
            "memory": self.memory_footprint(),
            "cache": {"events": dict(self.cache_events),
                      **plancache.stats().to_dict(),
                      "chardb": chardb.stats()},
        }

    def report(self) -> str:
        """Human-readable ``describe()`` (chosen kernel, predicted vs
        measured time per direction, memory footprint, and *why* any
        backend was skipped)."""
        d = self.describe()
        s = d["signature"]
        lines = [
            f"Plan {s['grid']} l_max={s['l_max']} m_max={s['m_max']} "
            f"K={s['K']} {s['dtype']} fold={s['fold']} "
            f"spin={s['spin']} mode={d['mode']}",
            f"  rings={s['n_rings']} n_phi={s['n_phi']} "
            f"n_lm={d['work']['n_lm']} "
            f"flops/dir~{d['work']['total_flops']:.3g}",
            f"  memory ~{d['memory']['total_bytes'] / 1e6:.2f} MB",
        ]
        ph = d["phase"]
        if ph["kind"] != "uniform":
            lines.append(
                f"  phase: {ph['kind']} x{ph['n_buckets']} buckets "
                f"{ph['bucket_lengths']} (+{ph['padded_frac'] * 100:.1f}% "
                f"fft padding)")
        pc = d["legendre"]["panels"]
        lines.append(
            f"  legendre: packed {pc['packed']} vs plain "
            f"{pc['plain_launched']} grid steps "
            f"({pc['launched_ratio']:.2f}x fewer, occupancy "
            f"{pc['packed_occupancy']:.2f})")
        for direction, b in d["legendre"]["jnp_blocks"].items():
            lines.append(
                f"  jnp loop {direction}: {b['blocks']} row blocks of "
                f"{b['rows_per_block']}, {b['row_step_share']:.3f} of the "
                f"M x L row-steps")
        for direction in ("synth", "anal"):
            chosen = d["backends"].get(direction, "?")
            pred = d["predicted_s"].get(chosen, {}).get(direction)
            meas = d["measured_s"].get(chosen, {}).get(direction) \
                if d["measured_s"] else None
            bits = [f"  {direction:5s} -> {chosen}"]
            lay = d["layouts"].get(direction)
            if lay:
                bits[0] += f"[{lay}]"
            cc = d["comm"]["chunks"].get(direction)
            if chosen == "dist" and cc:
                bits[0] += f"[C={cc}]"
            if pred is not None:
                bits.append(f"predicted {pred * 1e6:.1f} us")
            if meas is not None and np.isfinite(meas):
                bits.append(f"measured {meas * 1e6:.1f} us")
            lines.append("  ".join(bits))
        for b, reason in d["skipped"].items():
            lines.append(f"  skipped {b}: {reason}")
        ev = d["cache"]["events"]
        lines.append(f"  cache: {ev if ev else 'cold'} "
                     f"(mem_hits={d['cache']['memory_hits']} "
                     f"disk_hits={d['cache']['disk_hits']} "
                     f"builds={d['cache']['builds']})")
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"Plan(grid={self.grid.name!r}, l_max={self.l_max}, "
                f"K={self.K}, dtype={self.dtype!r}, "
                f"backends={self.backends})")


# ---------------------------------------------------------------------------
# make_plan
# ---------------------------------------------------------------------------


def _resolve_grid(grid, l_max, nside, cache_kind, cache_dir):
    """Grid spec -> (RingGrid, signature fields).  String specs go through
    the geometry cache (the GL Newton iteration is the expensive part)."""
    if isinstance(grid, RingGrid):
        return grid, {"grid_cos": grid.cos_theta, "grid_nphi": grid.n_phi,
                      "grid_w": grid.weights, "grid_name": grid.name}
    kind = str(grid)
    # Key each family only on the fields its geometry depends on: GL/ECP on
    # l_max, healpix on nside.  Keying on the irrelevant one would fragment
    # the cache (and the plan memoisation) for identical grids.
    by_lmax = kind in ("gl", "ecp")
    spec = {"grid_kind": kind, "grid_l_max": l_max if by_lmax else None,
            "grid_nside": None if by_lmax else nside}
    key = plancache.signature_key("geometry", **spec)

    def build():
        g = gridlib.make_grid(kind, l_max=l_max, nside=nside)
        return {"cos_theta": g.cos_theta, "sin_theta": g.sin_theta,
                "weights": g.weights, "n_phi": g.n_phi, "phi0": g.phi0,
                "uniform": np.array(g.uniform),
                "nside": np.array(-1 if g.nside is None else g.nside)}

    p = plancache.get_or_build(key, build, cache=cache_kind,
                               directory=cache_dir)
    g = RingGrid(name=kind, cos_theta=p["cos_theta"],
                 sin_theta=p["sin_theta"], weights=p["weights"],
                 n_phi=p["n_phi"], phi0=p["phi0"], uniform=bool(p["uniform"]),
                 nside=None if int(p["nside"]) < 0 else int(p["nside"]))
    return g, spec


def make_plan(grid: Union[str, RingGrid] = "gl", l_max: Optional[int] = None,
              *, nside: Optional[int] = None, m_max: Optional[int] = None,
              K: int = 1, dtype: Optional[str] = None, mode: str = "auto",
              fold: bool = False, spin: int = 0, cache: str = "auto",
              cache_dir: Optional[str] = None,
              n_shards: Optional[int] = None,
              comm_chunks: Union[int, str] = "auto") -> Plan:
    """Build (or fetch) the transform plan for a problem signature.

    Parameters
    ----------
    grid : ``"gl"`` | ``"ecp"`` | ``"healpix_ring"`` | ``"healpix"`` | RingGrid
        Grid spec (cached geometry) or a prebuilt grid instance.
    l_max, m_max : band limits (``m_max`` defaults to ``l_max``).
    nside : HEALPix resolution (required for healpix-family string specs).
    K : number of simultaneous maps the plan is specialised for (the
        batched Monte-Carlo workload; drives the VPU/MXU choice).
    dtype : ``"float64"`` (oracle precision, jnp backend only; needs
        ``jax_enable_x64`` and is refused on a TPU) or ``"float32"``
        (performance; enables the Pallas kernels).  None picks
        :func:`default_dtype`.
    mode : ``"auto"`` (autotune, cached), ``"model"`` (cost model), or an
        explicit backend name (``"jnp"``, ``"pallas_vpu"``, ``"pallas_mxu"``,
        ``"dist"``).
    fold : use the equator-fold optimisation (symmetric grids only).
    spin : 0 (scalar) or 2 (polarisation).  A spin-2 plan transforms
        (E, B) alm pairs ``(2, M, L, K)`` <-> (Q, U) map pairs
        ``(2, R, n_phi, K)`` on every backend; costs ~2x the Legendre
        panels (the lambda^{+/-} pair) at the same FFT structure.
    cache : ``"auto"`` (memory; disk iff $REPRO_CACHE_DIR is set),
        ``"memory"``, ``"disk"``, or ``"off"``.
    cache_dir : override the on-disk cache location.
    n_shards : device count for the ``dist`` backend (default: all).
    comm_chunks : exchange chunk count for the ``dist`` backend.
        ``"auto"`` (default) picks C from the overlapped roofline model
        (measured against the monolithic C=1 baseline under
        ``mode="auto"``); an int forces that chunk count.  ``C > 1``
        splits the Delta all_to_all into C chunks pipelined against the
        adjacent chunks' compute (bit-identical results).

    Returns the memoised :class:`Plan`: calling ``make_plan`` twice with an
    identical signature returns the same object and reuses every cached
    precompute payload.
    """
    if isinstance(grid, str) and grid in ("gl", "ecp") and l_max is None:
        raise ValueError(f"make_plan({grid!r}, ...) requires l_max")
    if dtype is None:
        dtype = default_dtype()
    check_dtype(dtype)
    if mode not in ("auto", "model") + BACKENDS:
        raise ValueError(f"unknown mode {mode!r}: expected 'auto', 'model' "
                         f"or a backend name {BACKENDS}")
    if spin not in (0, 2):
        raise ValueError(f"unsupported spin {spin!r}: expected 0 or 2")
    if comm_chunks != "auto":
        if not isinstance(comm_chunks, (int, np.integer)) or comm_chunks < 1:
            raise ValueError(f"comm_chunks must be 'auto' or an int >= 1, "
                             f"got {comm_chunks!r}")
        comm_chunks = int(comm_chunks)
    if spin and fold:
        raise ValueError("fold is not supported for spin transforms")
    if cache == "auto":
        cache_kind = "disk" if (cache_dir or os.environ.get("REPRO_CACHE_DIR")) \
            else "memory"
    else:
        cache_kind = cache
    assert cache_kind in ("off", "memory", "disk"), cache_kind

    g, grid_sig = _resolve_grid(grid, l_max, nside, cache_kind, cache_dir)
    if l_max is None:
        # derive a safe band limit from the grid (HEALPix rule of thumb)
        l_max = 2 * g.nside if g.nside else g.n_rings - 1
    m_max = l_max if m_max is None else m_max
    assert m_max <= l_max, (m_max, l_max)
    if spin:
        assert l_max >= spin, (l_max, spin)
    if fold:
        assert g.equator_symmetric, "fold requires a symmetric grid"

    # cache policy is part of the memoisation key: a plan built with
    # cache="off" must not shadow a later request for disk persistence.
    sig_key = plancache.signature_key(
        "plan", l_max=l_max, m_max=m_max, K=K, dtype=dtype, mode=mode,
        fold=fold, spin=spin, n_shards=n_shards, cache_kind=cache_kind,
        cache_dir=cache_dir, comm_chunks=comm_chunks, **grid_sig)
    if sig_key in _PLANS:
        plancache.stats().memory_hits += 1
        return _PLANS[sig_key]

    plan = Plan(g, l_max, m_max, K, dtype, mode=mode, fold=fold, spin=spin,
                cache_kind=cache_kind, cache_dir=cache_dir,
                n_shards=n_shards, signature_key=sig_key,
                comm_chunks=comm_chunks)
    elig = backend_eligibility(g, dtype, n_shards)
    cand = [b for b in BACKENDS if elig[b] is None]
    if mode in BACKENDS and mode not in cand:
        # explicit request overrides the eligibility policy (e.g. pallas
        # under float64: runs in f32 internally) -- but not impossibility.
        if mode.startswith("pallas") and dtype != "float32":
            cand = cand + [mode]
            elig[mode] = None
        else:
            raise ValueError(
                f"backend {mode!r} unavailable for this signature: "
                f"{elig[mode]} (candidates: {cand})")
    plan.skipped = {b: r for b, r in elig.items() if r is not None}
    for b in ("pallas_vpu", "pallas_mxu"):
        if b in cand and not plan._pallas_layouts(b):
            reason = (f"no layout compiles here: fused ineligible "
                      f"({plan._fusion_eligibility()[1]}); staged: "
                      f"{plan.skipped[f'{b}[plain]']}")
            if mode == b:
                raise ValueError(f"backend {b!r} unavailable for this "
                                 f"signature: {reason}")
            cand.remove(b)
            plan.skipped[b] = reason
    plan.candidates = cand
    plan._choose_backends()
    _PLANS[sig_key] = plan
    return plan
