"""Unified transform plans: one entry point for every SHT execution path.

This is the dispatch seam the paper's "dichotomy" demands (§4-5): the
winning path can differ between problem sizes *and between the direct and
inverse transforms*, so ``make_plan`` chooses an execution backend per
``(grid, l_max, K, dtype)`` signature and per direction, instead of callers
hand-wiring ``SHT`` / ``DistSHT`` themselves::

    import repro
    plan = repro.make_plan("gl", l_max=256, K=8, dtype="float32")
    maps = plan.alm2map(alm)       # inverse  (synthesis)
    alm2 = plan.map2alm(maps)      # direct   (analysis)
    print(plan.report())           # chosen backends, predicted vs measured

Backends
--------
``jnp``
    The jnp engine (`repro.core.sht.SHT`) on one device, in float32 or
    float64 (the oracle), on every grid (ragged HEALPix through the
    ring-bucket phase stage).  Its Legendre stage is the blocked, optionally
    equator-folded recurrence loop of `repro.core.legendre`.
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
``mode="auto"``   with one candidate (one device: ``jnp``) take it and time
                  nothing.  With more, measure each candidate once per
                  direction (compiled ahead of time, then one timed call)
                  and pick the fastest; the decision is cached by plan
                  signature (memory + optional disk), so the autotune pass
                  runs once per signature, ever.  The raw corner timings
                  additionally land in the persistent per-hardware
                  characterization DB (`repro.roofline.chardb`) keyed by
                  workload -- NOT by plan signature or mode -- so even a
                  decision-cache-cold rebuild re-measures zero corners, and
                  ``REPRO_CHARDB_SMOKE=1`` runs skip missing corners
                  entirely (cost-model fallback) instead of timing.
``mode=<backend>`` force one backend for both directions.

Differentiability
-----------------
``Plan.alm2map`` and ``Plan.map2alm`` carry adjoint-based custom JVP/VJP
rules on both backends (spin 0 and 2, folded loops, ragged bucket FFTs,
shard_map dist): the synthesis VJP is the weighted analysis and vice
versa, so ``jax.grad`` never traces loop internals.  See
``Plan.grad_ready``, ``describe()["differentiable"]`` and
docs/architecture.md ("Differentiation via adjoints").

Precompute caching
------------------
Grid geometry (Gauss-Legendre Newton iteration), the ring-bucket index
maps and autotune decisions are cached by plan signature through
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
from repro.core.sht import SHT, random_alm, random_alm_spin
from repro.roofline import analysis as roofline
from repro.tracing import ALM2MAP, MAP2ALM, RESHARD, scoped

__all__ = ["Plan", "make_plan", "available_backends", "backend_eligibility",
           "clear_plan_cache", "drop_plan"]

BACKENDS = ("jnp", "dist")

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


def backend_eligibility(n_devices: Optional[int] = None
                        ) -> dict[str, Optional[str]]:
    """Why-or-why-not per backend: ``{backend: None | skip_reason}``.

    dist needs >= 2 devices (default: all visible).  Neither the dtype nor
    the grid is a restriction: both backends run float32 and float64
    (`check_dtype` decides where float64 runs at all), and the phase stage
    (`repro.core.phase`) serves both on every grid.
    """
    out: dict[str, Optional[str]] = {b: None for b in BACKENDS}
    n_dev = jax.device_count() if n_devices is None else n_devices
    if n_dev < 2:
        out["dist"] = f"needs >= 2 devices (visible: {n_dev})"
    return out


def available_backends(n_devices: Optional[int] = None) -> list[str]:
    """Backends eligible on ``n_devices`` devices (see
    `backend_eligibility` for the skip reasons of the rest)."""
    elig = backend_eligibility(n_devices)
    return [b for b in BACKENDS if elig[b] is None]


def _complex_dtype(dtype: str):
    return jnp.complex128 if jnp.dtype(dtype) == jnp.float64 else jnp.complex64


def _time_call_us(fn, arg) -> float:
    """Microseconds of one call ``fn(arg)``, compile excluded.  A jitted
    plan function is compiled ahead of time and run once; anything else
    (the dist wrapper) gets one untimed warm-up call first, so no
    execution is spent on warming up a jitted one."""
    if hasattr(fn, "lower"):
        call = functools.partial(fn.lower(arg).compile(), arg)
    else:
        jax.block_until_ready(fn(arg))
        call = functools.partial(fn, arg)
    t0 = time.perf_counter()
    jax.block_until_ready(call())
    return (time.perf_counter() - t0) * 1e6


class Plan:
    """An executable SHT plan: precompute + backend choice.

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
        self._dists: dict = {}          # comm_chunks C -> DistSHT engine
        self._dist_splan = None
        self._comm_spec = comm_chunks   # "auto" or a forced chunk count
        self._compiled: dict = {}
        self.backends: dict = {}
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
            self._dists[C] = DistSHT(splan, mesh, ("sht",), dtype=self.dtype,
                                     fold=False, comm_chunks=C)
        return self._dists[C]

    def _dist_fns(self, direction: str, C: int):
        """The dist backend's dense-in, dense-out callable for one
        direction: the plan's reorder and reshard into the dealt layout,
        the sharded two-stage core, and the reorder back, the reorders
        jitted under the reshard scope."""
        d = self._dist_engine(comm_chunks=C)
        sp = d.plan

        def both(f):                     # spin 2: the pair's leading axis
            return lambda x: jnp.stack([f(x[0]), f(x[1])], axis=0)

        spin = self.spin != 0
        if direction == "synth":
            pre = jax.jit(scoped(RESHARD)(both(sp.pack_alm) if spin
                                          else sp.pack_alm))
            post = jax.jit(scoped(RESHARD)(both(sp.scatter_map) if spin
                                           else sp.scatter_map))
            core = d.alm2map_spin if spin else d.alm2map
        else:
            pre = jax.jit(scoped(RESHARD)(both(sp.gather_map) if spin
                                          else sp.gather_map))
            post = jax.jit(scoped(RESHARD)(both(sp.unpack_alm) if spin
                                           else sp.unpack_alm))
            core = d.map2alm_spin if spin else d.map2alm
        return lambda x: post(core(pre(x)))

    # -- per-backend execution ------------------------------------------------

    def _synth_fn(self, backend: str, chunks: Optional[int] = None):
        """Synthesis callable alm -> maps for ``backend`` (jitted; compiled
        executables are cached on the plan).  ``chunks`` is the dist
        backend's exchange chunk count C (default: the plan's choice)."""
        return self._direction_fn("synth", backend, chunks)

    def _anal_fn(self, backend: str, chunks: Optional[int] = None):
        """Analysis callable maps -> alm for ``backend`` (``chunks``: see
        :meth:`_synth_fn`)."""
        return self._direction_fn("anal", backend, chunks)

    def _direction_fn(self, direction: str, backend: str, chunks):
        if backend == "dist":
            chunks = int(chunks or self.comm_chunks.get(direction) or 1)
        elif backend == "jnp":
            chunks = None
        else:
            raise ValueError(f"unknown backend {backend!r}")
        key = (direction, backend, chunks)
        if key not in self._compiled:
            if backend == "dist":
                fn = self._dist_fns(direction, chunks)
            elif direction == "synth":
                fn = jax.jit(self._sht.alm2map_spin if self.spin
                             else self._sht.alm2map)
            else:
                fn = jax.jit(self._sht.map2alm_spin if self.spin
                             else self._sht.map2alm)
            self._compiled[key] = fn
        return self._compiled[key]

    # -- dispatch -------------------------------------------------------------

    def _predict_all(self, hw=None) -> dict:
        """Cost-model prediction per candidate per direction (seconds);
        for dist also the chunk count C it models fastest
        (``out["dist"][f"{d}_chunks"]``)."""
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
                if b == "dist":
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

    def _corner_fields(self, backend: str, direction: str, chunks) -> dict:
        """Workload coordinates of one autotune corner.  Deliberately
        excludes the dispatch mode and the plan signature key: any plan
        exercising the same workload on the same hardware reuses the
        measurement.  Dist corners carry their exchange chunk count."""
        fields = dict(
            grid=self.grid.name, n_rings=self.grid.n_rings,
            n_phi=self.grid.max_n_phi, l_max=self.l_max, m_max=self.m_max,
            K=self.K, dtype=self.dtype, spin=self.spin, fold=self.fold,
            backend=backend, direction=direction,
            n_devices=((self._n_shards or jax.device_count())
                       if backend == "dist" else 1))
        if backend == "dist":
            fields["comm_chunks"] = max(1, int(chunks or 1))
        return fields

    def _measure_all(self) -> dict:
        """Corner timings per candidate per direction, through the chardb:
        already-characterized corners are reused without running anything;
        missing/stale ones are compiled and timed over one call (or are
        skipped entirely under ``REPRO_CHARDB_SMOKE=1``).  The dist
        backend is timed at each of its candidate chunk counts
        (``out["dist"][f"{d}_{C}"]``), its best under ``f"{d}_chunks"``."""
        db = self._chardb()
        cdt = _complex_dtype(self.dtype)
        if self.spin == 0:
            alm = random_alm(jax.random.PRNGKey(0), self.l_max, self.m_max,
                             K=self.K).astype(cdt)
        else:
            alm = random_alm_spin(jax.random.PRNGKey(0), self.l_max,
                                  self.m_max, K=self.K).astype(cdt)
        maps = jnp.zeros(self._maps_shape, jnp.dtype(self.dtype))
        out: dict = {}
        for b in self.candidates:
            out[b] = {}
            for direction, fn_of, arg in (("synth", self._synth_fn, alm),
                                          ("anal", self._anal_fn, maps)):
                variants = (self._dist_chunk_variants(direction)
                            if b == "dist" else (None,))
                best, best_c, errs = float("inf"), None, {}
                for c in variants:

                    def measure(b=b, c=c, fn_of=fn_of, arg=arg):
                        return _time_call_us(fn_of(b, c), arg)

                    try:
                        us, status = db.get_or_measure(
                            measure, **self._corner_fields(b, direction, c))
                        t = float("inf") if us is None else us * 1e-6
                        if status == "skipped":
                            out[b][f"{direction}_skipped"] = True
                    except Exception as e:  # unusable here: rank last,
                        if _on_tpu():       # but on the chip it is an error
                            raise
                        t = float("inf")
                        errs[c] = f"{type(e).__name__}: {e}"
                        if c is not None:
                            out[b][f"{direction}_{c}_error"] = errs[c]
                    if c is not None:
                        out[b][f"{direction}_{c}"] = t
                    if t < best:
                        best, best_c = t, c
                out[b][direction] = best
                if not np.isfinite(best):   # every variant failed: backend
                    out[b][f"{direction}_error"] = \
                        "; ".join(errs.values())            # unusable
                if best_c is not None:
                    out[b][f"{direction}_chunks"] = best_c
        return out

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
        """Fill ``self.backends``/``self.comm_chunks`` according to
        ``mode``."""
        self.predicted_s = self._predict_all()
        if self.mode in BACKENDS:                   # forced backend
            self.backends = {"synth": self.mode, "anal": self.mode}
        elif self.mode == "model":
            self.backends = {
                d: min(self.candidates, key=lambda b: self.predicted_s[b][d])
                for d in ("synth", "anal")}
        elif len(self.candidates) == 1:             # auto, nothing to time
            self.backends = {d: self.candidates[0] for d in ("synth", "anal")}
            self.cache_events["decision"] = "only-candidate"
        else:
            self._autotune()
            return
        self._fill_comm_chunks(self.predicted_s)

    def _autotune(self) -> None:
        """``mode="auto"`` over two or more candidates: the cached decision
        for this signature, else a measurement of every candidate."""
        dkey = plancache.signature_key("decision", sig=self._signature_key)
        cached = plancache.load_decision(dkey, cache=self._cache_kind,
                                         directory=self._cache_dir)
        if cached is not None and all(
                cached.get(d) in self.candidates for d in ("synth", "anal")):
            self.backends = {d: cached[d] for d in ("synth", "anal")}
            self.measured_s = cached.get("measured", {})
            self._fill_comm_chunks(self.measured_s)
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
        self._fill_comm_chunks(self.measured_s)
        if fell_back:
            # an un-measured decision must not shadow a later real autotune
            self.cache_events["decision"] = "model-fallback"
            return
        self.cache_events["decision"] = "autotuned"
        plancache.save_decision(
            dkey, {**self.backends, "measured": self.measured_s},
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
        :meth:`map2alm` without tracing loop internals.  Both built-in
        backends (jnp, dist) qualify; the rules are first-order (no
        reverse-over-reverse).
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
        }
        out["total_bytes"] = sum(out.values())
        return out

    def _jnp_blocks(self) -> dict:
        """Per direction run by the jnp backend: the row blocks of its
        Legendre loop (`legendre.loop_blocks`: block count and the share
        of the M x L row-steps it runs), whether it folds, and the
        operand planes a step streams (`legendre.planes_per_step`).
        Spin-2 loops run every row."""
        out = {}
        for d in ("synth", "anal"):
            if self.backends.get(d) != "jnp":
                continue
            if self.spin:
                out[d] = {"blocks": 1, "rows_per_block": 2 * (self.m_max + 1),
                          "row_step_share": 1.0}
            else:
                rings = self._sht.n_north if self.fold else self.grid.n_rings
                out[d] = legendre.loop_blocks(
                    self._m_vals, l_max=self.l_max,
                    row_bytes=legendre.row_step_bytes(
                        d, fold=self.fold, n_rings=rings, K=self.K,
                        dtype=self.dtype))
            out[d].update(fold=self.fold, planes_per_step=(
                legendre.planes_per_step(d, fold=self.fold)))
        return out

    def _dist_layout(self) -> Optional[dict]:
        """The dist backend's shards, if a direction runs it: the dealt m
        rows per shard, and per direction the chunk
        count and the row blocks of shard 0's stage-1 loop (per chunk)."""
        dirs = [d for d in ("synth", "anal") if self.backends.get(d) == "dist"]
        if not dirs:
            return None
        out, ncomp = {"chunks": {}, "row_blocks": {}}, 1 + (self.spin != 0)
        for d in dirs:
            C = self.comm_chunks.get(d) or 1
            eng = self._dist_engine(C)
            sp = eng.plan
            out.update(shards=sp.n_shards, m_local=sp.m_local)
            axis, bounds = sp.chunk_schedule(self.K, ncomp=ncomp, chunks=C)
            rows, K = sp.m_assignment[0], self.K
            if axis == "k":
                K = max(b - a for a, b in bounds)
            elif axis == "m":
                rows = rows[bounds[0][0]:bounds[0][1]]
            out["chunks"][d] = {"C": C, "axis": axis}
            out["row_blocks"][d] = (
                {"blocks": 1, "rows_per_block": 2 * len(rows),
                 "row_step_share": 1.0} if self.spin else
                legendre.loop_blocks(rows, l_max=self.l_max,
                                     row_bytes=legendre.row_step_bytes(
                                         d, fold=False, n_rings=sp.r_pad,
                                         K=K, dtype=self.dtype)))
        return out

    def describe(self) -> dict:
        """Structured report: signature, chosen backends, predicted vs
        measured seconds per candidate, memory footprint, cache counters.

        Benchmarks and docs consume this dict; ``report()`` pretty-prints
        it.
        """
        w = roofline.sht_work(self.l_max, self.m_max, self.grid.n_rings,
                              self.grid.max_n_phi, self.K,
                              fft_lengths=self._sht.phase.fft_lengths,
                              spin=self.spin)
        from repro.roofline import chardb
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
            # no backend has a Legendre layout to choose; the key stays
            # for the benchmark's [plan] line
            "layouts": {"synth": None, "anal": None},
            "comm": {
                "spec": self._comm_spec,
                "chunks": dict(self.comm_chunks),
                "pipelined": {d: (self.comm_chunks.get(d) or 1) > 1
                              for d in ("synth", "anal")},
            },
            "candidates": list(self.candidates),
            "skipped": dict(self.skipped),
            "legendre": {"jnp_blocks": self._jnp_blocks()},
            "dist": self._dist_layout(),
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
        """Human-readable ``describe()`` (chosen backend, predicted vs
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
        for direction, b in d["legendre"]["jnp_blocks"].items():
            lines.append(
                f"  jnp loop {direction}: {b['blocks']} row blocks of "
                f"{b['rows_per_block']}, {b['row_step_share']:.3f} of the "
                f"M x L row-steps, fold={b['fold']}, "
                f"{b['planes_per_step']} plane(s) a step")
        dl = d["dist"]
        if dl:
            lines.append(f"  dist: {dl['shards']} shards x {dl['m_local']} "
                         "m rows")
            for direction, b in dl["row_blocks"].items():
                cc = dl["chunks"][direction]
                lines.append(
                    f"  dist {direction}: C={cc['C']} ({cc['axis']}), shard "
                    f"0 loop: {b['blocks']} row blocks of "
                    f"{b['rows_per_block']}, {b['row_step_share']:.3f} of "
                    f"its row-steps")
        for direction in ("synth", "anal"):
            chosen = d["backends"].get(direction, "?")
            pred = d["predicted_s"].get(chosen, {}).get(direction)
            meas = d["measured_s"].get(chosen, {}).get(direction) \
                if d["measured_s"] else None
            bits = [f"  {direction:5s} -> {chosen}"]
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
              fold: Optional[bool] = None, spin: int = 0,
              cache: str = "auto",
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
        batched Monte-Carlo workload).
    dtype : ``"float64"`` (oracle precision; needs ``jax_enable_x64`` and
        is refused on a TPU) or ``"float32"`` (performance).  None picks
        :func:`default_dtype`.
    mode : ``"auto"`` (autotune, cached; takes the only candidate without
        timing it), ``"model"`` (cost model), or an explicit backend name
        (``"jnp"``, ``"dist"``).
    fold : run the Legendre stage over the northern rings only, with even
        and odd (l + m) sums for the mirror pairs (symmetric grids, spin 0
        only).  None (default) folds on a uniform grid symmetric about the
        equator (GL, ECP) at spin 0, outside ``mode="dist"`` (whose
        sharded stage 1 does not fold); ragged grids stay unfolded, their
        fold unmeasured on a chip.  True or False forces it.
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
    if fold is None:
        fold = (g.equator_symmetric and g.uniform and spin == 0
                and mode != "dist")
    elif fold:
        assert g.equator_symmetric, "fold requires a symmetric grid"
    fold = bool(fold)

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
    elig = backend_eligibility(n_shards)
    cand = [b for b in BACKENDS if elig[b] is None]
    if mode in BACKENDS and mode not in cand:
        raise ValueError(
            f"backend {mode!r} unavailable for this signature: "
            f"{elig[mode]} (candidates: {cand})")
    plan.skipped = {b: r for b, r in elig.items() if r is not None}
    plan.candidates = cand
    plan._choose_backends()
    _PLANS[sig_key] = plan
    return plan
