#!/usr/bin/env python3
"""Smoke run of the SHT library and its serving engine on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the distributed phase only

One process drives the chip through the library's own entry points
(``repro.make_plan``, ``Plan.alm2map``/``map2alm``, ``repro.serve.ShtEngine``)
at the paper's widths (``repro.configs.sht_cmb``):

  fused    the fused Legendre+phase Pallas pipeline, VPU and MXU variants
           forced per plan, at ``synth_4k_k1`` (l_max=4096, K=1; MXU
           synthesis) and ``anal_4k_k4`` (l_max=4096, K=4; MXU analysis);
           the round-trip error D_err (paper eq. 19) of
           map2alm(alm2map(a)) on the exact-quadrature Gauss-Legendre grid
           is checked for every kernel run.
  reference the same kernels against a float64 numpy transform written
           here, independent of the library (its own Gauss-Legendre
           nodes and weights, checked against the plan's grid), at
           l_max=512.
  auto     one ``mode="auto"`` plan at ``synth_4k_k1``: every candidate
           backend is measured (a slow backend's further layouts are
           pruned, see ``repro.core.transform.PRUNE_FACTOR``); none may
           fail.
  engine   eight float32 alm2map requests at l_max=2048 coalesced into one
           K=8 batch (``synth_2k_k8``); each result must equal the batch
           plan's own column bit for bit, and agree with a K=1 plan run of
           the same request.
  dist     (``--chips 4`` only) the distributed two-stage transform at
           ``anal_4k_k4`` (l_max=4096, K=4), with the plan's default
           exchange chunk count and, where that is not 1, with one chunk,
           against the one-chip jnp plan.

Each phase prints its backend and layout, whether Pallas ran in interpret
mode (it must not), the backend compile seconds, one warm call's seconds,
its errors against their bounds and the device's peak bytes in use.  A
failed phase prints its traceback, the remaining phases still run, and
the script exits non-zero.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.  Without a TPU the script exits non-zero before
any phase.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0

#: Round-trip D_err bound at l_max=4096 in float32.  The same transform
#: evaluated in float32 by the jnp path on a CPU (IEEE float32 throughout)
#: gives D_err 2.6e-5, 6.1e-5, 2.0e-4 and 3.3e-4 at l_max 512, 1024, 2048
#: and 4096.  The bound allows 3x the last; bfloat16 contractions (a TPU
#: matmul's default precision) give ~3e-3, and an indexing or
#: accumulation fault gives D_err of order 1.
D_ERR_BOUND = 1e-3
#: Kernels vs the float64 numpy reference at l_max=512, as the largest
#: error over the map (or alm) relative to its largest value.  The float32
#: transform on a CPU is off by 5.9e-4 to 1.2e-3 over two inputs: x =
#: cos(theta) rounded to float32 keeps little of 1 - x on the polar rings.
#: The bound allows 2.5x the larger.
REF_BOUND = 3e-3
#: Engine results vs per-request K=1 plans (float32): only the width of
#: the coefficient matmul differs, so a few ulps of the map's scale.
ENGINE_BOUND = 1e-5
#: Shape (l_max, K) of the reference phase: the numpy transform's cost
#: grows as l_max^3, ~10 s here at 512.
SMALL = (512, 2)
#: Distributed plan vs the one-chip fused plan (both float32): two
#: different kernel chains of the same transform, l_max=4096.
DIST_BOUND = 1e-4


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(name: str, value: float, bound: float) -> str:
    """Raise unless ``value <= bound`` (NaN fails); returns a log field."""
    if not value <= bound:
        raise AssertionError(f"{name} = {value!r} exceeds its bound {bound}")
    return f"{value:.3e}<={bound:.0e}"


def random_alm(rng, l_max: int, K: int) -> np.ndarray:
    """(l_max+1, l_max+1, K) complex64 a_lm, uniform in (-1, 1) (paper
    section 5); m = 0 real, zero below the diagonal l < m."""
    shape = (l_max + 1, l_max + 1, K)
    a = rng.uniform(-1, 1, shape) + 1j * rng.uniform(-1, 1, shape)
    a[0] = a[0].real
    m, l = np.indices(shape[:2])
    return np.where((l >= m)[..., None], a, 0).astype(np.complex64)


def rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class CompileClock:
    """Backend compile seconds (a persistent-cache hit counts only its
    read) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.total = self.all_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration
            self.all_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


def timed(fn, *args):
    """(seconds to block_until_ready, result)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0, out


def device_fields() -> dict:
    """Interpret-mode flag and the first device's peak bytes in use."""
    import jax
    from repro.kernels import ops
    stats = jax.devices()[0].memory_stats() or {}
    return {"interpret": ops.should_interpret(),
            "peak_bytes": stats.get("peak_bytes_in_use", "n/a")}


def release(*plans) -> None:
    """Drop memoised plans so their device tables can be freed."""
    from repro.core import transform
    for p in plans:
        transform.drop_plan(p)
    gc.collect()


# ---------------------------------------------------------------------------
# float64 numpy reference (independent of the library's transform code)
# ---------------------------------------------------------------------------


def _legendre_by_l(l_max: int, m_max: int, x, sin):
    """Yield (l, P_lm(x_r)) for l = 0..l_max as (m_max+1, R) float64:
    orthonormal associated Legendre functions (2 pi int P^2 dx = 1, no
    Condon-Shortley phase), by the three-term recurrence in l from
    P_mm = sqrt((2m+1)!! / (4 pi (2m)!!)) sin^m."""
    m = np.arange(m_max + 1, dtype=np.float64)[:, None]
    k = np.arange(1, m_max + 1, dtype=np.float64)
    log_mu = -0.5 * np.log(4 * np.pi) + np.concatenate(
        [[0.0], np.cumsum(0.5 * np.log((2 * k + 1) / (2 * k)))])
    with np.errstate(under="ignore"):
        pmm = np.exp(log_mu[:, None] + m * np.log(sin)[None, :])
    prev = np.zeros_like(pmm)
    curr = np.zeros_like(pmm)
    for l in range(l_max + 1):
        if l == 0:
            new = np.zeros_like(pmm)
        else:
            b_l = np.sqrt((4.0 * l * l - 1) / np.maximum(l * l - m * m, 1))
            lm1 = l - 1
            b_lm1 = np.sqrt(max(4.0 * lm1 * lm1 - 1, 1)
                            / np.maximum(lm1 * lm1 - m * m, 1))
            back = np.where(lm1 > m, prev / b_lm1, 0.0)
            new = np.where(l > m, b_l * (x[None, :] * curr - back), 0.0)
        new = np.where(m == l, pmm, new)
        yield l, new
        prev, curr = curr, new


def gl_geometry(l_max: int) -> SimpleNamespace:
    """The Gauss-Legendre grid computed here: l_max+1 rings at the
    Gauss-Legendre nodes x_r (numpy's leggauss, north to south),
    n_phi = 2 l_max + 2 pixels from phi0 = 0, per-pixel weights
    w_r 2 pi / n_phi.  Attribute names follow the library's RingGrid."""
    x, w = np.polynomial.legendre.leggauss(l_max + 1)
    x, w = x[::-1], w[::-1]
    n_phi = 2 * l_max + 2
    return SimpleNamespace(cos_theta=x, sin_theta=np.sqrt(1.0 - x * x),
                           weights=w * (2 * np.pi / n_phi),
                           phi0=np.zeros(l_max + 1), max_n_phi=n_phi)


def check_grid(grid, geo) -> None:
    """Raise unless the library's grid is the geometry computed here."""
    if grid.max_n_phi != geo.max_n_phi or not all(
            np.allclose(getattr(grid, k), getattr(geo, k), rtol=0,
                        atol=1e-13)
            for k in ("cos_theta", "sin_theta", "weights", "phi0")):
        raise AssertionError("the plan's Gauss-Legendre grid differs from "
                             "numpy's leggauss nodes/weights")


def numpy_synth(alm, grid) -> np.ndarray:
    """alm (M, L, K) -> maps (R, n_phi, K) on a uniform ring grid:
    f(r, phi) = Re sum_m fac_m e^{i m phi} sum_l a_lm P_lm(x_r),
    fac = 1 for m = 0 and 2 otherwise, phi = phi0_r + 2 pi j / n_phi."""
    alm = np.asarray(alm, np.complex128)
    M, L, K = alm.shape
    x = np.asarray(grid.cos_theta, np.float64)
    sin = np.asarray(grid.sin_theta, np.float64)
    delta = np.zeros((M, x.shape[0], K), np.complex128)
    for l, p in _legendre_by_l(L - 1, M - 1, x, sin):
        delta += p[:, :, None] * alm[:, l, None, :]
    delta[1:] *= 2.0
    n = int(grid.max_n_phi)
    m = np.arange(M)
    out = np.empty((x.shape[0], n, K))
    for r in range(x.shape[0]):
        phi = grid.phi0[r] + 2 * np.pi * np.arange(n) / n
        out[r] = (np.exp(1j * np.outer(phi, m)) @ delta[:, r, :]).real
    return out


def numpy_anal(maps, grid, l_max: int) -> np.ndarray:
    """maps (R, n_phi, K) -> alm (M, L, K) by quadrature:
    a_lm = sum_r w_r P_lm(x_r) sum_j f(r, phi_j) e^{-i m phi_j}, with the
    grid's per-pixel weights w_r (Gauss-Legendre weight times
    2 pi / n_phi)."""
    maps = np.asarray(maps, np.float64)
    R, n, K = maps.shape
    x = np.asarray(grid.cos_theta, np.float64)
    sin = np.asarray(grid.sin_theta, np.float64)
    w = np.asarray(grid.weights, np.float64)
    m = np.arange(l_max + 1)
    f = np.empty((l_max + 1, R, K), np.complex128)
    for r in range(R):
        phi = grid.phi0[r] + 2 * np.pi * np.arange(n) / n
        f[:, r, :] = np.exp(-1j * np.outer(m, phi)) @ maps[r]
    f *= w[None, :, None]
    alm = np.zeros((l_max + 1, l_max + 1, K), np.complex128)
    for l, p in _legendre_by_l(l_max, l_max, x, sin):
        alm[:, l, :] = np.einsum("mr,mrk->mk", p, f)
    return alm


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _plan(l_max: int, K: int, mode: str, **kw):
    import repro
    return repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                           mode=mode, **kw)


def _fused_plan(tag: str, variant: str, l_max: int, K: int):
    plan = _plan(l_max, K, f"pallas_{variant}")
    if plan.layouts != {"synth": "fused", "anal": "fused"}:
        raise AssertionError(f"{tag}: fused layout not chosen for {variant}: "
                             f"{plan.layouts}")
    return plan


def _finite(tag: str, *arrays) -> None:
    if not all(np.isfinite(np.asarray(a)).all() for a in arrays):
        raise AssertionError(f"{tag}: non-finite output")


def phase_fused(tag: str, l_max: int, K: int, mxu_direction: str, rng,
                clock: CompileClock) -> None:
    """Forced fused pipeline, VPU and MXU, round-trip D_err.

    VPU: both directions, each compiled, then timed warm.  MXU: the one
    direction the shape stands for (``mxu_direction``), one call with its
    compile.  One MXU call takes ~68 s at l_max=4096 on a v5e, six times
    the VPU kernel, so its warm calls are left to benchmarks.  The MXU
    direction is checked by a round trip through the VPU kernel of the
    other direction."""
    from repro.core import spectra
    vpu = _fused_plan(tag, "vpu", l_max, K)
    mxu = _fused_plan(tag, "mxu", l_max, K)
    alm = random_alm(rng, l_max, K)
    clock.lap()
    _, maps = timed(vpu.alm2map, alm)
    c_synth = clock.lap()
    t_synth, maps = timed(vpu.alm2map, alm)
    _, back = timed(vpu.map2alm, maps)
    c_anal = clock.lap()
    t_anal, back = timed(vpu.map2alm, maps)
    _finite(tag, maps, back)
    log(f"fused/{tag}/vpu", backend=vpu.backends, layout=vpu.layouts,
        compile_synth_s=f"{c_synth:.1f}", compile_anal_s=f"{c_anal:.1f}",
        warm_synth_s=f"{t_synth:.4f}", warm_anal_s=f"{t_anal:.4f}",
        d_err=check(f"{tag} vpu D_err", spectra.d_err(alm, back),
                    D_ERR_BOUND),
        **device_fields())
    if mxu_direction == "synth":
        t_first, maps = timed(mxu.alm2map, alm)
        c_mxu = clock.lap()
        back = vpu.map2alm(maps)
        trip = "vpu.map2alm(mxu.alm2map(a))"
    else:
        t_first, back = timed(mxu.map2alm, maps)
        c_mxu = clock.lap()
        trip = "mxu.map2alm(vpu.alm2map(a))"
    _finite(tag, maps, back)
    log(f"fused/{tag}/mxu", backend=mxu.backends, layout=mxu.layouts,
        direction=mxu_direction, compile_s=f"{c_mxu:.1f}",
        first_call_s=f"{t_first:.4f}", round_trip=trip,
        d_err=check(f"{tag} mxu D_err", spectra.d_err(alm, back),
                    D_ERR_BOUND),
        **device_fields())
    release(vpu, mxu)


def phase_reference(l_max: int, K: int, rng, clock: CompileClock) -> None:
    """Fused kernels vs the float64 numpy transform on the same input,
    on a grid computed here and checked against the plans'."""
    alm = random_alm(rng, l_max, K)
    plans = {v: _plan(l_max, K, f"pallas_{v}") for v in ("vpu", "mxu")}
    geo = gl_geometry(l_max)
    for plan in plans.values():
        check_grid(plan.grid, geo)
    ref_maps = numpy_synth(alm, geo)
    ref_alm = numpy_anal(ref_maps, geo, l_max)
    maps32 = ref_maps.astype(np.float32)
    for v, plan in plans.items():
        clock.lap()
        maps = np.asarray(plan.alm2map(alm))
        alm_k = np.asarray(plan.map2alm(maps32))
        log(f"reference/lmax{l_max}/{v}", layout=plan.layouts,
            compile_s=f"{clock.lap():.1f}",
            synth_err=check(f"{v} synth vs float64", rel_err(maps, ref_maps),
                            REF_BOUND),
            anal_err=check(f"{v} anal vs float64", rel_err(alm_k, ref_alm),
                           REF_BOUND),
            **device_fields())
    release(*plans.values())


def phase_auto(l_max: int, K: int, rng, clock: CompileClock) -> None:
    """One autotuned plan: every candidate measured, none failing."""
    from repro.core import spectra
    clock.lap()
    t_build, plan = timed(lambda: _plan(l_max, K, "auto"))
    d = plan.describe()
    table = d["measured_s"]
    for b, row in table.items():
        log(f"auto/lmax{l_max}_k{K}/candidate", backend=b,
            **{k: (f"{v:.4f}" if isinstance(v, float) else v)
               for k, v in row.items()})
    errors = {f"{b}.{k}": v for b, row in table.items()
              for k, v in row.items() if k.endswith("_error")}
    if errors:
        raise AssertionError(f"auto: candidates failed: {errors}")
    alm = random_alm(rng, l_max, K)
    _, back = timed(lambda a: plan.map2alm(plan.alm2map(a)), alm)
    log(f"auto/lmax{l_max}_k{K}", backend=d["backends"], layout=d["layouts"],
        skipped=sorted(d["skipped"]), build_s=f"{t_build:.1f}",
        compile_s=f"{clock.lap():.1f}",
        d_err=check("auto D_err", spectra.d_err(alm, back), D_ERR_BOUND),
        **device_fields())
    release(plan)


def phase_engine(l_max: int, k: int, mode: str, rng,
                 clock: CompileClock) -> None:
    """k single-map requests coalesced into one K=k engine batch."""
    from repro.serve import ShtEngine
    alms = [random_alm(rng, l_max, 1)[..., 0] for _ in range(k)]
    clock.lap()
    with ShtEngine(max_k=k, mode=mode) as eng:
        futs = [eng.submit(direction="alm2map", payload=a, grid="gl",
                           l_max=l_max, dtype="float32") for a in alms]
        t_serve, _ = timed(eng.drain)
        results = [np.asarray(f.result()) for f in futs]
        batches = [(b["n_requests"], b["k_plan"]) for b in eng.batch_log]
    c_engine = clock.lap()
    if batches != [(k, k)]:
        raise AssertionError(f"engine: expected one K={k} batch, "
                             f"got {batches}")
    batch_plan = _plan(l_max, k, mode)          # the pool's memoised plan
    batch = np.asarray(batch_plan.alm2map(np.stack(alms, axis=-1)))
    identical = all(np.array_equal(r, batch[..., i])
                    for i, r in enumerate(results))
    if not identical:
        raise AssertionError("engine: results differ from the batch plan")
    single = _plan(l_max, 1, mode)
    err = max(rel_err(r, single.alm2map(a[..., None])[..., 0])
              for r, a in zip(results, alms))
    log(f"engine/lmax{l_max}_k{k}", mode=mode,
        layout=batch_plan.layouts, batches=batches,
        bit_identical_to_batch_plan=identical,
        compile_s=f"{c_engine:.1f}", serve_s=f"{t_serve:.4f}",
        vs_k1_plans=check("engine vs K=1 plans", err, ENGINE_BOUND),
        **device_fields())
    release(batch_plan, single)


def phase_dist(l_max: int, K: int, rng, clock: CompileClock) -> None:
    """The distributed plan over every visible chip vs the one-chip jnp
    plan, with the plan's default exchange chunk count and, where that is
    not 1, with one chunk.  One call per direction, its compile included
    (``first_*_s``)."""
    import jax
    from jax.sharding import NamedSharding
    ref = _plan(l_max, K, "jnp")
    alm = random_alm(rng, l_max, K)
    clock.lap()
    maps_ref = np.asarray(ref.alm2map(alm))
    alm_ref = np.asarray(ref.map2alm(maps_ref))
    log(f"dist/lmax{l_max}_k{K}/one-chip", layout=ref.layouts,
        compile_s=f"{clock.lap():.1f}", **device_fields())
    release(ref)
    default = _plan(l_max, K, "dist").comm_chunks
    for chunks in ("auto",) + ((1,) if set(default.values()) != {1} else ()):
        plan = _plan(l_max, K, "dist", comm_chunks=chunks)
        eng = plan._dist_engine(plan.comm_chunks["synth"])
        packed = eng.plan.pack_alm(alm)
        operand = jax.device_put(
            np.real(packed), NamedSharding(eng.mesh, eng._spec_sharded()))
        shards = [(s.device.id, tuple(s.data.shape))
                  for s in operand.addressable_shards]
        clock.lap()
        t_synth, maps = timed(plan.alm2map, alm)
        t_anal, back = timed(plan.map2alm, maps_ref)
        c = clock.lap()
        peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use")
                 for dv in jax.devices()]
        log(f"dist/lmax{l_max}_k{K}/chunks={chunks}",
            comm_chunks=plan.comm_chunks, stage1=eng.stage1,
            operand_sharding=operand.sharding.spec, operand_shards=shards,
            compile_s=f"{c:.1f}", first_synth_s=f"{t_synth:.4f}",
            first_anal_s=f"{t_anal:.4f}",
            synth_err=check("dist synth vs one-chip",
                            rel_err(maps, maps_ref), DIST_BOUND),
            anal_err=check("dist anal vs one-chip",
                           rel_err(back, alm_ref), DIST_BOUND),
            peak_bytes_per_device=peaks)
        release(plan)


# ---------------------------------------------------------------------------


def one_chip_phases():
    """(name, thunk(rng, clock)) for every one-chip phase, in run order."""
    from repro.configs.sht_cmb import SHT_SHAPES
    phases = []
    for tag, direction in (("synth_4k_k1", "synth"), ("anal_4k_k4", "anal")):
        cfg = SHT_SHAPES[tag]
        phases.append((f"fused/{tag}",
                       lambda r, c, t=tag, d=direction, s=cfg:
                       phase_fused(t, s.l_max, s.K, d, r, c)))
    phases.append(("reference", lambda r, c: phase_reference(*SMALL, r, c)))
    auto = SHT_SHAPES["synth_4k_k1"]
    phases.append(("auto", lambda r, c: phase_auto(auto.l_max, auto.K,
                                                   r, c)))
    eng = SHT_SHAPES["synth_2k_k8"]
    phases.append(("engine", lambda r, c: phase_engine(
        eng.l_max, eng.K, "pallas_vpu", r, c)))
    return phases


def dist_phases():
    from repro.configs.sht_cmb import SHT_SHAPES
    cfg = SHT_SHAPES["anal_4k_k4"]
    return [("dist", lambda r, c: phase_dist(cfg.l_max, cfg.K, r, c))]


def run_phases(phases, rng, clock: CompileClock) -> list:
    """Run every phase; a failed phase is printed and the next one still
    runs (on fresh plans), so one chip run reports them all.  Returns the
    names of the failed phases."""
    import traceback
    from repro.core import transform
    failed = []
    for name, run in phases:
        try:
            run(rng, clock)
        except Exception:                 # reported, and fails the run
            failed.append(name)
            print(f"[FAILED {name}]", flush=True)
            traceback.print_exc()
            transform.clear_plan_cache()
            gc.collect()
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed phase across 4 chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro import compile_cache
    log("setup", jax=jax.__version__, devices=len(devices),
        kind=devices[0].device_kind, compile_cache=compile_cache.enable())
    clock = CompileClock()
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    phases = dist_phases() if args.chips == 4 else one_chip_phases()
    failed = run_phases(phases, rng, clock)
    log("done", wall_s=f"{time.perf_counter() - t0:.1f}",
        compile_s=f"{clock.all_s:.1f}", cache_hits=clock.cache_hits,
        failed=failed)
    if failed:
        print(f"chip_smoke: {len(failed)} phase(s) failed: {failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
