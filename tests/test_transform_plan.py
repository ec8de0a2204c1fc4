"""The unified Plan API: dispatch, precompute caching, describe()."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import cache as plancache
from repro.core import grids, sht, spectra, transform

LMAX, K = 24, 2
KEY = jax.random.PRNGKey(7)


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Each test sees empty plan/precompute caches and zeroed counters."""
    transform.clear_plan_cache()
    plancache.reset_stats()
    yield
    transform.clear_plan_cache()
    plancache.reset_stats()


def _oracle_pair():
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K)
    oracle = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64",
                             mode="jnp")
    maps = np.asarray(oracle.alm2map(alm))
    return alm, maps, np.asarray(oracle.map2alm(jnp.asarray(maps)))


# -- plan-signature cache ----------------------------------------------------


def test_make_plan_is_memoised():
    p1 = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="model")
    builds = plancache.stats().builds
    p2 = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="model")
    assert p2 is p1
    assert plancache.stats().builds == builds       # no recompute at all


def test_signature_distinguishes_problems():
    p1 = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="model")
    p2 = repro.make_plan("gl", l_max=LMAX, K=K + 1, dtype="float64",
                         mode="model")
    p3 = repro.make_plan("gl", l_max=LMAX + 8, K=K, dtype="float64",
                         mode="model")
    assert p1 is not p2 and p1 is not p3 and p2 is not p3


def test_disk_cache_skips_recompute(tmp_path):
    d = str(tmp_path)
    p1 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32", mode="auto",
                         cache="disk", cache_dir=d)
    builds = plancache.stats().builds
    assert builds > 0
    # simulate a fresh process: drop every in-memory tier
    transform.clear_plan_cache()
    p2 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32", mode="auto",
                         cache="disk", cache_dir=d)
    assert p2 is not p1                              # new object...
    assert plancache.stats().builds == builds        # ...zero rebuilt payloads
    assert plancache.stats().disk_hits > 0
    assert p2.backends == p1.backends                # autotune decision reused
    assert p2.cache_events.get("decision") == "hit"


def test_clear_plan_cache_disk_tier(tmp_path):
    """clear_plan_cache(disk=True) removes the persistent entries too.

    Regression: a bare clear_plan_cache() left stale .npz/.json entries
    under the cache dir, so a later cache="disk" plan silently resurrected
    payloads the caller believed cleared.
    """
    import os
    d = str(tmp_path)
    repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32", mode="auto",
                    cache="disk", cache_dir=d)
    entries = [f for f in os.listdir(d) if f.endswith((".npz", ".json"))]
    assert entries, "disk tier should have been populated"
    # default clear keeps the disk tier (documented behaviour) ...
    transform.clear_plan_cache()
    assert [f for f in os.listdir(d) if f.endswith((".npz", ".json"))]
    # ... disk=True wipes it: a rebuild must not see a single disk hit
    transform.clear_plan_cache(disk=True, directory=d)
    assert not [f for f in os.listdir(d) if f.endswith((".npz", ".json"))]
    plancache.reset_stats()
    repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32", mode="auto",
                    cache="disk", cache_dir=d)
    assert plancache.stats().disk_hits == 0
    assert plancache.stats().builds > 0
    # foreign files are never touched
    alien = os.path.join(d, "keep.me")
    with open(alien, "w") as f:
        f.write("not a cache entry")
    transform.clear_plan_cache(disk=True, directory=d)
    assert os.path.exists(alien)


def test_disk_cache_keys_distinguish_layout_and_spin(tmp_path):
    """Signature keys must not collide across spin / layout variants.

    A spin-2 plan's seed tables have different shapes than the scalar
    ones; a key collision would resurrect the wrong payload from disk and
    crash (or worse, silently corrupt) the kernel stage.
    """
    d = str(tmp_path)
    p0 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32",
                         mode="pallas_vpu", cache="disk", cache_dir=d)
    p2 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32",
                         mode="pallas_vpu", spin=2, cache="disk", cache_dir=d)
    s0 = p0._seeds()
    s2 = p2._seeds_spin()
    assert p0.cache_events["seeds"] != p2.cache_events["seeds_spin"]
    # fold changes the seed table layout -> its own key (p0 folds by
    # default: a symmetric spin-0 plan)
    pf = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32",
                         mode="pallas_vpu", fold=False, cache="disk",
                         cache_dir=d)
    sf = pf._seeds()
    assert pf.cache_events["seeds"] != p0.cache_events["seeds"]
    assert sf[0].shape != s0[0].shape
    # cold reload from disk returns the right payload for each signature
    transform.clear_plan_cache()
    q0 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32",
                         mode="pallas_vpu", cache="disk", cache_dir=d)
    q2 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float32",
                         mode="pallas_vpu", spin=2, cache="disk", cache_dir=d)
    np.testing.assert_array_equal(np.asarray(q0._seeds()[0]),
                                  np.asarray(s0[0]))
    np.testing.assert_array_equal(np.asarray(q2._seeds_spin()[0]),
                                  np.asarray(s2[0]))


def test_geometry_payload_roundtrip(tmp_path):
    """A disk-cached GL grid is bit-identical to a fresh one."""
    d = str(tmp_path)
    p1 = repro.make_plan("gl", l_max=33, dtype="float64", mode="jnp",
                         cache="disk", cache_dir=d)
    transform.clear_plan_cache()
    p2 = repro.make_plan("gl", l_max=33, dtype="float64", mode="jnp",
                         cache="disk", cache_dir=d)
    g_ref = grids.make_grid("gl", l_max=33)
    for g in (p1.grid, p2.grid):
        np.testing.assert_array_equal(g.cos_theta, g_ref.cos_theta)
        np.testing.assert_array_equal(g.weights, g_ref.weights)


# -- backend agreement -------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas_vpu", "pallas_mxu"])
@pytest.mark.parametrize("fold", [False, True])
def test_backends_agree_with_f64_oracle(backend, fold):
    alm, maps_ref, alm_ref = _oracle_pair()
    dtype = "float64" if backend == "jnp" else "float32"
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype=dtype, mode=backend,
                        fold=fold)
    tol = 1e-12 if dtype == "float64" else 1e-4
    m = np.asarray(p.alm2map(alm.astype(jnp.complex64)
                             if dtype == "float32" else alm))
    assert np.max(np.abs(m - maps_ref)) / np.max(np.abs(maps_ref)) < tol
    a = np.asarray(p.map2alm(jnp.asarray(maps_ref, p.dtype)))
    assert np.max(np.abs(a - alm_ref)) / np.max(np.abs(alm_ref)) < tol


def test_auto_and_model_modes_roundtrip():
    for mode in ("auto", "model"):
        p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float32", mode=mode)
        assert p.backends["synth"] in p.candidates
        assert p.backends["anal"] in p.candidates
        alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
        err = spectra.d_err(alm, p.map2alm(p.alm2map(alm)))
        assert err < 1e-4, (mode, err)


def test_float64_restricted_to_oracle():
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="auto")
    assert p.candidates == ["jnp"] or "pallas_vpu" not in p.candidates
    assert p.backends == {"synth": "jnp", "anal": "jnp"}


def test_dist_backend_requires_devices():
    if jax.device_count() >= 2:
        pytest.skip("multi-device host: dist is legitimately available")
    with pytest.raises(ValueError):
        repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="dist")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="dist backend needs >= 2 devices (covered by "
                           "tests/helpers/dist_sht_check.py in a subprocess)")
def test_dist_backend_agrees():  # pragma: no cover - TPU/multi-device hosts
    alm, maps_ref, _ = _oracle_pair()
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="dist")
    m = np.asarray(p.alm2map(alm))
    assert np.max(np.abs(m - maps_ref)) / np.max(np.abs(maps_ref)) < 1e-10


def test_map2alm_iters_refines_on_healpix():
    p = repro.make_plan("healpix_ring", nside=8, dtype="float64", mode="jnp")
    alm = sht.random_alm(KEY, p.l_max, p.m_max, K=1)
    maps = p.alm2map(alm)
    e0 = spectra.d_err(alm, p.map2alm(maps))
    e1 = spectra.d_err(alm, p.map2alm(maps, iters=1))
    assert e1 < e0 / 3                               # Jacobi refinement bites


# -- describe() --------------------------------------------------------------


def test_describe_well_formed():
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float32", mode="auto")
    d = p.describe()
    for key in ("signature", "mode", "backends", "candidates", "predicted_s",
                "measured_s", "work", "memory", "cache"):
        assert key in d, key
    assert d["signature"]["l_max"] == LMAX
    assert set(d["backends"]) == {"synth", "anal"}
    for b in d["candidates"]:
        assert {"synth", "anal"} <= set(d["predicted_s"][b])
        assert all(d["predicted_s"][b][direction] > 0
                   for direction in ("synth", "anal"))
        if b.startswith("pallas"):
            # pallas candidates carry the packed/plain/fused layout decision
            assert d["predicted_s"][b]["synth_layout"] in (
                "packed", "plain", "fused")
        for direction in ("synth", "anal"):
            assert direction in d["measured_s"][b]
    assert d["memory"]["total_bytes"] > 0
    assert d["work"]["n_lm"] == (LMAX + 1) * (LMAX + 2) // 2
    # report() renders every section without blowing up
    r = p.report()
    assert "synth ->" in r and "anal" in r and "cache" in r


def test_describe_predicted_vs_measured_present_in_auto():
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float32", mode="auto")
    d = p.describe()
    chosen = d["backends"]["synth"]
    assert np.isfinite(d["measured_s"][chosen]["synth"])
    assert d["measured_s"][chosen]["synth"] > 0


def test_plan_shape_validation():
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="jnp")
    with pytest.raises(AssertionError):
        p.alm2map(jnp.zeros((LMAX + 1, LMAX + 1, K + 1), jnp.complex128))
    with pytest.raises(AssertionError):
        p.map2alm(jnp.zeros((3, 4, K)))


def test_available_backends_policy():
    g = grids.make_grid("gl", l_max=16)
    assert repro.available_backends(g, "float64", 1) == ["jnp"]
    f32 = repro.available_backends(g, "float32", 1)
    assert "pallas_vpu" in f32 and "pallas_mxu" in f32
    # raggedness is no longer a restriction: the bucket phase stage serves
    # every backend
    ragged = grids.make_grid("healpix", nside=4)
    assert repro.available_backends(ragged, "float32", 1) == f32
    assert repro.available_backends(ragged, "float32", 4) == f32 + ["dist"]


def test_backend_eligibility_reasons():
    g = grids.make_grid("gl", l_max=16)
    elig = transform.backend_eligibility(g, "float64", 1)
    assert elig["jnp"] is None
    assert "float32" in elig["pallas_vpu"]
    assert "devices" in elig["dist"]
    assert transform.backend_eligibility(g, "float32", 2)["dist"] is None


def test_describe_reports_skip_reasons():
    p = repro.make_plan("healpix", nside=4, K=1, dtype="float64", mode="jnp")
    d = p.describe()
    assert "float32" in d["skipped"]["pallas_vpu"]
    assert all(b not in d["candidates"] for b in d["skipped"])
    assert d["phase"]["kind"] == "bucket"
    assert d["phase"]["n_buckets"] >= 2
    r = p.report()
    assert "skipped pallas_vpu" in r and "phase: bucket" in r


# -- ragged (true HEALPix) grids through the full dispatch stack -------------


def _healpix_oracle_pair(nside=4):
    p = repro.make_plan("healpix", nside=nside, K=K, dtype="float64",
                        mode="jnp")
    alm = sht.random_alm(KEY, p.l_max, p.m_max, K=K)
    maps = np.asarray(p.alm2map(alm))
    return p, alm, maps, np.asarray(p.map2alm(jnp.asarray(maps)))


@pytest.mark.parametrize("backend", ["jnp", "pallas_vpu", "pallas_mxu"])
def test_healpix_backends_agree_with_f64_oracle(backend):
    _, alm, maps_ref, alm_ref = _healpix_oracle_pair()
    dtype = "float64" if backend == "jnp" else "float32"
    p = repro.make_plan("healpix", nside=4, K=K, dtype=dtype, mode=backend)
    tol = 1e-12 if dtype == "float64" else 1e-4
    m = np.asarray(p.alm2map(alm.astype(jnp.complex64)
                             if dtype == "float32" else alm))
    assert np.max(np.abs(m - maps_ref)) / np.max(np.abs(maps_ref)) < tol
    a = np.asarray(p.map2alm(jnp.asarray(maps_ref, p.dtype)))
    assert np.max(np.abs(a - alm_ref)) / np.max(np.abs(alm_ref)) < tol


def test_healpix_auto_mode_roundtrips():
    p = repro.make_plan("healpix", nside=4, K=K, dtype="float32",
                        mode="auto")
    assert p.backends["synth"] in p.candidates
    alm = sht.random_alm(KEY, p.l_max, p.m_max, K=K).astype(jnp.complex64)
    err = spectra.d_err(alm, p.map2alm(p.alm2map(alm)))
    assert err < 0.1                     # quadrature-level, not precision


@pytest.mark.parametrize("kind", ["healpix", "healpix_ring"])
def test_map2alm_iters_monotone_on_approximate_grids(kind):
    """Jacobi refinement must reduce the quadrature error monotonically on
    both HEALPix variants (paper §5 accuracy discussion)."""
    p = repro.make_plan(kind, nside=8, dtype="float64", mode="jnp")
    alm = sht.random_alm(KEY, p.l_max, p.m_max, K=1)
    maps = p.alm2map(alm)
    errs = [spectra.d_err(alm, p.map2alm(maps, iters=i)) for i in range(3)]
    assert errs[1] < errs[0] / 3         # first pass bites hard
    assert errs[2] < errs[1]             # and keeps shrinking


# ---------------------------------------------------------------------------
# device policy: float64, peaks table, staged VPU eligibility on a TPU
# ---------------------------------------------------------------------------


def test_float64_refused_on_tpu(monkeypatch):
    """A TPU has no float64: plans and engine requests fail at once with
    the reason, never running in float32 or emulation."""
    from repro.serve import ShtEngine
    monkeypatch.setattr(transform, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="not available on a TPU"):
        repro.make_plan("gl", l_max=8, dtype="float64", mode="jnp")
    alm = np.zeros((9, 9), np.complex128)
    with pytest.raises(ValueError, match="not available on a TPU"):
        ShtEngine().submit(direction="alm2map", payload=alm, grid="gl",
                           l_max=8, dtype="float64")
    assert transform.default_dtype() == "float32"


def test_float64_needs_x64_mode():
    with jax.enable_x64(False):
        with pytest.raises(ValueError, match="64-bit mode"):
            repro.make_plan("gl", l_max=8, dtype="float64", mode="jnp")
        assert transform.default_dtype() == "float32"
    assert transform.default_dtype() == "float64"


def test_staged_vpu_ineligible_on_tpu(monkeypatch):
    """On a TPU the staged VPU kernels are skipped with Mosaic's reason;
    the VPU backend keeps its fused layout."""
    monkeypatch.setattr(transform, "_on_tpu", lambda: True)
    elig = transform.backend_eligibility(grids.make_grid("gl", l_max=8),
                                         "float32")
    for lay in ("plain", "packed"):
        assert "do not compile for TPU" in elig[f"pallas_vpu[{lay}]"]
    assert elig["pallas_vpu"] is None


def test_peaks_table_rejects_unknown_device():
    from repro.roofline import analysis

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    with pytest.raises(ValueError, match="no peaks for device kind"):
        analysis.hardware_for(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert analysis.hardware_for(Dev()) is analysis.HW_V5E
    assert analysis.hardware_for(jax.devices("cpu")[0]) is analysis.HW_HOST


# ---------------------------------------------------------------------------
# the equator fold: derived default, engagement counter, chip policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case, want", [
    (dict(grid="gl"), True),
    (dict(grid="ecp"), True),
    (dict(grid="gl", spin=2), False),
    (dict(grid="gl", mode="dist", n_shards=2), False),
    (dict(grid="gl", fold=False), False),
    (dict(grid="healpix", nside=4), False),
    (dict(grid="healpix", nside=4, fold=True), True),
])
def test_fold_default_is_derived(case, want):
    """``fold=None`` folds a uniform symmetric spin-0 plan outside the
    dist backend (a ragged HEALPix grid keeps its fused layout unfolded);
    an explicit value is honoured."""
    kw = dict(l_max=None if "nside" in case else 8, dtype="float64",
              mode="jnp", cache="off")
    kw.update(case)
    plan = repro.make_plan(kw.pop("grid"), **kw)
    assert plan.fold is want
    if kw["mode"] == "jnp":          # describe() of a dist plan needs devices
        d = plan.describe()
        assert d["signature"]["fold"] is want
        assert all(b["fold"] is want
                   for b in d["legendre"]["jnp_blocks"].values())


def test_describe_reports_fold_and_planes_a_step():
    plan = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64",
                           mode="jnp")
    blocks = plan.describe()["legendre"]["jnp_blocks"]
    assert set(blocks) == {"synth", "anal"}
    for b in blocks.values():
        assert b["fold"] is True and b["planes_per_step"] == 1
    report = plan.report()
    assert "fold=True" in report.splitlines()[0]
    assert "fold=True, 1 plane(s) a step" in report


@pytest.mark.parametrize("direction, K, arrays, blocks, rows", [
    ("anal", 4, 17, 2, 2056),       # the benchmark's cell
    ("synth", 1, 13, 2, 2056),      # 1.6 x BLOCK_STEP_BYTES a step
    ("synth", 4, 25, 3, 1368),
])
def test_folded_row_blocks_at_4096(direction, K, arrays, blocks, rows):
    """At l_max 4096 in float32 a folded step streams 9 + 2K (analysis)
    or 9 + 4K (synthesis) arrays of 2049 northern rings a row, and the
    rows split into the block count nearest to the step's bytes over
    BLOCK_STEP_BYTES."""
    from repro.core import legendre
    row_bytes = legendre.row_step_bytes(direction, fold=True, n_rings=2049,
                                        K=K, dtype="float32")
    assert row_bytes == arrays * 2049 * 4
    got = legendre.loop_blocks(np.arange(4097), l_max=4096,
                               row_bytes=row_bytes)
    assert (got["blocks"], got["rows_per_block"]) == (blocks, rows)
    assert legendre.planes_per_step(direction, fold=True) == 1


@pytest.mark.parametrize("fold", [True, False])
def test_failed_folded_pallas_layout_is_skipped_on_tpu(fold, monkeypatch):
    """On a TPU a folded plan's pallas layout that fails to build is
    skipped with its reason, as the staged VPU kernels are, and the auto
    build ends; an unfolded plan's failure is still an error."""
    def refuse(self, *a, **k):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(transform, "_on_tpu", lambda: True)
    monkeypatch.setattr(transform.Plan, "_make_fused_anal", refuse)
    monkeypatch.setattr(transform.Plan, "_make_fused_synth", refuse)
    build = lambda: repro.make_plan("gl", l_max=10, K=3, dtype="float32",
                                    mode="auto", fold=fold, cache="off")
    if not fold:
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            build()
        return
    plan = build()
    skipped = plan.describe()["skipped"]
    assert "Mosaic refused" in skipped["pallas_vpu[fused]"]
    assert set(plan.backends) == {"synth", "anal"}
    assert all(np.isfinite(plan.measured_s[b][d])
               for d, b in plan.backends.items())
