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
    assert p2.backends == p1.backends == {"synth": "jnp", "anal": "jnp"}
    # one device, one candidate: there is no decision to time or reuse
    assert p2.cache_events.get("decision") == "only-candidate"


def test_disk_cache_reloads_auto_decision(tmp_path):
    """With two candidates (jnp and dist on two host devices, in a
    subprocess) a cold rebuild from the disk tier reloads the autotune
    decision, comm chunks included, and times nothing."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import json, repro\n"
        "from repro.core import transform\n"
        f"kw = dict(l_max=8, K=2, dtype='float32', mode='auto', "
        f"cache='disk', cache_dir={str(tmp_path)!r})\n"
        "p1 = repro.make_plan('gl', **kw)\n"
        "transform.clear_plan_cache()\n"
        "def untimed(*a, **k):\n"
        "    raise AssertionError('timed a candidate on a cached decision')\n"
        "transform._time_call_us = untimed\n"
        "p2 = repro.make_plan('gl', **kw)\n"
        "print(json.dumps({'same': p2 is p1, 'cand': p2.candidates,"
        " 'decisions': [p1.cache_events['decision'],"
        " p2.cache_events['decision']],"
        " 'backends': [p1.backends, p2.backends],"
        " 'chunks': [p1.comm_chunks, p2.comm_chunks],"
        " 'measured': [p1.measured_s, p2.measured_s]}))\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert not d["same"] and d["cand"] == ["jnp", "dist"]
    assert d["decisions"] == ["autotuned", "hit"]
    assert d["backends"][0] == d["backends"][1]
    assert d["chunks"][0] == d["chunks"][1]
    assert d["measured"][0] == d["measured"][1]


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
    """Signature keys must not collide across spin / fold variants.

    A spin-2 or unfolded plan runs another program on other shapes than
    the folded scalar one; a key collision would hand one signature the
    other's cached plan or decision.  After a cold reload from disk each
    signature transforms exactly as before.
    """
    d = str(tmp_path)
    kw = dict(l_max=LMAX, K=1, dtype="float32", mode="jnp", cache="disk",
              cache_dir=d)
    variants = [dict(), dict(spin=2), dict(fold=False)]
    plans = [repro.make_plan("gl", **kw, **v) for v in variants]
    assert plans[0].fold and not plans[2].fold   # spin 0 folds by default
    keys = {p._signature_key for p in plans}
    assert len(keys) == 3

    def outputs(plan):
        f = sht.random_alm_spin if plan.spin else sht.random_alm
        alm = f(seed=0, l_max=LMAX, m_max=LMAX).astype(jnp.complex64)
        return np.asarray(plan.alm2map(alm))

    want = [outputs(p) for p in plans]
    assert want[1].shape != want[0].shape
    # cold reload from disk returns the right plan for each signature
    transform.clear_plan_cache()
    again = [repro.make_plan("gl", **kw, **v) for v in variants]
    assert {p._signature_key for p in again} == keys
    for p, w in zip(again, want):
        np.testing.assert_array_equal(outputs(p), w)


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


@pytest.mark.parametrize("backend", ["jnp"])
@pytest.mark.parametrize("fold", [False, True])
def test_backends_agree_with_f64_oracle(backend, fold):
    alm, maps_ref, alm_ref = _oracle_pair()
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode=backend,
                        fold=fold)
    m = np.asarray(p.alm2map(alm))
    assert np.max(np.abs(m - maps_ref)) / np.max(np.abs(maps_ref)) < 1e-12
    a = np.asarray(p.map2alm(jnp.asarray(maps_ref)))
    assert np.max(np.abs(a - alm_ref)) / np.max(np.abs(alm_ref)) < 1e-12


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
    assert p.candidates == ["jnp"]
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
    # one candidate: auto times nothing, and no backend has a layout
    assert d["candidates"] == ["jnp"] and d["measured_s"] == {}
    assert d["layouts"] == {"synth": None, "anal": None}
    assert d["cache"]["events"]["decision"] == "only-candidate"
    assert d["memory"]["total_bytes"] > 0
    assert d["work"]["n_lm"] == (LMAX + 1) * (LMAX + 2) // 2
    # report() renders every section without blowing up
    r = p.report()
    assert "synth ->" in r and "anal" in r and "cache" in r


def test_describe_predicted_vs_measured_present_in_auto():
    """With two candidates (jnp and dist on two host devices, in a
    subprocess) auto measures both in both directions, and describe()
    gives each measurement beside its prediction."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import json, jax, repro\n"
        "p = repro.make_plan('gl', l_max=8, K=2, dtype='float32', "
        "mode='auto', cache='off')\n"
        "d = p.describe()\n"
        "print(json.dumps({'cand': d['candidates'], 'pred': d['predicted_s'],"
        " 'meas': d['measured_s'], 'backends': d['backends'],"
        " 'decision': d['cache']['events']['decision']}))\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert d["cand"] == ["jnp", "dist"] and d["decision"] == "autotuned"
    for b in d["cand"]:
        for direction in ("synth", "anal"):
            assert d["pred"][b][direction] > 0
            assert 0 < d["meas"][b][direction] < float("inf")
    assert d["meas"]["dist"]["synth_chunks"] >= 1
    for direction, b in d["backends"].items():
        assert d["meas"][b][direction] == min(
            d["meas"][c][direction] for c in d["cand"])


def test_plan_shape_validation():
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64", mode="jnp")
    with pytest.raises(AssertionError):
        p.alm2map(jnp.zeros((LMAX + 1, LMAX + 1, K + 1), jnp.complex128))
    with pytest.raises(AssertionError):
        p.map2alm(jnp.zeros((3, 4, K)))


def test_available_backends_policy():
    # neither the dtype nor the grid restricts a backend: the bucket phase
    # stage serves both on every grid; only the device count does
    assert repro.available_backends(1) == ["jnp"]
    assert repro.available_backends(4) == ["jnp", "dist"]


def test_backend_eligibility_reasons():
    elig = transform.backend_eligibility(1)
    assert set(elig) == {"jnp", "dist"}
    assert elig["jnp"] is None
    assert "devices" in elig["dist"]
    assert transform.backend_eligibility(2)["dist"] is None


def test_describe_reports_skip_reasons():
    p = repro.make_plan("healpix", nside=4, K=1, dtype="float64", mode="jnp")
    d = p.describe()
    assert "devices" in d["skipped"]["dist"]
    assert all(b not in d["candidates"] for b in d["skipped"])
    assert d["phase"]["kind"] == "bucket"
    assert d["phase"]["n_buckets"] >= 2
    r = p.report()
    assert "skipped dist" in r and "phase: bucket" in r


# -- ragged (true HEALPix) grids through the full dispatch stack -------------


def _healpix_oracle_pair(nside=4):
    p = repro.make_plan("healpix", nside=nside, K=K, dtype="float64",
                        mode="jnp")
    alm = sht.random_alm(KEY, p.l_max, p.m_max, K=K)
    maps = np.asarray(p.alm2map(alm))
    return p, alm, maps, np.asarray(p.map2alm(jnp.asarray(maps)))


@pytest.mark.parametrize("backend", ["jnp"])
def test_healpix_backends_agree_with_f64_oracle(backend):
    _, alm, maps_ref, alm_ref = _healpix_oracle_pair()
    p = repro.make_plan("healpix", nside=4, K=K, dtype="float64",
                        mode=backend)
    m = np.asarray(p.alm2map(alm))
    assert np.max(np.abs(m - maps_ref)) / np.max(np.abs(maps_ref)) < 1e-12
    a = np.asarray(p.map2alm(jnp.asarray(maps_ref)))
    assert np.max(np.abs(a - alm_ref)) / np.max(np.abs(alm_ref)) < 1e-12


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
# device policy: float64 and the peaks table on a TPU
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
    dist backend (a ragged HEALPix grid stays unfolded); an explicit value
    is honoured."""
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


# ---------------------------------------------------------------------------
# the two backends: float32 jnp plans against the float64 oracle, auto on
# one device, and the backend names that went
# ---------------------------------------------------------------------------

_GRID_KW = {"gl": dict(l_max=LMAX), "ecp": dict(l_max=LMAX),
            "healpix": dict(nside=4), "healpix_ring": dict(nside=4)}


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize("spin", [0, 2])
@pytest.mark.parametrize("grid", sorted(_GRID_KW))
def test_jnp_float32_agrees_with_f64_oracle(grid, spin, direction):
    """The float32 jnp plan (the dtype every cell runs, with its default
    fold) against the float64 plan of the same signature."""
    kw = dict(K=K, mode="jnp", spin=spin, cache="off", **_GRID_KW[grid])
    oracle = repro.make_plan(grid, dtype="float64", **kw)
    p = repro.make_plan(grid, dtype="float32", **kw)
    f = sht.random_alm_spin if spin else sht.random_alm
    alm = f(KEY, p.l_max, p.m_max, K=K)
    maps = oracle.alm2map(alm)
    if direction == "synth":
        got = p.alm2map(alm.astype(jnp.complex64))
        want = maps
    else:
        got = p.map2alm(jnp.asarray(maps, jnp.float32))
        want = oracle.map2alm(maps)
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype in (np.float32, np.complex64)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-5


def test_auto_on_one_device_times_nothing(monkeypatch):
    """One device leaves one candidate: ``mode="auto"`` takes jnp without
    compiling or timing a call."""
    def refuse(*a, **k):
        raise AssertionError("auto timed a call")

    monkeypatch.setattr(transform, "_time_call_us", refuse)
    p = repro.make_plan("gl", l_max=LMAX, K=K, dtype="float32", mode="auto",
                        cache="off")
    assert p.backends == {"synth": "jnp", "anal": "jnp"}
    assert p.measured_s == {} and not p._compiled
    assert p.cache_events["decision"] == "only-candidate"


@pytest.mark.parametrize("mode", ["pallas_vpu", "pallas_mxu"])
def test_removed_backends_raise(mode):
    with pytest.raises(ValueError, match=r"\('jnp', 'dist'\)"):
        repro.make_plan("gl", l_max=LMAX, K=K, dtype="float32", mode=mode)
