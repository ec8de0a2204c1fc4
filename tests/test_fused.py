"""Fused Legendre+phase pipeline (kernels/fused.py) and the persistent
per-hardware characterization DB (roofline/chardb.py): fused-vs-staged
equality, single-kernel (no Delta HBM round-trip) pin, adjointness of the
linear_pair wrappers, bf16 error band, plan-level dispatch/describe()
wiring, chardb staleness / reuse / fingerprint isolation."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import cache as plancache
from repro.core import sht, spectra, transform
from repro.roofline import chardb

KEY = jax.random.PRNGKey(11)
LMAX, K = 24, 2


@pytest.fixture(autouse=True)
def _fresh_caches():
    transform.clear_plan_cache()
    plancache.reset_stats()
    chardb.clear()
    yield
    transform.clear_plan_cache()
    plancache.reset_stats()
    chardb.clear()


def _plan(l_max=LMAX, k=K, var="vpu", **kw):
    return repro.make_plan("gl", l_max=l_max, K=k, dtype="float32",
                           mode=f"pallas_{var}", cache="memory", **kw)


# ---------------------------------------------------------------------------
# fused == staged
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("var", ["vpu", "mxu"])
@pytest.mark.parametrize("l_max", [LMAX, 17])
def test_fused_matches_staged_synth(var, l_max):
    plan = _plan(l_max=l_max, var=var)
    alm = sht.random_alm(KEY, l_max, l_max, K=K).astype(jnp.complex64)
    got = plan._synth_fn(f"pallas_{var}", "fused")(alm)
    want = plan._synth_fn(f"pallas_{var}", "packed")(alm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5 * float(jnp.max(
                                   jnp.abs(want))))


@pytest.mark.parametrize("var", ["vpu", "mxu"])
@pytest.mark.parametrize("l_max", [LMAX, 17])
def test_fused_matches_staged_anal(var, l_max):
    plan = _plan(l_max=l_max, var=var)
    maps = jnp.asarray(
        np.random.default_rng(0).normal(
            size=(plan.grid.n_rings, plan.grid.max_n_phi, K)), jnp.float32)
    got = plan._anal_fn(f"pallas_{var}", "fused")(maps)
    want = plan._anal_fn(f"pallas_{var}", "packed")(maps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5 * float(jnp.max(
                                   jnp.abs(want))))


def test_fused_roundtrip_accuracy():
    plan = _plan()
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
    synth = plan._synth_fn("pallas_vpu", "fused")
    anal = plan._anal_fn("pallas_vpu", "fused")
    err = float(spectra.d_err(alm, anal(synth(alm))))
    assert err < 1e-4, err


def test_fused_synth_is_one_kernel_no_delta_hbm():
    """The tentpole property: the fused pipeline runs Legendre+phase as a
    single pallas_call, so the Delta intermediate never round-trips HBM.
    The staged chain shows >= 2 device ops with the Delta array between
    them; fused must show exactly one pallas_call in its jaxpr."""
    plan = _plan()
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
    for direction, fn_of, arg in (("synth", plan._synth_fn, alm),):
        fused = fn_of("pallas_vpu", "fused")
        txt = str(jax.make_jaxpr(fused)(arg))
        assert txt.count("pallas_call") == 1, (direction, txt.count(
            "pallas_call"))
    maps = jnp.zeros((plan.grid.n_rings, plan.grid.max_n_phi, K),
                     jnp.float32)
    txt = str(jax.make_jaxpr(plan._anal_fn("pallas_vpu", "fused"))(maps))
    assert txt.count("pallas_call") == 1


# ---------------------------------------------------------------------------
# adjointness (linear_pair wiring)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("var", ["vpu", "mxu"])
def test_fused_synth_adjoint_identity(var):
    """<J v, y> == Re(sum(vjp(y) * v)) -- the JAX bilinear pairing, same
    convention as tests/test_adjoint.py."""
    plan = _plan(var=var)
    f = plan._synth_fn(f"pallas_{var}", "fused")
    rng = np.random.default_rng(3)
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
    v = sht.random_alm(jax.random.PRNGKey(4), LMAX, LMAX,
                       K=K).astype(jnp.complex64)
    y = jnp.asarray(rng.normal(size=(plan.grid.n_rings,
                                     plan.grid.max_n_phi, K)), jnp.float32)
    _, vjp = jax.vjp(f, alm)
    (ct,) = vjp(y)
    _, jv = jax.jvp(f, (alm,), (v,))
    lhs = float(jnp.sum(jv * y))
    rhs = float(jnp.real(jnp.sum(ct * v)))
    scale = max(abs(lhs), abs(rhs), 1.0)
    assert abs(lhs - rhs) / scale < 1e-4, (lhs, rhs)


def test_fused_anal_jvp_runs():
    plan = _plan()
    f = plan._anal_fn("pallas_vpu", "fused")
    maps = jnp.asarray(np.random.default_rng(5).normal(
        size=(plan.grid.n_rings, plan.grid.max_n_phi, K)), jnp.float32)
    out, tangent = jax.jvp(f, (maps,), (maps,))
    # linear map: f(x) pushed forward along x is f(x) itself
    np.testing.assert_allclose(np.asarray(tangent), np.asarray(out),
                               rtol=0, atol=1e-5 * float(jnp.max(
                                   jnp.abs(out))))


# ---------------------------------------------------------------------------
# bf16 MXU contraction error band
# ---------------------------------------------------------------------------


def test_fused_bf16_error_band():
    plan = _plan(var="mxu")
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
    m32 = plan._make_fused_synth("mxu", bf16=False)(alm)
    m16 = plan._make_fused_synth("mxu", bf16=True)(alm)
    err = float(jnp.max(jnp.abs(m16 - m32)) / jnp.max(jnp.abs(m32)))
    assert 0.0 < err < 1e-2, err    # bf16 differs from f32 but stays banded
    a32 = plan._make_fused_anal("mxu", bf16=False)(m32)
    a16 = plan._make_fused_anal("mxu", bf16=True)(m32)
    err = float(jnp.max(jnp.abs(a16 - a32)) / jnp.max(jnp.abs(a32)))
    assert 0.0 < err < 1e-2, err


# ---------------------------------------------------------------------------
# eligibility + describe()
# ---------------------------------------------------------------------------


def test_fusion_eligible_on_uniform_spin0():
    plan = _plan()
    ok, reason = plan._fusion_eligibility()
    assert ok and reason is None
    assert "fused" in plan._pallas_layouts()
    d = plan.describe()["fusion"]
    assert d["eligible"] is True and d["reason"] is None
    assert set(d["pipelines"]) == {"synth", "anal"}
    for direction in ("synth", "anal"):
        assert d["pipelines"][direction] in ("fused", "staged")
        assert d["active"][direction] == (
            plan.layouts[direction] == "fused")


# ---------------------------------------------------------------------------
# full coverage: spin-2, equator fold, bucketed (HEALPix) through the
# fused pipeline
# ---------------------------------------------------------------------------

SHAPES = ["fold", "spin2", "bucket", "spin2-bucket"]


def _shape_plan(shape, var="vpu", k=K):
    kw = dict(K=k, dtype="float32", mode=f"pallas_{var}", cache="memory")
    if shape == "fold":
        return repro.make_plan("gl", l_max=LMAX, fold=True, **kw)
    if shape == "spin2":
        return repro.make_plan("gl", l_max=LMAX, spin=2, **kw)
    if shape == "bucket":
        return repro.make_plan("healpix", nside=8, **kw)
    assert shape == "spin2-bucket", shape
    return repro.make_plan("healpix", nside=8, spin=2, **kw)


def _shape_alm(plan, key=KEY):
    mk = sht.random_alm_spin if plan.spin else sht.random_alm
    return mk(key, plan.l_max, plan.m_max, K=plan.K).astype(jnp.complex64)


def _assert_fused_matches_staged(plan, var="vpu", tol=1e-5):
    ok, reason = plan._fusion_eligibility()
    assert ok, reason
    alm = _shape_alm(plan)
    got = plan._synth_fn(f"pallas_{var}", "fused")(alm)
    want = plan._synth_fn(f"pallas_{var}", "packed")(alm)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0,
        atol=tol * float(jnp.max(jnp.abs(want))))
    ga = plan._anal_fn(f"pallas_{var}", "fused")(want)
    wa = plan._anal_fn(f"pallas_{var}", "packed")(want)
    np.testing.assert_allclose(
        np.asarray(ga), np.asarray(wa), rtol=0,
        atol=tol * float(jnp.max(jnp.abs(wa))))


@pytest.mark.parametrize("var", ["vpu", "mxu"])
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_matches_staged_all_shapes(shape, var):
    _assert_fused_matches_staged(_shape_plan(shape, var=var), var=var)


@pytest.mark.parametrize("var", ["vpu", "mxu"])
@pytest.mark.parametrize("shape", SHAPES)
def test_fused_gradients_match_staged_all_shapes(shape, var):
    """linear_pair wiring per variant: the fused VJPs must equal the
    staged VJPs (property-tested in tests/test_adjoint.py) both ways."""
    plan = _shape_plan(shape, var=var)
    alm = _shape_alm(plan)
    maps, vjp_f = jax.vjp(plan._synth_fn(f"pallas_{var}", "fused"), alm)
    _, vjp_s = jax.vjp(plan._synth_fn(f"pallas_{var}", "packed"), alm)
    t = jax.random.normal(jax.random.PRNGKey(8), maps.shape, maps.dtype)
    (cf,), (cs,) = vjp_f(t), vjp_s(t)
    rel = float(jnp.max(jnp.abs(cf - cs)) / (jnp.max(jnp.abs(cs)) + 1e-30))
    assert rel < 1e-4, (shape, var, rel)
    _, vjpa_f = jax.vjp(plan._anal_fn(f"pallas_{var}", "fused"), maps)
    _, vjpa_s = jax.vjp(plan._anal_fn(f"pallas_{var}", "packed"), maps)
    g = _shape_alm(plan, key=jax.random.PRNGKey(9))
    (mf,), (ms,) = vjpa_f(g), vjpa_s(g)
    rel = float(jnp.max(jnp.abs(mf - ms)) / (jnp.max(jnp.abs(ms)) + 1e-30))
    assert rel < 1e-4, (shape, var, rel)


def test_fused_edge_fold_odd_rings_k1():
    """Odd ring count exercises the folded equator zero-pad; K=1 the
    minimal channel block."""
    plan = repro.make_plan("gl", l_max=16, K=1, dtype="float32",
                           mode="pallas_vpu", cache="memory", fold=True)
    assert plan.grid.n_rings % 2 == 1
    _assert_fused_matches_staged(plan)


def test_fused_edge_spin2_odd_lmax_k1():
    plan = repro.make_plan("gl", l_max=17, K=1, dtype="float32",
                           mode="pallas_vpu", cache="memory", spin=2)
    _assert_fused_matches_staged(plan)


def test_fused_edge_single_bucket_healpix():
    """nside=2 collapses every HEALPix ring into one FFT bucket -- the
    degenerate bin-map scatter."""
    plan = repro.make_plan("healpix", nside=2, K=1, dtype="float32",
                           mode="pallas_vpu", cache="memory")
    assert plan.phase.layout.n_buckets == 1
    _assert_fused_matches_staged(plan)


def test_fused_bucket_synth_is_one_kernel():
    """The bucket engine must also skip the Delta HBM round-trip."""
    plan = _shape_plan("bucket")
    alm = _shape_alm(plan)
    txt = str(jax.make_jaxpr(plan._synth_fn("pallas_vpu", "fused"))(alm))
    assert txt.count("pallas_call") == 1


# ---------------------------------------------------------------------------
# residual ineligible shapes + the $REPRO_LEGENDRE_LAYOUT override
# ---------------------------------------------------------------------------


def test_fusion_ineligible_fold_on_bucket():
    plan = repro.make_plan("healpix", nside=8, fold=True, dtype="float32",
                           mode="pallas_vpu", cache="memory")
    ok, reason = plan._fusion_eligibility()
    assert not ok and "fold" in reason
    assert "fused" not in plan._pallas_layouts()
    with pytest.raises(ValueError, match="fused layout unavailable"):
        plan._synth_fn("pallas_vpu", "fused")
    d = plan.describe()["fusion"]
    assert d["eligible"] is False
    assert d["skipped"] == reason


def test_fusion_ineligible_spin2_nyquist():
    from repro.core import grids
    g = grids.gauss_legendre_grid(LMAX, n_phi=2 * LMAX)
    plan = repro.make_plan(g, l_max=LMAX, K=1, dtype="float32", spin=2,
                           mode="pallas_vpu", cache="memory")
    ok, reason = plan._fusion_eligibility()
    assert not ok and "Nyquist" in reason
    assert "fused" not in plan._pallas_layouts()
    with pytest.raises(ValueError, match="fused layout unavailable"):
        plan._anal_fn("pallas_vpu", "fused")
    assert plan.describe()["fusion"]["skipped"] == reason


def test_layout_env_override_raises_on_ineligible(monkeypatch):
    plan = repro.make_plan("healpix", nside=8, fold=True, dtype="float32",
                           mode="pallas_vpu", cache="memory")
    monkeypatch.setenv("REPRO_LEGENDRE_LAYOUT", "fused")
    with pytest.raises(ValueError, match="ineligible"):
        plan._synth_fn("pallas_vpu", "packed")
    with pytest.raises(ValueError, match="equator fold"):
        plan._anal_fn("pallas_vpu", "packed")


def test_layout_env_override_routes_eligible_to_fused(monkeypatch):
    plan = _plan()
    monkeypatch.setenv("REPRO_LEGENDRE_LAYOUT", "fused")
    fn = plan._synth_fn("pallas_vpu", "packed")
    alm = sht.random_alm(KEY, LMAX, LMAX, K=K).astype(jnp.complex64)
    txt = str(jax.make_jaxpr(fn)(alm))
    assert txt.count("pallas_call") == 1    # rerouted onto the fused kernel


def test_ops_pick_layout_env_fused_rejected(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_LEGENDRE_LAYOUT", "fused")
    with pytest.raises(ValueError, match="plan level"):
        ops.pick_layout(np.arange(4))


def test_ops_pick_layout_traced_warns_once_then_degrades():
    import warnings as _warnings

    from repro.kernels import ops
    ops._TRACED_WARNED = False
    picked = []

    @jax.jit
    def probe(m):
        picked.append(ops.pick_layout(m))
        return m

    with pytest.warns(RuntimeWarning, match="plain rectangular"):
        probe(jnp.arange(4))
    assert picked == ["plain"]

    @jax.jit
    def probe2(m):
        picked.append(ops.pick_layout(m, layout="packed"))
        return m

    with _warnings.catch_warnings():        # one-time: no second warning
        _warnings.simplefilter("error")
        probe2(jnp.arange(5))
    assert picked[-1] == "plain"


# ---------------------------------------------------------------------------
# characterization DB
# ---------------------------------------------------------------------------


def test_chardb_measures_once_then_reuses(tmp_path):
    db = chardb.CharDB("cafe" * 4, "test-hw", str(tmp_path))
    calls = []

    def measure():
        calls.append(1)
        return 42.0

    us, status = db.get_or_measure(measure, l_max=8, backend="pallas_vpu")
    assert (us, status) == (42.0, "measured") and len(calls) == 1
    us, status = db.get_or_measure(measure, l_max=8, backend="pallas_vpu")
    assert (us, status) == (42.0, "reused") and len(calls) == 1
    # a fresh DB instance on the same directory reloads from disk
    db2 = chardb.CharDB("cafe" * 4, "test-hw", str(tmp_path))
    us, status = db2.get_or_measure(measure, l_max=8, backend="pallas_vpu")
    assert (us, status) == (42.0, "reused") and len(calls) == 1


def test_chardb_stale_schema_remeasured(tmp_path):
    db = chardb.CharDB("beef" * 4, "test-hw", str(tmp_path))
    key = db.corner_key(l_max=8, backend="jnp")
    db._store[key] = {"schema": chardb.SCHEMA - 1, "us": 1.0, "fields": {}}
    assert db.lookup(l_max=8, backend="jnp") is None
    us, status = db.get_or_measure(lambda: 7.0, l_max=8, backend="jnp")
    assert (us, status) == (7.0, "measured")
    assert db.counters["stale"] == 1
    assert db.lookup(l_max=8, backend="jnp")["us"] == 7.0


def test_chardb_fingerprint_isolation(tmp_path):
    """Corners measured on one hardware fingerprint must never leak into
    another DB sharing the same cache directory (the hardware-key
    collision regression)."""
    a = chardb.CharDB("a" * 16, "hw-a", str(tmp_path))
    b = chardb.CharDB("b" * 16, "hw-b", str(tmp_path))
    a.get_or_measure(lambda: 1.0, l_max=8, backend="jnp")
    assert a.path != b.path
    assert b.lookup(l_max=8, backend="jnp") is None
    us, status = b.get_or_measure(lambda: 2.0, l_max=8, backend="jnp")
    assert (us, status) == (2.0, "measured")
    # reload both from disk: each sees only its own corner value
    assert chardb.CharDB("a" * 16, "hw-a", str(tmp_path)).lookup(
        l_max=8, backend="jnp")["us"] == 1.0
    assert chardb.CharDB("b" * 16, "hw-b", str(tmp_path)).lookup(
        l_max=8, backend="jnp")["us"] == 2.0


def test_chardb_corner_key_order_invariant():
    k1 = chardb.CharDB.corner_key(l_max=8, backend="jnp", K=2)
    k2 = chardb.CharDB.corner_key(K=2, backend="jnp", l_max=8)
    k3 = chardb.CharDB.corner_key(K=3, backend="jnp", l_max=8)
    assert k1 == k2 and k1 != k3


def test_chardb_smoke_skips_missing_reuses_present(monkeypatch, tmp_path):
    db = chardb.CharDB("d00d" * 4, "test-hw", str(tmp_path))
    db.get_or_measure(lambda: 5.0, l_max=8, backend="jnp")
    monkeypatch.setenv("REPRO_CHARDB_SMOKE", "1")
    assert chardb.smoke_mode()
    us, status = db.get_or_measure(lambda: 9.0, l_max=8, backend="jnp")
    assert (us, status) == (5.0, "reused")        # present: reused
    us, status = db.get_or_measure(lambda: 9.0, l_max=99, backend="jnp")
    assert (us, status) == (None, "skipped")      # missing: never timed
    assert db.counters["skipped"] == 1


def test_chardb_exception_not_stored(tmp_path):
    db = chardb.CharDB("f00d" * 4, "test-hw", str(tmp_path))

    def boom():
        raise RuntimeError("transient")

    with pytest.raises(RuntimeError):
        db.get_or_measure(boom, l_max=8, backend="jnp")
    assert db.lookup(l_max=8, backend="jnp") is None    # retryable
    us, status = db.get_or_measure(lambda: 3.0, l_max=8, backend="jnp")
    assert (us, status) == (3.0, "measured")


def test_auto_plan_second_build_remeasures_zero_corners():
    """The acceptance property: after a first mode='auto' build
    characterizes its corners, clearing every plan/decision cache and
    rebuilding re-measures nothing -- all corners come from the chardb."""
    chardb.get_db().counters.update(
        {k: 0 for k in chardb.get_db().counters})
    repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                    cache="memory")
    first = dict(chardb.get_db().counters)
    assert first["measured"] > 0
    transform.clear_plan_cache()
    plancache.clear_memory()          # decision cache gone too
    chardb.reset_stats()
    plan = repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                           cache="memory")
    again = dict(chardb.get_db().counters)
    assert again["measured"] == 0, again
    assert again["reused"] >= first["measured"]
    assert plan.backends["synth"] in transform.BACKENDS
    ch = plan.describe()["cache"]["chardb"]
    assert ch["corners"] >= first["measured"]


def test_auto_plan_smoke_mode_model_fallback(monkeypatch):
    """REPRO_CHARDB_SMOKE on a cold signature: zero corners are timed and
    dispatch falls back to the cost-model ordering (decision not saved)."""
    monkeypatch.setenv("REPRO_CHARDB_SMOKE", "1")
    chardb.clear()
    plan = repro.make_plan("gl", l_max=10, K=1, dtype="float32",
                           mode="auto", cache="memory")
    st = chardb.stats()
    assert st["measured"] == 0 and st["skipped"] > 0
    assert plan.cache_events.get("decision") == "model-fallback"
    assert plan.backends["synth"] in transform.BACKENDS
    alm = sht.random_alm(KEY, 10, 10, K=1).astype(jnp.complex64)
    maps = plan.alm2map(alm)        # the fallback plan still transforms
    assert np.all(np.isfinite(np.asarray(maps)))


def test_fused_lp_candidates_schedule():
    from repro.kernels import pack as kpack
    assert kpack.fused_lp_candidates(24) == (128,)
    assert kpack.fused_lp_candidates(127) == (128,)
    assert kpack.fused_lp_candidates(128) == (128, 256)


def test_chardb_lp_corners_remeasured_zero(monkeypatch):
    """Block-shape (lp_size) autotune corners persist in the chardb: a
    second plan build after clearing every plan/decision cache re-measures
    zero corners, and picks the same panel length."""
    from repro.kernels import pack as kpack
    monkeypatch.setattr(kpack, "fused_lp_candidates",
                        lambda l_max: (128, 256))
    plan = repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                           cache="memory")
    lp1 = plan._fused_lp_size()
    assert lp1 in (128, 256)
    db = chardb.get_db()
    lp_sizes = {rec["fields"].get("lp_size")
                for rec in db._store.values()
                if rec["fields"].get("layout") == "fused"}
    assert {128, 256} <= lp_sizes        # both candidates characterized
    assert db.counters["measured"] > 0
    transform.clear_plan_cache()
    plancache.clear_memory()
    chardb.reset_stats()
    plan2 = repro.make_plan("gl", l_max=8, K=1, dtype="float32",
                            mode="auto", cache="memory")
    assert plan2._fused_lp_size() == lp1
    again = dict(chardb.get_db().counters)
    assert again["measured"] == 0, again
    assert plan2.describe()["fusion"]["lp_size"] == lp1


@pytest.mark.parametrize("mxu_us,pruned", [(100.0, True), (30.0, False)])
def test_auto_prunes_the_layouts_of_a_slow_backend(monkeypatch, mxu_us,
                                                   pruned):
    """mode='auto' times a pallas backend's packed/plain layouts only when
    its fused layout (timed first) is within PRUNE_FACTOR of the best so
    far; pruned layouts are listed, not timed, and are no error."""
    timed = []
    orig = {d: getattr(transform.Plan, f"_{d}_fn") for d in ("synth", "anal")}

    def spy(d):
        def fn_of(self, backend, layout=None):
            timed.append((d, backend, layout))
            return orig[d](self, backend, layout)
        return fn_of

    for d in ("synth", "anal"):
        monkeypatch.setattr(transform.Plan, f"_{d}_fn", spy(d))
    speed = {"jnp": 10.0, "pallas_vpu": 20.0, "pallas_mxu": mxu_us}
    monkeypatch.setattr(transform, "_time_call_us",
                        lambda fn, arg: speed[timed[-1][1]])
    plan = repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                           cache="memory")
    table = plan.describe()["measured_s"]
    assert not [k for row in table.values() for k in row
                if k.endswith("_error")]
    for d in ("synth", "anal"):
        mxu_layouts = [lay for dd, b, lay in timed
                       if dd == d and b == "pallas_mxu"]
        assert mxu_layouts[0] == "fused"
        assert (table["pallas_mxu"].get(f"{d}_pruned")
                == (["packed", "plain"] if pruned else None))
        assert len(mxu_layouts) == (1 if pruned else 3)
        assert plan.backends[d] == "jnp"
