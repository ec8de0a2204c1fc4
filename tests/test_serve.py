"""The SHT serving engine: K-coalescing correctness, signature grouping,
FIFO fairness, futures, percentile math, and the warm plan pool."""

import threading
import time

import numpy as np
import pytest

import repro
from repro.core import cache as plancache
from repro.core import sht, spectra, transform
from repro.serve import (BackpressureError, InvalidStateError, PlanPool,
                         PlanSig, ShtEngine, ShtFuture, ShtRequest,
                         percentile)

from _hypothesis_compat import given, settings, strategies as st

LMAX = 16


@pytest.fixture(autouse=True)
def _fresh_caches():
    transform.clear_plan_cache()
    plancache.reset_stats()
    yield
    transform.clear_plan_cache()
    plancache.reset_stats()


def _alm(seed, l_max=LMAX, K=None, spin=0):
    fn = sht.random_alm_spin if spin else sht.random_alm
    a = np.asarray(fn(seed=seed, l_max=l_max, m_max=l_max, K=K or 1))
    return a if K else a[..., 0]


def _engine(**kw):
    kw.setdefault("max_k", 4)
    kw.setdefault("mode", "jnp")
    return ShtEngine(**kw)


# -- coalescing correctness ---------------------------------------------------


def test_coalesced_batch_matches_independent_plan_calls():
    """A K-stacked batch of mixed requests returns results identical to
    per-request Plan calls (synthesis bitwise on the f64 jnp path;
    analysis to 1e-12 -- the contraction order over K may differ)."""
    eng = _engine(max_k=4)
    plan = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float64",
                           mode="jnp")
    alms = [_alm(seed=i) for i in range(3)]
    maps = [np.asarray(plan.alm2map(a[..., None]))[..., 0] for a in alms]

    futs_s = [eng.submit(direction="alm2map", payload=a, grid="gl",
                         l_max=LMAX) for a in alms]
    futs_a = [eng.submit(direction="map2alm", payload=m, grid="gl",
                         l_max=LMAX) for m in maps]
    eng.drain()

    for f, ref in zip(futs_s, maps):
        np.testing.assert_array_equal(f.result(), ref)     # bit-identical
    for f, a in zip(futs_a, alms):
        ref = np.asarray(plan.map2alm(
            np.asarray(plan.alm2map(a[..., None]))))[..., 0]
        assert np.max(np.abs(f.result() - ref)) < 1e-12
    # the synthesis requests actually shared one device batch
    synth_batches = [b for b in eng.batch_log
                     if b["direction"] == "alm2map"]
    assert len(synth_batches) == 1
    assert synth_batches[0]["n_requests"] == 3


def test_coalesced_multi_k_and_spin2_requests():
    """Requests carrying their own K axis, and spin-2 (E,B)->(Q,U) pairs,
    coalesce and come back allclose to independent plans (f64 <= 1e-12)."""
    eng = _engine(max_k=8)
    a2 = _alm(seed=0, K=2)                       # (M, L, 2)
    a1 = _alm(seed=1)                            # (M, L)
    s2 = _alm(seed=2, spin=2)                    # (2, M, L)
    f2 = eng.submit(direction="alm2map", payload=a2, grid="gl", l_max=LMAX)
    f1 = eng.submit(direction="alm2map", payload=a1, grid="gl", l_max=LMAX)
    fs = eng.submit(direction="alm2map", payload=s2, grid="gl", l_max=LMAX,
                    spin=2)
    eng.drain()

    p2 = repro.make_plan("gl", l_max=LMAX, K=2, dtype="float64", mode="jnp")
    p1 = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float64", mode="jnp")
    ps = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float64", mode="jnp",
                         spin=2)
    assert np.max(np.abs(f2.result() - np.asarray(p2.alm2map(a2)))) < 1e-12
    assert np.max(np.abs(f1.result()
                         - np.asarray(p1.alm2map(a1[..., None]))[..., 0])) \
        < 1e-12
    assert np.max(np.abs(fs.result()
                         - np.asarray(ps.alm2map(s2[..., None]))[..., 0])) \
        < 1e-12
    # scalar requests coalesced (K=2 + K=1 -> one batch); spin-2 separate
    scalar = [b for b in eng.batch_log if "spin0" in b["signature"]]
    assert len(scalar) == 1 and scalar[0]["k_total"] == 3
    assert scalar[0]["k_plan"] == 4              # padded to the K bucket


def test_no_cross_signature_mixing():
    """Different (grid, l_max, spin, dtype) signatures never share a
    device batch, even when submitted interleaved."""
    eng = _engine(max_k=8)
    for i in range(3):
        eng.submit(direction="alm2map", payload=_alm(seed=i), grid="gl",
                   l_max=LMAX)
        eng.submit(direction="alm2map", payload=_alm(seed=10 + i, l_max=24),
                   grid="gl", l_max=24)
    eng.drain()
    assert len(eng.batch_log) == 2
    for b in eng.batch_log:
        assert b["n_requests"] == 3              # each group fully coalesced
    assert {b["signature"] for b in eng.batch_log} == \
        {"gl/lmax16/spin0/float64", "gl/lmax24/spin0/float64"}


def test_direction_and_iters_split_groups():
    """alm2map vs map2alm, and differing Jacobi iters, are separate
    groups -- they cannot share one device call."""
    eng = _engine(max_k=8)
    plan = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float64",
                           mode="jnp")
    m = np.asarray(plan.alm2map(_alm(seed=0)[..., None]))[..., 0]
    eng.submit(direction="alm2map", payload=_alm(seed=1), grid="gl",
               l_max=LMAX)
    eng.submit(direction="map2alm", payload=m, grid="gl", l_max=LMAX)
    eng.submit(direction="map2alm", payload=m, grid="gl", l_max=LMAX,
               iters=1)
    eng.drain()
    assert len(eng.batch_log) == 3


def test_fifo_within_signature():
    """Requests of one signature retire in submission order, across
    however many micro-batches the max_k budget forces."""
    eng = _engine(max_k=2)
    futs = [eng.submit(direction="alm2map", payload=_alm(seed=i), grid="gl",
                       l_max=LMAX) for i in range(5)]
    eng.drain()
    rids = [rid for b in eng.batch_log for rid in b["rids"]]
    assert rids == [f.rid for f in futs]         # strict FIFO
    assert [b["n_requests"] for b in eng.batch_log] == [2, 2, 1]


def test_oldest_request_picks_next_group():
    """Across signatures the batch former serves the group whose head
    waited longest (no starvation of a low-traffic signature)."""
    eng = _engine(max_k=8)
    f_old = eng.submit(direction="alm2map", payload=_alm(seed=0, l_max=24),
                       grid="gl", l_max=24)
    for i in range(3):
        eng.submit(direction="alm2map", payload=_alm(seed=1 + i), grid="gl",
                   l_max=LMAX)
    assert eng.step() > 0
    assert f_old.done()                          # oldest head went first


# -- futures ------------------------------------------------------------------


def test_futures_resolve_exactly_once():
    eng = _engine()
    fut = eng.submit(direction="alm2map", payload=_alm(seed=0), grid="gl",
                     l_max=LMAX)
    eng.drain()
    assert fut.done()
    r1 = fut.result()
    assert r1 is fut.result()                    # cached, not recomputed
    with pytest.raises(InvalidStateError):
        fut._resolve(None)
    with pytest.raises(InvalidStateError):
        fut._fail(RuntimeError("x"))
    f = ShtFuture(rid=99)
    f._resolve(1)
    with pytest.raises(InvalidStateError):
        f._resolve(2)


def test_future_timing_populated():
    eng = _engine()
    fut = eng.submit(direction="alm2map", payload=_alm(seed=0), grid="gl",
                     l_max=LMAX)
    eng.drain()
    t = fut.timing
    assert t["total_s"] >= t["compute_s"] >= 0
    assert t["queue_s"] >= 0
    assert t["k_plan"] == 1 and t["coalesced_with"] == 0


def test_submit_validation_is_eager():
    eng = _engine()
    with pytest.raises(ValueError):              # bad direction
        eng.submit(direction="sideways", payload=_alm(seed=0))
    with pytest.raises(ValueError):              # real payload for alm2map
        eng.submit(direction="alm2map", payload=np.zeros((17, 17)))
    with pytest.raises(ValueError):              # complex maps payload
        eng.submit(direction="map2alm",
                   payload=np.zeros((17, 34), complex))
    with pytest.raises(ValueError):              # ndim mismatch for spin
        eng.submit(direction="alm2map", payload=_alm(seed=0), spin=2)
    with pytest.raises(ValueError):              # K wider than the engine
        eng.submit(direction="alm2map", payload=_alm(seed=0, K=9),
                   grid="gl", l_max=LMAX)
    assert eng.pending == 0                      # nothing leaked into queue


# -- stats() ------------------------------------------------------------------


def test_percentile_pinned_against_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 10, 101):
        xs = rng.exponential(size=n).tolist()
        for q in (0.0, 25.0, 50.0, 90.0, 95.0, 99.0, 100.0):
            np.testing.assert_allclose(percentile(xs, q),
                                       np.percentile(xs, q), rtol=1e-12)
    assert np.isnan(percentile([], 50.0))


def test_stats_shape_and_counters():
    eng = _engine(max_k=4)
    for i in range(4):
        eng.submit(direction="alm2map", payload=_alm(seed=i), grid="gl",
                   l_max=LMAX)
    eng.drain()
    s = eng.stats()
    assert s["requests"]["submitted"] == 4
    assert s["requests"]["completed"] == 4
    assert s["requests"]["pending"] == 0
    assert s["coalescing"]["batches"] == 1
    assert s["coalescing"]["k_per_batch"] == 4.0
    assert s["coalescing"]["k_occupancy"] == 1.0
    lat = s["latency"]["total"]
    assert lat["count"] == 4
    assert lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"] <= lat["max_s"]
    assert np.isfinite(s["throughput_rps"]) and s["throughput_rps"] > 0
    r = eng.report()
    assert "p99" in r and "coalescing" in r and "pool" in r


def test_stats_percentiles_match_numpy_over_recorded_latencies():
    eng = _engine(max_k=1)                       # one batch per request
    for i in range(5):
        eng.submit(direction="alm2map", payload=_alm(seed=i), grid="gl",
                   l_max=LMAX)
    eng.drain()
    xs = eng._lat_total.samples()
    assert len(xs) == 5
    s = eng.stats()["latency"]["total"]
    np.testing.assert_allclose(s["p50_s"], np.percentile(xs, 50))
    np.testing.assert_allclose(s["p95_s"], np.percentile(xs, 95))
    np.testing.assert_allclose(s["p99_s"], np.percentile(xs, 99))


# -- warm plan pool -----------------------------------------------------------


def test_pool_hits_and_warmup():
    eng = _engine(max_k=2)
    eng.prewarm(grid="gl", l_max=LMAX, dtype="float64")
    assert eng.pool.stats()["warmups"] == 1
    for i in range(4):
        eng.submit(direction="alm2map", payload=_alm(seed=i), grid="gl",
                   l_max=LMAX)
    eng.drain()
    p = eng.pool.stats()
    # prewarm built the (sig, max_k=2) plan; both batches then hit it
    assert p["misses"] == 1 and p["hits"] == 2
    assert eng.stats()["pool"]["hit_rate"] == pytest.approx(2 / 3)
    assert p["size"] == 1 and p["evictions"] == 0


def test_pool_lru_eviction_releases_plans():
    pool = PlanPool(capacity=2, mode="jnp")
    sigs = [PlanSig(grid="gl", l_max=8 * (i + 1), dtype="float64")
            for i in range(3)]
    plans = [pool.get(s, 1) for s in sigs]
    assert pool.stats()["evictions"] == 1
    assert len(pool) == 2
    # the evicted plan is also gone from make_plan's memoisation...
    key0 = plans[0]._signature_key
    assert key0 not in transform._PLANS
    # ...while the survivors are still memoised
    assert plans[2]._signature_key in transform._PLANS
    # re-requesting the evicted signature rebuilds (a miss, not a hit)
    misses = pool.stats()["misses"]
    pool.get(sigs[0], 1)
    assert pool.stats()["misses"] == misses + 1


def test_background_thread_serves():
    eng = _engine(max_k=4)
    with eng:
        futs = [eng.submit(direction="alm2map", payload=_alm(seed=i),
                           grid="gl", l_max=LMAX) for i in range(3)]
        res = [f.result(timeout=120) for f in futs]
    plan = repro.make_plan("gl", l_max=LMAX, K=1, dtype="float64",
                           mode="jnp")
    for a, r in zip([_alm(seed=i) for i in range(3)], res):
        ref = np.asarray(plan.alm2map(a[..., None]))[..., 0]
        assert np.max(np.abs(r - ref)) < 1e-12


# -- property: random interleavings never drop/duplicate/cross-wire ----------


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_sigs=st.integers(2, 4),
       max_k=st.integers(1, 6))
def test_random_interleavings_roundtrip(seed, n_sigs, max_k):
    """Random submit interleavings across 2-4 signatures with request K in
    1..max_k: every future resolves exactly once with *its own* payload's
    transform (seeded random_alm per request; any cross-wiring, drop or
    duplication shows up as a wrong result or an unresolved future)."""
    rng = np.random.default_rng(seed)
    transform.clear_plan_cache()
    eng = _engine(max_k=max_k, max_queue=256)
    lmaxes = [8, 12, 16, 20][:n_sigs]
    plans = {L: repro.make_plan("gl", l_max=L, K=1, dtype="float64",
                                mode="jnp") for L in lmaxes}
    jobs = []
    for rid in range(12):
        L = int(rng.choice(lmaxes))
        # the engine clamps max_k to a power of two; submits above the
        # effective cap are rejected, so draw against eng.max_k
        k = int(rng.integers(1, eng.max_k + 1))
        alm = np.asarray(sht.random_alm(seed=1000 + rid, l_max=L, m_max=L,
                                        K=k))
        if rng.integers(2) == 0:
            fut = eng.submit(direction="alm2map", payload=alm, grid="gl",
                             l_max=L)
            jobs.append(("alm2map", L, alm, fut))
        else:
            maps = np.asarray(plans[L].alm2map(alm[..., :1]))
            fut = eng.submit(direction="map2alm", payload=maps[..., 0],
                             grid="gl", l_max=L)
            jobs.append(("map2alm", L, alm[..., :1], fut))
        if rng.integers(3) == 0:                 # interleave partial drains
            eng.step()
    eng.drain()
    for direction, L, alm, fut in jobs:
        assert fut.done(), "request dropped"
        got = fut.result()
        if direction == "alm2map":
            ref = np.asarray(repro.make_plan(
                "gl", l_max=L, K=alm.shape[-1], dtype="float64",
                mode="jnp").alm2map(alm))
            assert np.max(np.abs(got - ref)) < 1e-12
        else:
            # recovery: analysing the synthesised map returns the payload
            err = spectra.d_err(alm[..., 0], got)
            assert err < 1e-10, err
    s = eng.stats()["requests"]
    assert s["completed"] == len(jobs) and s["pending"] == 0


# -- request object API -------------------------------------------------------


def test_submit_request_object_and_tag():
    eng = _engine()
    req = ShtRequest(direction="alm2map", payload=_alm(seed=0), grid="gl",
                     l_max=LMAX, tag="mc-chain-7")
    fut = eng.submit(req)
    with pytest.raises(TypeError):               # object XOR keywords
        eng.submit(req, grid="gl")
    eng.drain()
    assert fut.done() and req.tag == "mc-chain-7"


# -- phase 2: K buckets, in-flight accounting, double buffering ---------------


class _StallPlan:
    """Proxy around a real plan whose synthesis blocks until released --
    makes the 'popped but not retired' in-flight window observable."""

    def __init__(self, plan, started, release):
        self._plan = plan
        self._started = started
        self._release = release

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def alm2map(self, x):
        self._started.set()
        assert self._release.wait(30.0), "test forgot to release the batch"
        return self._plan.alm2map(x)


def _stall_pool(eng):
    """Wrap eng.pool.get so every served plan stalls in alm2map; returns
    the (started, release) events."""
    started, release = threading.Event(), threading.Event()
    real_get = eng.pool.get
    eng.pool.get = lambda sig, k: _StallPlan(real_get(sig, k), started,
                                             release)
    return started, release


def test_max_k_clamped_to_power_of_two_and_bucket_invariants():
    """K buckets are power-of-two by contract.  Historically max_k=6 with
    a 5-wide batch produced bucket 6 (min(8, 6)) -- a shape no pooled plan
    key space expects.  Now the engine clamps max_k itself to a power of
    two and every bucket is an admissible plan width."""
    eng = _engine(max_k=6)
    assert eng.max_k == 4 and eng.requested_max_k == 6
    for req_max in (1, 2, 3, 4, 5, 6, 7, 8, 12, 16):
        e = _engine(max_k=req_max)
        assert e.max_k & (e.max_k - 1) == 0          # power of two
        assert e.max_k <= req_max < 2 * e.max_k      # largest such
        for k in range(1, e.max_k + 1):
            b = e._k_bucket(k)
            assert b & (b - 1) == 0, (req_max, k, b)
            assert k <= b <= e.max_k
    # a request wider than the *effective* cap is rejected eagerly
    with pytest.raises(ValueError, match="max_k"):
        eng.submit(direction="alm2map", payload=_alm(seed=0, K=5),
                   grid="gl", l_max=LMAX)


def test_drain_waits_for_in_flight_batch():
    """Regression: drain() used to watch only the *queued* count, so with
    the background threads running it could return while a popped
    micro-batch was still executing -- leaving the caller holding an
    unresolved future after a 'complete' drain."""
    eng = _engine(max_k=2)
    started, release = _stall_pool(eng)
    with eng:
        fut = eng.submit(direction="alm2map", payload=_alm(seed=0),
                         grid="gl", l_max=LMAX)
        assert started.wait(30.0)                # popped, mid-execution
        assert eng.pending == 1                  # in-flight, not queued
        t = threading.Timer(0.05, release.set)
        t.start()
        eng.drain(timeout=30.0)
        assert fut.done(), "drain() returned with the batch in flight"
        t.join()
    assert fut.exception() is None
    assert fut.timing["compute_s"] > 0.0


def test_backpressure_counts_in_flight():
    """max_queue bounds engine *occupancy*: a request executing on the
    background threads still holds its slot, so submit() past the bound
    raises BackpressureError even though the queue proper is empty."""
    eng = _engine(max_k=1, max_queue=1)
    started, release = _stall_pool(eng)
    with eng:
        fut = eng.submit(direction="alm2map", payload=_alm(seed=0),
                         grid="gl", l_max=LMAX)
        assert started.wait(30.0)
        s = eng.stats()["requests"]
        assert s["queued"] == 0 and s["in_flight"] == 1 and s["pending"] == 1
        with pytest.raises(BackpressureError):
            eng.submit(direction="alm2map", payload=_alm(seed=1),
                       grid="gl", l_max=LMAX)
        release.set()
        eng.drain(timeout=30.0)
    assert fut.done() and fut.exception() is None
    late = eng.submit(direction="alm2map", payload=_alm(seed=2), grid="gl",
                      l_max=LMAX)                # slot freed by retirement
    eng.drain()
    assert late.exception() is None


# -- phase 2: WDRR fairness ---------------------------------------------------


def test_wdrr_minority_group_not_starved():
    """10+:1 hot:minority mix.  Oldest-head-wins served the hot group's
    whole backlog first; WDRR visits groups round-robin, so the minority
    signature's batch ships within the first scheduling rounds."""
    eng = _engine(max_k=2)
    hot = [eng.submit(direction="alm2map", payload=_alm(seed=i, l_max=8),
                      grid="gl", l_max=8) for i in range(12)]
    mino = eng.submit(direction="alm2map", payload=_alm(seed=99, l_max=12),
                      grid="gl", l_max=12)
    eng.drain()
    assert mino.exception() is None
    assert all(f.exception() is None for f in hot)
    mino_batches = [i for i, b in enumerate(eng.batch_log)
                    if "lmax12" in b["signature"]]
    assert mino_batches and mino_batches[0] <= 2, eng.batch_log


def test_wdrr_weight_throttles_group():
    """A weight-1/4 group earns a quarter of the K-unit deficit per round
    and must wait out extra rounds between its batches -- so the unit-
    weight group finishes well before the throttled hot group."""
    hot_label = "gl/lmax8/spin0/float64"
    eng = _engine(max_k=2, weights={hot_label: 0.25})
    assert eng.describe()["fairness"]["weights"][hot_label] == 0.25
    hot = [eng.submit(direction="alm2map", payload=_alm(seed=i, l_max=8),
                      grid="gl", l_max=8) for i in range(4)]
    mino = [eng.submit(direction="alm2map", payload=_alm(seed=50 + i,
                                                         l_max=12),
                       grid="gl", l_max=12) for i in range(4)]
    eng.drain()
    assert all(f.exception() is None for f in hot + mino)
    log = eng.batch_log
    last_mino = max(i for i, b in enumerate(log)
                    if "lmax12" in b["signature"])
    hot_before = sum(b["n_requests"] for b in log[:last_mino]
                     if "lmax8" in b["signature"])
    # by the time the minority stream finishes, the throttled hot group
    # has shipped at most half its backlog
    assert hot_before <= 2, log
    assert eng.stats()["fairness"]["policy"] == "wdrr"


# -- phase 2: roofline admission control --------------------------------------


def test_admission_tiny_target_caps_coalescing_at_k1():
    """An unachievable p99 target (1 ns) caps every batch at K=1 and
    flags the group infeasible -- service degrades to singles, never to
    refusal."""
    eng = _engine(max_k=4, p99_target_s=1e-9)
    futs = [eng.submit(direction="alm2map", payload=_alm(seed=i),
                       grid="gl", l_max=LMAX) for i in range(4)]
    eng.drain()
    assert all(f.exception() is None for f in futs)
    assert [b["k_plan"] for b in eng.batch_log] == [1, 1, 1, 1]
    adm = eng.stats()["admission"]
    assert adm["p99_target_s"] == 1e-9
    (group,) = adm["groups"].values()
    assert group["k_cap"] == 1 and group["feasible"] is False


def test_admission_generous_target_keeps_full_bucket_and_calibrates():
    """A 60 s p99 target admits the full max_k bucket, and every executed
    batch feeds the predicted-vs-measured calibration tracker."""
    eng = _engine(max_k=4, p99_target_s=60.0)
    futs = [eng.submit(direction="alm2map", payload=_alm(seed=i),
                       grid="gl", l_max=LMAX) for i in range(4)]
    eng.drain()
    assert all(f.exception() is None for f in futs)
    assert len(eng.batch_log) == 1 and eng.batch_log[0]["k_plan"] == 4
    adm = eng.stats()["admission"]
    (group,) = adm["groups"].values()
    assert group["k_cap"] == 4 and group["feasible"] is True
    cal = adm["calibration"]
    assert cal["count"] == 1
    assert np.isfinite(cal["ratio"]) and cal["ratio"] > 0.0
    assert "admission" in eng.report()


@pytest.mark.parametrize("device_kind", ["cpu", "TPU v5 lite"])
def test_admission_prices_the_jnp_backend(device_kind, monkeypatch):
    """Admission prices a batch as the backend the engine's one-device
    plans run, jnp, on every host, at that host's peaks."""
    from repro.roofline import admission, analysis
    if device_kind != "cpu":
        monkeypatch.setattr(admission, "hardware_for",
                            lambda: analysis.PEAKS[device_kind])
    backend, hw = admission.default_model()
    assert backend == "jnp"
    assert hw is (analysis.HW_HOST if device_kind == "cpu"
                  else analysis.HW_V5E)
    caps = admission.k_caps_for_target(l_max=LMAX, n_rings=LMAX + 1,
                                       n_phi=2 * LMAX + 2, max_k=4,
                                       p99_target_s=60.0)
    assert caps["backend"] == "jnp" and caps["k_cap"] == 4


def test_engine_describe():
    eng = _engine(max_k=6, p99_target_s=0.5,
                  weights={"gl/lmax16/spin0/float64": 0.5})
    d = eng.describe()
    assert d["max_k"] == 4 and d["requested_max_k"] == 6
    assert d["states"] == ("queued", "in_flight", "retired")
    assert d["fairness"]["policy"] == "wdrr" and d["fairness"]["quantum_k"]
    assert d["admission"]["p99_target_s"] == 0.5
    assert d["pipeline"]["double_buffered"] is False
    assert d["pool"]["capacity"] == eng.pool.capacity
    with eng:
        d2 = eng.describe()
        assert d2["pipeline"]["double_buffered"] is True
        assert len(d2["pipeline"]["threads"]) == 2
    # admission verdicts appear per group after first sighting
    eng.submit(direction="alm2map", payload=_alm(seed=0), grid="gl",
               l_max=LMAX)
    eng.drain()
    (group,) = eng.describe()["admission"]["groups"].values()
    assert set(group) >= {"k_cap", "feasible", "predicted_s"}


def test_pool_concurrent_get_builds_once():
    """Racing get() calls for one key build the plan exactly once (the
    build happens outside the pool lock behind a per-key event)."""
    pool = PlanPool(4, mode="jnp")
    out, errs = [], []

    def worker():
        try:
            out.append(pool.get(PlanSig(grid="gl", l_max=8), 2))
        except Exception as e:                    # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(out) == 4 and len({id(p) for p in out}) == 1
    assert pool.misses == 1


# -- phase 2: threaded clients, exactly-once resolution -----------------------


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_threaded_submissions_resolve_exactly_once(seed):
    """Several client threads submit mixed signatures against the live
    double-buffered engine; every future resolves exactly once with its
    own request's transform, and the in-flight accounting lands at zero."""
    transform.clear_plan_cache()
    lmaxes = [8, 12]
    refs = {L: repro.make_plan("gl", l_max=L, K=1, dtype="float64",
                               mode="jnp") for L in lmaxes}
    eng = _engine(max_k=4, max_queue=256)
    jobs, jlock = [], threading.Lock()

    def client(tid):
        rng = np.random.default_rng(seed * 17 + tid)
        for i in range(6):
            L = int(rng.choice(lmaxes))
            alm = np.asarray(sht.random_alm(
                seed=seed % 1000 + tid * 100 + i, l_max=L, m_max=L,
                K=1))[..., 0]
            fut = eng.submit(direction="alm2map", payload=alm, grid="gl",
                             l_max=L)
            with jlock:
                jobs.append((L, alm, fut))
            if rng.integers(2):
                time.sleep(0.001)

    with eng:
        clients = [threading.Thread(target=client, args=(t,))
                   for t in range(3)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        eng.drain(timeout=120.0)

    assert len(jobs) == 18
    for L, alm, fut in jobs:
        assert fut.done(), "request dropped"
        ref = np.asarray(refs[L].alm2map(alm[..., None]))[..., 0]
        np.testing.assert_array_equal(fut.result(), ref)
    s = eng.stats()["requests"]
    assert s["completed"] == 18 and s["pending"] == 0
    assert s["queued"] == 0 and s["in_flight"] == 0
    with pytest.raises(InvalidStateError):       # write-once enforced
        jobs[0][2]._resolve(None)
