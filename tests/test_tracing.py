"""The program's profiler names (`repro.tracing`): stage scopes in the
compiled programs of the jnp loops, the serving engine's per-batch spans
and timing, and the compile counter."""

from __future__ import annotations

import glob
import os
import re

import jax
import numpy as np
import pytest

import repro
from repro import compile_cache, tracing
from repro.core import sht
from repro.serve import ShtEngine

L = 8

#: (backend, fold) of the one-device backend
PATHS = [("jnp", False), ("jnp", True)]


def _texts(fn, arg) -> tuple:
    """(lowered StableHLO with locations, compiled HLO) of a jitted plan
    function."""
    lowered = fn.lower(arg)
    return lowered.as_text(debug_info=True), lowered.compile().as_text()


def _op_names(compiled: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled))


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize("backend,fold", PATHS)
def test_stage_scopes_in_every_backend(backend, fold, direction):
    plan = repro.make_plan("gl", l_max=L, K=2, dtype="float32", mode=backend,
                           fold=fold, cache="off")
    _assert_scopes(plan, direction, fold)


def _assert_scopes(plan, direction, fold):
    """Every stage scope (the fold's when folded) and the loop's
    sub-scopes are in the plan direction's lowered and compiled text."""
    if direction == "synth":
        fn = plan._synth_fn("jnp")
        f = sht.random_alm_spin if plan.spin else sht.random_alm
        arg = np.asarray(f(seed=0, l_max=plan.l_max, m_max=plan.m_max,
                           K=plan.K), np.complex64)
    else:
        fn = plan._anal_fn("jnp")
        arg = np.zeros(plan._maps_shape, np.float32)
    lowered, compiled = _texts(fn, arg)
    names = _op_names(compiled)
    want = [tracing.PHASE, tracing.LEGENDRE, tracing.RECURRENCE,
            tracing.ACCUMULATE]
    if fold:
        want.append(tracing.FOLD)
    for s in want:
        assert any(s in n.split("/") for n in names), (s, sorted(names))
        assert s in lowered


#: (grid, spin, fold, row blocks) of the jnp loops beyond PATHS: blocked
#: loops (C > 1), spin 2 (one loop over every row, no fold), the ragged
#: bucket phase stage
LOOPS = [("gl", 0, False, 2), ("gl", 0, True, 2), ("gl", 2, False, 1)] + \
    [("healpix", s, f, c) for s, f, c in ((0, False, 1), (0, False, 2),
                                          (0, True, 1), (0, True, 2),
                                          (2, False, 1))]


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize(
    "grid,spin,fold,blocks", LOOPS,
    ids=[f"{g}-spin{s}-{'fold' if f else 'nofold'}-C{c}"
         for g, s, f, c in LOOPS])
def test_stage_scopes_in_jnp_loops(grid, spin, fold, blocks, direction,
                                   monkeypatch):
    from repro.core import legendre
    if blocks > 1:               # 8-row blocks: 2 of them for 9 rows
        monkeypatch.setattr(legendre, "BLOCK_STEP_BYTES", 1)
    kw = dict(l_max=L) if grid == "gl" else dict(nside=4, l_max=L)
    plan = repro.make_plan(grid, K=2, dtype="float32", mode="jnp",
                           fold=fold, spin=spin, cache="off", **kw)
    got = plan.describe()["legendre"]["jnp_blocks"][direction]["blocks"]
    assert got == blocks
    _assert_scopes(plan, direction, fold)


def _spans(trace_dir: str) -> list:
    """(name, batch) of every program span in the trace under
    ``trace_dir``, whatever line it was on."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in tracing.ENGINE_SPANS:
                    out.append((ev.name, dict(ev.stats).get("batch")))
    return out


def test_engine_spans_carry_batch_numbers(tmp_path):
    maps = [np.asarray(np.random.default_rng(i).standard_normal(
        (L + 1, 2 * L + 2)), np.float32) for i in range(5)]
    eng = ShtEngine(max_k=4, mode="jnp", cache="off")
    eng.pool.warm(repro.serve.PlanSig(grid="gl", l_max=L, dtype="float32"),
                  1)
    with jax.profiler.trace(str(tmp_path)):
        with eng:
            for m in maps:
                eng.submit(direction="map2alm", payload=m, grid="gl",
                           l_max=L, dtype="float32").result(timeout=120)
    spans = _spans(str(tmp_path))
    batches = {b["batch"] for b in eng.batch_log}
    assert len(batches) == len(eng.batch_log) >= 1
    assert (tracing.ENGINE_IDLE, None) in spans
    per_batch = {n for n in tracing.ENGINE_SPANS
                 if n != tracing.ENGINE_IDLE}
    for b in batches:
        assert {n for n, x in spans if x == b} == per_batch, b
        assert spans.count((tracing.ENGINE_EXECUTE, b)) == 1
    assert {x for _, x in spans if x is not None} == batches


def test_future_timing_splits_the_batch():
    eng = ShtEngine(max_k=2, mode="jnp", cache="off")
    alm = np.asarray(sht.random_alm(seed=1, l_max=L, m_max=L))[..., 0]
    futs = [eng.submit(direction="alm2map", payload=alm, grid="gl",
                       l_max=L) for _ in range(3)]
    eng.drain()
    for f in futs:
        t = f.timing
        assert {"upload_s", "download_s", "batch", "compute_s",
                "queue_s", "total_s"} <= set(t)
        assert t["upload_s"] >= 0 and t["download_s"] >= 0
        assert t["form_s"] >= t["upload_s"]
        assert t["total_s"] >= t["queue_s"] + t["compute_s"] - 1e-9
    assert sorted({f.timing["batch"] for f in futs}) == \
        sorted(b["batch"] for b in eng.batch_log)


@pytest.fixture
def enabled(monkeypatch, tmp_path):
    """`compile_cache.enable()` with the cache directory in ``tmp_path``
    (JAX read no directory at import, so none is used), JAX's settings
    restored afterwards."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_traceback_in_locations_limit")
    was = {k: getattr(jax.config, k) for k in keys}
    compile_cache.enable()
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_counter_counts_a_new_shape_once(enabled):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.arange(7, dtype=np.float32)
    before = compile_cache.stats()
    jax.block_until_ready(f(x))
    once = compile_cache.stats()
    jax.block_until_ready(f(x))
    assert compile_cache.stats() == once
    assert once["compiles"] == before["compiles"] + 1
    assert once["compile_s"] > before["compile_s"]
    assert once["loads"] == before["loads"]


def _lowered_scoped():
    def f(x):
        with jax.named_scope(tracing.PHASE):
            return jax.numpy.sin(x) * 2.0
    return jax.jit(f).lower(np.zeros(3, np.float32)).as_text(
        debug_info=True)


def test_cache_key_holds_scopes_not_callers(enabled):
    """What the persistent cache's key hashes once `enable()` ran: the
    program with its op metadata, scopes included, and the op's own
    source line, but not the frames of whoever traced it first."""
    def deeper():
        return _lowered_scoped()
    text = _lowered_scoped()
    assert tracing.PHASE in text
    assert deeper() == text
