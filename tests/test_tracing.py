"""The program's profiler names (`repro.tracing`): stage scopes in the
compiled programs of every CPU-eligible backend, the serving engine's
per-batch spans and timing, and the compile counter."""

from __future__ import annotations

import functools
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

#: (backend, layout, fold): every backend the CPU runs, each layout
PATHS = [("jnp", None, False), ("jnp", None, True),
         ("pallas_vpu", "plain", False), ("pallas_vpu", "packed", False),
         ("pallas_vpu", "plain", True), ("pallas_vpu", "fused", False),
         ("pallas_mxu", "plain", False), ("pallas_mxu", "packed", False),
         ("pallas_mxu", "fused", True)]


def _texts(fn, arg) -> tuple:
    """(lowered StableHLO with locations, compiled HLO) of a plan
    function (``jax.jit`` or a ``transform._bind`` partial)."""
    jitted, kw = ((fn.func, fn.keywords)
                  if isinstance(fn, functools.partial) else (fn, {}))
    lowered = jitted.lower(arg, **kw)
    return lowered.as_text(debug_info=True), lowered.compile().as_text()


def _op_names(compiled: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled))


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize("backend,layout,fold", PATHS)
def test_stage_scopes_in_every_backend(backend, layout, fold, direction):
    plan = repro.make_plan("gl", l_max=L, K=2, dtype="float32", mode="jnp",
                           fold=fold, cache="off")
    if direction == "synth":
        fn = plan._synth_fn(backend, layout)
        arg = np.asarray(sht.random_alm(seed=0, l_max=L, m_max=L, K=2),
                         np.complex64)
    else:
        fn = plan._anal_fn(backend, layout)
        arg = np.zeros(plan._maps_shape, np.float32)
    lowered, compiled = _texts(fn, arg)
    names = _op_names(compiled)
    want = [tracing.PHASE, tracing.LEGENDRE]
    if fold:
        want.append(tracing.FOLD)
    if backend == "jnp":
        want += [tracing.RECURRENCE, tracing.ACCUMULATE]
    for s in want:
        assert any(s in n.split("/") for n in names), (s, sorted(names))
        assert s in lowered


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
