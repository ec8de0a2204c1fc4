#!/usr/bin/env bash
# CI / local gate: install deps (when the network allows), run tier-1, then
# a CPU smoke benchmark of the plan-dispatch layer.  Exists so a missing
# test dependency (the hypothesis-at-collection breakage) or a broken
# dispatch path can't land silently.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== deps =="
if python -m pip install -q -e ".[test]" 2>/dev/null; then
    echo "installed repro-sht[test]"
else
    echo "pip unavailable/offline: using baked-in deps (tests degrade gracefully)"
fi

echo "== tier-1 =="
PYTHONPATH=src python -m pytest -x -q

echo "== smoke benchmark (plan dispatch, CPU) =="
PYTHONPATH=src REPRO_BENCH_SMOKE=1 python -m benchmarks.bench_dispatch

echo "== ragged-grid smoke (true-HEALPix plan roundtrip) =="
PYTHONPATH=src python - <<'PY'
import jax, numpy as np
import repro
from repro.core import sht, spectra
jax.config.update("jax_enable_x64", True)   # float64 oracle plans
plan = repro.make_plan("healpix", nside=8, dtype="float64", mode="auto")
alm = sht.random_alm(seed=0, l_max=plan.l_max, m_max=plan.m_max)
err = float(spectra.d_err(alm, plan.map2alm(plan.alm2map(alm), iters=1)))
assert err < 0.05, f"healpix roundtrip regressed: d_err={err}"
assert plan.describe()["phase"]["kind"] == "bucket"
print(f"healpix nside=8 roundtrip d_err={err:.2e} backends={plan.backends}")
PY

echo "== spin-2 smoke (Q/U roundtrips through make_plan(..., spin=2)) =="
PYTHONPATH=src python - <<'PY'
import jax, numpy as np
import repro
from repro.core import sht, spectra
jax.config.update("jax_enable_x64", True)   # float64 oracle plans
# exact grid: machine precision; pure-E must synthesise with zero B leakage
plan = repro.make_plan("gl", l_max=32, dtype="float64", mode="auto", spin=2)
alm = sht.random_alm_spin(seed=0, l_max=32, m_max=32)
err = float(spectra.d_err(alm, plan.map2alm(plan.alm2map(alm))))
assert err < 1e-12, f"gl spin-2 roundtrip regressed: d_err={err}"
alm_e = alm.at[1].set(0.0)
back = plan.map2alm(plan.alm2map(alm_e))
leak = float(np.max(np.abs(np.asarray(back[1]))))
assert leak < 1e-12, f"E->B leakage: {leak}"
print(f"gl spin-2 roundtrip d_err={err:.2e}  E->B leakage={leak:.2e}")
# ragged HEALPix spin-2 (quadrature accuracy + Jacobi refinement)
plan = repro.make_plan("healpix", nside=8, dtype="float64", mode="auto",
                       spin=2)
alm = sht.random_alm_spin(seed=1, l_max=plan.l_max, m_max=plan.m_max)
err = float(spectra.d_err(alm, plan.map2alm(plan.alm2map(alm), iters=1)))
assert err < 0.05, f"healpix spin-2 roundtrip regressed: d_err={err}"
print(f"healpix nside=8 spin-2 roundtrip d_err={err:.2e} "
      f"backends={plan.backends}")
PY

echo "== differentiable-transform smoke (grad example, one optimizer step) =="
PYTHONPATH=src python examples/grad_cl_estimate.py --lmax 8 --steps 1 --mode jnp

echo "== serving smoke (K-coalesced engine, mixed-signature traffic) =="
# the example asserts every coalesced result matches an independent Plan
# call to <1e-12, so a serving-layer regression fails here loudly; the
# second run turns on roofline admission control (p99-target-capped K)
PYTHONPATH=src python examples/serve_sht.py --smoke
PYTHONPATH=src python examples/serve_sht.py --smoke --p99-target-ms 50

echo "== chardb smoke (characterize once, second build re-measures zero) =="
# two host devices: auto times its candidates (jnp, dist) only when it has
# more than one
XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src python - <<'PY'
# the persistent autotune characterization DB: a cold auto plan measures
# its corners exactly once; after every plan/decision cache is cleared a
# rebuild must reuse them all (one-rep, tiny size)
import repro
from repro.core import cache as plancache, transform
from repro.roofline import chardb
chardb.clear()
repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                cache="memory")
first = chardb.stats()
assert first["measured"] > 0, first
transform.clear_plan_cache()
plancache.clear_memory()
chardb.reset_stats()
repro.make_plan("gl", l_max=8, K=1, dtype="float32", mode="auto",
                cache="memory")
again = chardb.stats()
assert again["measured"] == 0, f"chardb re-measured corners: {again}"
assert again["reused"] >= first["measured"], (first, again)
print(f"chardb OK: {first['measured']} corners characterized once, "
      f"{again['reused']} reused on rebuild")
PY

echo "== spin benchmark (one-rep smoke) =="
# standalone (also part of benchmarks.run below) so a spin-bench
# regression fails the gate loudly -- run.py swallows per-module errors
PYTHONPATH=src REPRO_BENCH_SMOKE=1 python -m benchmarks.bench_spin

echo "== full benchmark set (one-rep smoke) + JSON trajectory validation =="
BENCH_OUT="$(mktemp -t bench_check_XXXX.json)"
PYTHONPATH=src python -m benchmarks.run --smoke -o "$BENCH_OUT"
# the perf trajectory (BENCH_<date>.json) is only trustworthy if run.py
# keeps emitting valid numeric rows -- fail loudly if it stops
PYTHONPATH=src BENCH_OUT="$BENCH_OUT" python - <<'PY'
import json, math, os
path = os.environ["BENCH_OUT"]
d = json.load(open(path))
rows = d.get("us_per_call", {})
assert len(rows) >= 10, f"too few benchmark rows ({len(rows)}) in {path}"
bad = {k: v for k, v in rows.items()
       if not isinstance(v, (int, float)) or not math.isfinite(v)}
assert not bad, f"non-numeric benchmark rows: {bad}"
assert not d.get("errors"), f"benchmark modules errored: {d['errors']}"
# chunked-exchange overlap (PR 8): the measured dist speedup rows must
# land and never lose to the monolithic baseline (C=1 is always in the
# candidate set, so < 1.0 means the bench or the pipeline broke)
ov = {k: v for k, v in rows.items() if k.startswith("dist/overlap_speedup/")}
assert "dist/overlap_speedup/synth" in ov, "dist overlap speedup row missing"
assert all(isinstance(v, (int, float)) and math.isfinite(v)
           for v in ov.values()), f"non-numeric overlap rows: {ov}"
assert ov["dist/overlap_speedup/synth"] >= 1.0, \
    f"chunked exchange lost to monolithic: {ov}"
# modelled overlap rows: present, numeric, and the comm-bound TPU corner
# must hide more than half of the hideable time
model_ov = {k: v for k, v in rows.items()
            if k.startswith("scaling-model/overlap/")}
assert model_ov, "scaling-model overlap rows missing"
assert all(isinstance(v, (int, float)) and math.isfinite(v)
           for v in model_ov.values()), f"non-numeric model rows: {model_ov}"
hidden = rows.get("scaling-model/overlap/hidden/tpu-v5e/nside4096/p1024")
assert hidden is not None, "tpu-v5e nside4096/p1024 hidden-frac row missing"
assert hidden > 0.5, f"modelled hidden-comm fraction regressed: {hidden}"
# serving trajectory: throughput + tail-latency rows must keep landing
for prefix in ("serve/throughput/", "serve/p99/"):
    hits = [k for k in rows if k.startswith(prefix)]
    assert hits, f"serving benchmark row missing (prefix {prefix})"
serve_err = next(v for k, v in d.get("derived", {}).items()
                 if k.startswith("serve/derr/"))
assert float(serve_err) < 1e-12, \
    f"serving coalescing diverged from independent plans: {serve_err}"
# serving frontier (PR 10): single-threaded vs double-buffered walls over
# the 10:1 hot:minority mix.  Staging overlaps compute only where the
# host has cores the compute doesn't own, so the smoke gate is a
# no-regression bound (a single-core CI box caps the honest ceiling at
# ~1.0x and smoke-size batches are dispatch-bound, GIL-held; the cpu
# count rides in the row's derived string; full runs measure ~1.0x on
# 1 cpu).  The fairness ratio bounds how much the 10:1 hot tenant may
# inflate the minority tenant's worst-case latency: WDRR costs the
# minority at most ~one hot batch per own batch (~2-3x solo at smoke
# sizes where the batches cost the same); the old oldest-head-wins
# policy put the whole hot backlog in front of it (~7x here), which is
# what the bound rejects.
for prefix in ("serve/frontier/single/", "serve/frontier/double/",
               "serve/frontier/p99/"):
    assert any(k.startswith(prefix) for k in rows), \
        f"serving frontier row missing (prefix {prefix})"
sp = rows.get("serve/frontier/speedup")
assert sp is not None and math.isfinite(sp), "frontier speedup row missing"
assert sp >= 0.7, \
    f"double-buffered serving regressed vs single-threaded pump: {sp}"
fair = rows.get("serve/frontier/fair_p99_ratio")
assert fair is not None and math.isfinite(fair), \
    "frontier fairness row missing"
assert 0.0 < fair < 4.0, \
    f"minority tenant starved under the 10:1 hot mix: {fair}"
for key in ("git_rev", "jax_version", "generated_utc"):
    assert d.get(key), f"missing {key} in {path}"
print(f"bench JSON OK: {len(rows)} rows, "
      f"overlap_speedup={ov['dist/overlap_speedup/synth']:.2f}, "
      f"hidden_frac(tpu-v5e,4096/1024)={hidden:.2f}, "
      f"serve_frontier={sp:.2f}x fair={fair:.2f}")
PY
rm -f "$BENCH_OUT"

echo "check.sh: OK"
