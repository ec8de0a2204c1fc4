"""Plan-dispatch benchmark: the paper's dichotomy table, via make_plan.

For each problem size, builds an autotuned plan and prints the
``describe()`` numbers: the chosen backend per direction, the cost-model
prediction vs the measurement that decided it, and the warm-vs-cold
``make_plan`` cost (the precompute-cache win).

Columns: name, us_per_call, derived.

``REPRO_BENCH_SMOKE=1`` shrinks the sweep to one small size (CI smoke).
"""

import math
import os
import time

import jax
import jax.numpy as jnp

import repro
from repro.core import sht, spectra
from benchmarks.common import emit


def _sizes():
    if os.environ.get("REPRO_BENCH_SMOKE"):
        return [(32, 2)]
    return [(64, 1), (128, 4), (128, 16)]


def main():
    for l_max, K in _sizes():
        t0 = time.perf_counter()
        plan = repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                               mode="auto")
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan2 = repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                                mode="auto")
        t_warm = time.perf_counter() - t0
        assert plan2 is plan, "plan memoisation regressed"

        d = plan.describe()
        for direction in ("synth", "anal"):
            chosen = d["backends"][direction]
            meas = d["measured_s"].get(chosen, {}).get(direction, float("nan"))
            pred = d["predicted_s"].get(chosen, {}).get(direction, float("nan"))
            if math.isfinite(meas):
                emit(f"dispatch/{direction}/lmax{l_max}-K{K}", meas * 1e6,
                     f"{chosen} (predicted {pred * 1e6:.1f}us)")
            else:
                # one device: auto takes the only candidate untimed (and
                # chardb smoke mode skips corners missing from the DB);
                # keep the trajectory numeric with the model's value
                emit(f"dispatch/{direction}/lmax{l_max}-K{K}", pred * 1e6,
                     f"{chosen} (model, unmeasured)")
        emit(f"dispatch/make_plan-cold/lmax{l_max}-K{K}", t_cold * 1e6,
             f"warm x{t_cold / max(t_warm, 1e-9):.0f} faster")

        # correctness spot-check through the dispatched path
        alm = sht.random_alm(jax.random.PRNGKey(0), l_max, plan.m_max,
                             K=K).astype(jnp.complex64)
        err = spectra.d_err(alm, plan.map2alm(plan.alm2map(alm)))
        emit(f"dispatch/roundtrip-derr/lmax{l_max}-K{K}", 0.0, f"{err:.2e}")


if __name__ == "__main__":
    main()
