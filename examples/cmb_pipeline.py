"""End-to-end CMB T/Q/U pipeline (the paper's target application, S2HAT's
actual workload: spin-weighted polarised transforms):

  TT/EE/BB/TE spectra -> correlated Gaussian (T, E, B) a_lm realisations
  (a Monte-Carlo batch) -> T synthesis (spin 0) + E/B -> Q/U synthesis
  (spin 2) -> add white noise -> analysis back (spin 0 + spin 2) ->
  pseudo-C_l estimation (TT, EE, BB, TE) against the inputs.

Both plans dispatch through ``repro.make_plan`` -- the spin-2 plan runs the
same backend menu (jnp | dist) as the scalar one.
Set XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise the
distributed two-stage transforms on CPU.

    PYTHONPATH=src python examples/cmb_pipeline.py --lmax 96 --K 8
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import spectra


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lmax", type=int, default=96)
    ap.add_argument("--K", type=int, default=8, help="Monte-Carlo batch")
    ap.add_argument("--noise", type=float, default=1e-5)
    a = ap.parse_args()

    key = jax.random.PRNGKey(1)
    cls = spectra.cmb_like_cl_pol(a.lmax)
    alm_teb = spectra.alm_from_cl_pol(key, cls, K=a.K)     # (3, M, L, K)

    plan_t = repro.make_plan("gl", l_max=a.lmax, K=a.K, mode="auto")
    plan_p = repro.make_plan("gl", l_max=a.lmax, K=a.K, mode="auto", spin=2)
    print(f"T   transforms on {plan_t.grid.name} ({plan_t.grid.n_rings} "
          f"rings), backends={plan_t.backends}")
    print(f"Q/U transforms (spin 2), backends={plan_p.backends}")

    t_map = plan_t.alm2map(alm_teb[0])                     # (R, nphi, K)
    qu_maps = plan_p.alm2map(alm_teb[1:])                  # (2, R, nphi, K)

    kn1, kn2 = jax.random.split(key)
    t_map = t_map + a.noise * jax.random.normal(kn1, t_map.shape)
    qu_maps = qu_maps + a.noise * jax.random.normal(kn2, qu_maps.shape)

    alm_t = plan_t.map2alm(t_map)
    alm_eb = plan_p.map2alm(qu_maps)

    est = {
        "tt": np.asarray(spectra.cl_from_alm(alm_t)).mean(-1),
        "ee": np.asarray(spectra.cl_from_alm(alm_eb[0])).mean(-1),
        "bb": np.asarray(spectra.cl_from_alm(alm_eb[1])).mean(-1),
        "te": np.asarray(spectra.cl_cross_from_alm(alm_t,
                                                   alm_eb[0])).mean(-1),
    }

    l = np.arange(2, a.lmax + 1)
    cosmic = np.sqrt(2.0 / (2 * l + 1) / a.K)              # cosmic variance
    print(f"map rms: T={float(jnp.std(t_map)):.3e} "
          f"QU={float(jnp.std(qu_maps)):.3e}  noise rms: {a.noise:.1e}")
    ok = True
    for name in ("tt", "ee", "bb", "te"):
        truth = cls[name][2:]
        # TE crosses zero: normalise by the spectrum's scale, not pointwise
        denom = np.abs(truth) if name != "te" \
            else np.sqrt(cls["tt"][2:] * cls["ee"][2:])
        good = denom > 0
        rel = np.abs(est[name][2:][good] - truth[good]) / denom[good]
        med, bound = np.median(rel), 5 * np.median(cosmic)
        this_ok = med < bound + a.noise * 100
        ok &= this_ok
        print(f"pseudo-C_l {name.upper()}: median rel. err={med:.3f} "
              f"(bound ~{bound:.3f}) {'ok' if this_ok else 'FAIL'}")
    print("PASS" if ok else "FAIL: spectrum recovery outside expectations")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
