"""Chunked-exchange overlap: measured speedup of the pipelined all_to_all.

Runs in a SUBPROCESS with 8 host devices (this process stays 1-device).
For each direction, the C=1 monolithic exchange and the chunked C=2/C=4
pipelines are timed in ONE group-interleaved loop (`common.time_multi`),
so ``speedup = t[C=1] / min(t)`` is drift-free and >= 1.0 by construction
(the monolithic baseline is in the candidate set -- "best chunking never
loses").  On the host-CPU simulated mesh the collective is a memcpy, so
the measured hiding is modest; the modelled hiding at cluster scale rides
in ``scaling-model/overlap/*`` (bench_scaling_model).

Columns: name, us_per_call (speedup ratio for the ``overlap_speedup``
rows), derived = chosen C and raw per-C times.
"""

from benchmarks.common import run_helper

_HELPER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import repro
from repro.core import sht
from benchmarks.common import enable_float64_oracle, time_multi

enable_float64_oracle()
SMOKE = bool(os.environ.get("REPRO_BENCH_SMOKE"))
LMAX = 64 if SMOKE else 256
K = 4
REPS = 1 if SMOKE else 5
CHUNKS = (1, 2, 4)

plans = {c: repro.make_plan("gl", l_max=LMAX, K=K, dtype="float64",
                            mode="dist", n_shards=8, comm_chunks=c)
         for c in CHUNKS}
alm = sht.random_alm(jax.random.PRNGKey(0), LMAX, LMAX, K=K)
maps = jax.block_until_ready(plans[1].alm2map(alm))

for direction, make in (("synth", lambda p: (lambda: p.alm2map(alm))),
                        ("anal", lambda p: (lambda: p.map2alm(maps)))):
    ts = time_multi({c: make(p) for c, p in plans.items()}, iters=REPS)
    for c, t in ts.items():
        print(f"CSV dist/overlap/{direction}/C{c},{t*1e6:.1f},"
              f"8dev-lmax{LMAX}-K{K}")
    best = min(ts, key=ts.get)
    speedup = ts[1] / ts[best]
    print(f"CSV dist/overlap_speedup/{direction},{speedup:.4f},"
          f"best C={best} t1={ts[1]*1e6:.1f}us tbest={ts[best]*1e6:.1f}us")
'''


def main():
    r = run_helper(_HELPER)
    if r.returncode != 0:
        print(f"dist/overlap/error,0.0,"
              f"{r.stderr.splitlines()[-1] if r.stderr else 'unknown'}")


if __name__ == "__main__":
    main()
