"""Paper Figs. 12-13: runtime breakdown of the distributed transforms into
recurrence / communication / FFT stages, under MPI-style sharding.

Runs in a SUBPROCESS with 8 host devices (this process stays 1-device).
The transforms are reached through ``repro.make_plan(..., mode="dist")``;
each stage is then timed by jitting it in isolation with the same
shardings.  All stages of one breakdown are timed in ONE group-interleaved
loop (`common.time_multi`) so the stage fractions are not distorted by
host drift between runs.  Includes a true-HEALPix (ragged) breakdown: its
FFT stage is the bucket engine with bucket-aware ring sharding.
Columns: name, us_per_call, derived = stage.
"""

import os
import subprocess
import sys

from benchmarks.common import emit

_HELPER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.core import sht
from benchmarks.common import enable_float64_oracle, time_multi
from jax.sharding import PartitionSpec as P

enable_float64_oracle()
SMOKE = bool(os.environ.get("REPRO_BENCH_SMOKE"))
K = 2
REPS = 1 if SMOKE else 3

def breakdown(tag, plan):
    d = plan._dist_engine()
    p = d.plan
    alm = sht.random_alm(jax.random.PRNGKey(0), plan.l_max, plan.m_max, K=K)
    maps = jax.block_until_ready(plan.alm2map(alm))

    packed = jnp.asarray(p.pack_alm(np.asarray(alm)))
    synth, anal, c = d._build(K)
    a_re, a_im = jnp.real(packed), jnp.imag(packed)
    spec = P(d.axis_names)

    stage1 = jax.jit(jax.shard_map(lambda ar, ai, m: jnp.concatenate(
        d._stage1_synth(ar, ai, m), -1), mesh=d.mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False))
    delta = stage1(a_re, a_im, c["m_flat"])

    exch = jax.jit(jax.shard_map(lambda x: d._exchange(x, to_rings=True),
        mesh=d.mesh, in_specs=(spec,), out_specs=spec, check_vma=False))
    exch_out = exch(delta)

    nops = len(c["synth_ops"])
    fft = jax.jit(jax.shard_map(lambda x, ph, vl, *ops: d._synth_fft(
        x[..., :K], x[..., K:], ph, vl, ops), mesh=d.mesh,
        in_specs=(spec,) * (3 + nops), out_specs=spec, check_vma=False))

    ts = time_multi({
        "full_s": lambda: plan.alm2map(alm),
        "recurrence": lambda: stage1(a_re, a_im, c["m_flat"]),
        "all_to_all": lambda: exch(delta),
        "fft": lambda: fft(exch_out, c["phi0"], c["valid"], *c["synth_ops"]),
        "full_a": lambda: plan.map2alm(maps),
    }, iters=REPS)

    kind = plan.phase.describe()["kind"]
    print(f"CSV breakdown/{tag}/alm2map/full,{ts['full_s']*1e6:.1f},"
          f"8dev-lmax{plan.l_max}")
    print(f"CSV breakdown/{tag}/alm2map/recurrence,"
          f"{ts['recurrence']*1e6:.1f},stage1")
    print(f"CSV breakdown/{tag}/alm2map/all_to_all,"
          f"{ts['all_to_all']*1e6:.1f},comm")
    print(f"CSV breakdown/{tag}/alm2map/fft,{ts['fft']*1e6:.1f},"
          f"{kind}-phase")
    print(f"CSV breakdown/{tag}/map2alm/full,{ts['full_a']*1e6:.1f},"
          f"8dev-lmax{plan.l_max}")

lmax = 64 if SMOKE else 256
breakdown("gl", repro.make_plan("gl", l_max=lmax, K=K, dtype="float64",
                                mode="dist", n_shards=8))
nside = 8 if SMOKE else 32
breakdown("healpix", repro.make_plan("healpix", nside=nside, K=K,
                                     dtype="float64", mode="dist",
                                     n_shards=8))
'''


def run_helper(helper: str, timeout: int = 560):
    """Run a multi-device benchmark helper in a subprocess and re-emit its
    ``CSV name,us,derived`` lines through `common.emit` so they land in
    the BENCH_<date>.json trajectory.

    The helper simulates 8 host devices in a child process, which is
    only possible on the CPU backend: on a TPU this process holds the
    chip, and a child that needs it fails or hangs -- refused."""
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "multi-device benchmark helpers simulate host devices in a "
            "child process; run them with JAX_PLATFORMS=cpu, not on "
            f"the {jax.default_backend()} backend this process holds")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # src for repro, the repo root for benchmarks.common (time_multi)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    r = subprocess.run([sys.executable, "-c", helper], capture_output=True,
                       text=True, timeout=timeout, env=env)
    for line in r.stdout.splitlines():
        if line.startswith("CSV "):
            name, us, derived = line[4:].split(",", 2)
            emit(name, float(us), derived)
    return r


def main():
    r = run_helper(_HELPER)
    if r.returncode != 0:
        print(f"breakdown/error,0.0,{r.stderr.splitlines()[-1] if r.stderr else 'unknown'}")


if __name__ == "__main__":
    main()
