"""Subprocess helper: ``make_plan(mode="dist")`` against the serial float64
transform on 4 host devices, both directions, through the plan's own
dense-in, dense-out calls.  ``case`` is ``float32``, ``float64`` or
``blocks`` (float64 with each shard's stage-1 rows split into row blocks
by a small row budget).  Prints OK lines; exits nonzero on a mismatch or
on any RuntimeWarning (the stage-1 path must not degrade)."""
import os
import sys
import warnings

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)   # float64 reference engine
import repro  # noqa: E402
from repro import tracing  # noqa: E402
from repro.core import grids, legendre, sht  # noqa: E402

warnings.simplefilter("error", RuntimeWarning)
case = sys.argv[1]
lmax, K = (40, 2) if case == "blocks" else (24, 3)
dtype = "float32" if case == "float32" else "float64"
tol = 2e-5 if dtype == "float32" else 1e-12
if case == "blocks":
    legendre.BLOCK_STEP_BYTES = 1        # blocks of 8 rows

g = grids.make_grid("gl", l_max=lmax)
t = sht.SHT(g, l_max=lmax, m_max=lmax)
alm = sht.random_alm(jax.random.PRNGKey(1), lmax, lmax, K=K)
maps_ref = np.asarray(t.alm2map(alm))
alm_ref = np.asarray(t.map2alm(jnp.asarray(maps_ref)))
cdt = jnp.complex64 if dtype == "float32" else jnp.complex128

p = repro.make_plan("gl", l_max=lmax, K=K, dtype=dtype, mode="dist",
                    cache="off")
ok = True


def report(name, good, **fields):
    global ok
    ok &= bool(good)
    print(f"{case}/{name}: " + " ".join(f"{k}={v}" for k, v in fields.items())
          + (" OK" if good else " FAIL"), flush=True)


d = p.describe()["dist"]
blocks = {k: v["blocks"] for k, v in d["row_blocks"].items()}
want = 2 if case == "blocks" else 1
report("layout", d["stage1"] == "jnp" and d["shards"] == 4
       and set(blocks.values()) == {want}
       and p.comm_chunks == {"synth": 1, "anal": 1},
       stage1=d["stage1"], blocks=blocks, chunks=p.comm_chunks)
maps = np.asarray(p.alm2map(alm.astype(cdt)))
err_s = np.max(np.abs(maps - maps_ref)) / np.max(np.abs(maps_ref))
report("synth", err_s < tol, err=f"{err_s:.2e}")
back = np.asarray(p.map2alm(jnp.asarray(maps_ref, dtype)))
err_a = np.max(np.abs(back - alm_ref)) / np.max(np.abs(alm_ref))
report("anal", err_a < tol, err=f"{err_a:.2e}")
hlo = jax.jit(p._anal_fn("dist")).lower(
    jax.ShapeDtypeStruct(maps_ref.shape, jnp.dtype(dtype))).as_text(
        debug_info=True)
report("scopes", all(s in hlo for s in (tracing.EXCHANGE, tracing.RESHARD,
                                        tracing.LEGENDRE, tracing.PHASE)))
sys.exit(0 if ok else 1)
