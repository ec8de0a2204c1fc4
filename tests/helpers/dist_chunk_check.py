"""Subprocess helper: chunked pipelined exchange == monolithic all_to_all.

Runs on 4 simulated host devices.  For spin 0 and spin 2, C in {2, 4}
must reproduce the C=1 (monolithic) output bit-identically in f64 for
synthesis and to < 1e-12 for analysis, covering both the K-axis schedule
(K >= C) and the m-axis fallback (K < C).  Also gradchecks jax.grad
through the chunked pipeline against the monolithic gradient, and
verifies the fail-fast ValueError in `_exchange`.

Prints OK lines; exits nonzero on mismatch.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import numpy as np, jax, jax.numpy as jnp
import repro  # noqa

jax.config.update("jax_enable_x64", True)   # float64 reference engine
from repro.core import grids, sht, plan as planlib, dist_sht

key = jax.random.PRNGKey(11)
lmax = 24
g = grids.make_grid("gl", l_max=lmax)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = planlib.SHTPlan(g, lmax, lmax, 4)
ok = True


def engines(chunk_list, **kw):
    return {c: dist_sht.DistSHT(p, mesh, ("data", "model"), dtype="float64",
                                comm_chunks=c, **kw) for c in chunk_list}


def rel(a, b):
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300)


def check_spin0(K):
    global ok
    alm = sht.random_alm(jax.random.PRNGKey(K), lmax, lmax, K=K)
    packed = jnp.asarray(p.pack_alm(np.asarray(alm)))
    maps0 = None
    d = engines([1, 2, 4])
    maps = {c: np.asarray(d[c].alm2map(packed)) for c in d}
    maps0 = jnp.asarray(maps[1])
    alms = {c: np.asarray(d[c].map2alm(maps0)) for c in d}
    for c in (2, 4):
        axis, bounds = d[c].plan.chunk_schedule(K, chunks=c)
        bit = bool(np.array_equal(maps[c], maps[1]))
        ea = rel(alms[c], alms[1])
        good = bit and ea < 1e-12
        print(f"spin0 K={K} C={c} [{axis}]: synth bit-identical={bit} "
              f"anal={ea:.2e} {'OK' if good else 'FAIL'}")
        ok &= good


def check_spin2(K):
    global ok
    alm_eb = sht.random_alm_spin(jax.random.PRNGKey(40 + K), lmax, lmax, K=K)
    packed = jnp.stack([jnp.asarray(p.pack_alm(np.asarray(alm_eb[i])))
                        for i in range(2)])
    d = engines([1, 2, 4])
    maps = {c: np.asarray(d[c].alm2map_spin(packed)) for c in d}
    maps0 = jnp.asarray(maps[1])
    alms = {c: np.asarray(d[c].map2alm_spin(maps0)) for c in d}
    for c in (2, 4):
        axis, bounds = d[c].plan.chunk_schedule(K, ncomp=2, chunks=c)
        bit = bool(np.array_equal(maps[c], maps[1]))
        ea = rel(alms[c], alms[1])
        good = bit and ea < 1e-12
        print(f"spin2 K={K} C={c} [{axis}]: synth bit-identical={bit} "
              f"anal={ea:.2e} {'OK' if good else 'FAIL'}")
        ok &= good


check_spin0(K=4)   # K-axis schedule for C=2 and C=4
check_spin0(K=1)   # m-axis fallback for both
check_spin2(K=4)   # K-axis schedule
check_spin2(K=1)   # m-axis fallback

# -- gradient through the chunked pipeline must match the monolithic one
#    (the chunked exchange is the same linear op, so the transposes agree)
rng = np.random.default_rng(13)
alm = sht.random_alm(jax.random.PRNGKey(2), lmax, lmax, K=4)
packed = jnp.asarray(p.pack_alm(np.asarray(alm)))
t = jnp.asarray(rng.normal(size=(p.r_pad, g.max_n_phi, 4)), jnp.float64)
d = engines([1, 2])


def loss(eng, a):
    return jnp.sum(eng.alm2map(a) * t)


g1 = jax.grad(lambda a: loss(d[1], a))(packed)
g2 = jax.grad(lambda a: loss(d[2], a))(packed)
eg = rel(np.asarray(g2), np.asarray(g1))
eps = 1e-6
v = jnp.asarray(rng.normal(size=packed.shape)
                + 1j * rng.normal(size=packed.shape)).astype(packed.dtype)
fd = float((loss(d[2], packed + eps * v) - loss(d[2], packed - eps * v))
           / (2 * eps))
dd = float(jnp.real(jnp.sum(g2 * v)))
efd = abs(fd - dd) / max(abs(fd), 1e-9)
g_ok = eg < 1e-12 and efd < 1e-7
print(f"grad C=2 vs C=1: graddiff={eg:.2e} fd={efd:.2e} "
      f"{'OK' if g_ok else 'FAIL'}")
ok &= g_ok

# -- fail-fast: a slot count that the device count does not divide must
#    raise a ValueError naming the mesh before reaching lax.all_to_all
d1 = dist_sht.DistSHT(p, mesh, ("data", "model"))
try:
    d1._exchange(jnp.zeros((9, 4, 2)), to_rings=False)
    print("fail-fast: no error raised FAIL")
    ok = False
except ValueError as e:
    msg_ok = "mesh" in str(e) and "axis 0" in str(e)
    print(f"fail-fast: ValueError raised, names mesh/axis={msg_ok} "
          f"{'OK' if msg_ok else 'FAIL'}")
    ok &= msg_ok

sys.exit(0 if ok else 1)
