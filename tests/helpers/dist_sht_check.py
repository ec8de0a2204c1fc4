"""Subprocess helper: distributed SHT == serial engine on 8 host devices.
Prints OK lines; exits nonzero on mismatch."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import numpy as np, jax, jax.numpy as jnp
import repro  # noqa

jax.config.update("jax_enable_x64", True)   # float64 reference engine
from repro.core import grids, sht, plan as planlib, dist_sht

key = jax.random.PRNGKey(3)
lmax = 40
g = grids.make_grid("gl", l_max=lmax)
t = sht.SHT(g, l_max=lmax, m_max=lmax)
alm = sht.random_alm(key, lmax, lmax, K=2)
maps_ref = np.asarray(t.alm2map(alm))
alm_ref = np.asarray(t.map2alm(jnp.asarray(maps_ref)))
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
p = planlib.SHTPlan(g, lmax, lmax, 8)

def check(name, fold, comm_dtype, stage1, dtype, tol_s, tol_a):
    d = dist_sht.DistSHT(p, mesh, ("data", "model"), dtype=dtype, fold=fold,
                         comm_dtype=comm_dtype, stage1=stage1)
    packed = np.asarray(p.pack_alm(np.asarray(alm)))
    if dtype == "float32":
        packed = packed.astype(np.complex64)
    maps_plan = d.alm2map(jnp.asarray(packed))
    maps_grid = np.asarray(p.scatter_map(np.asarray(maps_plan)))
    err_s = np.max(np.abs(maps_grid - maps_ref)) / np.max(np.abs(maps_ref))
    mp = p.gather_map(jnp.asarray(maps_ref).astype(d.dtype))
    alm_out = np.asarray(p.unpack_alm(np.asarray(d.map2alm(mp))))
    err_a = np.max(np.abs(alm_out - alm_ref)) / np.max(np.abs(alm_ref))
    ok = err_s < tol_s and err_a < tol_a
    print(f"{name}: synth={err_s:.2e} anal={err_a:.2e} {'OK' if ok else 'FAIL'}")
    return ok

ok = True
ok &= check("f64", False, None, "jnp", "float64", 1e-12, 1e-12)
ok &= check("f64+fold", True, None, "jnp", "float64", 1e-12, 1e-12)
ok &= check("f64+bf16comm", False, "bfloat16", "jnp", "float64", 2e-2, 2e-2)
ok &= check("f32+pallas", False, None, "pallas", "float32", 5e-4, 5e-4)
ok &= check("f32+pallas+fold", True, None, "pallas", "float32", 5e-4, 5e-4)

# ragged true-HEALPix: bucket-aware ring sharding + bucket phase stage
gh = grids.make_grid("healpix", nside=8)
lmax_h = 16
th = sht.SHT(gh, l_max=lmax_h, m_max=lmax_h)
alm_h = sht.random_alm(jax.random.PRNGKey(4), lmax_h, lmax_h, K=2)
maps_h = np.asarray(th.alm2map(alm_h))
alm_h_ref = np.asarray(th.map2alm(jnp.asarray(maps_h)))
ph = planlib.SHTPlan(gh, lmax_h, lmax_h, 8)
dh = dist_sht.DistSHT(ph, mesh, ("data", "model"))
mg = np.asarray(ph.scatter_map(np.asarray(
    dh.alm2map(jnp.asarray(ph.pack_alm(np.asarray(alm_h)))))))
err_s = np.max(np.abs(mg - maps_h)) / np.max(np.abs(maps_h))
ah = np.asarray(ph.unpack_alm(np.asarray(
    dh.map2alm(ph.gather_map(jnp.asarray(maps_h))))))
err_a = np.max(np.abs(ah - alm_h_ref)) / np.max(np.abs(alm_h_ref))
hp_ok = err_s < 1e-12 and err_a < 1e-12
print(f"f64+healpix-ragged: synth={err_s:.2e} anal={err_a:.2e} "
      f"{'OK' if hp_ok else 'FAIL'}")
ok &= hp_ok

# -- spin-2 (E/B <-> Q/U): the component pair rides the trailing channel
#    axis through the same two-stage path (one all_to_all, 4K channels)
alm_eb = sht.random_alm_spin(jax.random.PRNGKey(5), lmax, lmax, K=2)
maps_qu_ref = np.asarray(t.alm2map_spin(alm_eb))
alm_eb_ref = np.asarray(t.map2alm_spin(jnp.asarray(maps_qu_ref)))


def check_spin(name, stage1, dtype, tol_s, tol_a):
    d = dist_sht.DistSHT(p, mesh, ("data", "model"), dtype=dtype,
                         stage1=stage1)
    packed = np.stack([np.asarray(p.pack_alm(np.asarray(alm_eb[i])))
                       for i in range(2)])
    if dtype == "float32":
        packed = packed.astype(np.complex64)
    mp2 = np.asarray(d.alm2map_spin(jnp.asarray(packed)))
    mg = np.stack([np.asarray(p.scatter_map(mp2[i])) for i in range(2)])
    err_s = np.max(np.abs(mg - maps_qu_ref)) / np.max(np.abs(maps_qu_ref))
    gm = jnp.stack([jnp.asarray(p.gather_map(
        jnp.asarray(maps_qu_ref[i]).astype(d.dtype))) for i in range(2)])
    alm_out = np.asarray(d.map2alm_spin(gm))
    au = np.stack([np.asarray(p.unpack_alm(alm_out[i])) for i in range(2)])
    err_a = np.max(np.abs(au - alm_eb_ref)) / np.max(np.abs(alm_eb_ref))
    s_ok = err_s < tol_s and err_a < tol_a
    print(f"{name}: synth={err_s:.2e} anal={err_a:.2e} "
          f"{'OK' if s_ok else 'FAIL'}")
    return s_ok


ok &= check_spin("f64+spin2", "jnp", "float64", 1e-12, 1e-12)
ok &= check_spin("f32+pallas+spin2", "pallas", "float32", 5e-4, 5e-4)

# -- adjoint-based VJP through shard_map: jax.grad of a scalar loss through
#    the distributed transform matches central finite differences (the
#    custom linear_call rules must transpose across the all_to_all)
rng = np.random.default_rng(7)


def check_grad(name, stage1, dtype, tol):
    d = dist_sht.DistSHT(p, mesh, ("data", "model"), dtype=dtype,
                         stage1=stage1)
    packed = jnp.asarray(p.pack_alm(np.asarray(alm))).astype(
        jnp.complex64 if dtype == "float32" else jnp.complex128)
    t = jnp.asarray(rng.normal(size=(p.r_pad, g.max_n_phi, 2)),
                    jnp.dtype(dtype))

    def loss(a):
        return jnp.sum(d.alm2map(a) * t)

    gr = jax.grad(loss)(packed)
    v = jnp.asarray(rng.normal(size=packed.shape)
                    + 1j * rng.normal(size=packed.shape)).astype(packed.dtype)
    eps = 1e-6 if dtype == "float64" else 1e-2
    fd = float((loss(packed + eps * v) - loss(packed - eps * v)) / (2 * eps))
    dd = float(jnp.real(jnp.sum(gr * v)))      # JAX pairing: Re(g . v)
    err_s = abs(fd - dd) / max(abs(fd), 1e-9)

    maps0 = d.alm2map(packed)

    def loss_a(mp):
        return jnp.sum(jnp.abs(d.map2alm(mp)) ** 2)

    gm = jax.grad(loss_a)(maps0)
    vm = jnp.asarray(rng.normal(size=maps0.shape), maps0.dtype)
    fda = float((loss_a(maps0 + eps * vm) - loss_a(maps0 - eps * vm))
                / (2 * eps))
    err_a = abs(fda - float(jnp.sum(gm * vm))) / max(abs(fda), 1e-9)
    g_ok = err_s < tol and err_a < tol
    print(f"{name}: synth={err_s:.2e} anal={err_a:.2e} "
          f"{'OK' if g_ok else 'FAIL'}")
    return g_ok


ok &= check_grad("grad+f64+jnp", "jnp", "float64", 1e-7)
ok &= check_grad("grad+f32+pallas", "pallas", "float32", 3e-2)

# -- spin-2 ragged healpix through the full plan dispatch (mode="dist")
ps = repro.make_plan("healpix", nside=8, l_max=lmax_h, K=2,
                     dtype="float64", mode="dist", spin=2)
alm_hs = sht.random_alm_spin(jax.random.PRNGKey(6), lmax_h, lmax_h, K=2)
m_ref = np.asarray(th.alm2map_spin(alm_hs))
a_ref = np.asarray(th.map2alm_spin(jnp.asarray(m_ref)))
m_dist = np.asarray(ps.alm2map(alm_hs))
err_s = np.max(np.abs(m_dist - m_ref)) / np.max(np.abs(m_ref))
a_dist = np.asarray(ps.map2alm(jnp.asarray(m_ref)))
err_a = np.max(np.abs(a_dist - a_ref)) / np.max(np.abs(a_ref))
sp_ok = err_s < 1e-12 and err_a < 1e-12
print(f"dist-plan+healpix+spin2: synth={err_s:.2e} anal={err_a:.2e} "
      f"{'OK' if sp_ok else 'FAIL'}")
ok &= sp_ok
sys.exit(0 if ok else 1)
