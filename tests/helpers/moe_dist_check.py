"""Subprocess helper: MoE all-to-all EP path == single-shard reference."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, dataclasses
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro  # noqa

jax.config.update("jax_enable_x64", True)   # float64 reference engine
from repro.configs import registry
from repro.configs.base import reduced
from repro.models import moe as M
from repro.models.transformer import make_rules

cfg = reduced(registry.ARCHS["deepseek-v3-671b"],
              n_experts=8, top_k=2, capacity_factor=4.0,   # high cap: no drops
              n_shared_experts=0)  # routed part only; shared tested below
key = jax.random.PRNGKey(0)
p = M.init_moe(key, cfg, jnp.float32)
T_tokens, d = 64, cfg.d_model
T = T_tokens
x = jax.random.normal(jax.random.PRNGKey(1), (T, d), jnp.float32)

y_ref, aux_ref = M.moe_apply_local(p, x, cfg, cdt=jnp.float32)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
pspec = M.spec_moe(cfg, make_rules(cfg, mesh), layer_stacked=False)
def body(p_loc, x_loc):
    return M.moe_apply(p_loc, x_loc, cfg, axis_name="model", cdt=jnp.float32)
y, aux = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(pspec, P("model", None)),
                out_specs=(P("model", None), P()), check_vma=False))(p, x)
err = float(jnp.max(jnp.abs(y - y_ref))) / float(jnp.max(jnp.abs(y_ref)))

def body2(p_loc, x_loc):
    return M.moe_apply_replicated(p_loc, x_loc, cfg, axis_name="model", cdt=jnp.float32)
y2, _ = jax.jit(jax.shard_map(body2, mesh=mesh,
                in_specs=(pspec, P(None, None)),
                out_specs=(P(None, None), P()), check_vma=False))(p, x)
err2 = float(jnp.max(jnp.abs(y2 - y_ref))) / float(jnp.max(jnp.abs(y_ref)))
# full-block equivalence incl. shared expert, through _moe_block
import dataclasses
from repro.models import transformer as T
cfg_s = dataclasses.replace(cfg, n_shared_experts=1)
p_s = M.init_moe(jax.random.PRNGKey(4), cfg_s, jnp.float32)
xb = x.reshape(2, T_tokens // 2, d)
y_ref_s, _ = M.moe_apply_local(p_s, x, cfg_s, cdt=jnp.float32)
rt = T.Runtime(cfg=cfg_s, mesh=mesh, rules=make_rules(cfg_s, mesh))
yb, _ = jax.jit(lambda p_, x_: T._moe_block(p_, x_, rt))(p_s, xb)
err3 = float(jnp.max(jnp.abs(yb.reshape(-1, d) - y_ref_s))) / float(jnp.max(jnp.abs(y_ref_s)))
print(f"a2a_err={err:.2e} replicated_err={err2:.2e} block_err={err3:.2e}")
sys.exit(0 if (err < 1e-5 and err2 < 1e-5 and err3 < 1e-5) else 1)
