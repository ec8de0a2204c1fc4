"""Subprocess helper: Ulysses seq<->head attention == plain mea."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import numpy as np, jax, jax.numpy as jnp
import repro  # noqa

jax.config.update("jax_enable_x64", True)   # float64 reference engine
from repro.models.attention import mea, ulysses_attention

key = jax.random.PRNGKey(0)
B, S, H, D = 2, 64, 8, 16
q = jax.random.normal(key, (B, S, H, D), jnp.float32)
k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), jnp.float32)
v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), jnp.float32)
pos = jnp.arange(S, dtype=jnp.int32)
ref = mea(q, k, v, pos, pos)
mesh = jax.make_mesh((4,), ("model",))
out = jax.jit(lambda q, k, v: ulysses_attention(
    q, k, v, pos, pos, mesh, axis="model"))(q, k, v)
err = float(jnp.max(jnp.abs(out - ref)))
print(f"ulysses_err={err:.2e}")
sys.exit(0 if err < 1e-5 else 1)
