"""Decoder-LM assembly: config-driven blocks, scan-over-layers, caches.

Layers are grouped by the architecture's block pattern and stacked so the
whole depth is ONE `lax.scan` per group (small HLO => tractable 512-way
SPMD compiles; standard MaxText-style remat point).

Block kinds:
  dense  -- attention + dense MLP          (qwen*, danube, internvl)
  moe    -- attention + expert-parallel MoE (kimi, deepseek)
  mlstm / slstm -- xLSTM blocks
  rglru  -- RG-LRU mixer + MLP; local -- windowed attention + MLP (gemma)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

__all__ = ["make_rules", "build_groups", "init_lm", "lm_specs", "Runtime",
           "forward_train", "init_caches", "caches_specs", "decode_step",
           "prefill"]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Everything the model functions need besides params & inputs."""
    cfg: object
    mesh: Optional[Mesh]
    rules: L.ShardingRules

    @property
    def cdt(self):
        return jnp.dtype(self.cfg.compute_dtype)

    @property
    def pdt(self):
        return jnp.dtype(self.cfg.param_dtype)

    def axis_size(self, name):
        if self.mesh is None or name not in self.mesh.shape:
            return 1
        return self.mesh.shape[name]


def make_rules(cfg, mesh: Optional[Mesh]) -> L.ShardingRules:
    axes = set(mesh.axis_names) if mesh is not None else set()
    batch = tuple(a for a in ("pod", "data") if a in axes) or None
    model = "model" if "model" in axes else None
    if cfg.tp_profile == "dp":
        # pure data parallelism: the model axis joins the batch axes and
        # every parameter is replicated (perf iteration for small archs
        # whose TP shards are too thin; see EXPERIMENTS.md §Perf)
        batch = tuple(a for a in ("pod", "data", "model") if a in axes) or None
        return L.ShardingRules(batch=batch, heads=None, kv_heads=None,
                               d_ff=None, vocab=None, d_model=None,
                               experts=None, seq=None, layers=None)
    msize = mesh.shape["model"] if (mesh and model) else 1
    small = cfg.tp_profile == "small"
    heads = None if small else model
    kv = model if (not small and model and cfg.n_kv_heads % msize == 0
                   and cfg.n_kv_heads >= msize) else None
    d_ff = model if (cfg.d_ff or cfg.lru_width) and not (
        cfg.family == "ssm") else None
    if small and cfg.family == "ssm":
        d_ff = None
    vocab = model if (model and cfg.vocab % msize == 0) else None
    return L.ShardingRules(
        batch=batch, heads=heads, kv_heads=kv, d_ff=d_ff,
        vocab=vocab, d_model=None, experts=model, seq=None, layers=None)


def build_groups(cfg):
    """[(pattern tuple, n_repeat), ...] covering all layers."""
    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        n_full = cfg.n_layers // len(pat)
        rem = cfg.n_layers - n_full * len(pat)
        groups = [(pat, n_full)] if n_full else []
        if rem:
            groups.append((pat[:rem], 1))
        return groups
    if cfg.n_experts:
        g = []
        if cfg.first_dense_layers:
            g.append((("dense",), cfg.first_dense_layers))
        g.append((("moe",), cfg.n_layers - cfg.first_dense_layers))
        return g
    return [(("dense",), cfg.n_layers)]


# -- per-kind block init/spec/apply -------------------------------------------


def block_init(key, kind, cfg, dtype):
    ks = jax.random.split(key, 4)
    if kind in ("dense", "moe", "local"):
        p = {"ln1": L.init_norm(cfg.d_model, kind=cfg.norm),
             "attn": A.init_attention(ks[0], cfg, dtype),
             "ln2": L.init_norm(cfg.d_model, kind=cfg.norm)}
        if kind == "moe":
            p["moe"] = M.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, act=cfg.act,
                                  dtype=dtype)
        return p
    if kind == "mlstm":
        return {"ln": L.init_norm(cfg.d_model, kind=cfg.norm),
                "cell": S.init_mlstm(ks[0], cfg.d_model, cfg.n_heads,
                                     pf=cfg.mlstm_pf, dtype=dtype)}
    if kind == "slstm":
        return {"ln": L.init_norm(cfg.d_model, kind=cfg.norm),
                "cell": S.init_slstm(ks[0], cfg.d_model, cfg.n_heads,
                                     dtype=dtype)}
    if kind == "rglru":
        return {"ln1": L.init_norm(cfg.d_model, kind=cfg.norm),
                "cell": S.init_rglru(ks[0], cfg.d_model,
                                     cfg.lru_width or cfg.d_model,
                                     dtype=dtype),
                "ln2": L.init_norm(cfg.d_model, kind=cfg.norm),
                "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, act=cfg.act,
                                  dtype=dtype)}
    raise ValueError(kind)


def block_spec(kind, cfg, rules, *, layer_stacked=True):
    kw = dict(layer_stacked=layer_stacked)
    nk = dict(kind=cfg.norm, layer_stacked=layer_stacked)
    if kind in ("dense", "moe", "local"):
        s = {"ln1": L.spec_norm(rules, **nk),
             "attn": A.spec_attention(cfg, rules, **kw),
             "ln2": L.spec_norm(rules, **nk)}
        if kind == "moe":
            s["moe"] = M.spec_moe(cfg, rules, **kw)
        else:
            s["mlp"] = L.spec_mlp(rules, act=cfg.act, **kw)
        return s
    if kind in ("mlstm", "slstm"):
        cell = S.spec_mlstm(rules, **kw) if kind == "mlstm" \
            else S.spec_slstm(rules, **kw)
        return {"ln": L.spec_norm(rules, **nk), "cell": cell}
    if kind == "rglru":
        return {"ln1": L.spec_norm(rules, **nk),
                "cell": S.spec_rglru(rules, **kw),
                "ln2": L.spec_norm(rules, **nk),
                "mlp": L.spec_mlp(rules, act=cfg.act, **kw)}
    raise ValueError(kind)


def _moe_block(p, x, rt: Runtime):
    """Expert-parallel MoE sub-layer.  Chooses the all-to-all path when the
    per-row token count splits over the model axis, else the replicated
    (decode-friendly) path."""
    cfg = rt.cfg
    B, Sq, d = x.shape
    ms = rt.axis_size("model")
    batch_axes = rt.rules.batch or ()
    rows = int(np.prod([rt.axis_size(a) for a in batch_axes])) or 1
    cdt = rt.cdt

    if rt.mesh is None or ms == 1:
        # single-shard fallback (smoke tests)
        y, aux = M.moe_apply_local(p, x.reshape(-1, d), cfg, cdt=cdt)
        return y.reshape(B, Sq, d), aux

    if cfg.moe_impl == "a2a" and Sq % ms == 0 and Sq // ms > 0:
        in_spec = P(rt.rules.batch, "model", None)
        def body(p_loc, x_loc):
            b, s, _ = x_loc.shape
            y, aux = M.moe_apply(p_loc, x_loc.reshape(b * s, d), cfg,
                                 axis_name="model", cdt=cdt)
            return y.reshape(b, s, d), aux
    else:
        in_spec = P(rt.rules.batch, None, None)
        def body(p_loc, x_loc):
            b, s, _ = x_loc.shape
            y, aux = M.moe_apply_replicated(p_loc, x_loc.reshape(b * s, d),
                                            cfg, axis_name="model", cdt=cdt)
            return y.reshape(b, s, d), aux

    pspec = M.spec_moe(cfg, rt.rules, layer_stacked=False)
    routed_keys = ("router", "gate", "up", "down")
    p_routed = {k: p[k] for k in routed_keys}
    pspec_routed = {k: pspec[k] for k in routed_keys}
    # Pin the boundary shardings explicitly: without these GSPMD resolves
    # the (replicated-seq -> seq-sharded) transition at the shard_map edge
    # with a last-resort FULL replication of the global activation
    # (hundreds of GB of all-gather per layer in the 7168-wide models).
    # Measured in EXPERIMENTS.md §Perf (deepseek hillclimb, iteration 1).
    if rt.mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, jax.NamedSharding(rt.mesh, in_spec))
    y, aux = jax.shard_map(
        body, mesh=rt.mesh, in_specs=(pspec_routed, in_spec),
        out_specs=(in_spec, P()), check_vma=False)(p_routed, x)
    if rt.mesh is not None:
        # ...and bring the output BACK to batch-only sharding: letting the
        # seq-sharding leak into the next layer's attention makes GSPMD
        # replicate q/k/v globally there (the 103 GB/layer all-gathers).
        y = jax.lax.with_sharding_constraint(
            y, jax.NamedSharding(rt.mesh, P(rt.rules.batch, None, None)))
    if cfg.n_shared_experts:
        y = y + L.swiglu(p["shared"], x.astype(cdt), cdt)
    return y, aux


def block_apply(kind, p, x, positions, rt: Runtime):
    """Training/prefill forward for one block.  Returns (x', aux_loss)."""
    cfg = rt.cfg
    cdt = rt.cdt
    aux = jnp.float32(0.0)
    if kind in ("dense", "moe", "local"):
        win = cfg.local_window if kind == "local" else cfg.sliding_window
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, _ = A.attention_train(p["attn"], h, positions, cfg, window=win,
                                 cdt=cdt)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        if kind == "moe":
            y, aux = _moe_block(p["moe"], h, rt)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg.act, cdt)
        return x + y, aux
    if kind == "mlstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        return x + S.mlstm_train(p["cell"], h, cfg.n_heads, cdt=cdt,
                                 unroll=cfg.inner_unroll), aux
    if kind == "slstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        return x + S.slstm_train(p["cell"], h, cdt=cdt), aux
    if kind == "rglru":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        x = x + S.rglru_train(p["cell"], h, cdt=cdt)
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        return x + L.apply_mlp(p["mlp"], h, cfg.act, cdt), aux
    raise ValueError(kind)


# -- whole-model init / specs ----------------------------------------------------


def init_lm(key, cfg, dtype=None):
    dtype = jnp.dtype(cfg.param_dtype) if dtype is None else dtype
    groups = build_groups(cfg)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    params = {"embed": L.init_embedding(k_emb, cfg.vocab, cfg.d_model, dtype),
              "final_norm": L.init_norm(cfg.d_model, kind=cfg.norm)}
    gparams = []
    kg = jax.random.split(k_blocks, len(groups))
    for (pat, n_rep), gk in zip(groups, kg):
        keys = jax.random.split(gk, n_rep * len(pat)).reshape(
            n_rep, len(pat), 2)
        stacked = []
        for j, kind in enumerate(pat):
            init_one = lambda k, kind=kind: block_init(k, kind, cfg, dtype)
            stacked.append(jax.vmap(init_one)(keys[:, j]))
        gparams.append(stacked)
    params["groups"] = gparams
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(k_head, cfg.d_model, cfg.vocab,
                                         dtype=dtype)
    return params


def lm_specs(cfg, rules):
    groups = build_groups(cfg)
    specs = {"embed": L.spec_embedding(rules),
             "final_norm": L.spec_norm(rules, kind=cfg.norm)}
    gspecs = []
    for pat, _ in groups:
        gspecs.append([block_spec(kind, cfg, rules) for kind in pat])
    specs["groups"] = gspecs
    if not cfg.tie_embeddings:
        specs["lm_head"] = L.spec_dense(rules, "d_model", "vocab")
    return specs


# -- training forward --------------------------------------------------------------


def _run_groups(params, x, positions, rt: Runtime):
    cfg = rt.cfg
    aux_total = jnp.float32(0.0)
    for (pat, n_rep), stacked in zip(build_groups(cfg), params["groups"]):
        def body(carry, layer_params):
            x, aux = carry
            for kind, p in zip(pat, layer_params):
                x, a = block_apply(kind, p, x, positions, rt)
                aux = aux + a
            return (x, aux), None
        if cfg.remat:
            # full per-layer remat: saves only the residual stream between
            # layers (peak = carry + one layer) -- the 1M-token cells need it
            body = jax.checkpoint(body)
        (x, aux_total), _ = jax.lax.scan(
            body, (x, aux_total), tuple(stacked),
            unroll=True if cfg.scan_unroll else 1)
    return x, aux_total


def embed_tokens(params, tokens, rt: Runtime):
    table = params["embed"]["table"]
    return jnp.take(table, tokens, axis=0).astype(rt.cdt)


def forward_train(params, tokens, rt: Runtime, *, extra=None,
                  aux_weight: float = 0.01):
    """Decoder-LM loss.  tokens: (B, S) int32.  extra: dict for vlm stubs
    ({"patch_embeds": (B, n_vis, d)}).  Targets = tokens shifted left."""
    cfg = rt.cfg
    x = embed_tokens(params, tokens, rt)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    if extra is not None and "patch_embeds" in extra:
        pe = extra["patch_embeds"].astype(rt.cdt)
        x = jnp.concatenate([pe, x], axis=1)
        targets = jnp.concatenate(
            [jnp.full(pe.shape[:2], -1, targets.dtype), targets], axis=1)
    if rt.rules.batch:
        x = jax.lax.with_sharding_constraint(
            x, jax.NamedSharding(rt.mesh, P(rt.rules.batch, None, None)))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, aux = _run_groups(params, x, positions, rt)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    table = params.get("lm_head", {}).get("w")
    if table is None:
        table = params["embed"]["table"]
    else:
        table = table.T
    loss = L.cross_entropy_loss(table, x, targets, compute_dtype=rt.cdt,
                                n_chunks=cfg.loss_chunks)
    return loss + aux_weight * aux


# -- serving: caches, prefill, decode --------------------------------------------------


def block_cache(kind, cfg, batch, max_len, dtype):
    if kind in ("dense", "moe"):
        return A.init_cache(cfg, batch, max_len, dtype)
    if kind == "local":
        local_cfg = dataclasses.replace(cfg, sliding_window=cfg.local_window)
        return A.init_cache(local_cfg, batch, max_len, dtype)
    if kind == "mlstm":
        return S.mlstm_state(cfg, batch, cfg.d_model, cfg.n_heads,
                             cfg.mlstm_pf)
    if kind == "slstm":
        return S.slstm_state(batch, cfg.d_model)
    if kind == "rglru":
        return S.rglru_state(batch, cfg.lru_width or cfg.d_model)
    raise ValueError(kind)


def init_caches(cfg, batch, max_len, dtype=jnp.bfloat16):
    caches = []
    for pat, n_rep in build_groups(cfg):
        stacked = []
        for kind in pat:
            one = block_cache(kind, cfg, batch, max_len, dtype)
            stacked.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_rep,) + a.shape), one))
        caches.append(stacked)
    return caches


def caches_specs(cfg, rules):
    out = []
    for pat, _ in build_groups(cfg):
        stacked = []
        for kind in pat:
            if kind in ("dense", "moe", "local"):
                s = A.cache_specs(cfg, rules)
            else:
                b = rules.batch
                if kind == "mlstm":
                    s = {"C": P(b, None, None, None), "N": P(b, None, None),
                         "M": P(b, None)}
                elif kind == "slstm":
                    s = {"c": P(b, None), "n": P(b, None), "m": P(b, None)}
                else:
                    s = {"h": P(b, None), "conv": P(b, None, None)}
            stacked.append(jax.tree.map(lambda sp: P(*((None,) + tuple(sp))),
                                        s, is_leaf=lambda v: isinstance(v, P)))
        out.append(stacked)
    return out


def block_decode(kind, p, x, pos, cache, rt: Runtime):
    cfg = rt.cfg
    cdt = rt.cdt
    if kind in ("dense", "moe", "local"):
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, cache = A.attention_decode(p["attn"], h, pos, cache, cfg, cdt=cdt)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        if kind == "moe":
            y, _ = _moe_block(p["moe"], h, rt)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg.act, cdt)
        return x + y, cache
    if kind == "mlstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        y, cache = S.mlstm_decode(p["cell"], h, cache, cfg.n_heads, cdt=cdt)
        return x + y, cache
    if kind == "slstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        y, cache = S.slstm_decode(p["cell"], h, cache, cdt=cdt)
        return x + y, cache
    if kind == "rglru":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, cache = S.rglru_decode(p["cell"], h, cache, cdt=cdt)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        return x + L.apply_mlp(p["mlp"], h, cfg.act, cdt), cache
    raise ValueError(kind)


def decode_step(params, token, pos, caches, rt: Runtime):
    """One decode step.  token: (B, 1) int32; pos: scalar int32.

    Returns (logits (B, vocab), caches')."""
    cfg = rt.cfg
    x = embed_tokens(params, token, rt)
    new_caches = []
    for (pat, n_rep), stacked, cstack in zip(build_groups(cfg),
                                             params["groups"], caches):
        def body(x, xs):
            layer_params, layer_caches = xs
            new_lc = []
            for j, kind in enumerate(pat):
                x, c2 = block_decode(kind, layer_params[j], x, pos,
                                     layer_caches[j], rt)
                new_lc.append(c2)
            return x, new_lc
        x, ncs = jax.lax.scan(body, x, (tuple(stacked), tuple(cstack)),
                              unroll=True if cfg.scan_unroll else 1)
        new_caches.append(ncs)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    table = params.get("lm_head", {}).get("w")
    if table is None:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(rt.cdt),
                            params["embed"]["table"].astype(rt.cdt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x.astype(rt.cdt),
                            table.astype(rt.cdt))
    return logits[:, 0].astype(jnp.float32), new_caches


def prefill(params, tokens, caches, rt: Runtime):
    """Prefill the caches with a full prompt.  tokens: (B, S).

    Returns (last-token logits (B, vocab), caches')."""
    cfg = rt.cfg
    x = embed_tokens(params, tokens, rt)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    new_caches = []
    for (pat, n_rep), stacked, cstack in zip(build_groups(cfg),
                                             params["groups"], caches):
        def body(x, xs):
            layer_params, layer_caches = xs
            new_lc = []
            for j, kind in enumerate(pat):
                x, c2 = _block_prefill(kind, layer_params[j], x, positions,
                                       layer_caches[j], rt)
                new_lc.append(c2)
            return x, new_lc
        x, ncs = jax.lax.scan(body, x, (tuple(stacked), tuple(cstack)),
                              unroll=True if cfg.scan_unroll else 1)
        new_caches.append(ncs)
    x = L.apply_norm(params["final_norm"], x[:, -1:], cfg.norm)
    table = params.get("lm_head", {}).get("w")
    if table is None:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(rt.cdt),
                            params["embed"]["table"].astype(rt.cdt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x.astype(rt.cdt),
                            table.astype(rt.cdt))
    return logits[:, 0].astype(jnp.float32), new_caches


def _block_prefill(kind, p, x, positions, cache, rt: Runtime):
    cfg = rt.cfg
    cdt = rt.cdt
    if kind in ("dense", "moe", "local"):
        win = cfg.local_window if kind == "local" else cfg.sliding_window
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, cache = A.attention_train(p["attn"], h, positions, cfg, window=win,
                                     cdt=cdt, cache=cache)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        if kind == "moe":
            y, _ = _moe_block(p["moe"], h, rt)
        else:
            y = L.apply_mlp(p["mlp"], h, cfg.act, cdt)
        return x + y, cache
    # recurrent blocks: the chunkwise/scan training path also emits the
    # final state, which becomes the decode cache.
    if kind == "mlstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        y, st = S.mlstm_train(p["cell"], h, cfg.n_heads, cdt=cdt,
                              return_state=True, unroll=cfg.inner_unroll)
        return x + y, st
    if kind == "slstm":
        h = L.apply_norm(p["ln"], x, cfg.norm)
        y, st = S.slstm_train(p["cell"], h, cdt=cdt, return_state=True)
        return x + y, st
    if kind == "rglru":
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        y, st = S.rglru_train(p["cell"], h, cdt=cdt, return_state=True)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        return x + L.apply_mlp(p["mlp"], h, cfg.act, cdt), st
    raise ValueError(kind)
