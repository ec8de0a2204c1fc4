"""Attention family: GQA (+bias/qk_norm/sliding-window), MLA, caches.

Memory discipline: training/prefill attention uses a blockwise
online-softmax implementation (`mea`) so the (S, T) score matrix is never
materialised -- at the assigned shapes (4k x 1M-token batches, 32k prefill)
a dense score tensor would dominate the HBM budget.  FLOPs are identical,
so the roofline accounting is unaffected.

Decode uses position-indexed caches:
  * dense GQA cache (B, S, Kv, Dh)
  * ring-buffer sliding-window cache (B, W, Kv, Dh)  [SWA / local attention]
  * MLA compressed cache (B, S, c_kv + rope) with absorbed-matmul scoring,
    so the per-token cache cost is (kv_lora + rope) elements instead of
    2 * H * Dh -- DeepSeek-V3's central serving trick.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L

__all__ = ["init_attention", "spec_attention", "attention_train",
           "attention_decode", "init_cache", "cache_specs", "mea",
           "dense_attention", "ulysses_attention"]


# =============================================================================
# blockwise attention core (online softmax; pure JAX flash-style)
# =============================================================================


def _mask_bias(qpos, kpos, window):
    """Additive mask: causal, optionally sliding-window.  qpos: (Sq,),
    kpos: (Sk,) -> (Sq, Sk) float32 {0, -inf}."""
    ok = kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok &= (qpos[:, None] - kpos[None, :]) < window
    ok &= kpos[None, :] >= 0          # invalid slots carry position -1
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def mea(q, k, v, qpos, kpos, *, window=None, q_block=512, kv_block=1024,
        causal=True):
    """Memory-efficient attention.  q: (B, Sq, H, D); k/v: (B, Sk, KvH, D).

    GQA: H must be a multiple of KvH.  Returns (B, Sq, H, Dv) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Sk, KvH, Dv = v.shape
    G = H // KvH
    scale = float(1.0 / np.sqrt(D))
    q_block = min(q_block, Sq)
    while Sq % q_block:
        q_block //= 2
    kv_block = min(kv_block, Sk)
    while Sk % kv_block:
        kv_block //= 2
    nq, nk = Sq // q_block, Sk // kv_block

    qg = q.reshape(B, Sq, KvH, G, D)

    def q_step(qi):
        qs = jax.lax.dynamic_slice_in_dim(qg, qi * q_block, q_block, 1)
        qp = jax.lax.dynamic_slice_in_dim(qpos, qi * q_block, q_block, 0)

        def kv_step(carry, ki):
            acc, m_run, l_run = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ki * kv_block, kv_block, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, ki * kv_block, kv_block, 1)
            kp = jax.lax.dynamic_slice_in_dim(kpos, ki * kv_block, kv_block, 0)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qs.astype(jnp.float32),
                           ks.astype(jnp.float32)) * scale
            bias = _mask_bias(qp, kp, window) if causal else \
                jnp.where(kp[None, :] >= 0, 0.0, -jnp.inf).astype(jnp.float32)
            s = s + bias[None, None, None]
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vs.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, KvH, G, q_block, Dv), jnp.float32)
        m0 = jnp.full((B, KvH, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KvH, G, q_block), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), jnp.arange(nk))
        out = acc / jnp.maximum(l_run[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1).reshape(B, q_block, H, Dv)

    # remat per q-block: the kv-scan VJP otherwise saves its carries for
    # every (q-block, kv-block) pair; recomputing per block keeps the
    # backward working set at one q-block's scan.
    q_step = jax.checkpoint(q_step)
    outs = jax.lax.map(q_step, jnp.arange(nq))            # (nq, B, qb, H, Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, qpos, kpos, mesh, axis="model", *, window=None,
                      causal=True):
    """Sequence<->head re-sharded attention (DeepSpeed-Ulysses pattern).

    This is the LM-side instance of the paper's two-domain structure
    (DESIGN.md §4): activations arrive SEQUENCE-sharded over ``axis``; one
    all_to_all moves them to the HEAD-sharded domain where the attention
    contraction is local; the reverse all_to_all brings outputs home --
    exactly the SHT's m-domain / ring-domain exchange.

    q/k/v: global (B, S, H, D) arrays, sequence(-dim-1)-sharded on ``axis``.
    H must be divisible by the axis size.  qpos/kpos are global (S,).
    """
    from jax.sharding import PartitionSpec as P

    def body(q_loc, k_loc, v_loc):
        # (B, S/n, H, D) -> (B, S, H/n, D): heads scatter, sequence gathers
        a2a = lambda t: jax.lax.all_to_all(t, axis, split_axis=2,
                                           concat_axis=1, tiled=True)
        qh, kh, vh = a2a(q_loc), a2a(k_loc), a2a(v_loc)
        out = mea(qh, kh, vh, qpos, kpos, window=window, causal=causal)
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# =============================================================================
# GQA
# =============================================================================


def init_attention(key, cfg, dtype=jnp.bfloat16):
    if cfg.attention == "mla":
        return _init_mla(key, cfg, dtype)
    d, H, KvH = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.head_dim or d // H
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.init_dense(ks[0], d, H * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": L.init_dense(ks[1], d, KvH * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": L.init_dense(ks[2], d, KvH * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": L.init_dense(ks[3], H * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_norm(hd)
        p["k_norm"] = L.init_norm(hd)
    return p


def spec_attention(cfg, rules: L.ShardingRules, *, layer_stacked=True):
    if cfg.attention == "mla":
        return _spec_mla(cfg, rules, layer_stacked=layer_stacked)
    kw = dict(bias=cfg.qkv_bias, layer_stacked=layer_stacked)
    s = {
        "wq": L.spec_dense(rules, "d_model", "heads", **kw),
        "wk": L.spec_dense(rules, "d_model", "kv_heads", **kw),
        "wv": L.spec_dense(rules, "d_model", "kv_heads", **kw),
        "wo": L.spec_dense(rules, "heads", "d_model",
                           layer_stacked=layer_stacked),
    }
    if cfg.qk_norm:
        s["q_norm"] = L.spec_norm(rules, layer_stacked=layer_stacked)
        s["k_norm"] = L.spec_norm(rules, layer_stacked=layer_stacked)
    return s


def _qkv(p, x, cfg, positions, cdt):
    B, S, d = x.shape
    H, KvH = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.head_dim or d // H
    q = L.dense(p["wq"], x, cdt).reshape(B, S, H, hd)
    k = L.dense(p["wk"], x, cdt).reshape(B, S, KvH, hd)
    v = L.dense(p["wv"], x, cdt).reshape(B, S, KvH, hd)
    if cfg.qk_norm:
        q = L.rms_norm(p["q_norm"], q)
        k = L.rms_norm(p["k_norm"], k)
    q = L.apply_rope(q, positions, theta=cfg.rope_theta)
    k = L.apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def dense_attention(q, k, v, qpos, kpos, *, window=None, causal=True):
    """Unblocked attention (materialised scores).  Used by the accounting
    lowerings (single-pass flop counting) and tiny smoke shapes."""
    B, Sq, H, D = q.shape
    KvH = v.shape[2]
    G = H // KvH
    scale = float(1.0 / np.sqrt(D))
    qg = q.reshape(B, Sq, KvH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        bias = _mask_bias(qpos, kpos, window)
    else:
        bias = jnp.where(kpos[None, :] >= 0, 0.0,
                         -jnp.inf).astype(jnp.float32)
    w = jax.nn.softmax(s + bias[None, None, None], axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", w, v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, -1).astype(q.dtype)


def attention_train(p, x, positions, cfg, *, window=None, cdt=jnp.bfloat16,
                    cache=None, cache_pos0=None):
    """Causal self-attention for train/prefill.  Optionally fills a cache.

    Returns (y, cache') -- cache' is None when cache is None.
    """
    if cfg.attention == "mla":
        return _mla_train(p, x, positions, cfg, cdt=cdt, cache=cache)
    B, S, d = x.shape
    q, k, v = _qkv(p, x, cfg, positions, cdt)
    win = window if window is not None else cfg.sliding_window
    impl = mea if getattr(cfg, "attn_impl", "mea") == "mea" else dense_attention
    out = impl(q, k, v, positions[0] if positions.ndim > 1 else positions,
               positions[0] if positions.ndim > 1 else positions, window=win)
    y = L.dense(p["wo"], out.reshape(B, S, -1), cdt)
    new_cache = None
    if cache is not None:
        new_cache = _fill_cache(cache, k, v, positions, win)
    return y, new_cache


# -- caches ---------------------------------------------------------------------


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Self-attention cache for one layer."""
    if cfg.attention == "mla":
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
            "pos": jnp.full((max_len,), -1, jnp.int32),
        }
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    W = cfg.sliding_window
    slots = min(max_len, W) if W else max_len
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),
    }


def cache_specs(cfg, rules: L.ShardingRules):
    if cfg.attention == "mla":
        return {"ckv": P(rules.ax("batch"), None, None),
                "krope": P(rules.ax("batch"), None, None),
                "pos": P(None)}
    return {"k": P(rules.ax("batch"), None, rules.ax("kv_heads"), None),
            "v": P(rules.ax("batch"), None, rules.ax("kv_heads"), None),
            "pos": P(None)}


def _fill_cache(cache, k, v, positions, window):
    """Write a prefill chunk into the (possibly ring-buffer) cache."""
    pos = positions[0] if positions.ndim > 1 else positions    # (S,)
    slots = cache["k"].shape[1]
    idx = pos % slots
    ck = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
    cp = cache["pos"].at[idx].set(pos.astype(jnp.int32))
    return {"k": ck, "v": cv, "pos": cp}


def attention_decode(p, x, pos, cache, cfg, *, cdt=jnp.bfloat16):
    """One-token decode.  x: (B, 1, d); pos: scalar int32 (current position).

    Returns (y (B, 1, d), cache').
    """
    if cfg.attention == "mla":
        return _mla_decode(p, x, pos, cache, cfg, cdt=cdt)
    B = x.shape[0]
    H, KvH = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions, cdt)
    slots = cache["k"].shape[1]
    slot = pos % slots
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions, slot, axis=0)
    win = cfg.sliding_window
    scale = float(1.0 / float(np.sqrt(hd)))
    qh = q.reshape(B, 1, KvH, H // KvH, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh.astype(jnp.float32),
                   ck.astype(jnp.float32)) * scale
    bias = _mask_bias(positions, cp, win)                      # (1, slots)
    s = s + bias[None, None, None]
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", w, cv.astype(jnp.float32))
    out = jnp.moveaxis(out, 3, 1).reshape(B, 1, H * hd).astype(cdt)
    y = L.dense(p["wo"], out, cdt)
    return y, {"k": ck, "v": cv, "pos": cp}


# =============================================================================
# MLA (DeepSeek-V3 style multi-head latent attention)
# =============================================================================


def _init_mla(key, cfg, dtype):
    d, H = cfg.d_model, cfg.n_heads
    qn, qr, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq_a": L.init_dense(ks[0], d, cfg.q_lora_rank, dtype=dtype),
        "q_norm": L.init_norm(cfg.q_lora_rank),
        "wq_b": L.init_dense(ks[1], cfg.q_lora_rank, H * (qn + qr), dtype=dtype),
        "wkv_a": L.init_dense(ks[2], d, cfg.kv_lora_rank + qr, dtype=dtype),
        "kv_norm": L.init_norm(cfg.kv_lora_rank),
        "wk_b": L.init_dense(ks[3], cfg.kv_lora_rank, H * qn, dtype=dtype),
        "wv_b": L.init_dense(ks[4], cfg.kv_lora_rank, H * vh, dtype=dtype),
        "wo": L.init_dense(ks[5], H * vh, d, dtype=dtype),
    }
    return p


def _spec_mla(cfg, rules, *, layer_stacked=True):
    kw = dict(layer_stacked=layer_stacked)
    return {
        "wq_a": L.spec_dense(rules, "d_model", None, **kw),
        "q_norm": L.spec_norm(rules, **kw),
        "wq_b": L.spec_dense(rules, None, "heads", **kw),
        "wkv_a": L.spec_dense(rules, "d_model", None, **kw),
        "kv_norm": L.spec_norm(rules, **kw),
        "wk_b": L.spec_dense(rules, None, "heads", **kw),
        "wv_b": L.spec_dense(rules, None, "heads", **kw),
        "wo": L.spec_dense(rules, "heads", "d_model", **kw),
    }


def _mla_qkv_expand(p, x, positions, cfg, cdt):
    """Expanded-KV MLA path (train/prefill)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    qn, qr, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    cq = L.rms_norm(p["q_norm"], L.dense(p["wq_a"], x, cdt))
    q = L.dense(p["wq_b"], cq, cdt).reshape(B, S, H, qn + qr)
    q_nope, q_rope = q[..., :qn], q[..., qn:]
    q_rope = L.apply_rope(q_rope, positions, theta=cfg.rope_theta)

    kv = L.dense(p["wkv_a"], x, cdt)
    ckv = L.rms_norm(p["kv_norm"], kv[..., : cfg.kv_lora_rank])
    k_rope = kv[..., cfg.kv_lora_rank:].reshape(B, S, 1, qr)
    k_rope = L.apply_rope(k_rope, positions, theta=cfg.rope_theta)

    k_nope = L.dense(p["wk_b"], ckv, cdt).reshape(B, S, H, qn)
    v = L.dense(p["wv_b"], ckv, cdt).reshape(B, S, H, vh)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, qr))],
                        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    return q_full, k, v, ckv, k_rope


def _mla_train(p, x, positions, cfg, *, cdt, cache=None):
    B, S, _ = x.shape
    q, k, v, ckv, k_rope = _mla_qkv_expand(p, x, positions, cfg, cdt)
    pos1 = positions[0] if positions.ndim > 1 else positions
    impl = mea if getattr(cfg, "attn_impl", "mea") == "mea" else dense_attention
    out = impl(q, k, v, pos1, pos1, window=None)
    y = L.dense(p["wo"], out.reshape(B, S, -1), cdt)
    new_cache = None
    if cache is not None:
        idx = pos1 % cache["ckv"].shape[1]
        new_cache = {
            "ckv": cache["ckv"].at[:, idx].set(ckv.astype(cache["ckv"].dtype)),
            "krope": cache["krope"].at[:, idx].set(
                k_rope[:, :, 0].astype(cache["krope"].dtype)),
            "pos": cache["pos"].at[idx].set(pos1.astype(jnp.int32)),
        }
    return y, new_cache


def _mla_decode(p, x, pos, cache, cfg, *, cdt):
    """Absorbed-matmul decode: scores and values computed against the
    *compressed* cache; W_uk / W_uv are folded into the query/output sides.
    Per-token cache traffic: kv_lora + rope elements (vs 2*H*Dh dense)."""
    B = x.shape[0]
    H = cfg.n_heads
    qn, qr, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    positions = jnp.full((1,), pos, jnp.int32)
    cq = L.rms_norm(p["q_norm"], L.dense(p["wq_a"], x, cdt))
    q = L.dense(p["wq_b"], cq, cdt).reshape(B, 1, H, qn + qr)
    q_nope, q_rope = q[..., :qn], q[..., qn:]
    q_rope = L.apply_rope(q_rope, positions, theta=cfg.rope_theta)

    kv = L.dense(p["wkv_a"], x, cdt)
    ckv_new = L.rms_norm(p["kv_norm"], kv[..., : cfg.kv_lora_rank])
    k_rope_new = kv[..., cfg.kv_lora_rank:].reshape(B, 1, 1, qr)
    k_rope_new = L.apply_rope(k_rope_new, positions, theta=cfg.rope_theta)

    slots = cache["ckv"].shape[1]
    slot = pos % slots
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), slot, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], k_rope_new[:, :, 0].astype(cache["krope"].dtype),
        slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions, slot, axis=0)

    wk_b = p["wk_b"]["w"].astype(cdt).reshape(cfg.kv_lora_rank, H, qn)
    q_eff = jnp.einsum("bshd,chd->bshc", q_nope, wk_b)    # absorb W_uk
    s = jnp.einsum("bshc,btc->bhst", q_eff.astype(jnp.float32),
                   ckv.astype(jnp.float32))
    s = s + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                       krope.astype(jnp.float32))
    s = s * float(1.0 / np.sqrt(qn + qr))
    bias = _mask_bias(positions, cpos, None)
    s = s + bias[None, None]
    w = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhst,btc->bshc", w, ckv.astype(jnp.float32))
    wv_b = p["wv_b"]["w"].astype(cdt).reshape(cfg.kv_lora_rank, H, vh)
    out = jnp.einsum("bshc,chd->bshd", o_c.astype(cdt), wv_b)  # absorb W_uv
    y = L.dense(p["wo"], out.reshape(B, 1, H * vh), cdt)
    return y, {"ckv": ckv, "krope": krope, "pos": cpos}
