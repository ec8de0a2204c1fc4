"""Mixture-of-Experts layer with expert-parallel all-to-all dispatch.

This is the LM-side incarnation of the paper's two-domain pattern
(DESIGN.md §4): tokens are computed in the sequence-sharded domain, one
all-to-all moves them to the expert-sharded domain, expert FFNs run locally,
and the reverse all-to-all brings results home -- exactly the
Delta-exchange structure of the SHT (stage / all_to_all / stage).

Mechanics (inside one shard_map over the full mesh):
  * activations arrive sequence-sharded over the "model" axis (SP), token-
    sharded over ("pod", "data");
  * router (replicated weights) computes top-k experts per token;
  * tokens are bucketed per destination expert-shard with a static capacity
    C = ceil(T_local * k / n_shards * capacity_factor); overflow tokens are
    dropped (standard capacity-style MoE; the aux loss keeps routing
    balanced so drops are rare);
  * ONE all_to_all ships (payload, expert-id) buckets; expert shards run a
    grouped matmul (jax.lax.ragged_dot) over their local experts; ONE
    reverse all_to_all ships results back;
  * source shards combine with router probabilities (scatter-add).

A shared-expert branch (DeepSeek-style) and the load-balance auxiliary
loss are included.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L

__all__ = ["init_moe", "spec_moe", "moe_apply"]


def init_moe(key, cfg, dtype=jnp.bfloat16):
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, E), jnp.float32)
                         * scale).astype(jnp.float32)},
        "gate": (jax.random.normal(ks[1], (E, d, ff), jnp.float32)
                 * scale).astype(dtype),
        "up": (jax.random.normal(ks[2], (E, d, ff), jnp.float32)
               * scale).astype(dtype),
        "down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
                 / np.sqrt(ff)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks[4], d, ff * cfg.n_shared_experts,
                                 act="swiglu", dtype=dtype)
    return p


def spec_moe(cfg, rules: L.ShardingRules, *, layer_stacked=True):
    lead = (rules.ax("layers"),) if layer_stacked else ()
    e = rules.ax("experts")
    s = {
        "router": {"w": P(*lead, None, None)},
        "gate": P(*lead, e, None, None),
        "up": P(*lead, e, None, None),
        "down": P(*lead, e, None, None),
    }
    if cfg.n_shared_experts:
        s["shared"] = L.spec_mlp(rules, layer_stacked=layer_stacked)
    return s


def _router(p, x, cfg):
    """x: (T, d) -> (probs (T, k), experts (T, k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalise
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return top_p.astype(jnp.float32), top_e.astype(jnp.int32), aux


def _dispatch_buckets(flat_e, n_shards, e_per_shard, capacity):
    """flat_e: (N,) expert ids.  Returns (dest, rank) with rank = position
    within the destination's bucket (== capacity -> dropped)."""
    dest = flat_e // e_per_shard                              # (N,)
    order = jnp.argsort(dest, stable=True)
    dest_sorted = dest[order]
    counts = jnp.bincount(dest_sorted, length=n_shards)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(dest.shape[0]) - starts[dest_sorted]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    rank = jnp.minimum(rank, capacity)                        # overflow slot
    return dest, rank


def _grouped_ffn(p, xs, eids, e_per_shard, cdt):
    """Grouped SwiGLU over local experts.  xs: (N, d); eids: (N,) local ids."""
    order = jnp.argsort(eids, stable=True)
    xs_s = xs[order]
    gsz = jnp.bincount(eids, length=e_per_shard).astype(jnp.int32)
    g = jax.lax.ragged_dot(xs_s.astype(cdt), p["gate"].astype(cdt), gsz)
    u = jax.lax.ragged_dot(xs_s.astype(cdt), p["up"].astype(cdt), gsz)
    h = jax.nn.silu(g) * u
    y_s = jax.lax.ragged_dot(h, p["down"].astype(cdt), gsz)
    return jnp.zeros_like(y_s).at[order].set(y_s)


def moe_apply(p, x_loc, cfg, axis_name="model", *, cdt=jnp.bfloat16):
    """Expert-parallel MoE on one shard (call inside shard_map).

    x_loc: (T_local, d) tokens owned by this model shard (sequence-split).
    Returns (y_loc (T_local, d), aux_loss scalar local mean).
    """
    T, d = x_loc.shape
    E, k = cfg.n_experts, cfg.top_k
    S = jax.lax.axis_size(axis_name)
    e_per_shard = E // S
    cap = int(np.ceil(T * k / S * cfg.capacity_factor))

    top_p, top_e, aux = _router(p, x_loc, cfg)
    flat_e = top_e.reshape(-1)                                # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)

    dest, rank = _dispatch_buckets(flat_e, S, e_per_shard, cap)

    # Build send buffers; overflow rank == cap lands in a discarded slot.
    send = jnp.zeros((S, cap + 1, d), cdt)
    send = send.at[dest, rank].set(x_loc[flat_tok].astype(cdt))
    send_eid = jnp.full((S, cap + 1), e_per_shard - 1, jnp.int32)
    send_eid = send_eid.at[dest, rank].set(flat_e % e_per_shard)
    send, send_eid = send[:, :cap], send_eid[:, :cap]

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=True).reshape(S * cap, d)
    recv_eid = jax.lax.all_to_all(send_eid, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True).reshape(S * cap)

    y = _grouped_ffn(p, recv, recv_eid, e_per_shard, cdt)     # (S*cap, d)

    back = jax.lax.all_to_all(y.reshape(S, cap, d), axis_name, split_axis=0,
                              concat_axis=0, tiled=True)      # (S, cap, d)

    # Combine: slot (dest, rank) corresponds to flat entry; gather + weight.
    valid = (rank < cap).astype(jnp.float32)
    contrib = back[dest, jnp.minimum(rank, cap - 1)]          # (T*k, d)
    w = (flat_p * valid)[:, None].astype(jnp.float32)
    out = jnp.zeros((T, d), jnp.float32).at[flat_tok].add(
        contrib.astype(jnp.float32) * w)
    out = out.astype(cdt)
    # NOTE: the shared-expert branch is applied OUTSIDE the shard_map (its
    # d_ff axis is model-sharded; the partial-sum reduction belongs to
    # GSPMD, not to this token-sharded body).  See transformer._moe_block.
    return out, aux


def moe_apply_replicated(p_loc, x_loc, cfg, axis_name="model", *,
                         cdt=jnp.bfloat16):
    """Decode-path MoE: activations replicated across the expert axis.

    Each expert shard routes ALL local tokens, computes the subset that hit
    its experts, and a psum combines.  No all-to-all; right when the token
    count is too small to split (single-token decode steps).
    x_loc: (T, d) (same on every shard of ``axis_name``).
    """
    T, d = x_loc.shape
    E, k = cfg.n_experts, cfg.top_k
    S = jax.lax.axis_size(axis_name)
    e_loc = E // S
    off = jax.lax.axis_index(axis_name) * e_loc

    top_p, top_e, aux = _router(p_loc, x_loc, cfg)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    mine = (flat_e >= off) & (flat_e < off + e_loc)
    # Capacity-gather ONLY the locally-routed assignments before the
    # grouped matmul -- computing all T*k rows on every shard costs S x the
    # necessary flops (measured: 12x compute blow-up at 61 MoE layers;
    # EXPERIMENTS.md deepseek hillclimb, iteration 2a vs 2b).
    cap = int(np.ceil(T * k / S * cfg.capacity_factor))
    rank = jnp.cumsum(mine.astype(jnp.int32)) - 1
    slot = jnp.where(mine & (rank < cap), rank, cap)
    buf = jnp.zeros((cap + 1, d), cdt).at[slot].set(x_loc[flat_tok].astype(cdt))
    eid_buf = jnp.full((cap + 1,), e_loc - 1, jnp.int32).at[slot].set(
        jnp.where(mine, flat_e - off, e_loc - 1))
    y = _grouped_ffn(p_loc, buf[:cap], eid_buf[:cap], e_loc, cdt)
    contrib = y[jnp.minimum(slot, cap - 1)]                  # (T*k, d)
    w = jnp.where(mine & (slot < cap), flat_p, 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[flat_tok].add(
        contrib.astype(jnp.float32) * w[:, None])
    out = jax.lax.psum(out, axis_name).astype(cdt)
    return out, aux / S


def moe_apply_local(p, x, cfg, *, cdt=jnp.bfloat16):
    """Single-shard MoE (smoke tests / 1-device runs)."""
    T, d = x.shape
    top_p, top_e, aux = _router(p, x, cfg)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), cfg.top_k)
    y = _grouped_ffn(p, x[flat_tok].astype(cdt), flat_e, cfg.n_experts, cdt)
    out = jnp.zeros((T, d), jnp.float32).at[flat_tok].add(
        y.astype(jnp.float32) * flat_p[:, None])
    out = out.astype(cdt)
    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x.astype(cdt), cdt)
    return out, aux
