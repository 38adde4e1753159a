"""Mixture-of-Experts: GShard-style capacity routing, expert-parallel layout.

Router (``_router``): float32 scores over all ``n_experts`` routed experts,
softmax or sigmoid (DeepSeek-V3).  Sigmoid routers pick the top-k by score
plus ``e_score_correction_bias``, a selection-only bias: the chosen
experts' weights are their scores.  ``norm_topk_prob`` rescales the k
weights to sum to 1, then ``routed_scaling_factor`` multiplies them.

Held share: a layer holds the weights of only ``cfg.experts_held``
(first, count) of the routed experts — its chip's share under expert
parallelism.  It still routes over all of them and adds the weighted
output of the choices that fall on its own experts; shared experts are
computed in full.  What the other chips' experts would add is not here:
on one chip there is no exchange, and nothing stands in for it.

Train/prefill path (``moe_ffn``): per-sequence token-choice routing —
positions-in-expert via cumulative counts (no sort, no (S,E,C) dispatch
tensor), scatter into (B, E_held, C, d) expert buckets, batched expert
matmuls, gather+weighted-combine back.  Expert axis E is sharded over the
`model` mesh axis (expert parallelism): the scatter/gather over the
sharded E dim partitions into masked ops + an all-reduce — the GSPMD
analogue of the MoE all-to-all.

Decode path (``moe_ffn_decode``): every held expert runs on every row in
one pass (``kernels.ops.held_experts``), each row weighted by its gate —
decode is bound by reading the expert weights, so no routing scatter.

Experts are SwiGLU blocks.  Shared experts (DeepSeek/Llama4) are one plain
SwiGLU FFN of width ``n_shared_experts * moe_d_ff`` added to the routed
output.  Router z-loss and load-balance aux loss are returned for the
train loop.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, pad_to
from ..dist.sharding import shard_hint
from ..kernels.ops import held_experts
from .layers import apply_ffn, dense_init, dtype_of

__all__ = ["init_moe", "moe_specs", "moe_ffn", "moe_ffn_decode"]


def init_moe(key, cfg: ModelConfig):
    if cfg.activation != "swiglu":
        raise ValueError(f"{cfg.name}: MoE experts are SwiGLU blocks")
    d, ff = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    e = cfg.experts_held[1]
    pd = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    # the router keeps its published width whatever share is held
    p = {"router": dense_init(ks[0], (d, cfg.n_experts), pd, scale=0.02)}
    if cfg.router_scoring == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((cfg.n_experts,), pd)
    p["w_gate"] = dense_init(ks[1], (e, d, ff), pd)
    p["w_up"] = dense_init(ks[2], (e, d, ff), pd)
    p["w_down"] = dense_init(ks[3], (e, ff, d), pd)
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        sks = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(sks[0], (d, sf), pd),
                       "w_up": dense_init(sks[1], (d, sf), pd),
                       "w_down": dense_init(sks[2], (sf, d), pd)}
    return p


def moe_specs(cfg: ModelConfig):
    p = {"router": P(None, None)}
    if cfg.router_scoring == "sigmoid":
        p["e_score_correction_bias"] = P(None)
    p.update(w_gate=P("model", None, None), w_up=P("model", None, None),
             w_down=P("model", None, None))
    if cfg.n_shared_experts:
        p["shared"] = {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                       "w_down": P("model", None)}
    return p


def _router(p, x, cfg: ModelConfig):
    """x (..., d) -> (weights (..., k) f32, idx (..., k) of all experts,
    aux losses)."""
    f32 = jnp.float32
    logits = jnp.matmul(x.astype(f32), p["router"].astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = p["e_score_correction_bias"].astype(f32)
        _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    # aux: load-balance (Switch) + router z-loss
    me = jnp.mean(scores.reshape(-1, cfg.n_experts), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(idx.reshape(-1, cfg.top_k), cfg.n_experts).sum(1), axis=0)
    lb_loss = cfg.n_experts * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return w, idx, lb_loss, z_loss


def _held(idx, cfg: ModelConfig):
    """(local index of each choice among the held experts, -1 where the
    choice lies on another chip's expert; held mask)."""
    first, count = cfg.experts_held
    local = idx - first
    held = (local >= 0) & (local < count)
    return jnp.where(held, local, -1), held


def _shared_ffn(p, x, cfg: ModelConfig, **matmuls):
    return apply_ffn(p["shared"], x, cfg, **matmuls)


def _expert_apply(p, buckets, cfg: ModelConfig):
    """buckets (B, E, C, d) -> (B, E, C, d) through per-expert FFN."""
    cd = dtype_of(cfg, "compute")
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", buckets, p["w_gate"].astype(cd)))
         * jnp.einsum("becd,edf->becf", buckets, p["w_up"].astype(cd)))
    return jnp.einsum("becf,efd->becd", h, p["w_down"].astype(cd))


def moe_ffn(p, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out, lb_loss, z_loss).  Per-sequence capacity
    routing over all experts; the held ones compute."""
    cd = dtype_of(cfg, "compute")
    x = x.astype(cd)
    b, s, d = x.shape
    e, k = cfg.experts_held[1], cfg.top_k
    cap = pad_to(max(int(s * k / cfg.n_experts * cfg.capacity_factor), 4), 4)

    w, idx, lb_loss, z_loss = _router(p, x, cfg)         # (B,S,k)
    local, held = _held(idx, cfg)

    # position of each (token, choice) within its held expert, per sequence
    onehot = jax.nn.one_hot(local, e, dtype=jnp.int32)   # (B,S,k,E); -1 -> 0s
    flat = onehot.reshape(b, s * k, e)
    pos_all = jnp.cumsum(flat, axis=1) - 1               # (B,S*k,E) exclusive count
    local = jnp.maximum(local, 0)
    pos = jnp.take_along_axis(
        pos_all.reshape(b, s, k, e), local[..., None], axis=-1)[..., 0]  # (B,S,k)
    keep = held & (pos < cap)

    # scatter tokens into (B, E, C, d) buckets (dropped -> clamped, zeroed)
    pos_c = jnp.clip(pos, 0, cap - 1)
    vals = jnp.broadcast_to(x[:, :, None, :], (b, s, k, d)) * keep[..., None].astype(cd)
    # batched scatter (vmap over the sequence row) — lowers to a scatter with
    # batching dims, which the partitioner splits along batch cleanly (an
    # explicit leading batch index array would not).  Dropped (over-capacity
    # or not held) tokens route to a dedicated dump slot so they can never
    # collide with a live slot.
    slot = jnp.where(keep, local * cap + pos_c, e * cap)  # (B,S,k) in [0, E*cap]

    def dispatch_one(vals_b, slot_b):
        return jnp.zeros((e * cap + 1, d), cd).at[slot_b.reshape(-1)].add(
            vals_b.reshape(-1, d))[: e * cap]

    buckets = jax.vmap(dispatch_one)(vals, slot).reshape(b, e, cap, d)
    # The scatter defeats GSPMD propagation: re-pin the expert buckets
    # (E over model).  Batch shards over data when it divides (the prefill
    # path); under the train vmap b==1 and spmd_axis_name re-inserts the
    # block axis instead.
    b_ax = "data" if (b % 16 == 0) else None
    buckets = shard_hint(buckets, P(b_ax, "model", None, None))

    out_b = _expert_apply(p, buckets, cfg)               # (B,E,C,d)
    out_b = shard_hint(out_b, P(b_ax, "model", None, None))

    # Combine on the bucket side: scale each slot by its router weight and
    # scatter-add slots back to tokens.  Each model shard only touches its
    # local experts' slots, so the cross-shard reduction is an all-reduce of
    # (S, d) — k× smaller than gathering (S, k, d) first.
    w_cd = (w * keep).astype(cd)                         # (B,S,k)

    def combine_one(ob_flat, slot_b, w_b):
        # slot -> (router weight, destination token); dump slot e*cap inert
        w_slot = jnp.zeros((e * cap + 1,), cd).at[slot_b.reshape(-1)].add(
            w_b.reshape(-1))
        tok = jnp.full((e * cap + 1,), s, jnp.int32).at[slot_b.reshape(-1)].set(
            jnp.repeat(jnp.arange(s, dtype=jnp.int32), k))
        ob_pad = jnp.concatenate([ob_flat, jnp.zeros((1, d), cd)], axis=0)
        scaled = ob_pad * w_slot[:, None]
        return jnp.zeros((s + 1, d), cd).at[tok].add(scaled)[:s]

    combined = jax.vmap(combine_one)(out_b.reshape(b, e * cap, d), slot, w_cd)
    if cfg.n_shared_experts:
        combined = combined + _shared_ffn(p, x, cfg)
    return combined, lb_loss, z_loss


def moe_ffn_decode(p, x, cfg: ModelConfig, *, matmul_shared_up=None,
                   matmul_shared_down=None):
    """x (B, 1, d) -> (out (B, 1, d), here (B,) int32): every held expert
    on every row, weighted by the row's gate (zero where it did not choose
    that expert), plus the shared experts; ``here`` counts each row's
    choices that fell on a held expert.  ``matmul_shared_*`` optionally
    replace the shared experts' projection matmuls (the coded serve path;
    see ``layers.apply_ffn``)."""
    cd = dtype_of(cfg, "compute")
    x2 = x[:, 0].astype(cd)                              # (B, d)
    w, idx, _, _ = _router(p, x2, cfg)                   # (B,k)
    local, held = _held(idx, cfg)
    chose = local[..., None] == jnp.arange(cfg.experts_held[1])
    gates = jnp.sum(jnp.where(chose, w[..., None], 0.0), axis=1)  # (B, E_held)
    out = held_experts(x2, gates, p["w_gate"], p["w_up"],
                       p["w_down"]).astype(cd)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x2, cfg, matmul_up=matmul_shared_up,
                                matmul_down=matmul_shared_down)
    return out[:, None, :], jnp.sum(held, axis=-1, dtype=jnp.int32)
