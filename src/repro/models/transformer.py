"""Decoder-only LM assembly for all non-enc-dec architectures.

Layers are organized as  [prelude (unrolled)] + scan over G groups of
``period`` layers, where ``period`` is the repeat length of the arch's
layer pattern (1 dense; 4 llama4 NoPE; 8 jamba mamba/attn; ...).  The scan
keeps HLO size and compile time flat in depth; ``jax.checkpoint`` on the
group body gives per-group remat for training.

Each layer position has a static descriptor (mixer kind, ffn kind, rope?)
derived from the ModelConfig, so one code path serves dense, MoE, SSM and
hybrid archs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_mod
from . import ssm
from .layers import (apply_ffn, apply_norm, dtype_of, embed, embedding_specs,
                     ffn_specs, init_embedding, init_ffn, init_norm,
                     norm_specs, unembed)


# --------------------------------------------------------------------------
# layer descriptors
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerDesc:
    mixer: str          # "attn" | "mla" | "mamba" | "rwkv"
    ffn: str            # "dense" | "moe" | "none"
    rope: bool


def layer_desc(cfg: ModelConfig, idx: int) -> LayerDesc:
    if cfg.ssm_type == "rwkv6":
        return LayerDesc("rwkv", "none", False)
    if cfg.ssm_type == "mamba" and not cfg.is_attn_layer(idx):
        mixer = "mamba"
    elif cfg.mla:
        mixer = "mla"
    else:
        mixer = "attn"
    ffn = "moe" if cfg.is_moe_layer(idx) else "dense"
    rope = not cfg.is_nope_layer(idx)
    return LayerDesc(mixer, ffn, rope)


def layer_pattern(cfg: ModelConfig) -> Tuple[int, int, List[LayerDesc]]:
    """(n_prelude, period, group descriptors).  prelude layers are unrolled."""
    n_pre = cfg.first_dense_layers
    periods = [1]
    if cfg.moe and cfg.moe_layer_period > 1:
        periods.append(cfg.moe_layer_period)
    if cfg.attn_layer_period:
        periods.append(cfg.attn_layer_period)
    if cfg.nope_layer_period:
        periods.append(cfg.nope_layer_period)
    import math
    period = math.lcm(*periods)
    rem = cfg.n_layers - n_pre
    if rem % period:
        raise ValueError(f"{cfg.name}: {rem} layers not divisible by period {period}")
    descs = [layer_desc(cfg, n_pre + i) for i in range(period)]
    # sanity: pattern must repeat identically across groups
    for g in range(1, rem // period):
        for i in range(period):
            if layer_desc(cfg, n_pre + g * period + i) != descs[i]:
                raise ValueError(f"{cfg.name}: non-periodic layer pattern")
    return n_pre, period, descs


# --------------------------------------------------------------------------
# per-layer init / specs / apply
# --------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, desc: LayerDesc):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(ks[0], cfg)}
    if desc.mixer == "attn":
        p["mixer"] = attn.init_attention(ks[1], cfg)
    elif desc.mixer == "mla":
        p["mixer"] = attn.init_mla(ks[1], cfg)
    elif desc.mixer == "mamba":
        p["mixer"] = ssm.init_mamba_block(ks[1], cfg)
    else:  # rwkv: block includes channel mix; norm2 used for it
        p["mixer"] = ssm.init_rwkv_block(ks[1], cfg)
    if desc.ffn != "none" or desc.mixer == "rwkv":
        p["norm2"] = init_norm(ks[2], cfg)
    if desc.ffn == "dense":
        p["ffn"] = init_ffn(ks[3], cfg)
    elif desc.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(ks[3], cfg)
    return p


def layer_specs(cfg: ModelConfig, desc: LayerDesc):
    p: Dict[str, Any] = {"norm1": norm_specs(cfg)}
    if desc.mixer == "attn":
        p["mixer"] = attn.attention_specs(cfg)
    elif desc.mixer == "mla":
        p["mixer"] = attn.mla_specs(cfg)
    elif desc.mixer == "mamba":
        p["mixer"] = ssm.mamba_block_specs(cfg)
    else:
        p["mixer"] = ssm.rwkv_block_specs(cfg)
    if desc.ffn != "none" or desc.mixer == "rwkv":
        p["norm2"] = norm_specs(cfg)
    if desc.ffn == "dense":
        p["ffn"] = ffn_specs(cfg)
    elif desc.ffn == "moe":
        p["ffn"] = moe_mod.moe_specs(cfg)
    return p


def apply_layer(p, x, cfg: ModelConfig, desc: LayerDesc, *, positions,
                mrope_positions=None, state=None):
    """Full-sequence layer (train/prefill).  Returns (x, new_state, (lb, z))."""
    zero = jnp.zeros((), jnp.float32)
    lb = z = zero
    new_state = state
    h = apply_norm(p["norm1"], x, cfg)
    if desc.mixer == "rwkv":
        y, new_state = ssm.rwkv_time_mix(p["mixer"], h, state, cfg)
        x = x + y
        h2 = apply_norm(p["norm2"], x, cfg)
        y2, new_state = ssm.rwkv_channel_mix(p["mixer"], h2, new_state, cfg)
        return x + y2, new_state, (lb, z)
    if desc.mixer == "mamba":
        y, new_state = ssm.mamba_forward(p["mixer"], h, state, cfg)
    elif desc.mixer == "mla":
        y = attn.mla_forward(p["mixer"], h, cfg, positions)
    else:
        y = attn.attn_forward(p["mixer"], h, cfg, positions, use_rope=desc.rope,
                              mrope_positions=mrope_positions)
    if cfg.parallel_block:
        f = apply_ffn(p["ffn"], h, cfg)
        return x + y + f, new_state, (lb, z)
    x = x + y
    h2 = apply_norm(p["norm2"], x, cfg)
    if desc.ffn == "moe":
        f, lb, z = moe_mod.moe_ffn(p["ffn"], h2, cfg)
    else:
        f = apply_ffn(p["ffn"], h2, cfg)
    return x + f, new_state, (lb, z)


def decode_layer(p, x, cfg: ModelConfig, desc: LayerDesc, *, cache, pos,
                 mrope_positions=None, proj=None):
    """One-token layer step.  Returns (x, new_cache, here): ``here`` is
    None, or in an MoE layer (B,) int32, each row's routing choices that
    fell on the experts this layer holds.

    ``pos`` is scalar (uniform) or (B,) per-slot positions (continuous
    batching); ``proj`` optionally reroutes this layer's projection matmuls
    through coded rounds: ``{"qkv", "o"}`` feed the attention/MLA mixer,
    ``{"up", "down"}`` the dense FFN, ``{"shared_up", "shared_down"}`` an
    MoE layer's shared experts (routed experts and SSM mixers stay uncoded
    — their maps are data-dependent or recurrent, not a fixed ``x @ W``)."""
    proj = proj or {}
    h = apply_norm(p["norm1"], x, cfg)
    if desc.mixer == "rwkv":
        y, cache = ssm.rwkv_decode_step(p["mixer"], h, cache, cfg)
        x = x + y
        h2 = apply_norm(p["norm2"], x, cfg)
        y2, cache = ssm.rwkv_channel_mix_decode(p["mixer"], h2, cache, cfg)
        return x + y2, cache, None
    if desc.mixer == "mamba":
        y, cache = ssm.mamba_decode_step(p["mixer"], h, cache, cfg)
    elif desc.mixer == "mla":
        y, cache = attn.mla_decode(p["mixer"], h, cache, pos, cfg,
                                   proj={k: proj.get(k) for k in ("qkv", "o")})
    else:
        y, cache = attn.attn_decode(p["mixer"], h, cache, pos, cfg,
                                    use_rope=desc.rope,
                                    mrope_positions=mrope_positions,
                                    proj={k: proj.get(k) for k in ("qkv", "o")})
    ffn_mm = {"matmul_up": proj.get("up"), "matmul_down": proj.get("down")}
    if cfg.parallel_block:
        f = apply_ffn(p["ffn"], h, cfg, **ffn_mm)
        return x + y + f, cache, None
    x = x + y
    h2 = apply_norm(p["norm2"], x, cfg)
    here = None
    if desc.ffn == "moe":
        f, here = moe_mod.moe_ffn_decode(
            p["ffn"], h2, cfg, matmul_shared_up=proj.get("shared_up"),
            matmul_shared_down=proj.get("shared_down"))
    else:
        f = apply_ffn(p["ffn"], h2, cfg, **ffn_mm)
    return x + f, cache, here


def layer_cache(cfg: ModelConfig, desc: LayerDesc, batch: int, max_len: int):
    if desc.mixer == "rwkv":
        return ssm.init_rwkv_state(cfg, batch)
    if desc.mixer == "mamba":
        return ssm.init_mamba_state(cfg, batch)
    if desc.mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_len)
    return attn.init_kv_cache(cfg, batch, max_len)


def layer_cache_specs(cfg: ModelConfig, desc: LayerDesc):
    if desc.mixer == "rwkv":
        return ssm.rwkv_state_specs(cfg)
    if desc.mixer == "mamba":
        return ssm.mamba_state_specs(cfg)
    if desc.mixer == "mla":
        return attn.mla_cache_specs(cfg)
    return attn.kv_cache_specs(cfg)


def layer_init_state(cfg: ModelConfig, desc: LayerDesc, batch: int):
    """Train-time recurrent state for SSM mixers (zeros each step)."""
    if desc.mixer == "rwkv":
        return ssm.init_rwkv_state(cfg, batch, jnp.float32)
    if desc.mixer == "mamba":
        return ssm.init_mamba_state(cfg, batch, jnp.float32)
    return None


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

class TransformerLM:
    """Decoder-only LM: init / loss / prefill / decode with scanned groups."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_pre, self.period, self.descs = layer_pattern(cfg)
        self.n_groups = (cfg.n_layers - self.n_pre) // self.period

    # ---- params ------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        k_emb, k_pre, k_groups, k_out = jax.random.split(key, 4)
        params: Dict[str, Any] = {"embedding": init_embedding(k_emb, cfg)}
        pre_desc = [layer_desc(cfg, i) for i in range(self.n_pre)]
        params["prelude"] = [
            init_layer(k, cfg, d)
            for k, d in zip(jax.random.split(k_pre, max(self.n_pre, 1)), pre_desc)
        ] if self.n_pre else []

        def init_group(gk):
            ks = jax.random.split(gk, self.period)
            return {f"pos{i}": init_layer(ks[i], cfg, self.descs[i])
                    for i in range(self.period)}

        params["groups"] = jax.vmap(init_group)(
            jax.random.split(k_groups, self.n_groups))
        params["final_norm"] = init_norm(k_out, cfg)
        return params

    def param_specs(self):
        cfg = self.cfg
        specs: Dict[str, Any] = {"embedding": embedding_specs(cfg)}
        specs["prelude"] = [layer_specs(cfg, layer_desc(cfg, i))
                            for i in range(self.n_pre)]
        group = {f"pos{i}": layer_specs(cfg, self.descs[i])
                 for i in range(self.period)}
        # stacked leading "groups" axis is unsharded
        specs["groups"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), group,
            is_leaf=lambda s: isinstance(s, P))
        specs["final_norm"] = norm_specs(cfg)
        return specs

    def _group_specs(self):
        return {f"pos{i}": layer_specs(self.cfg, self.descs[i])
                for i in range(self.period)}

    def _unshard_group(self, gp):
        """FSDP: per-group weight all-gather in compute dtype.  Constrains
        each sliced layer weight to its TP-only spec right before use so the
        partitioner emits AG(slice) inside the loop instead of partial
        compute + activation all-reduces (measured 10× collective blowup)."""
        cfg = self.cfg
        cd = jnp.dtype(cfg.compute_dtype)
        specs = self._group_specs()

        from ..dist.sharding import add_data_axis

        def one(w, spec):
            if jnp.issubdtype(w.dtype, jnp.floating) and w.dtype != cd:
                # pin the f32 master weight as STILL sharded, cast, then
                # unshard — otherwise GSPMD hoists the all-gather above the
                # convert and gathers in f32 (2× ICI bytes, measured)
                sharded = add_data_axis(spec, w.shape)
                w = jax.lax.with_sharding_constraint(w, sharded)
                w = w.astype(cd)
            return jax.lax.with_sharding_constraint(w, spec)

        leaves_w, treedef = jax.tree.flatten(gp)
        leaves_s = jax.tree.flatten(specs, is_leaf=lambda s: isinstance(s, P))[0]
        return jax.tree.unflatten(treedef, [one(w, s) for w, s
                                            in zip(leaves_w, leaves_s)])

    # ---- forward (train / prefill) ------------------------------------
    def forward(self, params, tokens, *, mrope_positions=None):
        """tokens (B,S) -> (logits (B,S,V), aux dict)."""
        cfg = self.cfg
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = embed(params["embedding"], tokens, cfg)
        lb_tot = z_tot = jnp.zeros((), jnp.float32)

        for i, lp in enumerate(params["prelude"]):
            desc = layer_desc(cfg, i)
            st = layer_init_state(cfg, desc, b)
            x, _, (lb, z) = apply_layer(lp, x, cfg, desc, positions=positions,
                                        mrope_positions=mrope_positions, state=st)
            lb_tot, z_tot = lb_tot + lb, z_tot + z

        states = {f"pos{i}": layer_init_state(cfg, self.descs[i], b)
                  for i in range(self.period)}

        def group_body(x, gp):
            if cfg.fsdp_in_scan:
                gp = self._unshard_group(gp)
            lb_g = z_g = jnp.zeros((), jnp.float32)
            for i in range(self.period):
                x, _, (lb, z) = apply_layer(
                    gp[f"pos{i}"], x, cfg, self.descs[i], positions=positions,
                    mrope_positions=mrope_positions, state=states[f"pos{i}"])
                if cfg.seq_shard_activations:
                    # sequence parallelism: the layer-boundary residual (and
                    # thus the remat-saved scan carry) lives seq-sharded on
                    # the model axis; the partitioner inserts RS/AG pairs at
                    # the attention/FFN boundaries
                    from ..dist.sharding import shard_hint
                    x = shard_hint(x, P(None, "model", None))
                lb_g, z_g = lb_g + lb, z_g + z
            return x, (lb_g, z_g)

        body = jax.checkpoint(group_body) if cfg.remat else group_body
        x, (lbs, zs) = jax.lax.scan(body, x, params["groups"])
        lb_tot = lb_tot + jnp.sum(lbs)
        z_tot = z_tot + jnp.sum(zs)

        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embedding"], x, cfg)
        return logits, {"lb_loss": lb_tot, "z_loss": z_tot}

    def loss_fn(self, params, batch):
        """batch: tokens (B,S), targets (B,S); optional mrope_positions."""
        logits, aux = self.forward(params, batch["tokens"],
                                   mrope_positions=batch.get("mrope_positions"))
        ce = softmax_xent(logits, batch["targets"])
        loss = ce + 0.01 * aux["lb_loss"] + 1e-3 * aux["z_loss"]
        return loss, {"ce": ce, **aux}

    # ---- decode --------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        cache: Dict[str, Any] = {
            "prelude": [layer_cache(cfg, layer_desc(cfg, i), batch, max_len)
                        for i in range(self.n_pre)],
        }
        group = {f"pos{i}": layer_cache(cfg, self.descs[i], batch, max_len)
                 for i in range(self.period)}
        cache["groups"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self.n_groups,) + a.shape).copy(),
            group)
        return cache

    def cache_specs(self):
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "prelude": [layer_cache_specs(cfg, layer_desc(cfg, i))
                        for i in range(self.n_pre)],
        }
        group = {f"pos{i}": layer_cache_specs(cfg, self.descs[i])
                 for i in range(self.period)}
        specs["groups"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), group,
            is_leaf=lambda s: isinstance(s, P))
        return specs

    def decode_step(self, params, cache, tokens, pos, *, mrope_positions=None,
                    return_hidden: bool = False):
        """tokens (B,1), pos scalar -> (logits (B,1,V), new cache);
        ``return_hidden`` yields the pre-unembed hidden state instead of
        logits (the coded serving path runs the output projection as a
        distributed round — see ``repro.api.Session.serve``)."""
        cfg = self.cfg
        x = embed(params["embedding"], tokens, cfg)
        new_pre = []
        for i, lp in enumerate(params["prelude"]):
            x, nc, _ = decode_layer(lp, x, cfg, layer_desc(cfg, i),
                                    cache=cache["prelude"][i], pos=pos,
                                    mrope_positions=mrope_positions)
            new_pre.append(nc)

        def group_body(x, xs):
            gp, gc = xs
            new_gc = {}
            for i in range(self.period):
                x, new_gc[f"pos{i}"], _ = decode_layer(
                    gp[f"pos{i}"], x, cfg, self.descs[i],
                    cache=gc[f"pos{i}"], pos=pos,
                    mrope_positions=mrope_positions)
            return x, new_gc

        x, new_groups = jax.lax.scan(group_body, x,
                                     (params["groups"], cache["groups"]))
        x = apply_norm(params["final_norm"], x, cfg)
        new_cache = {"prelude": new_pre, "groups": new_groups}
        if return_hidden:
            return x, new_cache
        logits = unembed(params["embedding"], x, cfg)
        return logits, new_cache


def softmax_xent(logits, targets):
    """Mean CE; vocab axis may be sharded (GSPMD inserts the reductions).
    targets == -1 are masked out."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.maximum(targets, 0)
    picked = jnp.take_along_axis(logits.astype(jnp.float32),
                                 tgt[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
