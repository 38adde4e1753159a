"""Coded per-step projections for serving: Eq.-23 generalized to the model.

The paper's coded matmul computes ``y = x @ W`` as a row-block-coded job
on ``A = W^T``: the master encodes A's row blocks once, worker *n* holds
shard ``C[n]`` (blk, d_in) and per step computes ``C[n] @ x^T``; any
decodable responder prefix reconstructs ``y^T``.  PR 5 applied this to
the unembed only.  This module applies it to **every** per-step
projection the :class:`~repro.api.spec.ServeSpec` selects:

* ``qkv`` — attention q|k|v stacked (they share the post-norm input), or
  MLA's wq|w_dkv stacked;
* ``o``   — the output projection (``wo`` flattened to 2-D);
* ``up``  — FFN up (gate|up stacked for swiglu);
* ``down``— FFN down;
* the unembed (always coded unless ``coded_layers="none"``).

Weights are encoded **once** at serve start (they are what lives on the
workers); only activations move per step.  All sites of a step share ONE
straggler plan and ONE decode mask — the whole decode step, every coded
site included, runs as a single jitted dispatch (``build_coded_logits``),
with the mask and the per-site wire material (``encrypt="real"``) as
runtime arguments so admission/eviction churn and responder churn never
retrigger compilation.

The non-matmul ops (bias, qk-norm, RoPE, softmax, activations, norms)
stay on the master, shared op-for-op with the plain decode path via the
projection hooks in ``models.attention`` / ``models.layers`` — greedy
decode tokens are bit-comparable across ``coded_layers`` settings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels.ops import berrut_combine
from .layers import apply_norm, dtype_of, embed, unembed
from .transformer import decode_layer, layer_desc

__all__ = ["SiteMeta", "ServingCode", "layer_sites", "encode_serving_weights",
           "build_coded_logits", "coded_flop_fraction"]

# deterministic site iteration order (material assignment, t_comp sums)
SITE_ORDER = ("qkv", "o", "up", "down")

# the workers' shard matmuls run at full f32 precision: the shards are
# f32, and on TPU a default-precision f32 matmul rounds its operands to
# bf16, an error the decode amplifies (read at trace time)
SITE_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SiteMeta:
    """Static description of one coded projection site ``y = x @ W``."""
    name: str
    d_in: int
    d_out: int                    # true output width (pre block padding)
    split: Tuple[int, ...]        # stacked projection widths (Σ == d_out)
    blk: int = 0                  # coded shard rows (set at encode time)


def _ordered(metas: Dict[str, SiteMeta]):
    return [n for n in SITE_ORDER if n in metas]


def layer_sites(cfg: ModelConfig, desc, coded_layers: str) -> Dict[str, SiteMeta]:
    """The coded sites of one layer under a ``coded_layers`` setting.

    MoE and SSM (mamba/rwkv) mixers have no fixed ``x @ W`` to pre-encode
    (data-dependent routing / recurrence) and stay uncoded — they only
    show up in the FLOP-fraction denominator.  MLA's latent w_uk/w_uv
    contractions are per-head maps, also kept on the master.
    """
    sites: Dict[str, SiteMeta] = {}
    want_attn = coded_layers in ("attn", "all")
    want_ffn = coded_layers in ("ffn", "all")
    d = cfg.d_model
    if want_attn and desc.mixer == "attn":
        hd, hq, kv = cfg.head_dim_, cfg.n_heads_padded, cfg.n_kv_heads_padded
        sites["qkv"] = SiteMeta("qkv", d, (hq + 2 * kv) * hd,
                                (hq * hd, kv * hd, kv * hd))
        sites["o"] = SiteMeta("o", hq * hd, d, (d,))
    elif want_attn and desc.mixer == "mla":
        h = cfg.n_heads_padded
        qw = h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        dkv = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        sites["qkv"] = SiteMeta("qkv", d, qw + dkv, (qw, dkv))
        sites["o"] = SiteMeta("o", h * cfg.v_head_dim, d, (d,))
    if want_ffn and desc.ffn == "dense":
        ff = cfg.d_ff
        if cfg.activation == "swiglu":
            sites["up"] = SiteMeta("up", d, 2 * ff, (ff, ff))
        else:
            sites["up"] = SiteMeta("up", d, ff, (ff,))
        sites["down"] = SiteMeta("down", ff, d, (d,))
    return sites


def _site_weight(lp, name: str, cfg: ModelConfig, desc):
    """The stacked (d_in, d_out) weight matrix of one site, in compute
    dtype (the values the plain path multiplies by)."""
    cd = dtype_of(cfg, "compute")
    d = cfg.d_model
    if name == "qkv" and desc.mixer == "attn":
        m = lp["mixer"]
        w = jnp.concatenate([m["wq"].reshape(d, -1), m["wk"].reshape(d, -1),
                             m["wv"].reshape(d, -1)], axis=1)
    elif name == "qkv":                                   # mla
        m = lp["mixer"]
        w = jnp.concatenate([m["wq"].reshape(d, -1), m["w_dkv"]], axis=1)
    elif name == "o":
        w = lp["mixer"]["wo"].reshape(-1, d)
    elif name == "up":
        f = lp["ffn"]
        w = (jnp.concatenate([f["w_gate"], f["w_up"]], axis=1)
             if cfg.activation == "swiglu" else f["w_up"])
    else:                                                 # down
        w = lp["ffn"]["w_down"]
    return w.astype(cd)


@dataclasses.dataclass
class ServingCode:
    """Pre-encoded serving weights + static site metadata for one model.

    ``arrays`` is the traced pytree handed to the jitted step:
    ``{"prelude": [{site: C (N, blk, d_in)}], "group": {"pos{i}": {site:
    C (G, N, blk, d_in)}}, "unembed": C | {}}``.  Group sites ride the
    group scan as xs, so the per-position HLO stays flat in depth.
    """
    coded_layers: str
    n_workers: int
    prelude_meta: List[Dict[str, SiteMeta]]
    group_meta: Dict[str, Dict[str, SiteMeta]]
    unembed_meta: Optional[SiteMeta]
    n_groups: int
    period: int
    arrays: Dict[str, Any]

    def _instances(self):
        """(scope, key, name, meta, count) per coded site, in material
        -assignment order — group sites take ``n_groups`` consecutive
        material pairs each."""
        for i, metas in enumerate(self.prelude_meta):
            for name in _ordered(metas):
                yield ("prelude", i, name, metas[name], 1)
        for i in range(self.period):
            metas = self.group_meta[f"pos{i}"]
            for name in _ordered(metas):
                yield ("group", f"pos{i}", name, metas[name], self.n_groups)
        if self.unembed_meta is not None:
            yield ("unembed", None, "unembed", self.unembed_meta, 1)

    @property
    def n_instances(self) -> int:
        """Coded site instances per step = wire-material pairs needed."""
        return sum(c for *_, c in self._instances())

    def site_shapes(self, batch: int):
        """One (lhs, rhs) per site instance: the per-worker shard matmul
        ``C[n] (blk, d_in) @ x^T (d_in, B)`` — feeds the virtual clock's
        worker pricing (a worker runs all its shards back-to-back)."""
        shapes = []
        for *_, meta, count in self._instances():
            shapes.extend([((meta.blk, meta.d_in), (meta.d_in, batch))] * count)
        return shapes

    def wire_elems(self, batch: int) -> Tuple[int, int]:
        """Per-channel wire payload element counts (out: activations to
        every worker; back: shard results) for crypto-time attribution."""
        out = back = 0
        for *_, meta, count in self._instances():
            out += count * batch * meta.d_in
            back += count * meta.blk * batch
        return out, back

    def step_materials(self, engine):
        """Fresh per-site wire material for ONE step, shaped like
        ``arrays`` (leaves: (out, back) each (N, W); group leaves
        (G, N, W)) so the group scan slices them alongside the weights."""
        out, back = engine.serve_wire_material(self.n_instances)
        mats: Dict[str, Any] = {"prelude": [dict() for _ in self.prelude_meta],
                                "group": {f"pos{i}": {}
                                          for i in range(self.period)}}
        idx = 0
        for scope, key, name, _meta, count in self._instances():
            o = jnp.asarray(out[idx:idx + count])
            b = jnp.asarray(back[idx:idx + count])
            idx += count
            if scope == "prelude":
                mats["prelude"][key][name] = (o[0], b[0])
            elif scope == "group":
                mats["group"][key][name] = (o, b)
            else:
                mats["unembed"] = (o[0], b[0])
        return mats


def encode_serving_weights(scheme, model, params,
                           coded_layers: str) -> ServingCode:
    """Host-side, once per Session×model: encode every selected site's
    ``W^T`` into its (N, blk, d_in) worker shards."""
    cfg = model.cfg

    def enc(meta: SiteMeta, w2d) -> Tuple[SiteMeta, jnp.ndarray]:
        c = scheme.encode(jnp.asarray(w2d, jnp.float32).T)   # (N, blk, d_in)
        return dataclasses.replace(meta, blk=int(c.shape[1])), c

    prelude_meta, prelude_arrays = [], []
    for i, lp in enumerate(params["prelude"]):
        desc = layer_desc(cfg, i)
        metas = layer_sites(cfg, desc, coded_layers)
        arrays = {}
        for name in _ordered(metas):
            metas[name], arrays[name] = enc(metas[name],
                                            _site_weight(lp, name, cfg, desc))
        prelude_meta.append(metas)
        prelude_arrays.append(arrays)

    group_meta, group_arrays = {}, {}
    for i in range(model.period):
        desc = model.descs[i]
        metas = layer_sites(cfg, desc, coded_layers)
        arrays = {}
        for name in _ordered(metas):
            shards = []
            for g in range(model.n_groups):
                lp = jax.tree.map(lambda a: a[g], params["groups"][f"pos{i}"])
                m, c = enc(metas[name], _site_weight(lp, name, cfg, desc))
                shards.append(c)
            metas[name] = m
            arrays[name] = jnp.stack(shards)                 # (G, N, blk, d)
        group_meta[f"pos{i}"] = metas
        group_arrays[f"pos{i}"] = arrays

    unembed_meta = None
    tree: Dict[str, Any] = {"prelude": prelude_arrays, "group": group_arrays,
                            "unembed": {}}
    if coded_layers != "none":
        emb = params["embedding"]
        wt = emb["table"].T if cfg.tie_embeddings else emb["unembed"]
        unembed_meta = SiteMeta("unembed", cfg.d_model, cfg.vocab_size,
                                (cfg.vocab_size,))
        unembed_meta, tree["unembed"] = enc(unembed_meta,
                                            wt.astype(dtype_of(cfg, "compute")))
    return ServingCode(coded_layers=coded_layers, n_workers=scheme.n_workers,
                       prelude_meta=prelude_meta, group_meta=group_meta,
                       unembed_meta=unembed_meta, n_groups=model.n_groups,
                       period=model.period, arrays=tree)


# --------------------------------------------------------------------------
# the coded step program
# --------------------------------------------------------------------------

def _coded_apply(c, x2d, dec_w, meta: SiteMeta, *, wire=None, mats=None,
                 force_kernel=None):
    """One coded site inside the step program.  ``c`` (N, blk, d_in)
    pre-encoded shards; ``x2d`` (B, d_in); ``dec_w`` (K, N) masked Berrut
    decode weights.  Returns (B, d_out) f32.

    This is the Eq.-23 layout with the encode hoisted out of the round:
    serving encodes each projection weight once at start-up, so per step
    only activations move — worker *n* computes ``c[n] @ x^T`` (at
    ``SITE_PRECISION``) and the masked decode is the
    :func:`berrut_combine` contraction the per-round path runs.

    With a wire (``encrypt="real"``), both transfers of the site cross
    the PR 6 one-dispatch cipher: the activations out to every worker
    (each worker gets its own ciphertext of x) and the shard results
    back — the bits codec keeps the round trip bit-identical, so the
    wired step equals the plain step exactly.
    """
    xf = x2d.astype(jnp.float32)
    xs = jnp.broadcast_to(xf[None], (c.shape[0],) + xf.shape)
    if wire is not None:
        xs = wire(xs, mats[0])
    results = jnp.einsum("nbd,nBd->nbB", c.astype(jnp.float32), xs,
                         precision=SITE_PRECISION)
    if wire is not None:
        results = wire(results, mats[1])
    dec = berrut_combine(dec_w, results, force_kernel=force_kernel)
    return dec.reshape(-1, x2d.shape[0])[: meta.d_out].T


def _layer_proj(cfg: ModelConfig, desc, metas, arrays, dec_w, *, wire=None,
                mats=None, force_kernel=None):
    """The ``proj`` dict for :func:`models.transformer.decode_layer`:
    closures running this layer's coded sites against the shared step
    decode weights."""
    if not metas:
        return None
    cd = dtype_of(cfg, "compute")
    mats = mats or {}

    def run(name, x2d):
        return _coded_apply(arrays[name], x2d, dec_w, metas[name], wire=wire,
                            mats=mats.get(name), force_kernel=force_kernel)

    proj: Dict[str, Any] = {}
    if "qkv" in metas:
        if desc.mixer == "attn":
            hd, hq, kvh = cfg.head_dim_, cfg.n_heads_padded, cfg.n_kv_heads_padded

            def qkv(x):                                   # (B,1,d)
                b = x.shape[0]
                y = run("qkv", x.reshape(b, -1)).astype(cd)
                s0, s1, _ = metas["qkv"].split
                return (y[:, :s0].reshape(b, 1, hq, hd),
                        y[:, s0:s0 + s1].reshape(b, 1, kvh, hd),
                        y[:, s0 + s1:].reshape(b, 1, kvh, hd))
        else:                                             # mla: wq | w_dkv

            def qkv(x):
                b = x.shape[0]
                y = run("qkv", x.reshape(b, -1)).astype(cd)
                qw = metas["qkv"].split[0]
                h = cfg.n_heads_padded
                return (y[:, :qw].reshape(
                            b, 1, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
                        y[:, None, qw:])
        proj["qkv"] = qkv
    if "o" in metas:
        if desc.mixer == "attn":
            def o_fn(out):                                # (B,1,f) -> (B,1,d)
                b = out.shape[0]
                return run("o", out.reshape(b, -1)).astype(cd)[:, None, :]
        else:
            def o_fn(o2d):                                # (B,h·vh) -> (B,d)
                return run("o", o2d).astype(cd)
        proj["o"] = o_fn
    if "up" in metas:
        if cfg.activation == "swiglu":
            def up_fn(x):                                 # -> (gate, up)
                b = x.shape[0]
                y = run("up", x.reshape(b, -1)).astype(cd)
                ff = metas["up"].split[0]
                return y[:, None, :ff], y[:, None, ff:]
        else:
            def up_fn(x):
                b = x.shape[0]
                return run("up", x.reshape(b, -1)).astype(cd)[:, None, :]
        proj["up"] = up_fn
    if "down" in metas:
        def down_fn(h):                                   # (B,1,ff) -> (B,1,d)
            b = h.shape[0]
            return run("down", h.reshape(b, -1)).astype(cd)[:, None, :]
        proj["down"] = down_fn
    return proj


def build_coded_logits(model, scheme, code: ServingCode, *,
                       wire_params=None):
    """The whole-step program up to the logits: embed → every layer with
    its projections routed through coded sites → coded unembed.  The
    serve loop jits it with a greedy argmax on top, ONE dispatch per pow2
    batch bucket; the logits themselves are what parity checks compare.

    Returns ``logits_step(params, cache, tokens (B,1), pos (B,), mask
    (N,), weights, materials) -> (logits (B, V) f32, new_cache)``.
    ``mask``, ``pos`` and ``materials`` are runtime arguments — responder
    churn, slot churn inside a bucket and fresh nonces never retrace.
    """
    cfg = model.cfg
    force_kernel = scheme.use_kernel
    if wire_params is not None:
        q, mode = wire_params
        from ..kernels.encrypted_round import wire_roundtrip

        # the specialized bits-codec wires on every platform, as in the
        # encrypted rounds (see kernels.encrypted_round): the general
        # Pallas mask_add carry chain moves all L limb planes where these
        # move 3 (stream) or 1 plus a selector byte (paper)
        def wire(payload, mat):
            return wire_roundtrip(payload, mat, q=q, mode=mode)
    else:
        wire = None

    use_wire = wire is not None

    def logits_step(params, cache, tokens, pos, mask, weights, materials):
        dec_w = scheme.decode_matrix_masked(mask)          # (K, N)
        x = embed(params["embedding"], tokens, cfg)
        new_pre = []
        for i, lp in enumerate(params["prelude"]):
            desc = layer_desc(cfg, i)
            proj = _layer_proj(
                cfg, desc, code.prelude_meta[i], weights["prelude"][i], dec_w,
                wire=wire, mats=materials["prelude"][i] if use_wire else None,
                force_kernel=force_kernel)
            x, nc = decode_layer(lp, x, cfg, desc, cache=cache["prelude"][i],
                                 pos=pos, proj=proj)
            new_pre.append(nc)

        def group_body(x, xs):
            if use_wire:
                gp, gc, gw, gm = xs
            else:
                (gp, gc, gw), gm = xs, {}
            new_gc = {}
            for i in range(model.period):
                desc = model.descs[i]
                proj = _layer_proj(cfg, desc, code.group_meta[f"pos{i}"],
                                   gw[f"pos{i}"], dec_w, wire=wire,
                                   mats=gm.get(f"pos{i}") if use_wire else None,
                                   force_kernel=force_kernel)
                x, new_gc[f"pos{i}"] = decode_layer(
                    gp[f"pos{i}"], x, cfg, desc, cache=gc[f"pos{i}"],
                    pos=pos, proj=proj)
            return x, new_gc

        xs = (params["groups"], cache["groups"], weights["group"])
        if use_wire:
            xs = xs + (materials["group"],)
        x, new_groups = jax.lax.scan(group_body, x, xs)
        x = apply_norm(params["final_norm"], x, cfg)
        if code.unembed_meta is not None:
            logits = _coded_apply(weights["unembed"], x[:, 0, :], dec_w,
                                  code.unembed_meta, wire=wire,
                                  mats=materials["unembed"] if use_wire else None,
                                  force_kernel=force_kernel)
            if cfg.logit_softcap:
                logits = cfg.logit_softcap * jnp.tanh(
                    logits / cfg.logit_softcap)
        else:
            logits = unembed(params["embedding"], x, cfg)[:, 0, :]
        return (logits.astype(jnp.float32),
                {"prelude": new_pre, "groups": new_groups})

    return logits_step



# --------------------------------------------------------------------------
# analytic coded FLOP fraction
# --------------------------------------------------------------------------

def coded_flop_fraction(cfg: ModelConfig, coded_layers: str = "all",
                        ctx_len: int = 2048) -> float:
    """Coded fraction of one decode step's matmul FLOPs, analytic from the
    model config (the acceptance gate's "reported from the model config").

    Counts every per-token matmul: projections, attention score/value
    contractions at ``ctx_len`` cached tokens, FFN, unembed.  MoE and SSM
    mixers are uncoded (coarse FLOP estimates — they only widen the
    denominator); the common factor 2 (multiply-add) cancels.
    """
    if coded_layers == "none":
        return 0.0
    want_attn = coded_layers in ("attn", "all")
    want_ffn = coded_layers in ("ffn", "all")
    d = cfg.d_model
    coded = total = 0.0
    for idx in range(cfg.n_layers):
        desc = layer_desc(cfg, idx)
        if desc.mixer == "attn":
            hd, hq, kv = cfg.head_dim_, cfg.n_heads_padded, cfg.n_kv_heads_padded
            proj = d * (hq + 2 * kv) * hd + hq * hd * d
            total += proj + 2 * ctx_len * hq * hd          # scores + values
            if want_attn:
                coded += proj
        elif desc.mixer == "mla":
            h = cfg.n_heads_padded
            nope, rp = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            lora, vh = cfg.kv_lora_rank, cfg.v_head_dim
            site = d * h * (nope + rp) + d * (lora + rp) + h * vh * d
            latent = (h * nope * lora + h * ctx_len * (lora + rp)
                      + h * ctx_len * lora + h * lora * vh)
            total += site + latent
            if want_attn:
                coded += site
        elif desc.mixer == "mamba":
            e = cfg.expand
            total += 3 * e * d * d + e * d * 3 * cfg.d_state
        elif desc.mixer == "rwkv":
            total += 8 * d * d
        if desc.ffn == "dense":
            f = (3 if cfg.activation == "swiglu" else 2) * d * cfg.d_ff
            total += f
            if want_ffn:
                coded += f
        elif desc.ffn == "moe":
            experts = cfg.top_k + (cfg.n_shared_experts or 0)
            total += (experts * 3 * d * cfg.moe_d_ff + d * cfg.n_experts)
    unemb = d * cfg.vocab_size
    total += unemb
    coded += unemb
    return coded / total
