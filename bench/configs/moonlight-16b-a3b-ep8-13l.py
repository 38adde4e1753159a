"""Plain reference of ``moonlight-16b-a3b-ep8-13l`` as the deployment serves
it: Moonlight-16B-A3B (arXiv:2502.16982), a DeepSeek-V3 model
(arXiv:2412.19437), on one chip's expert-parallel share, over a whole
request at once, in float32 at full matmul precision, with every coded
projection computed as the coded deployment computes it.

Layers: RMSNorm; multi-head latent attention written out in the plain
form (queries from ``wq``; the compressed kv ``w_dkv``, its latent half
RMS-normalised, expanded to per-head keys and values by ``w_uk`` and
``w_uv``; the rope half rotated as one shared key head; causal softmax at
1/sqrt(qk_nope + qk_rope); ``wo``); then the first layer's SwiGLU FFN, or
an MoE layer: the sigmoid router over all published experts, the top-k
picked by score plus ``e_score_correction_bias``, the chosen scores
renormalised and scaled, the held experts' SwiGLU outputs weighted by the
choices that fell on them, plus the shared experts.  What the other
chips' experts would add is left out, as in the program.  The rope is the
half-split rotation (the configuration's ``assumed``).

A coded projection ``y = x @ W`` (attention q|kv and output, the dense
FFN, the shared experts, the unembed) is approximate by design: the master
holds W^T's K row blocks and T noise blocks as N Berrut-coded shards, and
decodes y^T from the shard products of the workers that answered.  Since
the code is linear, decoded block k = sum_j M[k, j] (S_j x^T) with S the
K + T blocks and M = decode(responders) @ encode(responders).  The
reference multiplies x by every block once and mixes the products with
each position's own M: position t was fed in one serving step, and that
step's responders decoded every projection of it.  The router, the
routed experts and the latent w_uk / w_uv maps are uncoded.

The control computes the same weight products one precision below the
configuration's bfloat16: each product's operands scaled into float8
(e4m3) range, rounded to it, and multiplied in float32.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spacdc_ref  # noqa: E402

SITES = ("qkv", "o", "up", "down", "shared_up", "shared_down", "unembed")
CODED = {"none": (), "unembed": ("unembed",),
         "attn": ("qkv", "o", "unembed"),
         "ffn": ("up", "down", "shared_up", "shared_down", "unembed"),
         "all": SITES}


def sizes(config: dict) -> dict:
    c = config
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "lora": c["kv_lora_rank"], "vh": c["v_head_dim"],
            "ff": c["intermediate_size"],
            "sf": c["n_shared_experts"] * c["moe_intermediate_size"],
            "k": c["num_experts_per_tok"],
            "first": c["held_first_expert"], "held": c["n_routed_experts"],
            "scale": c["routed_scaling_factor"],
            "norm_topk": c["norm_topk_prob"],
            "layers": c["num_hidden_layers"], "eps": c["rms_norm_eps"],
            "theta": float(c["rope_theta"])}


def _every_layer(params, get):
    """``get`` of the dense layer stacked on ``get`` of the MoE layers."""
    import jax.numpy as jnp
    pre, g = params["prelude"][0], params["groups"]["pos0"]
    return jnp.concatenate([get(pre)[None], get(g)], axis=0)


def _site_matrices(params, s):
    """Each coded projection as stacked x @ W matrices (n, d_in, d_out):
    attention sites over all layers (the dense one first), the dense FFN
    over the one dense layer, the shared experts over the MoE layers."""
    import jax.numpy as jnp
    pre, g = params["prelude"][0], params["groups"]["pos0"]
    d = s["d"]
    return {
        "qkv": _every_layer(params, lambda lp: jnp.concatenate(
            [lp["mixer"]["wq"].reshape(lp["mixer"]["wq"].shape[:-3]
                                       + (d, -1)),
             lp["mixer"]["w_dkv"]], axis=-1)),
        "o": _every_layer(params, lambda lp: lp["mixer"]["wo"].reshape(
            lp["mixer"]["wo"].shape[:-3] + (-1, d))),
        "up": jnp.concatenate([pre["ffn"]["w_gate"], pre["ffn"]["w_up"]],
                              axis=1)[None],
        "down": pre["ffn"]["w_down"][None],
        "shared_up": jnp.concatenate([g["ffn"]["shared"]["w_gate"],
                                      g["ffn"]["shared"]["w_up"]], axis=2),
        "shared_down": g["ffn"]["shared"]["w_down"],
        "unembed": params["embedding"]["unembed"][None],
    }


class Reference:
    """The reference over one set of weights (the benchmark's own pytree:
    ``embedding``, the dense layer in ``prelude``, the MoE layers stacked
    in ``groups.pos0``, ``final_norm``)."""

    def __init__(self, config: dict, params, code: dict, coded_layers: str,
                 seed: int):
        import jax.numpy as jnp
        self.s = s = sizes(config)
        self.coded = CODED[coded_layers]
        n, k, t = code["n_workers"], code["k_blocks"], code["t_colluding"]
        self.k, self.t, self.n = k, t, n
        g = params["groups"]["pos0"]
        w = {"table": params["embedding"]["table"],
             "final": params["final_norm"]["scale"],
             "norm1": _every_layer(params, lambda lp: lp["norm1"]["scale"]),
             "norm2": _every_layer(params, lambda lp: lp["norm2"]["scale"]),
             "kv_norm": _every_layer(params, lambda lp: lp["mixer"]["kv_norm"]),
             "w_uk": _every_layer(params, lambda lp: lp["mixer"]["w_uk"]),
             "w_uv": _every_layer(params, lambda lp: lp["mixer"]["w_uv"]),
             "router": g["ffn"]["router"],
             "bias": g["ffn"]["e_score_correction_bias"],
             "w_gate": g["ffn"]["w_gate"], "w_up": g["ffn"]["w_up"],
             "w_down": g["ffn"]["w_down"]}
        self.d_out = {}
        for name, mat in _site_matrices(params, s).items():
            d_in, d_out = mat.shape[1:]
            self.d_out[name] = d_out
            if name in self.coded:
                # x @ [W padded to K blocks | noise blocks^T]: every block's
                # product with x, data blocks first
                blk = -(-d_out // k)
                noise = spacdc_ref.noise_blocks(seed, t, blk, d_in,
                                                code["noise_scale"])
                noise_t = jnp.transpose(noise, (2, 0, 1)).reshape(d_in, -1)
                mat = jnp.concatenate(
                    [jnp.pad(mat, ((0, 0), (0, 0), (0, k * blk - d_out))),
                     jnp.broadcast_to(noise_t, mat.shape[:1] + noise_t.shape)],
                    axis=2)
            w[name] = mat
        self.w = w
        self._mix = {}

    def mix(self, responders) -> np.ndarray:
        """(K, K + T) float32 decode-through-encode matrix of a responder set."""
        key = tuple(sorted(int(r) for r in responders))
        if key not in self._mix:
            self._mix[key] = spacdc_ref.decode_through_encode(
                key, self.n, self.k, self.t).astype(np.float32)
        return self._mix[key]

    def logits(self, tokens, responders, *, lowered: bool = False):
        """(T, V) float32 logits at every position of ``tokens`` (T,), each
        position's projections decoded from ``responders[t]``."""
        import jax.numpy as jnp
        mixes = jnp.asarray(np.stack([self.mix(r) for r in responders]))
        return _forward(self.w, jnp.asarray(tokens, jnp.int32), mixes,
                        self.coded, tuple(sorted(self.d_out.items())),
                        self.k, self.t, tuple(sorted(self.s.items())),
                        lowered)


def _fp8(x):
    """x rounded to float8 e4m3 after scaling its largest entry to the
    format's largest, back in float32."""
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _forward(w, tokens, mixes, coded, d_out, k, t, frozen, lowered):
    import jax.numpy as jnp
    s = dict(frozen)
    d_out = dict(d_out)
    hi = jax.lax.Precision.HIGHEST
    T = tokens.shape[0]
    h, nope, rp, lora = s["h"], s["nope"], s["rope"], s["lora"]

    def mm(x, m):
        """A weight product x (T, a) @ m (a, b)."""
        if lowered:
            x, m = _fp8(x), _fp8(m)
        return jnp.matmul(x, m, precision=hi)

    def proj(x, name, i):
        z = mm(x, w[name][i])
        if name not in coded:
            return z
        z = z.reshape(T, k + t, -1)                 # block j's product
        y = jnp.einsum("tkj,tjb->tkb", mixes, z, precision=hi)
        return y.reshape(T, -1)[:, :d_out[name]]

    def rms(x, scale, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    half = rp // 2
    inv = 1.0 / (s["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv       # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                                # (T, H, rp)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(gu, width):
        return jax.nn.silu(gu[:, :width]) * gu[:, width:]

    causal = jnp.tril(jnp.ones((T, T), bool))
    x = w["table"][tokens]
    for layer in range(s["layers"]):
        a = rms(x, w["norm1"][layer], s["eps"])
        qkv = proj(a, "qkv", layer)
        q = qkv[:, :h * (nope + rp)].reshape(T, h, nope + rp)
        dkv = qkv[:, h * (nope + rp):]
        ckv = rms(dkv[:, :lora], w["kv_norm"][layer], 1e-6)
        k_nope = mm(ckv, w["w_uk"][layer].reshape(lora, -1)).reshape(T, h, nope)
        v = mm(ckv, w["w_uv"][layer].reshape(lora, -1)).reshape(T, h, -1)
        k_rope = rope(dkv[:, None, lora:])                      # (T, 1, rp)
        sc = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope, precision=hi)
              + jnp.einsum("qhd,kd->hqk", rope(q[..., nope:]), k_rope[:, 0],
                           precision=hi)) / (nope + rp) ** 0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", p, v, precision=hi).reshape(T, -1)
        x = x + proj(att, "o", layer)
        b = rms(x, w["norm2"][layer], s["eps"])
        if layer == 0:                                          # dense
            x = x + proj(swiglu(proj(b, "up", 0), s["ff"]), "down", 0)
            continue
        g = layer - 1
        score = jax.nn.sigmoid(jnp.matmul(b, w["router"][g], precision=hi))
        _, idx = jax.lax.top_k(score + w["bias"][g], s["k"])
        gate = jnp.take_along_axis(score, idx, -1)
        if s["norm_topk"]:
            gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
        gate = gate * s["scale"]
        held = jnp.arange(s["first"], s["first"] + s["held"])
        weight = jnp.sum(jnp.where(idx[:, :, None] == held, gate[:, :, None],
                                   0.0), axis=1)                # (T, held)
        routed = 0.0
        for e in range(s["held"]):
            hid = (jax.nn.silu(mm(b, w["w_gate"][g, e]))
                   * mm(b, w["w_up"][g, e]))
            routed = routed + weight[:, e:e + 1] * mm(hid, w["w_down"][g, e])
        shared = proj(swiglu(proj(b, "shared_up", g), s["sf"]),
                      "shared_down", g)
        x = x + routed + shared
    return proj(rms(x, w["final"], s["eps"]), "unembed", 0)


def gaps(ref_logits, picked, valid) -> np.ndarray:
    """At each position marked ``valid``, how far the reference's logit of
    the ``picked`` token lies below the reference's best."""
    import jax.numpy as jnp
    ref = jnp.asarray(ref_logits)
    got = jnp.take_along_axis(ref, jnp.asarray(picked)[:, None], -1)[:, 0]
    return np.asarray(ref.max(axis=-1) - got)[np.asarray(valid)]
