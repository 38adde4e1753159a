"""Plain reference of ``phi3-mini-4l`` as the deployment serves it: Phi-3-mini
(arXiv:2404.14219; RMSNorm, rotary attention with the half-split rotation,
32 heads of 96, SwiGLU, untied unembedding) over a whole request at once,
in float32 at full matmul precision, with every coded projection computed
as the coded deployment computes it.

A coded projection ``y = x @ W`` is approximate by design: the master
holds W^T's K row blocks and T noise blocks as N Berrut-coded shards, and
decodes y^T from the shard products of the workers that answered.  Since
the code is linear, decoded block k = sum_j M[k, j] (S_j x^T) with S the
K + T blocks and M = decode(responders) @ encode(responders).  The
reference multiplies x by every block once and mixes the products with
each position's own M: position t was fed in one serving step, and that
step's responders decoded every projection of it.

The control computes the same projections one precision below the
configuration's bfloat16: each projection's operands scaled into
float8 (e4m3) range, rounded to it, and multiplied in float32.
"""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spacdc_ref  # noqa: E402

SITES = ("qkv", "o", "up", "down", "unembed")
CODED = {"none": (), "unembed": ("unembed",),
         "attn": ("qkv", "o", "unembed"), "ffn": ("up", "down", "unembed"),
         "all": SITES}


def sizes(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    return {"d": d, "h": h, "kv": model["num_key_value_heads"], "hd": d // h,
            "ff": model["intermediate_size"], "vocab": model["vocab_size"],
            "layers": model["num_hidden_layers"], "eps": model["rms_norm_eps"],
            "theta": model["rope_theta"]}


def _site_matrices(params, s):
    """Each projection as (layer-stacked) x @ W matrices, (L, d_in, d_out)."""
    import jax.numpy as jnp
    g = params["groups"]["pos0"]
    L, d = s["layers"], s["d"]
    m = g["mixer"]
    return {
        "qkv": jnp.concatenate([m["wq"].reshape(L, d, -1),
                                m["wk"].reshape(L, d, -1),
                                m["wv"].reshape(L, d, -1)], axis=2),
        "o": m["wo"].reshape(L, -1, d),
        "up": jnp.concatenate([g["ffn"]["w_gate"], g["ffn"]["w_up"]], axis=2),
        "down": g["ffn"]["w_down"],
        "unembed": params["embedding"]["unembed"][None],
    }


class Reference:
    """The reference over one set of weights (the benchmark's own pytree:
    ``embedding``, ``groups.pos0`` stacked over layers, ``final_norm``)."""

    def __init__(self, config: dict, params, code: dict, coded_layers: str,
                 seed: int):
        import jax.numpy as jnp
        self.s = s = sizes(config["model"])
        self.code = code
        self.coded = CODED[coded_layers]
        n, k, t = code["n_workers"], code["k_blocks"], code["t_colluding"]
        self.k, self.t, self.n = k, t, n
        w = {"table": params["embedding"]["table"],
             "norm1": params["groups"]["pos0"]["norm1"]["scale"],
             "norm2": params["groups"]["pos0"]["norm2"]["scale"],
             "final": params["final_norm"]["scale"]}
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
                        self.k, self.t, _freeze(self.s), lowered)


def _freeze(s: dict):
    return tuple(sorted(s.items()))


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

    def mm(x, m):
        if lowered:
            x, m = _fp8(x), _fp8(m)
        return jnp.matmul(x, m, precision=hi)

    def proj(x, name, layer):
        m = w[name][layer]
        z = mm(x, m)
        if name not in coded:
            return z
        z = z.reshape(T, k + t, -1)                 # block j's product
        y = jnp.einsum("tkj,tjb->tkb", mixes, z, precision=hi)
        return y.reshape(T, -1)[:, :d_out[name]]

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + s["eps"]) * scale

    half = s["hd"] // 2
    inv = 1.0 / (s["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv       # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                                # (T, H, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((T, T), bool))
    x = w["table"][tokens]
    h, kv, hd = s["h"], s["kv"], s["hd"]
    for layer in range(s["layers"]):
        a = rms(x, w["norm1"][layer])
        qkv = proj(a, "qkv", layer)
        q = rope(qkv[:, :h * hd].reshape(T, h, hd))
        kk = rope(qkv[:, h * hd:(h + kv) * hd].reshape(T, kv, hd))
        v = qkv[:, (h + kv) * hd:].reshape(T, kv, hd)
        kk = jnp.repeat(kk, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, kk, precision=hi) / hd ** 0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", p, v, precision=hi).reshape(T, -1)
        x = x + proj(att, "o", layer)
        b = rms(x, w["norm2"][layer])
        gu = proj(b, "up", layer)
        gate, up = gu[:, :s["ff"]], gu[:, s["ff"]:]
        x = x + proj(jax.nn.silu(gate) * up, "down", layer)
    return proj(rms(x, w["final"]), "unembed", 0)


def gaps(ref_logits, picked, valid) -> np.ndarray:
    """At each position marked ``valid``, how far the reference's logit of
    the ``picked`` token lies below the reference's best."""
    import jax.numpy as jnp
    ref = jnp.asarray(ref_logits)
    got = jnp.take_along_axis(ref, jnp.asarray(picked)[:, None], -1)[:, 0]
    return np.asarray(ref.max(axis=-1) - got)[np.asarray(valid)]
