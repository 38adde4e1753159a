"""Operations and bytes of a DeepSeek-V3-style model's work on one chip's
expert-parallel share, counted from the configuration's shapes.

``config`` is the configuration file: the published keys at its top
level, ``n_routed_experts`` the experts held here and ``published`` the
whole model's counts.  As in ``work.py``, the counts say what the
algorithm needs, whatever the program does to compute it: coding's own
work, padding and recomputation are not counted.
"""

from __future__ import annotations

F32, BF16 = 4, 2


def _sizes(config: dict) -> dict:
    c = config
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "lora": c["kv_lora_rank"], "vh": c["v_head_dim"],
            "ff": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "shared": c["n_shared_experts"], "k": c["num_experts_per_tok"],
            "held": c["n_routed_experts"],
            "experts": c["published"]["n_routed_experts"],
            "dense": c["first_k_dense_replace"],
            "layers": c["num_hidden_layers"], "vocab": c["vocab_size"]}


def moe_layers(config: dict) -> int:
    """The MoE layers of the chip's share (those after the dense ones)."""
    s = _sizes(config)
    return s["layers"] - s["dense"]


def position_flops(config: dict, ctx: int) -> float:
    """Plain forward of one token position that attends to ``ctx`` keys,
    itself included, on this chip's share: MLA in the absorbed form (q
    and compressed-kv projections, q_nope into the latent space, scores
    and values over the latent cache, the latent values out through w_uv,
    the output projection), the dense layers' FFN, each MoE layer's
    router, shared experts and the routed experts at ``top_k`` times the
    held share of all experts, and the unembed over the sliced
    vocabulary; 2 per multiply-add."""
    s = _sizes(config)
    d, h, lora = s["d"], s["h"], s["lora"]
    attn = (d * h * (s["nope"] + s["rope"]) + d * (lora + s["rope"])
            + h * s["nope"] * lora + h * ctx * (lora + s["rope"])
            + h * ctx * lora + h * lora * s["vh"] + h * s["vh"] * d)
    dense = 3 * d * s["ff"]
    expert = 3 * d * s["f"]
    moe = (d * s["experts"] + s["shared"] * expert
           + s["k"] * s["held"] / s["experts"] * expert)
    n_moe = s["layers"] - s["dense"]
    return 2.0 * (s["layers"] * attn + s["dense"] * dense + n_moe * moe
                  + d * s["vocab"])


def request_flops(config: dict, n_prompt: int, n_gen: int) -> float:
    """All positions one served request feeds: its prompt, then every
    generated token but the last, each attending to the positions before
    it and itself."""
    return sum(position_flops(config, t + 1)
               for t in range(n_prompt + n_gen - 1))


def held_experts_work(config: dict, rows: int) -> dict:
    """One call of the held-experts kernel on ``rows`` rows: every held
    expert's gate, up and down projections of every row.  Bytes are the
    least the call moves once: the held weights in the parameter dtype,
    the rows in the compute dtype, the float32 output."""
    s = _sizes(config)
    d, mats = s["d"], 3 * s["d"] * s["f"] * s["held"]
    w_bytes = F32 if config["param_dtype"] == "float32" else BF16
    x_bytes = F32 if config["compute_dtype"] == "float32" else BF16
    return {"flops": 2.0 * rows * mats,
            "bytes": float(w_bytes * mats + x_bytes * rows * d
                           + F32 * rows * d)}
