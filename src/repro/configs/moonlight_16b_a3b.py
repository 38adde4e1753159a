"""moonlight-16b-a3b [arXiv:2502.16982; hf moonshotai/Moonlight-16B-A3B]
— DeepSeek-V3 architecture (arXiv:2412.19437): MLA + fine-grained MoE
with a sigmoid router.

27L, d_model=2048, 16H, MLA kv_lora_rank=512 (no q-lora), qk_nope=128 /
qk_rope=64 / v_head=128.  MoE: 64 routed experts top-6 + 2 shared, expert
d_ff=1408; the first layer dense with d_ff=11264.  Router: sigmoid scores,
top-6 by score + ``e_score_correction_bias`` (noaux_tc, one group), the
chosen scores renormalised and scaled by 2.446.  Vocabulary 163,840,
RMSNorm eps 1e-5, RoPE theta 50,000 without scaling, untied.
"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,               # dense first layer
    vocab_size=163_840,
    mla=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    head_dim=192,             # qk_nope + qk_rope
    moe=True,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    router_scoring="sigmoid",
    norm_topk_prob=True,
    routed_scaling_factor=2.446,
    rope_theta=50_000.0,
    norm_eps=1e-5,
)
