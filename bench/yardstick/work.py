"""Operations and bytes of the benchmark's work, counted from shapes.

These are the yardstick's own counts: they say what the algorithm needs,
whatever the program does to compute it (padding, extra passes and
recomputation are not counted), so a share of a peak built from them can
only rise when the program does the same work faster.
"""

from __future__ import annotations

F32 = 4


def coded_round_work(m: int, d: int, n: int, n_workers: int, k_blocks: int,
                     t_noise: int) -> dict:
    """One coded round's encode and worker products for A (m, d) @ B (d, n).

    A is split into ``k_blocks`` row blocks of ``blk = ceil(m / K)`` rows;
    with ``t_noise`` noise blocks appended, each of the N workers' shards is
    a (K + T)-term combination of blocks (the encode), and each worker
    multiplies its (blk, d) shard by B.  Bytes are the least the work has to
    move once: A, the noise blocks and B read, the N shard products written,
    all float32.
    """
    blk = -(-m // k_blocks)
    j = k_blocks + t_noise
    encode = 2 * n_workers * j * blk * d
    products = 2 * n_workers * blk * d * n
    nbytes = F32 * (m * d + t_noise * blk * d + d * n + n_workers * blk * n)
    return {"flops": float(encode + products), "bytes": float(nbytes)}


def least_time_s(work: dict, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over the bf16 matmul peak
    and bytes over HBM bandwidth, and which of the two it is."""
    t_flop = work["flops"] / peaks["bf16_flop_s"]
    t_byte = work["bytes"] / peaks["hbm_byte_s"]
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")


def matmul_flops(m: int, d: int, n: int) -> float:
    """The uncoded product A (m, d) @ B (d, n)."""
    return 2.0 * m * d * n


def lm_position_flops(model: dict, ctx: int) -> float:
    """(``model``: the configuration's published keys.)  Plain forward of a dense decoder (RoPE attention, SwiGLU FFN) for one
    token position that attends to ``ctx`` keys, itself included: every
    projection, the score and value contractions, and the unembed."""
    d, hq = model["hidden_size"], model["num_attention_heads"]
    hd, kv = d // hq, model["num_key_value_heads"]
    per_layer = (d * (hq + 2 * kv) * hd + hq * hd * d
                 + 3 * d * model["intermediate_size"])
    attn = 2 * ctx * hq * hd
    return 2.0 * (model["num_hidden_layers"] * (per_layer + attn)
                  + d * model["vocab_size"])


def lm_request_flops(model: dict, n_prompt: int, n_gen: int) -> float:
    """All positions one served request feeds: its prompt, then every
    generated token but the last (which is returned, not fed), each
    attending to the positions before it and itself."""
    return sum(lm_position_flops(model, t + 1)
               for t in range(n_prompt + n_gen - 1))
