"""Driver for batch serving of a DeepSeek-V3-style mixture-of-experts model
on one chip's expert-parallel share: the closed backlog of ``serve.py``,
served in whole passes by the same continuous-batching loop over the
program's MoE/MLA layers, on weights the benchmark makes.

The configuration file holds the published keys at its top level, with
``n_routed_experts`` the experts this chip holds (from
``held_first_expert``) and ``published`` the whole model's counts; the
router keeps its published width.  Pass lengths, the sample of finished
requests and the comparison with the reference are ``serve.py``'s own.
The traced stretch is ``TRACED_STEPS`` whole loop steps from step
``TRACED_FIRST`` of one more pass, all 128 slots busy: a whole pass (379
steps of 13 layers) overflows the profiler's buffer.  It hands the
readers the plain forward work of the positions those steps fed, the
held-experts kernel's work per call (``moe_work``), and the MoE layer
count and experts per token that turn the ``routed_here`` counters into a
share.
"""

from __future__ import annotations

import collections
import contextlib
import math

import numpy as np

from yardstick import harness, moe_work, peaks
from yardstick.harness import Check, Outcome

serve = harness.load_module(harness.bench_dir() / "drivers" / "serve.py")

TRACED_FIRST, TRACED_STEPS = 64, 48


def program_config(config: dict):
    """The program's ``ModelConfig`` for the configuration's share."""
    from repro.configs.base import ModelConfig
    c = config
    if (c["hidden_act"] != "silu" or c["tie_word_embeddings"]
            or c["q_lora_rank"] is not None or c["attention_bias"]
            or c["moe_layer_freq"] != 1 or c["n_group"] != 1
            or c["topk_group"] != 1 or c["topk_method"] != "noaux_tc"):
        raise ValueError("the MoE serve driver runs DeepSeek-V3-style "
                         "models: SwiGLU, untied, no q-LoRA, one expert "
                         "group, every layer after the dense ones MoE")
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=nope + rope, mla=True,
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=c["v_head_dim"], moe=True,
        n_experts=c["published"]["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"], moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"],
        router_scoring=c["scoring_func"], norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"],
        held_experts=(c["held_first_expert"], c["n_routed_experts"]),
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        activation="swiglu", norm_type="rmsnorm", tie_embeddings=False,
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


def weight_shapes(config: dict) -> dict:
    """(path, shape, init) of every weight: ``init`` is the fan-in of a
    matrix drawn N(0, 1/fan_in), "rows" (embedding rows N(0, 1)), "norm"
    (1 + N(0, 0.01)) or "bias" (N(0, 0.05^2)).  MoE leaves are stacked
    over the MoE layers, as the program scans them."""
    c = config
    d, h, v = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    lora, vh = c["kv_lora_rank"], c["v_head_dim"]
    ff, f = c["intermediate_size"], c["moe_intermediate_size"]
    sf, e = c["n_shared_experts"] * f, c["n_routed_experts"]
    experts = c["published"]["n_routed_experts"]
    if c["first_k_dense_replace"] != 1:
        raise ValueError("one leading dense layer")
    g = (moe_work.moe_layers(c),)

    def attn(lead):
        return {("mixer", "wq"): (lead + (d, h, nope + rope), d),
                ("mixer", "w_dkv"): (lead + (d, lora + rope), d),
                ("mixer", "kv_norm"): (lead + (lora,), "norm"),
                ("mixer", "w_uk"): (lead + (lora, h, nope), lora),
                ("mixer", "w_uv"): (lead + (lora, h, vh), lora),
                ("mixer", "wo"): (lead + (h, vh, d), h * vh),
                ("norm1", "scale"): (lead + (d,), "norm"),
                ("norm2", "scale"): (lead + (d,), "norm")}

    out = {("embedding", "table"): ((v, d), "rows"),
           ("embedding", "unembed"): ((d, v), d),
           ("final_norm", "scale"): ((d,), "norm")}
    dense = {**attn(()), ("ffn", "w_gate"): ((d, ff), d),
             ("ffn", "w_up"): ((d, ff), d), ("ffn", "w_down"): ((ff, d), ff)}
    out.update({("prelude", 0) + k: s for k, s in dense.items()})
    moe = {
        **attn(g),
        ("ffn", "router"): (g + (d, experts), d),
        ("ffn", "e_score_correction_bias"): (g + (experts,), "bias"),
        ("ffn", "w_gate"): (g + (e, d, f), d),
        ("ffn", "w_up"): (g + (e, d, f), d),
        ("ffn", "w_down"): (g + (e, f, d), f),
        ("ffn", "shared", "w_gate"): (g + (d, sf), d),
        ("ffn", "shared", "w_up"): (g + (d, sf), d),
        ("ffn", "shared", "w_down"): (g + (sf, d), sf)}
    out.update({("groups", "pos0") + k: s for k, s in moe.items()})
    return out


def make_weights(config: dict, seed: int):
    """The benchmark's weights in the program's layout, made on the device
    in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    shapes = weight_shapes(config)
    dtype = config["param_dtype"]

    @jax.jit
    def build(key):
        tree: dict = {"prelude": [{}]}
        for k, (path, (shape, init)) in zip(
                jax.random.split(key, len(shapes)), shapes.items()):
            z = jax.random.normal(k, shape, jnp.float32)
            if init == "norm":
                w = 1.0 + 0.1 * z
            elif init == "bias":
                w = 0.05 * z
            elif init == "rows":
                w = z
            else:
                w = z / math.sqrt(init)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {}) if isinstance(node, dict) \
                    else node[p]
            node[path[-1]] = w.astype(dtype)
        return tree

    return jax.block_until_ready(build(harness.seed_key(seed, 1)))


class Serving(serve.Serving):
    """``serve.Serving`` over the MoE share's configuration and weights."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.api import Session
        from repro.models import build_model
        from repro.runtime.serve_loop import ContinuousBatcher
        self.spec = harness.cluster_spec(config, traffic, seed)
        self.model = build_model(program_config(config))
        self.params = make_weights(config, seed)
        expect = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(expect) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(expect), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        self.session = Session(self.spec)
        sv = self.spec.serve
        self.batcher = ContinuousBatcher(
            self.session.engine, self.model, self.params,
            coded_layers=sv.coded_layers, max_slots=sv.max_slots,
            eos_id=sv.eos_id, backend=self.spec.transport.backend)
        self.lengths = serve.pass_lengths(traffic)
        self.vocab = config["vocab_size"]
        self.rng = np.random.default_rng(seed)


def traced_pass(serving: Serving, ctx, first: int = TRACED_FIRST,
                steps: int = TRACED_STEPS):
    """(requests, ServeResult, trace summary) of one pass of which steps
    ``[first, first + steps)`` alone run under the profiler."""
    traced, stack = {}, contextlib.ExitStack()

    def on_step(i):
        if i == first:
            stack.enter_context(harness.traced(ctx, traced))
        elif i == first + steps:
            stack.close()

    reqs = serving.requests()
    with stack:
        res = serving.batcher.run(reqs, on_step=on_step)
    return reqs, res, traced["summary"]


def stretch_work(config: dict, res, first: int, steps: int):
    """(plain forward FLOPs, held-experts kernel [work, calls]) of the
    pass's steps ``[first, first + steps)``: each position a request fed
    in them, and one kernel call per MoE layer per step on the step's
    bucket of rows."""
    flops = 0.0
    for served in res.requests:
        fed = serve._request_steps(res, served)
        for s in range(max(first, fed.start), min(first + steps, fed.stop)):
            flops += moe_work.position_flops(config, s - fed.start + 1)
    per_bucket = collections.Counter(
        int(b) for b in res.buckets[first:first + steps])
    n = moe_work.moe_layers(config)
    return flops, [[moe_work.held_experts_work(config, b), n * c]
                   for b, c in sorted(per_bucket.items())]


def run(config: dict, traffic: dict, ctx) -> Outcome:
    import gc
    import jax
    serving = Serving(config, traffic, ctx.seed)
    ctx.mark("weights_and_encode")
    serving.one_pass()                         # compiles every bucket
    ctx.mark("warm_pass")
    gc.collect()
    setup_s = ctx.since_start()
    traces = serving.compiles()
    with harness.CompileWatch() as watch:
        passes = serving.window(ctx.seconds)
    compiles = watch.count + serving.compiles() - traces
    memory = harness.memory_peak_bytes()

    measure = breakdown = None
    if ctx.trace:
        _, res, summary = traced_pass(serving, ctx)
        units = min(TRACED_STEPS, len(res.step_stats) - TRACED_FIRST)
        summary.check_complete("jit_step", units)
        flops, calls = stretch_work(config, res, TRACED_FIRST, units)
        measure = {
            "kind": "serve", "summary": summary, "units": units,
            "peaks": peaks.peaks_for(jax.devices()[0].device_kind),
            "model_flops": flops,
            "kernel_calls": {"held_experts_kernel": calls},
            "moe_layers": moe_work.moe_layers(config),
            "top_k": config["num_experts_per_tok"]}
        breakdown = summary.breakdown()

    generated = sum(len(r.tokens) for _, res, _ in passes
                    for r in res.requests)
    attempted = sum(len(reqs) for reqs, _, _ in passes)
    failed = sum(1 for reqs, res, _ in passes
                 for q, r in zip(sorted(reqs, key=lambda q: q.rid),
                                 res.requests)
                 if len(r.tokens) != q.gen)
    wall = sum(w for _, _, w in passes)
    serving.close()
    picks = serve.sample_requests(passes, traffic["check_requests"],
                                  ctx.seed)
    gap = serve.readings(config, serving, passes, picks)["mean"]
    checks = [Check("served_gap_mean", gap,
                    config["limits"]["served_gap_mean"]),
              Check("compiles_in_window", compiles, 0)]
    return Outcome(attempted=attempted, failed=failed,
                   end_to_end={"serve_tok_s": generated / wall,
                               "setup_s": setup_s},
                   checks=checks, memory_peak_bytes=memory,
                   measure=measure, breakdown=breakdown)


def control_readings(config: dict, traffic: dict, seed: int,
                     seconds: float) -> dict:
    """As ``serve.control_readings``, on the MoE share."""
    serving = Serving(config, traffic, seed)
    serving.one_pass()
    passes = serving.window(seconds)
    serving.close()
    picks = serve.sample_requests(passes, traffic["check_requests"], seed)
    prog = serve.readings(config, serving, passes, picks)
    ctrl = serve.readings(config, serving, passes, picks, control=True)
    return {f"served_gap_{k}": (prog[k], ctrl[k]) for k in prog}
