"""Driver for batch serving: a closed backlog served in whole passes by the
continuous-batching loop (``repro.runtime.serve_loop.ContinuousBatcher``)
that ``Session.batcher`` builds, on weights the benchmark makes.

A pass is the traffic's fixed set of (prompt, generation) lengths, every
request due at once, with fresh token ids from the seed; greedy decoding
with no end token.  Every pass has the same lengths, so the cache length
and every batch bucket are the same and nothing compiles after the warm-up
pass.  The window runs passes until ``--seconds`` have passed, the last
one counted whole; ``serve_tok_s`` is the tokens the window's passes
generated over their wall.

Once the window has closed and the session is freed, a sample of the
finished requests, drawn from the seed with the longest request in it,
is compared with the configuration's reference: at every generated
position, how far the reference's logit of the served token lies below
the reference's best, each position decoded from the responders of the
serving step that fed it.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from yardstick import harness, peaks, work
from yardstick.harness import Check, Outcome, span


def program_config(config: dict):
    """The program's ``ModelConfig`` for the configuration as published."""
    from repro.configs.base import ModelConfig
    m = config["model"]
    if m["hidden_act"] != "silu" or m["tie_word_embeddings"]:
        raise ValueError("the serve driver runs SwiGLU, untied models")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        activation="swiglu", norm_type="rmsnorm", tie_embeddings=False,
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])


def make_weights(model: dict, seed: int, dtype: str):
    """The benchmark's weights, in the layout the program serves (layers
    stacked on a leading axis under ``groups.pos0``), made on the device
    in one jitted call: matrices N(0, 1/fan_in), embedding rows N(0, 1),
    norm scales 1 + N(0, 0.01)."""
    import jax
    import jax.numpy as jnp
    L, d = model["num_hidden_layers"], model["hidden_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, ff, v = d // h, model["intermediate_size"], model["vocab_size"]
    shapes = {
        "table": ((v, d), 1), "unembed": ((d, v), d),
        "wq": ((L, d, h, hd), d), "wk": ((L, d, kv, hd), d),
        "wv": ((L, d, kv, hd), d), "wo": ((L, h, hd, d), h * hd),
        "w_gate": ((L, d, ff), d), "w_up": ((L, d, ff), d),
        "w_down": ((L, ff, d), ff),
        "norm1": ((L, d), None), "norm2": ((L, d), None),
        "final": ((d,), None)}

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        w = {}
        for k, (name, (shape, fan_in)) in zip(keys, shapes.items()):
            z = jax.random.normal(k, shape, jnp.float32)
            w[name] = (1.0 + 0.1 * z if fan_in is None
                       else z / math.sqrt(fan_in)).astype(dtype)
        return {"embedding": {"table": w["table"], "unembed": w["unembed"]},
                "prelude": [],
                "groups": {"pos0": {
                    "norm1": {"scale": w["norm1"]},
                    "mixer": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                    "norm2": {"scale": w["norm2"]},
                    "ffn": {n: w[n] for n in ("w_gate", "w_up", "w_down")}}},
                "final_norm": {"scale": w["final"]}}

    return jax.block_until_ready(build(harness.seed_key(seed, 1)))


def pass_lengths(traffic: dict):
    """The mix's (prompt, generation) lengths, the same for every seed:
    log-uniform draws over the traffic's ranges from its own fixed seed."""
    rng = np.random.default_rng(traffic["lengths_seed"])

    def draw(lo, hi, n):
        return np.round(np.exp(rng.uniform(np.log(lo), np.log(hi), n)))

    n = traffic["requests_per_pass"]
    return list(zip(draw(*traffic["prompt"], n).astype(int).tolist(),
                    draw(*traffic["gen"], n).astype(int).tolist()))


class Serving:
    """One session, its batcher and the weights: the timed path of a
    serving cell."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.api import Session
        from repro.models import build_model
        from repro.runtime.serve_loop import ContinuousBatcher
        self.spec = harness.cluster_spec(config, traffic, seed)
        self.model = build_model(program_config(config))
        self.params = make_weights(config["model"], seed,
                                   config["param_dtype"])
        expect = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(expect) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(expect), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        self.session = Session(self.spec)
        serve = self.spec.serve
        # what Session.batcher builds, over the benchmark's weights
        self.batcher = ContinuousBatcher(
            self.session.engine, self.model, self.params,
            coded_layers=serve.coded_layers, max_slots=serve.max_slots,
            eos_id=serve.eos_id, backend=self.spec.transport.backend)
        self.lengths = pass_lengths(traffic)
        self.vocab = config["model"]["vocab_size"]
        self.rng = np.random.default_rng(seed)

    def requests(self):
        """One pass: the mix's lengths with fresh token ids."""
        from repro.runtime.serve_loop import Request
        return [Request(rid=i, prompt=self.rng.integers(
                    0, self.vocab, p).astype(np.int32), gen=g)
                for i, (p, g) in enumerate(self.lengths)]

    def one_pass(self):
        """(requests, ServeResult, wall seconds) of one pass."""
        reqs = self.requests()
        t0 = time.perf_counter()
        with span("pass"):
            res = self.batcher.run(reqs)
        return reqs, res, time.perf_counter() - t0

    def window(self, seconds: float):
        """Passes until ``seconds`` have passed, the last one whole."""
        passes, wall = [], 0.0
        while wall < seconds:
            passes.append(self.one_pass())
            wall += passes[-1][2]
        return passes

    def compiles(self) -> int:
        return self.batcher.trace_count + self.session.engine.trace_count

    def close(self):
        """Free the program's state; the weights stay for the reference.
        The batcher's jitted step refers back to it, so only a collection
        frees its encoded weights."""
        self.session.close()
        self.batcher = None
        gc.collect()


def _step_responders(res):
    """Sorted responders of every step of a pass (all workers, uncoded)."""
    out = []
    for st in res.step_stats:
        if st.arrivals:
            out.append(sorted(int(w) for _, w in st.arrivals[:st.n_waited]))
        else:
            out.append(None)
    return out


def _request_steps(res, served):
    """The step indices at which a served request fed its positions: from
    the step it was admitted (its admission time on the pass's timeline)
    to the step it finished, one position a step."""
    before = np.concatenate([[0.0], np.cumsum(res.step_virtual_s)])
    first = int(np.argmin(np.abs(before[:-1] - served.admitted_s)))
    n = served.n_prompt - 1 + len(served.tokens)
    last = first + n - 1
    if (abs(before[first] - served.admitted_s) > 1e-9 * max(1.0, before[-1])
            or last >= len(res.step_stats)
            or abs(before[last + 1] - served.done_s)
            > 1e-9 * max(1.0, before[-1])):
        raise ValueError(f"request {served.rid}: its positions do not map "
                         f"onto consecutive steps of the pass")
    return range(first, last + 1)


def sample_requests(passes, k: int, seed: int):
    """(pass index, request index) pairs: the longest request of a random
    pass, and ``k - 1`` more drawn uniformly from all finished requests."""
    rng = np.random.default_rng(seed)
    lengths = [len(r.prompt) + r.gen for r in passes[0][0]]
    longest = int(np.argmax(lengths))
    picks = {(int(rng.integers(len(passes))), longest)}
    pool = [(p, i) for p in range(len(passes)) for i in range(len(lengths))]
    for j in rng.permutation(len(pool)):
        if len(picks) >= k:
            break
        picks.add(pool[j])
    return sorted(picks)


def readings(config: dict, serving: Serving, passes, picks, *,
             control: bool = False) -> dict:
    """How far below the reference's best logit the reference puts the
    token served (or, for the control, the token the lowered reference
    puts first), over every generated position of the sampled requests:
    the mean (``served_gap_mean``, the compared number) and the widest."""
    ref_mod = harness.load_module(harness.bench_dir() / "configs"
                                  / f"{config['name']}.py")
    code = dict(config["cluster"]["code"], **config["cluster"]["privacy"])
    ref = ref_mod.Reference(config, serving.params, code,
                            serving.spec.serve.coded_layers,
                            serving.spec.seed)
    all_workers = list(range(code["n_workers"]))
    t_max = max(p + g - 1 for p, g in serving.lengths)
    found = []
    for p, i in picks:
        reqs, res, _ = passes[p]
        req = reqs[i]
        served = next(r for r in res.requests if r.rid == req.rid)
        per_step = _step_responders(res)
        try:
            steps = _request_steps(res, served)
        except ValueError:
            steps = None
        if len(served.tokens) != req.gen or steps is None:
            return {"mean": float("inf"), "widest": float("inf")}
        fed = np.concatenate([req.prompt, served.tokens[:-1]])
        n = len(fed)
        # pad every request to the longest, so the reference compiles once
        tokens = np.zeros(t_max, np.int32)
        tokens[:n] = fed
        resp = [per_step[s] or all_workers for s in steps]
        resp += [all_workers] * (t_max - n)
        logits = ref.logits(tokens, resp)
        valid = np.zeros(t_max, bool)
        valid[len(req.prompt) - 1:n] = True
        if control:
            picked = np.asarray(ref.logits(tokens, resp, lowered=True)
                                .argmax(-1))
        else:
            picked = np.zeros(t_max, np.int32)
            picked[len(req.prompt) - 1:n] = served.tokens
        found.append(ref_mod.gaps(logits, picked, valid))
    found = np.concatenate(found)
    if not np.isfinite(found).all():        # a NaN must fail, not vanish
        return {"mean": float("inf"), "widest": float("inf")}
    return {"mean": float(found.mean()), "widest": float(found.max())}


def run(config: dict, traffic: dict, ctx) -> Outcome:
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
        traced = {}
        with harness.traced(ctx, traced):
            reqs, res, _ = serving.one_pass()
        traced["summary"].check_complete("jit_step", len(res.step_stats))
        measure = {
            "kind": "serve", "summary": traced["summary"],
            "units": len(res.step_stats),
            "peaks": peaks.peaks_for(jax.devices()[0].device_kind),
            "model_flops": sum(work.lm_request_flops(
                config["model"], len(r.prompt), r.gen) for r in reqs)}
        breakdown = traced["summary"].breakdown()

    generated = sum(len(r.tokens) for _, res, _ in passes
                    for r in res.requests)
    attempted = sum(len(reqs) for reqs, _, _ in passes)
    failed = sum(1 for reqs, res, _ in passes
                 for q, r in zip(sorted(reqs, key=lambda q: q.rid),
                                 res.requests)
                 if len(r.tokens) != q.gen)
    wall = sum(w for _, _, w in passes)
    serving.close()
    picks = sample_requests(passes, traffic["check_requests"], ctx.seed)
    gap = readings(config, serving, passes, picks)["mean"]
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
    """One seed's served-token gaps (mean, the compared number, and
    widest) for the program's served tokens and for the control's choices
    at the same positions, after a window of ``seconds``."""
    serving = Serving(config, traffic, seed)
    serving.one_pass()
    passes = serving.window(seconds)
    serving.close()
    picks = sample_requests(passes, traffic["check_requests"], seed)
    prog = readings(config, serving, passes, picks)
    ctrl = readings(config, serving, passes, picks, control=True)
    return {f"served_gap_{k}": (prog[k], ctrl[k]) for k in prog}
