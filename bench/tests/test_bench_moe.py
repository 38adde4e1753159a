"""The MoE serving cell without a chip: the driver's weights against the
program's parameter tree at the configuration's own size, the served
logits against the reference at a tiny size, the work counts against
hand-computed values, and the three new metric readers on synthetic
measures."""

import contextlib
import json
import time

import jax
import numpy as np
import pytest

from conftest import BENCH
from yardstick import harness, moe_work, peaks, spans
from yardstick.trace import TraceSummary

CONFIG = "moonlight-16b-a3b-ep8-13l"
BIG_SEED = 2 ** 33 + 12345          # seeds may pass 32 bits


def _json(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def _driver():
    return harness.load_module(BENCH / "drivers" / "serve_moe.py")


def _tiny(compute_dtype="float32"):
    """The configuration at a tiny size (1 dense and 2 MoE layers, 8 held
    of 64 experts), with 4 slots and a short backlog."""
    cfg = _json("configs", CONFIG)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, kv_lora_rank=32,
               v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
               num_hidden_layers=3, vocab_size=512,
               compute_dtype=compute_dtype)
    cfg["cluster"]["serve"]["max_slots"] = 4
    tr = _json("traffic", "chat-backlog-c128")
    tr.update(requests_per_pass=6, prompt=[4, 16], gen=[4, 12],
              check_requests=6)
    return cfg, tr


def test_weights_match_the_program_tree_at_full_size():
    from repro.models import build_model
    drv = _driver()
    cfg = _json("configs", CONFIG)
    model = build_model(drv.program_config(cfg))
    expect = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: drv.make_weights(cfg, 0))
    assert jax.tree.structure(expect) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(expect), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    ffn = got["groups"]["pos0"]["ffn"]
    # the router keeps its published width; 8 experts held in 12 layers
    assert ffn["router"].shape == (12, 2048, 64)
    assert ffn["w_gate"].shape == (12, 8, 2048, 1408)
    assert got["embedding"]["unembed"].shape == (2048, 20480)


def test_served_logits_match_the_reference_at_every_fed_position():
    """``ContinuousBatcher`` with every site coded, at compute float32 and
    full matmul precision, against the reference decoding each position
    from that step's responders: the coded sites' f32 shard products and
    the decode are all that differ (as ``LOGIT_RTOL`` in
    tests/test_serve.py), so 1e-4 of the logit scale; a dropped expert,
    a wrong gate or a misplaced rope moves logits by O(scale)."""
    drv = _driver()
    cfg, tr = _tiny()
    with jax.default_matmul_precision("highest"):
        sv = drv.Serving(cfg, tr, BIG_SEED)
        reqs = sv.requests()
        res = sv.batcher.run(reqs, record_logits=True)
    ref_mod = harness.load_module(BENCH / "configs" / f"{CONFIG}.py")
    code = dict(cfg["cluster"]["code"], **cfg["cluster"]["privacy"])
    ref = ref_mod.Reference(cfg, sv.params, code, "all", sv.spec.seed)
    resp = drv.serve._step_responders(res)
    fed_rows = 0
    for q in reqs:
        served = next(r for r in res.requests if r.rid == q.rid)
        fed = np.concatenate([q.prompt, served.tokens[:-1]])
        want = np.asarray(ref.logits(fed, [
            resp[i] or list(range(8))
            for i in drv.serve._request_steps(res, served)]))
        assert served.logits.shape == want.shape
        np.testing.assert_allclose(served.logits, want, rtol=0,
                                   atol=1e-4 * np.abs(want).max())
        fed_rows += len(fed)
    assert fed_rows == sum(p + g - 1 for p, g in sv.lengths)


def test_run_is_correct_and_the_control_fails():
    """A run at tiny widths, but the configuration's 13 layers and compute
    bfloat16, passes the configuration's limit; the reference one
    precision below bfloat16 in the program's place does not.  The
    control's error grows with depth: at 3 layers it stays under the
    limit that the chip's readings set."""
    drv = _driver()
    cfg, tr = _tiny("bfloat16")
    cfg["num_hidden_layers"] = _json("configs", CONFIG)["num_hidden_layers"]
    ctx = harness.Context("test", BIG_SEED, 0.3, False, BENCH.parent,
                          time.perf_counter())
    out = drv.run(cfg, tr, ctx)
    assert all(c.ok for c in out.checks) and out.failed == 0
    assert out.attempted > 0
    prog, ctrl = drv.control_readings(cfg, tr, 7, 0.3)["served_gap_mean"]
    assert prog <= cfg["limits"]["served_gap_mean"] < ctrl


def test_traced_stretch_holds_its_steps_and_their_work(monkeypatch):
    """The profiler is open over steps [first, first + steps) of a pass
    alone, and the stretch's work is those steps' share of the pass's:
    stretches that cover the pass add up to its requests' work."""
    drv = _driver()
    cfg, tr = _tiny()
    sv = drv.Serving(cfg, tr, BIG_SEED)
    sv.one_pass()                     # compiles: one dispatch a step now
    calls, step = [], sv.batcher._step
    sv.batcher._step = lambda *a: (calls.append(1), step(*a))[1]

    @contextlib.contextmanager
    def traced(ctx, result):
        n0 = len(calls)
        yield
        result["summary"] = len(calls) - n0

    monkeypatch.setattr(drv.harness, "traced", traced)
    reqs, res, inside = drv.traced_pass(sv, None, first=2, steps=3)
    assert inside == 3 and res.n_steps > 5
    total = sum(moe_work.request_flops(cfg, len(q.prompt), q.gen)
                for q in reqs)
    flops, kernel = drv.stretch_work(cfg, res, 0, res.n_steps)
    assert flops == pytest.approx(total, rel=1e-12)
    assert sum(n for _, n in kernel) == res.n_steps * moe_work.moe_layers(cfg)
    parts = [drv.stretch_work(cfg, res, f, 2)[0]
             for f in range(0, res.n_steps, 2)]
    assert sum(parts) == pytest.approx(total, rel=1e-12)
    assert 0 < drv.stretch_work(cfg, res, 2, 3)[0] < total


def test_work_at_the_configuration_shapes():
    cfg = _json("configs", CONFIG)
    assert moe_work.moe_layers(cfg) == 12
    # one held-experts call on 128 rows: 8 experts' gate, up and down of
    # 2048 x 1408; the f32 weights, bf16 rows and f32 output moved once
    mats = 3 * 2048 * 1408 * 8
    w = moe_work.held_experts_work(cfg, 128)
    assert w["flops"] == 2 * 128 * mats
    assert w["bytes"] == 4 * mats + 2 * 128 * 2048 + 4 * 128 * 2048
    # one position at context 1: per layer MLA q 2048x16x192, kv
    # 2048x576, q_nope to latent 16x128x512, scores 16x1x576, values
    # 16x1x512, latent out 16x512x128, o 16x128x2048; the dense FFN
    # 3x2048x11264; 12 MoE layers of router 2048x64, shared 2x3x2048x1408
    # and 6 x 8/64 routed experts; the unembed 2048x20480
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 576
            + 16 * 512 + 16 * 512 * 128 + 16 * 128 * 2048)
    moe = 2048 * 64 + 2 * 3 * 2048 * 1408 + 6 * 8 / 64 * 3 * 2048 * 1408
    want = 2 * (13 * attn + 3 * 2048 * 11264 + 12 * moe + 2048 * 20480)
    assert moe_work.position_flops(cfg, 1) == pytest.approx(want, rel=1e-12)
    assert moe_work.request_flops(cfg, 2, 2) == sum(
        moe_work.position_flops(cfg, c) for c in (1, 2, 3))


def _measure(op_s, **kw):
    summary = TraceSummary(window_s=1.0, busy_s=0.5, n_chips=1, op_s=op_s,
                           gap_s={})
    m = {"kind": "serve", "summary": summary, "units": 10,
         "peaks": peaks.peaks_for("TPU v5 lite"),
         "kernel_calls": {"held_experts_kernel": [
             [{"flops": 1.97e9, "bytes": 8.19e8}, 24],     # 1 ms each
             [{"flops": 1.97e9, "bytes": 4.095e8}, 12]]},  # 0.5 ms each
         "moe_layers": 12, "top_k": 6}
    m.update(kw)
    return m


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_held_experts_readers():
    m = _measure({"jit_step:held_experts_kernel.1": 0.04,
                  "jit_step:fusion.3": 0.2})
    # least time 24 x 1 ms + 12 x 0.5 ms = 30 ms of 40 ms
    assert _reader("held_experts_roofline").read(m) == pytest.approx(75.0)
    assert _reader("held_experts_ms.serve").read(m) == pytest.approx(4.0)
    empty = _measure({"jit_step:fusion.3": 0.2})
    for name in ("held_experts_roofline", "held_experts_ms.serve"):
        assert _reader(name).read(empty) is None
        assert _reader(name).read(None) is None
    # a dense serving cell hands no kernel work
    dense = _measure({"jit_step:held_experts_kernel.1": 0.04})
    del dense["kernel_calls"]
    assert _reader("held_experts_roofline").read(dense) is None


def test_routed_here_reader(monkeypatch):
    reader = _reader("routed_here.serve")
    found = {}
    monkeypatch.setattr(spans, "for_measure", lambda m: found.get("s"))
    m = _measure({})
    assert reader.read(m) is None                      # no trace found
    found["s"] = spans.SpanSummary(
        window_s=1.0, count={"spacdc.serve.step": 3}, total_s={},
        self_s={}, idle_s={}, idle_unspanned_s=0.0,
        counters={"spacdc.serve.step": {"bucket": 48, "live": 40,
                                        "routed_here": 360}})
    # 360 of 40 rows x 6 choices x 12 layers
    assert reader.read(m) == pytest.approx(12.5)
    found["s"].counters["spacdc.serve.step"].pop("routed_here")
    assert reader.read(m) is None                      # a model that routes
    assert reader.read(None) is None                   # nothing
