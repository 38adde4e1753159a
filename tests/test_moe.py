"""The MoE layer against hand-written gates and against itself cut into
expert-parallel shares, at tiny sizes on seeded random weights: the
DeepSeek-V3 sigmoid router with its selection-only bias, DeepSeek-V2-Lite's
published softmax router, and a held share of the routed experts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.models import build_model
from repro.models import moe
from repro.models.coded import coded_flop_fraction


def _cfg(arch="moonlight-16b-a3b", **kw):
    return dataclasses.replace(tiny_config(arch), compute_dtype="float32",
                               **kw)


def _layer(cfg, seed=0):
    """One MoE layer's params with a nonzero selection bias (sigmoid
    routers), and a (B, d) input."""
    p = moe.init_moe(jax.random.PRNGKey(seed), cfg)
    r = np.random.default_rng(seed)
    if "e_score_correction_bias" in p:
        p["e_score_correction_bias"] = jnp.asarray(
            0.05 * r.standard_normal(cfg.n_experts), jnp.float32)
    x = jnp.asarray(r.standard_normal((6, cfg.d_model)), jnp.float32)
    return p, x


def _gate(x, p, cfg):
    """The published gates in numpy, float64: (weights, chosen experts)."""
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    if cfg.router_scoring == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
        pick = s + np.asarray(p["e_score_correction_bias"], np.float64)
    else:
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        pick = s
    idx = np.argsort(-pick, axis=-1)[:, :cfg.top_k]
    w = np.take_along_axis(s, idx, -1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b",
                                  "deepseek-v2-lite-16b"])
def test_router_matches_the_published_gate(arch):
    cfg = _cfg(arch)
    p, x = _layer(cfg)
    w, idx, _, _ = moe._router(p, x, cfg)
    want_w, want_idx = _gate(x, p, cfg)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    if arch == "moonlight-16b-a3b":      # renormalised, then scaled
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.446, rtol=1e-5)
    else:                                 # V2-Lite: raw softmax mass, < 1
        assert np.all(np.asarray(w).sum(-1) < 1.0)


def test_selection_bias_moves_the_choice_not_the_weight():
    cfg = _cfg(n_experts=4, top_k=2, moe_d_ff=32, held_experts=())
    p, x = _layer(cfg)
    x = x[:1]
    scores = jax.nn.sigmoid(x @ p["router"])[0]
    order = np.argsort(-np.asarray(scores))
    # lift the lowest-scored expert over the top two by its bias
    bias = np.zeros(4, np.float32)
    bias[order[-1]] = 2.0
    p["e_score_correction_bias"] = jnp.asarray(bias)
    w, idx, _, _ = moe._router(p, x, cfg)
    chosen = sorted(np.asarray(idx[0]).tolist())
    assert chosen == sorted([int(order[-1]), int(order[0])])
    # the weights are the chosen experts' scores, bias left out
    s = np.asarray(scores)[np.asarray(idx[0])]
    np.testing.assert_allclose(np.asarray(w[0]),
                               s / s.sum() * cfg.routed_scaling_factor,
                               rtol=1e-6)


def _share(p, cfg, first, count):
    """The params and config of one chip that holds experts
    [first, first + count)."""
    sub = dict(p, **{k: p[k][first:first + count]
                     for k in ("w_gate", "w_up", "w_down")})
    return sub, dataclasses.replace(cfg, held_experts=(first, count))


@pytest.mark.parametrize("count", [1, 2])
def test_held_shares_add_up_to_the_whole_layer(count):
    """Every chip's share of one layer, the shared experts (which every
    chip computes alike) counted once, adds up to the uncut layer — in
    decode and in the capacity-routed forward."""
    cfg = _cfg(capacity_factor=16.0)
    p, x = _layer(cfg, seed=1)
    whole, here_all = moe.moe_ffn_decode(p, x[:, None], cfg)
    whole_fwd, _, _ = moe.moe_ffn(p, x[None], cfg)
    shared = moe._shared_ffn(p, x, cfg)
    parts, fwd_parts, here = 0.0, 0.0, 0
    for first in range(0, cfg.n_experts, count):
        sp, scfg = _share(p, cfg, first, count)
        out, h = moe.moe_ffn_decode(sp, x[:, None], scfg)
        parts = parts + out[:, 0] - shared
        fwd_parts = fwd_parts + moe.moe_ffn(sp, x[None], scfg)[0][0] - shared
        here = here + h
    # float32 sums of the same products in another order
    tol = dict(rtol=1e-5, atol=1e-6 * float(jnp.abs(whole).max()))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole[:, 0]), **tol)
    np.testing.assert_allclose(np.asarray(fwd_parts + shared),
                               np.asarray(whole_fwd[0]), **tol)
    np.testing.assert_array_equal(np.asarray(here), cfg.top_k)
    np.testing.assert_array_equal(np.asarray(here_all), cfg.top_k)


def test_held_share_decode_matches_forward():
    """A model that holds part of its experts decodes through its cache
    to what its teacher-forced forward computes."""
    cfg = _cfg(capacity_factor=8.0, held_experts=(2, 4))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (1, 8)), jnp.int32)
    full, _ = model.forward(params, toks)
    cache = model.init_cache(1, 16)
    outs = []
    for t in range(8):
        logits, cache = model.decode_step(params, cache, toks[:, t:t + 1], t)
        outs.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), atol=1e-4, rtol=1e-4)


def test_flop_fraction_counts_the_held_routed_share():
    from repro.configs import get_config
    cfg = get_config("moonlight-16b-a3b")
    cut = dataclasses.replace(cfg, held_experts=(0, 8))
    # routed experts are uncoded: holding fewer leaves more coded share
    assert coded_flop_fraction(cut, "all") > coded_flop_fraction(cfg, "all")
    # the shared experts are coded under "ffn"
    assert coded_flop_fraction(cfg, "ffn") > coded_flop_fraction(
        cfg, "unembed")


def test_tiny_config_keeps_the_router_and_the_held_share(monkeypatch):
    from repro import configs
    share = dataclasses.replace(configs.get_config("moonlight-16b-a3b"),
                                held_experts=(8, 8))   # chip 1 of EP-8
    monkeypatch.setitem(configs.ARCHS, "moonlight-ep8-rank1", share)
    tiny = configs.tiny_config("moonlight-ep8-rank1")
    assert (tiny.router_scoring, tiny.norm_topk_prob,
            tiny.routed_scaling_factor) == ("sigmoid", True, 2.446)
    assert tiny.n_experts == 8 and tiny.experts_held == (1, 1)
    assert configs.tiny_config("moonlight-16b-a3b").experts_held == (0, 8)
