"""Continuous-batching coded serving: coded-vs-uncoded parity across
``coded_layers`` settings, compile-count under slot churn, scheduling
semantics, ServeSpec validation, and the report's latency accounting."""

import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.api import (ClusterSpec, CodeSpec, CryptoSpec, PrivacySpec,
                       ServeSpec, Session, StragglerSpec, TransportSpec,
                       WaitSpec)
from repro.configs import tiny_config
from repro.runtime.serve_loop import (ContinuousBatcher, Request, logit_gap,
                                      poisson_workload)


def exact_spec(coded_layers="all", *, backend="virtual", max_slots=4,
               eos_id=None, crypto=None):
    """MDS + wait-for-all + no stragglers: the decode is exact in exact
    arithmetic (linear Vandermonde inversion), so coded logits match the
    plain path's within ``LOGIT_RTOL`` — the parity configurations."""
    kw = dict(code=CodeSpec(scheme="mds", n_workers=8, k_blocks=4),
              wait=WaitSpec(policy="first_k", k=8),
              straggler=StragglerSpec(n_stragglers=0),
              transport=TransportSpec(backend=backend),
              serve=ServeSpec(coded_layers=coded_layers, max_slots=max_slots,
                              eos_id=eos_id))
    if crypto is not None:
        kw["crypto"] = crypto
    return ClusterSpec(**kw)


def ragged_requests(n=5, vocab=256, seed=3, rate=None):
    rng = np.random.default_rng(seed)
    arr = np.zeros(n)
    if rate:
        arr = np.cumsum(rng.exponential(1.0 / rate, n))
        arr -= arr[0]
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab, int(rng.integers(3, 9)))
                    .astype(np.int32),
                    gen=int(rng.integers(2, 7)), arrival_s=float(arr[i]))
            for i in range(n)]


def serve_tokens(spec, requests, **kw):
    with Session(spec) as s:
        rep = s.serve(arch="qwen2-7b", tiny=True, requests=requests,
                      check_agreement=False, **kw)
    return rep


# --------------------------------------------------------------------------
# parity: coded logits == uncoded logits, within stated tolerances
# --------------------------------------------------------------------------

# Parity runs the model at compute float32 and full matmul precision in
# both arms, so the only difference left is the coded path itself: f32
# shard matmuls and the f32 inverse of the responders' Vandermonde rows
# (K=4, condition ~10).  That is 1.4e-6 of the logit scale for every
# coded_layers setting at the tiny config on CPU (1.7e-5 on the threads
# transport, whose unembed is a host round); a decode that dropped or
# mis-weighted a worker is off by O(1) of the scale, and shard matmuls at
# bf16 operand precision (2^-9) by ~3e-3 — both far outside this bound.
LOGIT_RTOL = 1e-4


def parity_gap(spec, requests, arch="qwen2-7b"):
    """Serve ``requests`` (a ragged continuous batch) through the spec's
    coded step and through the uncoded step of the same session (same
    weights), both at compute float32 and full precision, recording every
    step's logits; returns the coded report and the
    :func:`~repro.runtime.serve_loop.logit_gap` between the two."""
    cfg = dataclasses.replace(tiny_config(arch), compute_dtype="float32")
    with jax.default_matmul_precision("highest"), Session(spec) as s:
        rep = s.serve(cfg, requests=requests, check_agreement=False,
                      record_logits=True)
        ref = s.batcher(cfg, coded_layers="none").run(requests,
                                                      record_logits=True)
    return rep, logit_gap(rep.requests, ref.requests)


def assert_parity(rep, gap):
    assert all(np.isfinite(r.logits).all() for r in rep.requests)
    # every slot is compared at least over its prefill and first token
    assert gap["rows"] >= sum(r.n_prompt for r in rep.requests)
    assert gap["max_abs_diff"] <= LOGIT_RTOL * gap["scale"], gap


class TestCodedServeParity:
    @pytest.mark.parametrize("coded_layers",
                             ["unembed", "attn", "ffn", "all"])
    def test_tokens_bit_identical_across_coded_layers(self, coded_layers):
        # the name predates the tolerance: tokens flip on near-ties when
        # the reduction order changes, so the logits are what is compared
        rep, gap = parity_gap(exact_spec(coded_layers), ragged_requests(n=5))
        assert rep.mode == "instep"
        assert len(rep.requests) == 5
        assert_parity(rep, gap)

    def test_parity_holds_on_mla_arch(self):
        # deepseek: MLA qkv/o sites + dense-FFN positions of the MoE stack
        assert_parity(*parity_gap(exact_spec("all"),
                                  ragged_requests(n=3, seed=5),
                                  arch="deepseek-v2-lite-16b"))

    def test_parity_with_real_encryption(self):
        # encrypt="real": every site's two transfers cross the one-dispatch
        # cipher in-step; the bits codec keeps the round trip lossless, so
        # the wired logits EQUAL the unwired ones and crypto time is
        # attributed
        reqs = ragged_requests(n=4)
        wired, gap = parity_gap(
            exact_spec("all", crypto=CryptoSpec(encrypt="real")), reqs)
        unwired, _ = parity_gap(exact_spec("all"), reqs)
        for a, b in zip(wired.requests, unwired.requests):
            np.testing.assert_array_equal(a.logits, b.logits)
        assert_parity(wired, gap)
        assert all(st.crypto_s > 0 for st in wired.step_stats)
        assert all(st.dispatches == 1 for st in wired.step_stats)

    def test_parity_on_threads_transport(self):
        # real transports keep the PR 5 semantics: unembed as a real round
        rep, gap = parity_gap(exact_spec("unembed", backend="threads"),
                              ragged_requests(n=3))
        assert rep.mode == "round"
        assert_parity(rep, gap)

    def test_session_agreement_diagnostic(self):
        # the built-in diagnostic replays the workload uncoded and compares
        # token for token: it must equal the agreement of a separate
        # coded serve with a separate uncoded serve of the same workload
        # (greedy tokens depend only on the request's own prompt).  Exact
        # codes agree on all but near-tie tokens.
        reqs = ragged_requests(n=3)
        with Session(exact_spec("all")) as s:
            rep = s.serve(arch="qwen2-7b", tiny=True, requests=reqs)
        coded = serve_tokens(exact_spec("all"), reqs).requests
        plain = serve_tokens(exact_spec("none"), reqs).requests
        match = sum(int(np.sum(a.tokens == b.tokens))
                    for a, b in zip(coded, plain))
        total = sum(len(a.tokens) for a in coded)
        assert rep.argmax_agreement == match / total
        assert rep.argmax_agreement >= 0.75

    def test_spacdc_deadline_agreement_is_bounded_not_exact(self):
        # the paper's own scheme is APPROXIMATED coded computing: under a
        # deadline the decode is a rational approximation, so agreement is
        # a diagnostic in [0, 1], not an exactness guarantee
        spec = ClusterSpec.serve_deadline(t_budget=0.008,
                                          coded_layers="unembed",
                                          max_slots=4)
        with Session(spec) as s:
            rep = s.serve(arch="qwen2-7b", tiny=True, batch=2, prompt_len=6,
                          gen=4, seed=0)
        assert 0.0 <= rep.argmax_agreement <= 1.0
        assert rep.steps_within_budget == len(rep.step_stats)


# --------------------------------------------------------------------------
# compilation: churn never retraces
# --------------------------------------------------------------------------

class TestServeCompileCount:
    def test_churn_never_retraces_within_buckets(self):
        # 12 ragged Poisson requests through 4 slots: admissions and
        # evictions churn the in-flight set every few steps, but the step
        # program only ever sees pow2 bucket widths — compiles are bounded
        # by the number of DISTINCT buckets, not the churn
        reqs = ragged_requests(n=12, seed=11, rate=150.0)
        rep = serve_tokens(exact_spec("all"), reqs)
        n_buckets = len(set((1, 2, 4)) & set(
            1 << i for i in range(3)))  # possible buckets for 4 slots: 1,2,4
        assert rep.trace_count <= 3, \
            (rep.trace_count, n_buckets)
        assert len(rep.step_stats) > rep.trace_count * 3

    def test_second_serve_reuses_compiled_steps(self):
        reqs = ragged_requests(n=4, seed=2)
        with Session(exact_spec("all")) as s:
            rep1 = s.serve(arch="qwen2-7b", tiny=True, requests=reqs,
                           check_agreement=False)
            rep2 = s.serve(arch="qwen2-7b", tiny=True, requests=reqs,
                           check_agreement=False)
        assert rep1.trace_count > 0
        assert rep2.trace_count == rep1.trace_count   # zero new traces

    def test_one_round_one_dispatch_per_step(self):
        rep = serve_tokens(exact_spec("all"), ragged_requests(n=4))
        assert all(st.dispatches == 1 for st in rep.step_stats)
        assert all(st.n_waited >= 1 for st in rep.step_stats)


# --------------------------------------------------------------------------
# scheduling semantics
# --------------------------------------------------------------------------

class TestContinuousBatching:
    def test_poisson_workload_shapes(self):
        reqs = poisson_workload(16, rate_rps=50.0, prompt_len=12, gen=8,
                                vocab=256, seed=0, ragged=True)
        assert len(reqs) == 16
        assert reqs[0].arrival_s == 0.0
        assert all(reqs[i].arrival_s <= reqs[i + 1].arrival_s
                   for i in range(15))
        assert all(2 <= len(r.prompt) <= 12 and 1 <= r.gen <= 8
                   for r in reqs)

    def test_every_request_served_with_full_budget(self):
        reqs = ragged_requests(n=7, seed=9, rate=100.0)
        rep = serve_tokens(exact_spec("all"), reqs)
        assert len(rep.requests) == 7
        got = {r.rid: r for r in rep.requests}
        for r in reqs:
            assert len(got[r.rid].tokens) == r.gen
            assert got[r.rid].first_token_s >= r.arrival_s
            assert got[r.rid].done_s >= got[r.rid].first_token_s

    def test_eos_evicts_early(self):
        # serve once to learn a token the model actually emits, then
        # declare it EOS and serve again: the request must stop early
        reqs = [Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                        gen=8)]
        free = serve_tokens(exact_spec("all"), reqs)
        eos = int(free.requests[0].tokens[2])
        rep = serve_tokens(exact_spec("all", eos_id=eos), reqs)
        toks = rep.requests[0].tokens
        assert len(toks) <= 8
        assert eos in toks.tolist() or len(toks) == 8

    def test_continuous_beats_gated_admission(self):
        # mixed short/long requests over a Poisson trace: static batching
        # (gated) holds finished shorts hostage to the longest request
        rng = np.random.default_rng(7)
        arr = np.cumsum(rng.exponential(1 / 150.0, 12))
        arr -= arr[0]
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, 256, 6).astype(np.int32),
                        gen=(24 if i % 4 == 0 else 3),
                        arrival_s=float(arr[i]))
                for i in range(12)]
        cont = serve_tokens(exact_spec("all"), reqs)
        gated = serve_tokens(exact_spec("all"), reqs, admission="gated")
        assert cont.requests_per_s > gated.requests_per_s
        assert len(cont.requests) == len(gated.requests) == 12

    def test_gen_budget_tokens_match_uniform_legacy_shape(self):
        # uniform workload at rate 0 keeps the legacy (batch, gen) shape
        with Session(exact_spec("all")) as s:
            rep = s.serve(arch="qwen2-7b", tiny=True, batch=3, prompt_len=6,
                          gen=5, seed=0, check_agreement=False)
        assert rep.tokens.shape == (3, 5)
        assert (rep.tokens >= 0).all()           # no padding needed
        assert len(rep.step_stats) == 6 - 1 + 5  # prefill rides the steps


# --------------------------------------------------------------------------
# report accounting
# --------------------------------------------------------------------------

class TestServeReportAccounting:
    def test_latency_summaries(self):
        reqs = ragged_requests(n=6, seed=4, rate=80.0)
        rep = serve_tokens(exact_spec("all"), reqs)
        assert rep.ttft_s.shape == (6,)
        assert (rep.ttft_s > 0).all()
        assert rep.step_latency_s.shape == (len(rep.step_stats),)
        assert 0 < rep.p50_step_s <= rep.p99_step_s
        assert rep.p99_step_s <= rep.step_latency_s.max() + 1e-12
        assert rep.requests_per_s > 0
        assert rep.virtual_s >= rep.step_latency_s.sum() - 1e-9

    def test_tok_s_excludes_admission_idle(self):
        # a huge arrival gap parks the loop idle on the virtual clock;
        # busy wall (the tok_s denominator) must not contain it
        reqs = [Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                        gen=3, arrival_s=0.0),
                Request(rid=1, prompt=np.arange(1, 5, dtype=np.int32),
                        gen=3, arrival_s=1e3)]
        rep = serve_tokens(exact_spec("all"), reqs)
        assert rep.virtual_s > 1e3               # the gap is on the clock
        assert rep.busy_wall_s < 1e2             # ...but not in busy wall
        assert rep.tok_s == pytest.approx(
            sum(len(r.tokens) for r in rep.requests) / rep.busy_wall_s)

    def test_coded_flop_fraction_gate_shape(self):
        from repro.configs import get_config
        from repro.models.coded import coded_flop_fraction
        cfg = get_config("qwen2-7b")
        full = coded_flop_fraction(cfg, "all")
        assert full >= 0.9                       # the acceptance gate
        assert coded_flop_fraction(cfg, "none") == 0.0
        order = [coded_flop_fraction(cfg, c)
                 for c in ("unembed", "attn", "ffn", "all")]
        assert order[0] < order[1] < order[3] and order[2] < order[3]


# --------------------------------------------------------------------------
# ServeSpec surface
# --------------------------------------------------------------------------

class TestServeSpec:
    def test_round_trip(self):
        spec = exact_spec("attn", max_slots=16, eos_id=7)
        again = ClusterSpec.from_dict(spec.to_dict())
        assert again.serve == spec.serve
        assert again == spec

    def test_validation(self):
        with pytest.raises(ValueError, match="coded_layers"):
            ServeSpec(coded_layers="everything")
        with pytest.raises(ValueError, match="max_slots"):
            ServeSpec(max_slots=0)
        with pytest.raises(ValueError, match="eos_id"):
            ServeSpec(eos_id=-2)

    def test_real_transport_rejects_stacked_layers(self):
        with pytest.raises(ValueError, match="virtual"):
            exact_spec("all", backend="threads").validate()
        # unembed / none stay valid on real transports
        exact_spec("unembed", backend="threads").validate()
        exact_spec("none", backend="threads").validate()

    def test_serve_deadline_preset_carries_serve_spec(self):
        spec = ClusterSpec.serve_deadline(coded_layers="ffn", max_slots=2,
                                          eos_id=5)
        assert spec.serve == ServeSpec(coded_layers="ffn", max_slots=2,
                                       eos_id=5)

    def test_batcher_rejects_unfusable_scheme_beyond_unembed(self):
        # a non-fused scheme can't run the in-step masked decode
        import jax
        from repro.configs import tiny_config
        from repro.models import build_model
        from repro.runtime.engine import RoundEngine
        spec = dataclasses.replace(
            exact_spec("unembed"),
            code=CodeSpec(scheme="conv", n_workers=4),
            wait=WaitSpec(policy="first_k", k=4))
        engine = RoundEngine(spec)
        cfg = tiny_config("qwen2-7b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if not getattr(engine.scheme, "supports_fused", False):
            with pytest.raises(ValueError, match="fused"):
                ContinuousBatcher(engine, model, params,
                                  coded_layers="all", backend="virtual")
        engine.close()
