"""Hypothesis property tests on the system's invariants."""

import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import SPACDCCode, SPACDCConfig, berrut, pad_to_blocks
from repro.crypto.mea_ecc import FixedPointCodec
from repro.crypto import CURVE_SECP256K1
from repro.dist.compression import int8_compress, int8_decompress

SET = dict(max_examples=25, deadline=None)


@settings(**SET)
@given(n=st.integers(3, 24), seed=st.integers(0, 2**16))
def test_berrut_weights_always_sum_to_one(n, seed):
    rng = np.random.default_rng(seed)
    nodes = np.sort(rng.uniform(-1, 1, n))
    if len(np.unique(nodes)) < n:
        return
    x = rng.uniform(-2, 2, 5)
    w = berrut.berrut_weights(jnp.asarray(x), jnp.asarray(nodes))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-4)


@settings(**SET)
@given(q=st.integers(1, 12), j=st.integers(1, 8), seed=st.integers(0, 99))
def test_combine_is_linear(q, j, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((q, j)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((j, 3)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((j, 3)), jnp.float32)
    lhs = berrut.combine(w, a + b)
    rhs = berrut.combine(w, a) + berrut.combine(w, b)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-4)


@settings(**SET)
@given(m=st.integers(1, 40), k=st.integers(1, 8))
def test_pad_to_blocks_divisible(m, k):
    x = jnp.ones((m, 2))
    out = pad_to_blocks(x, k)
    assert out.shape[0] % k == 0
    assert float(out.sum()) == 2 * m          # padding is zeros
    assert out.shape[0] - m < k


@settings(**SET)
@given(seed=st.integers(0, 99),
       vals=st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=1,
                     max_size=20))
def test_fixed_point_codec_roundtrip(seed, vals):
    codec = FixedPointCodec(CURVE_SECP256K1.q, frac_bits=16)
    m = np.asarray(vals, np.float32).reshape(-1, 1)
    out = codec.decode(codec.encode(m))
    np.testing.assert_allclose(out, np.round(m * 2**16) / 2**16, atol=1e-9)


@settings(**SET)
@given(seed=st.integers(0, 99), scale=st.floats(0.01, 100))
def test_int8_compression_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(64) * scale, jnp.float32)
    q, s = int8_compress(x)
    deq = int8_decompress(q, s)
    max_err = float(jnp.max(jnp.abs(deq - x)))
    assert max_err <= float(s) * 0.5 + 1e-6   # round-to-nearest bound


@settings(**SET)
@given(n=st.integers(4, 16), k=st.integers(1, 4), seed=st.integers(0, 50))
def test_decode_weights_renormalize_over_any_mask(n, k, seed):
    if k > n:
        return
    code = SPACDCCode(SPACDCConfig(n, k))
    rng = np.random.default_rng(seed)
    mask = np.zeros(n, np.float32)
    mask[rng.choice(n, size=rng.integers(1, n + 1), replace=False)] = 1.0
    dm_rows = code.decode_masked(jnp.eye(n, dtype=jnp.float32),
                                 jnp.asarray(mask))
    # decode of identity basis: rows are the decode weights; they sum to 1
    np.testing.assert_allclose(np.asarray(dm_rows.sum(-1)), 1.0, atol=1e-3)
    # non-responders get zero weight
    dead = np.where(mask == 0)[0]
    assert np.abs(np.asarray(dm_rows)[:, dead]).max() < 1e-6 if len(dead) else True


@settings(**SET)
@given(seed=st.integers(0, 30))
def test_gradient_code_decoder_weights_sum_to_one(seed):
    from repro.core import BerrutGradientCode
    rng = np.random.default_rng(seed)
    g = BerrutGradientCode(n_shards=8, n_blocks=8)
    mask = np.zeros(8, np.float32)
    mask[rng.choice(8, size=rng.integers(1, 9), replace=False)] = 1.0
    w = g.decoder_weights(jnp.asarray(mask)) * mask
    np.testing.assert_allclose(float(jnp.sum(w)), 1.0, atol=1e-3)
