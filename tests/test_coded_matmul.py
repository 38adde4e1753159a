"""Fused coded-matmul kernel + single-dispatch round pipeline.

Parity of ``coded_matmul`` (interpret mode) against the unfused
encode → per-worker matmul → decode oracle over N/K/T, dtype and
straggler-mask sweeps; the no-full-payload-padding regression for the
upgraded kernels; and the recompile-count contract of the jitted round
path (shape change recompiles, mask change never does)."""

import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import (ClusterSpec, CodeSpec, CryptoSpec, PrivacySpec,
                       Session, StragglerSpec)
from repro.core import registry
from repro.kernels import ref
from repro.kernels.berrut_encode import berrut_encode_kernel
from repro.kernels.coded_matmul import coded_matmul_kernel
from repro.kernels.ops import coded_matmul
from repro.runtime import engine
from repro.runtime.master_worker import DistributedMatmul

rng = np.random.default_rng(0)


# --------------------------------------------------------------------------
# kernel parity: (W @ blocks) @ B fused vs the unfused oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,j,blk,d,nout", [
    (30, 27, 22, 512, 256),     # fig-3 scale: N=30, J=K+T=24+3
    (10, 4, 64, 64, 32),
    (12, 5, 16, 48, 10),        # K=3, T=2
    (3, 3, 7, 130, 17),         # ragged everything
    (8, 8, 128, 256, 128),      # fully aligned
    (33, 33, 5, 1000, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_matmul_kernel_matches_unfused_oracle(n, j, blk, d, nout, dtype):
    w = jnp.asarray(rng.standard_normal((n, j)), jnp.float32)
    blocks = jnp.asarray(rng.standard_normal((j, blk, d)), dtype)
    rhs = jnp.asarray(rng.standard_normal((d, nout)), dtype)
    out = coded_matmul_kernel(w, blocks, rhs, interpret=True)
    want = ref.coded_matmul(w, blocks, rhs)
    assert out.shape == want.shape and out.dtype == want.dtype
    rel = (float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 want.astype(jnp.float32)))) /
           max(float(jnp.max(jnp.abs(want.astype(jnp.float32)))), 1e-9))
    tol = 1e-4 if dtype == jnp.float32 else 0.1
    assert rel < tol, (n, j, blk, d, nout, dtype, rel)


def test_coded_matmul_dispatcher_paths_agree():
    w = jnp.asarray(rng.standard_normal((9, 5)), jnp.float32)
    blocks = jnp.asarray(rng.standard_normal((5, 13, 70)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((70, 21)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(coded_matmul(w, blocks, rhs, force_kernel=True)),
        np.asarray(coded_matmul(w, blocks, rhs, force_kernel=False)),
        atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------------
# fused_round vs the unfused chain, over schemes and straggler masks
# --------------------------------------------------------------------------

FUSED_SCHEMES = {
    "spacdc": dict(n_workers=12, k_blocks=4, t_colluding=2),
    "bacc": dict(n_workers=12, k_blocks=4),
    "mds": dict(n_workers=12, k_blocks=4),
    "lcc": dict(n_workers=12, k_blocks=4, deg_f=1),
    "conv": dict(n_workers=6),
}
# fused-vs-unfused agreement.  The threshold schemes' unfused decode
# inverts the first-`threshold` responder submatrix while the fused masked
# decode least-squares over ALL survivors — both exact, but the f32 pinv of
# the (N, K) generator leaves ~1e-3 of conditioning noise between them.
FUSED_TOL = {"lcc": 2e-3, "mds": 2e-3}
M, D, NOUT = 36, 40, 24
A_NP = rng.standard_normal((M, D)).astype(np.float32)
B_NP = rng.standard_normal((D, NOUT)).astype(np.float32)


def _responder_sets(scheme, mask_seed):
    """Full set + two random straggler subsets of wait-policy size or more."""
    n = scheme.n_workers
    yield np.arange(n)
    if scheme.name == "conv":
        return                            # conv must wait for everyone
    r = np.random.default_rng(mask_seed)
    lo = scheme.wait_policy(0) if not scheme.rateless else max(n - 4, 1)
    for size in (lo, min(lo + 2, n)):
        yield np.sort(r.choice(n, size=size, replace=False))


@pytest.mark.parametrize("name", sorted(FUSED_SCHEMES))
def test_fused_round_matches_unfused_chain(name):
    scheme = registry.build(name, **FUSED_SCHEMES[name])
    assert scheme.supports_fused
    a = jnp.asarray(A_NP)
    b = jnp.asarray(B_NP)
    shards = scheme.encode(a)
    results = jax.vmap(lambda s: s @ b)(shards)
    for resp in _responder_sets(scheme, mask_seed=7):
        unfused = scheme.decode(results[resp], list(resp))
        unfused = np.asarray(scheme.reconstruct_matmul(unfused, M, NOUT))
        mask = np.zeros(scheme.n_workers, np.float32)
        mask[resp] = 1.0
        fused = scheme.fused_round(a, b, jnp.asarray(mask))
        fused = np.asarray(scheme.reconstruct_matmul(fused, M, NOUT))
        rel = np.abs(fused - unfused).max() / max(np.abs(unfused).max(), 1e-9)
        assert rel < FUSED_TOL.get(name, 1e-4), (name, resp, rel)


def test_fused_round_jittable_with_runtime_mask():
    scheme = registry.build("spacdc", n_workers=10, k_blocks=4, t_colluding=1)
    f = jax.jit(lambda a, b, m: scheme.fused_round(a, b, m))
    full = f(jnp.asarray(A_NP), jnp.asarray(B_NP), jnp.ones(10, jnp.float32))
    mask = np.ones(10, np.float32)
    mask[[2, 5]] = 0.0
    part = f(jnp.asarray(A_NP), jnp.asarray(B_NP), jnp.asarray(mask))
    assert full.shape == part.shape == (4, M // 4, NOUT)
    assert np.all(np.isfinite(np.asarray(part)))


def test_fused_round_bf16():
    scheme = registry.build("spacdc", n_workers=10, k_blocks=4)
    out = scheme.fused_round(jnp.asarray(A_NP, jnp.bfloat16),
                             jnp.asarray(B_NP, jnp.bfloat16),
                             jnp.ones(10, jnp.float32))
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_pair_coded_schemes_have_no_fused_path():
    for name, kw in [("polynomial", dict(n_workers=8, p=2, q=2)),
                     ("matdot", dict(n_workers=8, p=2))]:
        scheme = registry.build(name, **kw)
        assert not scheme.supports_fused
        with pytest.raises(NotImplementedError):
            scheme.fused_round(jnp.asarray(A_NP), jnp.asarray(B_NP),
                               jnp.ones(8, jnp.float32))


# --------------------------------------------------------------------------
# padding regression: aligned payloads move zero bytes
# --------------------------------------------------------------------------

def _payload_pad_eqns(jaxpr, payload_size):
    """pad/dynamic_update_slice equations producing >= payload-sized arrays
    (i.e. full-payload copies; the tiny coding-matrix pad is exempt)."""
    bad = []
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name in ("pad", "dynamic_update_slice"):
            if any(int(np.prod(v.aval.shape)) >= payload_size
                   for v in eqn.outvars):
                bad.append(eqn)
    return bad


def test_berrut_kernel_no_payload_copy_when_aligned():
    w = jnp.zeros((8, 8), jnp.float32)
    b = jnp.zeros((8, 1024), jnp.float32)
    jx = jax.make_jaxpr(
        lambda w, b: berrut_encode_kernel(w, b, interpret=True))(w, b)
    assert not _payload_pad_eqns(jx, b.size), jx


def test_coded_matmul_kernel_no_payload_copy_when_aligned():
    w = jnp.zeros((8, 8), jnp.float32)
    blocks = jnp.zeros((8, 128, 256), jnp.float32)
    rhs = jnp.zeros((256, 128), jnp.float32)
    jx = jax.make_jaxpr(
        lambda w, a, r: coded_matmul_kernel(w, a, r, interpret=True))(
            w, blocks, rhs)
    assert not _payload_pad_eqns(jx, blocks.size), jx


def test_berrut_kernel_misaligned_still_correct():
    w = jnp.asarray(rng.standard_normal((5, 6)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6, 999)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(berrut_encode_kernel(w, b, interpret=True)),
        np.asarray(ref.berrut_combine(w, b)), atol=1e-4, rtol=1e-4)


def test_berrut_kernel_j_past_tile_cap_pads_to_alignment_only():
    """J just past the tile cap must not round the payload up to ~2x: the
    tile shrinks to a divisor of the 8-aligned J instead (gradient-coding
    scale).  bj=8 cap forces the multi-J-tile accumulator path too."""
    from repro.kernels.berrut_encode import _tile
    assert _tile(513, 8, 512) == (104, 520)      # not (512, 1024)
    w = jnp.asarray(rng.standard_normal((4, 33)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((33, 256)), jnp.float32)
    out = berrut_encode_kernel(w, b, bj=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.berrut_combine(w, b)),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# the jitted round pipeline: recompile only on shape change
# --------------------------------------------------------------------------

def test_fused_round_path_recompiles_only_on_shape_change():
    dist = DistributedMatmul("spacdc", n_workers=8, k_blocks=4,
                             t_colluding=1, n_stragglers=2)
    assert dist.use_fused
    a = A_NP[:32]
    out1, stats1 = dist.matmul(a, B_NP, round_idx=0)
    assert dist.trace_count == 1
    # new round, new straggler mask, same shapes -> NO retrace
    out2, stats2 = dist.matmul(a, B_NP, round_idx=1)
    assert dist.trace_count == 1
    assert len(dist._fused_cache) == 1
    # shape change -> exactly one new trace
    dist.matmul(A_NP[:16], B_NP, round_idx=2)
    assert dist.trace_count == 2
    assert len(dist._fused_cache) == 2
    # back to the first shape: cached fn, still no retrace
    dist.matmul(a, B_NP, round_idx=3)
    assert dist.trace_count == 2
    assert out1.shape == (32, NOUT) and np.all(np.isfinite(out1))
    assert stats1.total_s > 0 and stats2.decode_s == 0.0


def test_ill_conditioned_threshold_schemes_do_not_default_to_fused():
    """MDS at paper scale (K=24, Vandermonde cond ~3e8) is past f32's
    reach — the f32 pinv masked decode would silently destroy the result,
    so the runtime must keep such schemes on the f64 loop decode unless
    the caller forces fused=True."""
    big = registry.build("mds", n_workers=30, k_blocks=24)
    assert big.supports_fused and not big.fused_decode_stable
    dist = DistributedMatmul("mds", n_workers=30, k_blocks=24, n_stragglers=3)
    assert not dist.use_fused                      # default: exact loop path
    forced = DistributedMatmul("mds", n_workers=30, k_blocks=24, fused=True)
    assert forced.use_fused                        # explicit opt-in honored
    # small-K MDS stays fused (well-conditioned); rateless is always stable
    assert registry.build("mds", n_workers=12, k_blocks=4).fused_decode_stable
    assert DistributedMatmul("mds", n_workers=12, k_blocks=4).use_fused
    assert registry.build("spacdc", n_workers=30, k_blocks=24,
                          t_colluding=3).fused_decode_stable


def test_fused_flag_validation_and_fallback():
    with pytest.raises(ValueError, match="fused"):
        DistributedMatmul("polynomial", 8, 2, p=2, q=2, fused=True)
    loop = DistributedMatmul("spacdc", 8, 4, fused=False)
    assert not loop.use_fused
    out, stats = loop.matmul(A_NP[:32], B_NP)
    assert stats.decode_s > 0            # loop path still times decode


def test_fused_and_loop_paths_agree():
    kw = dict(n_workers=10, k_blocks=4, t_colluding=1, n_stragglers=2, seed=3)
    fused = DistributedMatmul("spacdc", **kw)
    loop = DistributedMatmul("spacdc", fused=False, **kw)
    of, _ = fused.matmul(A_NP[:32], B_NP, round_idx=4)
    ol, _ = loop.matmul(A_NP[:32], B_NP, round_idx=4)
    np.testing.assert_allclose(of, ol, atol=1e-3, rtol=1e-3)


def test_spacdc_decode_matrix_cached_by_responder_tuple():
    code = registry.build("spacdc", n_workers=10, k_blocks=4)
    resp = [0, 2, 5, 7]
    m1 = code.decode_matrix(resp)
    m2 = code.decode_matrix(np.asarray(resp))
    assert m1 is m2                      # same object: cache hit
    info = code._decode_matrix_cached.cache_info()
    assert info.hits >= 1 and info.misses == 1


# --------------------------------------------------------------------------
# the product on the host: what Session.matmul hands back from the program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,encrypt,chunked", [
    (30, 24, None, False),    # m not a multiple of K = 4: decode pads rows
    (32, 130, None, False),   # n not a multiple of 128
    (5, 7, None, False),      # a product of under 128 elements
    (100, 24, None, True),    # seven row chunks, the last of four rows
    (30, 24, "real", False),  # the encrypted one-dispatch round, stream mode
    (100, 24, "real", True),
])
def test_session_product_is_the_round_chain_bit_for_bit(m, n, encrypt,
                                                        chunked, monkeypatch):
    """The fused round's product reaches the caller as the (m, n) float32
    C-contiguous array that the round's own chain (masked decode, then
    reassembly) computes, bit for bit, whatever layout it left the
    program in (whole, or in row chunks that host threads copy, which
    ``chunked`` turns on here at any size, as on a TPU from 32 MB on)."""
    if chunked:
        monkeypatch.setattr(engine, "_CHUNK_PLATFORMS",
                            (jax.default_backend(),))
        monkeypatch.setattr(engine, "_CHUNK_MIN_BYTES", 0)
    spec = ClusterSpec(
        code=CodeSpec(scheme="spacdc", n_workers=10, k_blocks=4),
        privacy=PrivacySpec(t_colluding=1, noise_scale=0.05),
        straggler=StragglerSpec(n_stragglers=2),
        crypto=CryptoSpec(encrypt=encrypt, cipher_mode="stream"), seed=3)
    r = np.random.default_rng(m * n)
    a = jnp.asarray(r.standard_normal((m, 16)), jnp.float32)
    b = jnp.asarray(r.standard_normal((16, n)), jnp.float32)
    with Session(spec) as s:
        out, stats = s.matmul(a, b, round_idx=1)
        scheme = s.engine.scheme
        assert (s.engine._copy_pool is not None) == chunked
    assert stats.dispatches == 1                  # a one-dispatch round
    # closing the session ends the threads that copied row chunks
    assert not [t for t in threading.enumerate()
                if t.name.startswith("round-copy")]
    mask = np.zeros(10, np.float32)
    mask[[w for _, w in stats.arrivals[:stats.n_waited]]] = 1.0
    chain = jax.jit(lambda a, b, mask: scheme.reconstruct_matmul(
        scheme.fused_round(a, b, mask), m, n))
    want = np.asarray(chain(a, b, jnp.asarray(mask)))
    assert out.shape == (m, n) and out.dtype == np.float32
    assert out.flags.c_contiguous and not out.flags.writeable
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("platform,m,n,chunks", [
    ("tpu", 18432, 512, [2304] * 8),          # fig. 3's 37.7 MB product
    ("tpu", 16392, 512, [2056] * 7 + [2000]), # 32 MB and up: chunks
    ("tpu", 16376, 512, [16376]),             # just under 32 MB: whole
    ("tpu", 8, 1 << 20, [8]),                 # one tile row: nothing to cut
    ("tpu", 256, 64, [256]),
    ("cpu", 18432, 512, [18432]),           # np.asarray is cheap there
])
def test_row_cuts_split_large_tpu_products_into_whole_tiles(platform, m, n,
                                                          chunks):
    cuts = engine._row_cuts(m, n, 4, platform)
    assert all(c % 8 == 0 for c in cuts)
    assert np.diff([0, *cuts, m]).tolist() == chunks
