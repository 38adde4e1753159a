"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the Pallas kernel body in Python on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.berrut_encode import berrut_encode_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.mask_add import mask_add_kernel

rng = np.random.default_rng(0)


def _rand_limbs(n, q, n_limbs, seed):
    """(n, n_limbs) uint32 planes of uniform field elements < q."""
    from repro.crypto.field import int_to_limbs
    r = np.random.default_rng(seed)
    vals = [int.from_bytes(r.bytes((q.bit_length() + 7) // 8), "big") % q
            for _ in range(n)]
    return np.stack([int_to_limbs(v, n_limbs) for v in vals]), vals


from repro.crypto import CURVE_SECP256K1

SECP_Q = CURVE_SECP256K1.q


@pytest.mark.parametrize("n", [1, 100, 513, 4096])
@pytest.mark.parametrize("subtract", [False, True])
def test_mask_add_kernel_matches_oracle(n, subtract):
    from repro.crypto.field import int_to_limbs
    a, av = _rand_limbs(n, SECP_Q, 8, seed=n)
    b, bv = _rand_limbs(n, SECP_Q, 8, seed=n + 1)
    q_limbs = tuple(int(v) for v in int_to_limbs(SECP_Q, 8))
    out = mask_add_kernel(jnp.asarray(a), jnp.asarray(b), q_limbs=q_limbs,
                          subtract=subtract, interpret=True)
    want = ref.mask_add(a, b, np.asarray(q_limbs, np.uint32),
                        subtract=subtract)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # and both match big-int ground truth
    from repro.crypto.field import limbs_to_int
    got = limbs_to_int(np.asarray(out))
    for g, x, y in zip(got, av, bv):
        assert int(g) == ((x - y) if subtract else (x + y)) % SECP_Q


def test_mask_add_kernel_edge_values():
    """Carry/borrow chains at the field edges: 0, 1, q-1, 2^256-adjacent."""
    from repro.kernels.ops import mask_add
    from repro.crypto.field import int_to_limbs, limbs_to_int
    vals = [0, 1, 2, SECP_Q - 1, SECP_Q - 2, (1 << 255) % SECP_Q,
            0xFFFFFFFF, 0xFFFFFFFF00000000 % SECP_Q]
    a = np.stack([int_to_limbs(v, 8) for v in vals])
    for other in (0, 1, SECP_Q - 1):
        b = np.broadcast_to(int_to_limbs(other, 8), a.shape)
        for subtract in (False, True):
            for force in (False, True):
                out = mask_add(a, b, SECP_Q, subtract=subtract,
                               force_kernel=force)
                got = limbs_to_int(np.asarray(out))
                for g, x in zip(got, vals):
                    want = (x - other) if subtract else (x + other)
                    assert int(g) == want % SECP_Q, (x, other, subtract)


def test_mask_add_broadcast_scalar_mask():
    """Paper mode masks every element with one field scalar."""
    from repro.kernels.ops import mask_add
    from repro.crypto.field import int_to_limbs, limbs_to_int
    a, av = _rand_limbs(37, SECP_Q, 8, seed=3)
    psi = 0x123456789ABCDEF0FEDCBA9876543210
    out = mask_add(a, int_to_limbs(psi, 8), SECP_Q, force_kernel=True)
    for g, x in zip(limbs_to_int(np.asarray(out)), av):
        assert int(g) == (x + psi) % SECP_Q


@pytest.mark.parametrize("q,j,m", [(8, 6, 1000), (20, 8, 4096), (3, 3, 77),
                                   (64, 32, 2048), (1, 1, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_berrut_kernel_matches_oracle(q, j, m, dtype):
    w = jnp.asarray(rng.standard_normal((q, j)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((j, m)), dtype)
    out = berrut_encode_kernel(w, b, interpret=True)
    want = ref.berrut_combine(w, b)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    assert out.shape == want.shape and out.dtype == want.dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 want.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("b,sq,skv,h,kv,hd,causal", [
    (1, 128, 128, 4, 4, 64, True),
    (2, 100, 100, 4, 2, 32, True),
    (1, 256, 256, 8, 8, 128, False),
    (2, 64, 192, 4, 1, 64, False),
    (1, 65, 130, 2, 2, 48, True),        # ragged, padded tiles
])
def test_flash_kernel_matches_oracle(b, sq, skv, h, kv, hd, causal):
    q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kv, hd)), jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=causal, bq=64, bkv=64,
                                 interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(out - want))) < 3e-5


def test_flash_kernel_softcap():
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 2, 32))[:, :, 0], jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    out = flash_attention_kernel(q, k, v, causal=True, softcap=20.0,
                                 bq=64, bkv=64, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, softcap=20.0)
    assert float(jnp.max(jnp.abs(out - want))) < 3e-5


def test_xla_flash_vjp_matches_dense_grads():
    """The train-path custom-vjp flash backward vs autodiff through the
    dense reference."""
    from repro.models.attention import flash_attention as xla_flash
    b, s, h, kv, hd = 2, 48, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def via_flash(q, k, v):
        return jnp.sum(jnp.sin(xla_flash(q, k, v, q_positions=pos,
                                         kv_positions=pos, causal=True,
                                         chunk=16)))

    def via_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.mha_reference(q, k, v, causal=True)))

    g1 = jax.grad(via_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(via_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b_))) < 1e-5


@pytest.mark.parametrize("b,d,e,f,tf", [(5, 64, 3, 128, 512),
                                        (16, 128, 2, 384, 128),
                                        (1, 64, 4, 96, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_held_experts_kernel_matches_oracle(b, d, e, f, tf, dtype):
    """The held-experts kernel in interpret mode against its XLA twin:
    rows that chose no held expert (zero gates) get zero, the f axis
    tiled (384 in 128s) or whole (96, not a lane multiple), rows padded
    to the sublane tile."""
    from repro.kernels.held_experts import held_experts_kernel
    r = np.random.default_rng(b * 100 + e)
    x = jnp.asarray(r.standard_normal((b, d)), dtype)
    gates = r.uniform(0.1, 1.0, (b, e)) * (r.random((b, e)) < 0.6)
    gates[0] = 0.0                                   # routed elsewhere
    gates = jnp.asarray(gates, jnp.float32)
    wg, wu = (jnp.asarray(r.standard_normal((e, d, f)) / np.sqrt(d),
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(r.standard_normal((e, f, d)) / np.sqrt(f), jnp.float32)
    got = held_experts_kernel(x, gates, wg, wu, wd, tf=tf, interpret=True)
    want = ref.held_experts(x, gates, wg, wu, wd)
    assert got.shape == (b, d) and got.dtype == jnp.float32
    # the same products in the same dtype, accumulated in float32 in
    # another order (tile by tile)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    np.testing.assert_array_equal(np.asarray(got[0]), 0.0)
