"""The one-dispatch encrypted round (``kernels.encrypted_round``): output
bit-parity with the plain pipeline, ciphertext limb parity with the staged
cipher cores, and bit-exactness of the specialized bits-codec wires
against the general carry-chain path (adversarial Ψ included)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.crypto import CURVE_SECP256K1
from repro.crypto import field as F
from repro.kernels import ops, ref
from repro.kernels.encrypted_round import wire_roundtrip

Q = CURVE_SECP256K1.q
L = 8
rng = np.random.default_rng(0)


def _psi_limbs(psi_ints):
    return jnp.asarray(np.stack([np.asarray(F.int_to_limbs(p, L), np.uint32)
                                 for p in psi_ints]))


def _materials(n, mode, seed):
    r = np.random.default_rng(seed)
    if mode == "stream":
        return jnp.asarray(r.integers(0, 2 ** 32, (n, 8), dtype=np.uint32))
    return _psi_limbs([int.from_bytes(r.bytes(32), "big") % (Q - 1) + 1
                       for _ in range(n)])


def _operands(n, j, blk, d, n_out):
    return (jnp.asarray(rng.standard_normal((n, j)), jnp.float32),
            jnp.asarray(rng.standard_normal((j, blk, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((d, n_out)), jnp.float32))


class TestEncryptedCodedMatmul:
    @pytest.mark.parametrize("mode", ["stream", "paper"])
    @pytest.mark.parametrize("force_kernel", [False, True])
    def test_bit_identical_to_plain_and_oracle(self, mode, force_kernel):
        n, j, blk, d, n_out = (6, 5, 4, 8, 16) if force_kernel \
            else (10, 8, 6, 12, 24)
        w, blocks, rhs = _operands(n, j, blk, d, n_out)
        mo, mb = _materials(n, mode, 1), _materials(n, mode, 2)
        plain = np.asarray(ref.coded_matmul(w, blocks, rhs))
        enc = ops.encrypted_coded_matmul(w, blocks, rhs, mo, mb, q=Q,
                                         mode=mode, force_kernel=force_kernel)
        oracle = ref.encrypted_coded_matmul(w, blocks, rhs, mo, mb, q=Q,
                                            mode=mode)
        # the wire is lossless: encrypted == plain on the SAME matmul path
        same_path = np.asarray(ops.coded_matmul(w, blocks, rhs,
                                                force_kernel=force_kernel))
        np.testing.assert_array_equal(np.asarray(enc), same_path)
        np.testing.assert_array_equal(np.asarray(oracle), plain)
        # the Pallas path accumulates its f32 contractions in tiles, in
        # another order than XLA's dot: f32 rounding of sums over J and d
        # (≤ 16 terms each of O(1)), a few ulps of the O(10) outputs
        np.testing.assert_allclose(np.asarray(enc), plain, rtol=1e-5,
                                   atol=1e-5 * np.abs(plain).max())

    @pytest.mark.parametrize("mode", ["stream", "paper"])
    def test_wire_ciphertext_matches_staged_core(self, mode):
        """The fused round's in-trace ciphertexts are the SAME bits the
        staged ``mea_encrypt_core`` dispatch produces, channel by channel
        — the fusion moves the wire, it doesn't change it."""
        n, j, blk, d, n_out = 5, 4, 3, 8, 6
        w, blocks, rhs = _operands(n, j, blk, d, n_out)
        mo, mb = _materials(n, mode, 3), _materials(n, mode, 4)
        _, ct_out, ct_back = ops.encrypted_coded_matmul(
            w, blocks, rhs, mo, mb, q=Q, mode=mode, force_kernel=False,
            return_wire=True)
        coded = jnp.dot(w, blocks.reshape(j, -1),
                        precision=jax.lax.Precision.HIGHEST).reshape(n, blk, d)
        words = jax.lax.bitcast_convert_type(coded.reshape(n, -1), jnp.uint32)
        for i in range(n):
            want = ops.mea_encrypt_core(words[i], mo[i], q=Q, frac_bits=16,
                                        mode=mode, codec="bits",
                                        use_kernel=False, interpret=True,
                                        n_limbs=L)
            np.testing.assert_array_equal(np.asarray(ct_out[i]),
                                          np.asarray(want))
        assert ct_back.shape == (n, blk * n_out, L)

    def test_stream_needs_wide_modulus(self):
        x = jnp.zeros((2, 8), jnp.float32)
        with pytest.raises(ValueError, match="64-bit"):
            wire_roundtrip(x, jnp.zeros((2, 8), jnp.uint32), q=(1 << 61) - 1,
                           mode="stream")


class TestSpecializedWires:
    """The fast XLA wires vs the general Pallas/carry-chain path."""

    @pytest.mark.parametrize("psi_int", [
        1, 2 ** 32 - 1, 2 ** 32, 2 ** 64 - 1, Q // 2, Q - 1,
        Q - 2 ** 32 + 1, Q - 2 ** 32, Q - 2 ** 32 - 1,   # reduction corner
    ])
    def test_paper_wire_exact_vs_general(self, psi_int):
        psi = _psi_limbs([psi_int])
        x = jnp.asarray(rng.standard_normal((1, 256)), jnp.float32)
        # plant payload words right at the single-limb overflow threshold
        wds = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint32)).copy()
        thr = (Q - psi_int) % (2 ** 32)
        wds[0, :4] = [thr % 2 ** 32, (thr - 1) % 2 ** 32,
                      (thr + 1) % 2 ** 32, 2 ** 32 - 1]
        x = jax.lax.bitcast_convert_type(jnp.asarray(wds), jnp.float32)
        out_s, ct_s = wire_roundtrip(x, psi, q=Q, mode="paper",
                                     use_kernel=False, return_ct=True)
        out_g, ct_g = wire_roundtrip(x, psi, q=Q, mode="paper",
                                     use_kernel=True, interpret=True,
                                     return_ct=True)
        np.testing.assert_array_equal(np.asarray(ct_s), np.asarray(ct_g))
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(out_s, jnp.uint32)),
            np.asarray(jax.lax.bitcast_convert_type(out_g, jnp.uint32)))

    def test_stream_narrow_wire_exact_vs_general(self):
        seeds = _materials(4, "stream", 5)
        x = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
        out_n, ct_n = wire_roundtrip(x, seeds, q=Q, mode="stream",
                                     use_kernel=False, return_ct=True)
        out_g, ct_g = wire_roundtrip(x, seeds, q=Q, mode="stream",
                                     use_kernel=True, interpret=True,
                                     return_ct=True)
        np.testing.assert_array_equal(np.asarray(ct_n), np.asarray(ct_g))
        np.testing.assert_array_equal(np.asarray(out_n), np.asarray(out_g))

    @pytest.mark.parametrize("mode", ["stream", "paper"])
    def test_roundtrip_is_bit_identity(self, mode):
        x = jnp.asarray(rng.standard_normal((3, 100)) * 1e20, jnp.float32)
        out = wire_roundtrip(x, _materials(3, mode, 6), q=Q, mode=mode)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


class TestFusedWire:
    @pytest.mark.parametrize("mode", ["stream", "paper"])
    @pytest.mark.parametrize("w", [1, 5, 1000, 1025])   # off-bucket sizes
    def test_standalone_wire_identity(self, mode, w):
        words = jnp.asarray(
            rng.integers(0, 2 ** 32, (3, w), dtype=np.uint32))
        out = ops.fused_wire(words, _materials(3, mode, 7), q=Q, mode=mode,
                             force_kernel=False)
        assert out.shape == (3, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(words))
