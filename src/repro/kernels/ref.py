"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each kernel's test sweeps shapes/dtypes and asserts allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def berrut_combine(weights: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """SPACDC encode/decode contraction: out[q] = Σ_j W[q,j]·blocks[j].

    weights (Q, J); blocks (J, M) (flattened block payload).  f32 accumulate.
    """
    return jnp.dot(weights.astype(jnp.float32), blocks.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST).astype(blocks.dtype)


def coded_matmul(weights: jnp.ndarray, blocks: jnp.ndarray,
                 rhs: jnp.ndarray) -> jnp.ndarray:
    """Fused coded-round oracle, computed *unfused*: encode the blocks, then
    run each worker's matmul.

    weights (N, J); blocks (J, blk, d); rhs (d, n_out) -> (N, blk, n_out).
    f32 accumulate throughout.
    """
    flat = blocks.reshape(blocks.shape[0], -1).astype(jnp.float32)
    coded = jnp.dot(weights.astype(jnp.float32), flat,
                    precision=jax.lax.Precision.HIGHEST)
    coded = coded.reshape((weights.shape[0],) + blocks.shape[1:])
    out = jnp.einsum("nij,jk->nik", coded, rhs.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(blocks.dtype)


def held_experts(x, gates, w_gate, w_up, w_down) -> jnp.ndarray:
    """Held-experts decode oracle, the kernel's math unfused: every held
    expert on every row, in the compute dtype (x's) with float32
    accumulation, each row's hidden state scaled by its gate before the
    down projection.

    x (B, d); gates (B, E); w_gate, w_up (E, d, f); w_down (E, f, d)
    -> (B, d) float32.
    """
    cd, f32 = x.dtype, jnp.float32
    gate = jnp.einsum("bd,edf->ebf", x, w_gate.astype(cd),
                      preferred_element_type=f32)
    up = jnp.einsum("bd,edf->ebf", x, w_up.astype(cd),
                    preferred_element_type=f32)
    h = (jax.nn.silu(gate) * up * gates.astype(f32).T[..., None]).astype(cd)
    return jnp.einsum("ebf,efd->bd", h, w_down.astype(cd),
                      preferred_element_type=f32)


def mask_add(payload: jnp.ndarray, mask: jnp.ndarray, q_limbs,
             *, subtract: bool = False) -> jnp.ndarray:
    """MEA-ECC mask add/sub oracle: (payload ± mask) mod q over uint32 limb
    planes ``(..., L)`` — the carry-chain + single-conditional-subtract
    reduction from ``repro.crypto.field``, traced with jnp (uint32-only, so
    it runs identically under XLA and numpy).
    """
    from ..crypto import field as _field
    payload = jnp.asarray(payload, jnp.uint32)
    mask = jnp.broadcast_to(jnp.asarray(mask, jnp.uint32), payload.shape)
    q_limbs = jnp.asarray(q_limbs, jnp.uint32)
    op = _field.sub_mod if subtract else _field.add_mod
    return op(payload, mask, q_limbs, xp=jnp)


def encrypted_coded_matmul(weights, blocks, rhs, material_out, material_back,
                           *, q: int, mode: str):
    """Encrypted-round oracle, computed naively: encode, run every wire
    through the *general* limb cipher (codec embed -> full-width
    ``add_mod`` mask-add -> ``sub_mod``), worker matmuls, wire the results
    back.  Same contractions/precision as :func:`coded_matmul`, so the
    output must be bit-identical to the plain oracle — the cipher round
    trips are lossless by construction.

    weights (N, J); blocks (J, blk, d); rhs (d, n_out); ``material_out`` /
    ``material_back`` are per-channel (N, 8) PRF seed words (stream) or
    (N, L) Ψ limbs (paper).
    """
    from ..crypto import field as _field
    n_limbs = max(-(-q.bit_length() // 32), 1)
    q_limbs = jnp.asarray(_field.int_to_limbs(q, n_limbs), jnp.uint32)

    def wire(x, material):
        words = jax.lax.bitcast_convert_type(
            x.reshape(x.shape[0], -1).astype(jnp.float32), jnp.uint32)
        zero = jnp.zeros_like(words)
        limbs = jnp.stack([words] + [zero] * (n_limbs - 1), axis=-1)
        material_ = jnp.asarray(material, jnp.uint32)
        if mode == "stream":
            mask = jax.vmap(lambda s: _field.stream_mask_traced(
                s, words.shape[1], n_limbs))(material_)
        else:
            mask = jnp.broadcast_to(material_[:, None, :], limbs.shape)
        ct = _field.add_mod(limbs, mask, q_limbs, xp=jnp)
        ct = jax.lax.optimization_barrier(ct)
        out = _field.sub_mod(ct, mask, q_limbs, xp=jnp)[..., 0]
        return jax.lax.bitcast_convert_type(out, jnp.float32).reshape(x.shape)

    flat = blocks.reshape(blocks.shape[0], -1).astype(jnp.float32)
    coded = jnp.dot(weights.astype(jnp.float32), flat,
                    precision=jax.lax.Precision.HIGHEST)
    coded = coded.reshape((weights.shape[0],) + blocks.shape[1:])
    coded = wire(coded, material_out)
    out = jnp.einsum("nij,jk->nik", coded, rhs.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return wire(out, material_back).astype(blocks.dtype)


def mha_reference(q, k, v, *, causal: bool, softcap: float = 0.0):
    """Dense multi-head attention oracle.  q (B,Sq,H,hd) k/v (B,Skv,KV,hd)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd).astype(jnp.float32) / (hd ** 0.5)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qg, k.astype(jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = jnp.arange(k.shape[1])[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
