"""The one-dispatch encrypted round: keystream + mask-add fused into the
coded-matmul pipeline.

``encrypted_coded_matmul`` is the traceable body of an encrypt="real"
round: encode -> MEA-ECC wire-out (master encrypts every coded shard, its
worker decrypts) -> batched worker matmul -> wire-back (every worker
encrypts its product, the master decrypts) — all inside ONE jit program,
where the staged path pays three jitted stages plus two host-side cipher
dispatches per transfer (``ops.mea_encrypt_core`` / ``mea_decrypt_core``).
Fusing buys three things:

* the SHA-256 counter keystream of each channel is generated ONCE per
  transfer and shared by the mask-add and the mask-sub (the staged cores
  regenerate it on both ends — 2× the SHA of the round's true cost);
* no host round trips: ciphertexts stay device arrays between the wire
  boundaries instead of bouncing through numpy between stages;
* the whole round compiles/caches like the plain fused round — straggler
  churn and fresh per-round nonces are runtime arguments and never
  retrace.

Every wire is a *genuine* cipher application, not a modeled cost: the
payload crosses as (n, L) uint32 field-element limbs masked with the same
mask material the staged ``MEAECC`` path derives, and a
``jax.lax.optimization_barrier`` pins each ciphertext so XLA can never
algebraically cancel ``decrypt(encrypt(x))`` back to ``x``.  Ciphertext
limb parity with ``ops.mea_encrypt_core`` is asserted in
``tests/test_encrypted_round.py``.

The bits-codec wire (raw float words in limb 0) admits two exact
specializations of the general carry-chain mask-add:

* **stream**: payload < 2^32 and mask < 2^64, so payload + mask < 2^65 —
  never reaches a >64-bit modulus and the reduction branch is provably
  dead.  The cipher runs on the 3 live limb planes; the transmitted
  ciphertext is those planes (limbs 3.. are structurally zero).
* **paper**: the mask Ψ is one per-channel constant, so the sum's high
  limbs take only three values (Ψ_hi, Ψ_hi+1, or 0 after the single
  conditional subtract of q) — the per-element work collapses to one u32
  add, two compares and a select; the reduction test ``w + Ψ ≥ q``
  becomes the single-limb threshold ``w ≥ (q - Ψ) mod 2^32``.

Both specializations are bit-identical to ``crypto.field.add_mod`` /
``sub_mod`` (fuzzed against the numpy oracle in tests, adversarial Ψ near
q included), and the rounds and the serving step run them on every
platform: they move 3 limb planes (stream) or one word plus a u8
selector (paper) where the general carry chain moves all L planes.  On a
v5e at the paper's fig-3 round (N=30, shards 768×3584) the general Pallas
``mask_add`` wire needed 12.5 GB of HBM in stream mode and ~130 s to
compile behind the encode kernel.  ``wire_roundtrip(use_kernel=True)``
keeps the general kernel path as the parity reference.  With
``use_kernel=True`` the round's encode and worker matmul run as the two
halves of the Pallas ``coded_matmul`` kernel (``coded_encode_kernel`` /
``coded_worker_kernel``), tiled as the fused kernel is, so the encrypted
round equals the plain kernel round bit for bit.

Retrace policy mirrors the plain fused round: the engine jits one program
per (a, b) shape class (LRU-cached), and everything per-round — straggler
mask, stream nonces/seeds — is a runtime argument.  The standalone
``ops.fused_wire`` entry pads the element axis to the same pow2 buckets
as ``mea_encrypt_core`` (`crypto.mea_ecc._bucket`), so host-side callers
compile one wire program per bucket, not per shape; the counter PRF is
prefix-stable, so bucket-padding then slicing is bit-identical.  The
in-trace path keeps exact sizes — padding the matmul operands would
change f32 accumulation order and break the round's bit-identity with the
plain fused round.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _n_limbs(q: int) -> int:
    return max(-(-q.bit_length() // 32), 1)


def _q_limbs(q: int, n_limbs: int):
    from ..crypto import field as _field
    return tuple(int(v) for v in _field.int_to_limbs(q, n_limbs))


def _stream_words(material, n_words: int):
    """(N, 8) seed words -> ((N, n_words) lo, (N, n_words) hi) u64 mask
    word halves, all channels in one cache-chunked SHA scan."""
    from ..crypto import field as _field
    return _field.keystream_words_traced_batched(material, n_words)


def _embed_limbs(words, n_limbs: int):
    """Raw u32 payload words -> (..., L) limb planes (word in limb 0)."""
    zero = jnp.zeros_like(words)
    return jnp.stack([words] + [zero] * (n_limbs - 1), axis=-1)


def _general_mask(material, mode: str, n_words: int, n_limbs: int):
    """The mask limb planes the staged cores derive: (N, n_words, L)."""
    from ..crypto import field as _field
    if mode == "stream":
        lo, hi = _field.keystream_words_traced_batched(material, n_words)
        zero = jnp.zeros_like(lo)
        return jnp.stack([lo, hi] + [zero] * (n_limbs - 2), axis=-1)
    return jnp.broadcast_to(material[:, None, :],
                            material.shape[:1] + (n_words,) + material.shape[1:])


def _limb_op(limbs, mask, q: int, use_kernel: bool, interpret: bool,
             subtract: bool):
    from .ops import _limb_ready
    lead = limbs.shape[:-1]
    out = _limb_ready(limbs.reshape(-1, limbs.shape[-1]),
                      mask.reshape(-1, mask.shape[-1]), q, use_kernel,
                      interpret, subtract)
    return out.reshape(lead + (limbs.shape[-1],))


def _paper_channel_consts(psi, q: int, n_limbs: int):
    """Per-channel constants of the specialized paper wire, in-trace from
    the (N, L) Ψ limbs: (psi0, psi_hi, psi_hi_plus1, thr0, ovf_possible).

    thr = q - Ψ is the single-limb overflow threshold: w + Ψ ≥ q iff
    thr < 2^32 and w ≥ thr (w < 2^32).  All (N,)/(N, L-1) — negligible.
    """
    ql = _q_limbs(q, n_limbs)
    psi0 = psi[:, 0]
    psi_hi = psi[:, 1:]
    # psi_hi + 1 with an unrolled carry chain over the L-1 high limbs
    plus1 = []
    carry = jnp.ones_like(psi0)
    for j in range(n_limbs - 1):
        s = psi_hi[:, j] + carry
        carry = (s < carry).astype(jnp.uint32)
        plus1.append(s)
    psi_hi1 = jnp.stack(plus1, axis=-1)
    # thr = q - Ψ (Ψ < q, so no borrow out of the top limb)
    thr = []
    borrow = jnp.zeros_like(psi0)
    for j in range(n_limbs):
        qj = jnp.uint32(ql[j])
        d = qj - psi[:, j]
        b1 = (qj < psi[:, j]).astype(jnp.uint32)
        d2 = d - borrow
        b2 = (d < borrow).astype(jnp.uint32)
        thr.append(d2)
        borrow = b1 | b2
    thr0 = thr[0]
    ovf_p = jnp.ones_like(psi0, bool)
    for j in range(1, n_limbs):
        ovf_p = ovf_p & (thr[j] == 0)
    return psi0, psi_hi, psi_hi1, thr0, ovf_p


def _paper_encrypt(words, consts):
    """(N, W) u32 payload words -> compact ciphertext (c0 plane, selector
    plane), bit-identical (after :func:`_paper_expand_ct`) to
    add_mod(embed(words), Ψ) — one add, two compares, one select per
    element instead of the general 8-limb carry chain.

    Because Ψ is channel-constant, the high limbs of the sum take only
    three per-channel values: Ψ_hi (no carry), Ψ_hi + 1 (carry out of limb
    0), or 0 (after the conditional subtract of q — possible only when
    Ψ > q - 2^32, and then Ψ_hi ≠ 0 and Ψ_hi + 1 ≠ 0, so the three cases
    never collide).  The *transmitted* representation is therefore c0 plus
    a 2-bit selector per word (a uint8 plane) next to a tiny per-channel
    header — a lossless recoding of the full (W, L) ciphertext that an
    actual transport would send to save 8× bandwidth.  The selector leaks
    nothing the full ciphertext doesn't: it is a public function of the
    ciphertext limbs and the channel header.
    """
    psi0, psi_hi, psi_hi1, thr0, ovf_p = consts
    s0 = words + psi0[:, None]
    carry = s0 < words                       # u32 wraparound
    ovf = ovf_p[:, None] & (words >= thr0[:, None])
    c0 = jnp.where(ovf, words - thr0[:, None], s0)
    sel = jnp.where(ovf, jnp.uint8(2),
                    jnp.where(carry, jnp.uint8(1), jnp.uint8(0)))
    return c0, sel


def _paper_decrypt(c0, sel, consts):
    """Inverse of :func:`_paper_encrypt` from the compact wire alone."""
    psi0, _, _, thr0, _ = consts
    return jnp.where(sel == jnp.uint8(2), c0 + thr0[:, None],
                     c0 - psi0[:, None])


def _paper_expand_ct(c0, sel, consts, n_limbs: int):
    """Compact wire -> full (N, W, L) ciphertext limb planes (parity tests
    against ``mea_encrypt_core``; never on the hot path)."""
    _, psi_hi, psi_hi1, _, _ = consts
    c_hi = jnp.where((sel == jnp.uint8(2))[..., None], jnp.uint32(0),
                     jnp.where((sel == jnp.uint8(1))[..., None],
                               psi_hi1[:, None, :], psi_hi[:, None, :]))
    return jnp.concatenate([c0[..., None], c_hi], axis=-1)


def _wire_stream_fast(words, material, n_limbs: int, return_ct: bool):
    """Narrow 3-limb stream wire: payload + u64 mask < 2^65 ≪ q, so the
    modular reduction is provably dead and limbs 3.. stay zero — the
    transmitted ciphertext is the 3 live limb planes."""
    lo, hi = _stream_words(material, words.shape[1])
    c0 = words + lo
    carry = (c0 < words).astype(jnp.uint32)
    c1 = hi + carry
    c2 = (c1 < hi).astype(jnp.uint32)        # wraps only at hi == 2^32-1
    ct = jnp.stack([c0, c1, c2], axis=-1)
    ct = jax.lax.optimization_barrier(ct)    # the wire: these bits exist
    out = ct[..., 0] - lo
    if not return_ct:
        return out, None
    pad = jnp.zeros(ct.shape[:-1] + (n_limbs - 3,), jnp.uint32)
    return out, jnp.concatenate([ct, pad], axis=-1)


def _wire_paper_fast(words, material, q: int, n_limbs: int, return_ct: bool):
    consts = _paper_channel_consts(jnp.asarray(material, jnp.uint32), q,
                                   n_limbs)
    c0, sel = _paper_encrypt(words, consts)
    c0, sel = jax.lax.optimization_barrier((c0, sel))  # the transmitted bits
    out = _paper_decrypt(c0, sel, consts)
    if not return_ct:
        return out, None
    return out, _paper_expand_ct(c0, sel, consts, n_limbs)


def _wire_general(words, material, q: int, mode: str, n_limbs: int,
                  use_kernel: bool, interpret: bool, return_ct: bool):
    mask = _general_mask(material, mode, words.shape[1], n_limbs)
    ct = _limb_op(_embed_limbs(words, n_limbs), mask, q, use_kernel,
                  interpret, subtract=False)
    ct = jax.lax.optimization_barrier(ct)
    out = _limb_op(ct, mask, q, use_kernel, interpret, subtract=True)
    return out[..., 0], (ct if return_ct else None)


def wire_roundtrip(x, material, *, q: int, mode: str,
                   use_kernel: bool = False, interpret: bool = True,
                   return_ct: bool = False):
    """One traceable wire round trip: encrypt ``x`` per channel, pin the
    ciphertext, decrypt.  ``x`` is (N, ...) float32 — axis 0 is the
    channel (worker) axis; ``material`` is (N, 8) PRF seed words (stream)
    or (N, L) Ψ limbs (paper).  Returns ``x`` bit-identically (the bits
    codec is lossless) — plus the (N, W, L) ciphertext limbs when
    ``return_ct`` (parity tests against ``mea_encrypt_core``).
    """
    if mode == "stream" and q.bit_length() <= 64:
        raise ValueError("fused stream wire needs a >64-bit modulus "
                         "(mask words are unreduced u64)")
    n_limbs = _n_limbs(q)
    shape = x.shape
    words = jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.float32).reshape(shape[0], -1), jnp.uint32)
    material = jnp.asarray(material, jnp.uint32)
    if use_kernel:
        out, ct = _wire_general(words, material, q, mode, n_limbs,
                                use_kernel, interpret, return_ct)
    elif mode == "stream":
        out, ct = _wire_stream_fast(words, material, n_limbs, return_ct)
    else:
        out, ct = _wire_paper_fast(words, material, q, n_limbs, return_ct)
    out = jax.lax.bitcast_convert_type(out, jnp.float32).reshape(shape)
    return (out, ct) if return_ct else out


def encrypted_coded_matmul(weights, blocks, rhs, material_out, material_back,
                           *, q: int, mode: str,
                           use_kernel: bool = False, interpret: bool = True,
                           return_wire: bool = False):
    """The encrypted round body: encode -> wire-out -> worker matmul ->
    wire-back, one traceable program.

    weights (N, J); blocks (J, blk, d); rhs (d, n_out); material_* as in
    :func:`wire_roundtrip` -> (N, blk, n_out) worker results, ready for
    the masked decode.  Because every wire is the lossless bits-codec
    round trip, the results are bit-identical to the plain round on the
    same path: ``ref.coded_matmul`` / the staged real path on the XLA
    path, the fused ``coded_matmul`` kernel on the kernel path — asserted
    in tests.  ``return_wire`` additionally returns the out/back ciphertext
    limb planes.
    """
    blocks = jnp.asarray(blocks)
    rhs = jnp.asarray(rhs, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    if use_kernel:
        # the two halves of the fused coded_matmul kernel, tiled as it is:
        # the shards and results match the plain kernel round bit for bit
        from .coded_matmul import coded_encode_kernel, coded_worker_kernel
        coded = coded_encode_kernel(weights, blocks, n_out=rhs.shape[1],
                                    interpret=interpret)
    else:
        flat = blocks.reshape(blocks.shape[0], -1).astype(jnp.float32)
        coded = jnp.dot(weights, flat, precision=jax.lax.Precision.HIGHEST)
        coded = coded.reshape((weights.shape[0],) + blocks.shape[1:])
    # wire out: each worker receives (and decrypts) its coded shard.  The
    # wires are the specialized bits-codec wires on every platform (see
    # the module docstring); ``use_kernel`` picks the matmul kernels only.
    coded, ct_out = (wire_roundtrip(coded, material_out, q=q, mode=mode,
                                    return_ct=True)
                     if return_wire else
                     (wire_roundtrip(coded, material_out, q=q, mode=mode),
                      None))
    if use_kernel:
        results = coded_worker_kernel(coded, rhs, n_blocks=blocks.shape[0],
                                      interpret=interpret)
    else:
        results = jnp.einsum("nij,jk->nik", coded, rhs,
                             precision=jax.lax.Precision.HIGHEST)
    # wire back: every worker's product returns encrypted (the straggler
    # slots are computed too — the virtual clock prices who actually ran)
    results, ct_back = (wire_roundtrip(results, material_back, q=q,
                                       mode=mode, return_ct=True)
                        if return_wire else
                        (wire_roundtrip(results, material_back, q=q,
                                        mode=mode), None))
    if return_wire:
        return results, ct_out, ct_back
    return results
