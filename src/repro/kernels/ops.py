"""Backend-dispatching wrappers for the Pallas kernels.

On TPU the Pallas kernels run compiled; everywhere else (CPU tests, the
dry-run's CPU target) they run the pure-XLA twin from models/ or the
interpret-mode kernel.  The dispatch is explicit and importable so tests can
force either path.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import ref
from .berrut_encode import berrut_encode_kernel
from .coded_matmul import coded_matmul_kernel
from .flash_attention import flash_attention_kernel
from .held_experts import held_experts_kernel
from .mask_add import mask_add_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def berrut_combine(weights, blocks, *, force_kernel: bool | None = None):
    """Coding-scheme encode/decode contraction with kernel dispatch.

    Every registered ``CodingScheme`` (see ``repro.core.registry``) routes
    its encode/decode matrix products here.  ``force_kernel`` is the
    schemes' ``use_kernel`` tri-state: None = kernel on TPU only, True =
    force the Pallas kernel (interpret mode off-TPU), False = pure XLA.

    blocks may be any (J, ...) tree-shaped payload; flattened internally.
    """
    blocks = jnp.asarray(blocks)
    j = blocks.shape[0]
    flat = blocks.reshape(j, -1)
    use_kernel = _on_tpu() if force_kernel is None else force_kernel
    if use_kernel:
        out = berrut_encode_kernel(weights, flat, interpret=not _on_tpu())
    else:
        out = ref.berrut_combine(weights, flat)
    return out.reshape((weights.shape[0],) + blocks.shape[1:])


def prefix_decode(weights, results, *, force_kernel: bool | None = None):
    """Batched prefix-masked decode: every responder prefix of a round in
    ONE contraction.

    ``weights`` (E, K, N) — stacked decode matrices, one per responder
    prefix (``CodingScheme.prefix_decode_weights``); ``results`` (N, ...)
    — the workers' outputs.  Returns (E, K, ...): row e is what decoding
    after the (e+1)-th arrival would have yielded.  The prefix axis folds
    into the output-row axis of :func:`berrut_combine`, so evaluating E
    error points of an anytime curve costs one dispatch, not E — the same
    kernel the per-round decode already runs.
    """
    weights = jnp.asarray(weights, jnp.float32)
    e, k, n = weights.shape
    out = berrut_combine(weights.reshape(e * k, n), results,
                         force_kernel=force_kernel)
    return out.reshape((e, k) + out.shape[1:])


def coded_matmul(weights, blocks, rhs, *, force_kernel: bool | None = None):
    """Fused encode + batched worker matmul with kernel dispatch.

    out[n] = (weights @ blocks)[n] @ rhs — the round hot path of every
    linear data-coded scheme (``CodingScheme.fused_round``).  On the kernel
    path the coded shards never materialize in HBM; the XLA twin computes
    the same contraction unfused.  ``force_kernel`` is the schemes'
    ``use_kernel`` tri-state (None = kernel on TPU only).
    """
    blocks = jnp.asarray(blocks)
    rhs = jnp.asarray(rhs)
    weights = jnp.asarray(weights, jnp.float32)
    use_kernel = _on_tpu() if force_kernel is None else force_kernel
    if use_kernel:
        return coded_matmul_kernel(weights, blocks, rhs,
                                   interpret=not _on_tpu())
    return ref.coded_matmul(weights, blocks, rhs)


def held_experts(x, gates, w_gate, w_up, w_down):
    """One decode step of the routed experts a chip holds, with kernel
    dispatch: the Pallas kernel on a TPU, its XLA twin elsewhere.

    x (B, d) in the compute dtype; gates (B, E) routed weights (zero
    where a row did not choose the expert); w_gate, w_up (E, d, f);
    w_down (E, f, d).  Returns (B, d) float32:
    ``sum_e gates[:, e] * (silu(x W_g,e) * x W_u,e) W_d,e``.
    """
    if _on_tpu():
        return held_experts_kernel(x, gates, w_gate, w_up, w_down,
                                   interpret=False)
    return ref.held_experts(x, gates, w_gate, w_up, w_down)


@functools.partial(jax.jit, static_argnames=("q", "use_kernel", "interpret",
                                             "subtract"))
def _mask_add_impl(payload, mask, *, q, use_kernel, interpret, subtract):
    return _limb_ready(payload, mask, q, use_kernel, interpret, subtract)


def mask_add(payload, mask, q: int, *, subtract=False,
             force_kernel: bool | None = None):
    """MEA-ECC mask add/sub with kernel dispatch.

    (payload ± mask) mod q over uint32 limb planes ``(..., L)`` — the
    encrypt/decrypt hot loop of the limb-vectorized cipher
    (``repro.crypto.mea_ecc``), the same tail the one-dispatch cipher
    cores run (``_limb_ready``).  ``q`` is the modulus as a python int
    (static: it selects the compiled kernel).  ``mask`` broadcasts against
    ``payload`` (paper mode passes one scalar mask element).
    ``force_kernel`` is the usual tri-state: None = kernel on TPU only,
    True = force the Pallas kernel (interpret mode off-TPU), False = pure
    XLA.
    """
    payload = jnp.asarray(payload, jnp.uint32)
    lead, L = payload.shape[:-1], payload.shape[-1]
    mask = jnp.broadcast_to(jnp.asarray(mask, jnp.uint32), payload.shape)
    use_kernel = _on_tpu() if force_kernel is None else force_kernel
    out = _mask_add_impl(payload.reshape(-1, L), mask.reshape(-1, L), q=q,
                         use_kernel=bool(use_kernel),
                         interpret=not _on_tpu(), subtract=subtract)
    return out.reshape(lead + (L,))


def _limb_ready(limbs, mask, q: int, use_kernel: bool, interpret: bool,
                subtract: bool):
    """Shared tail of the cipher cores: (limbs ± mask) mod q, through the
    Pallas kernel or the xp twin (both traceable — callable under jit)."""
    from ..crypto import field as _field
    q_limbs = tuple(int(v) for v in _field.int_to_limbs(q, limbs.shape[-1]))
    mask = jnp.broadcast_to(mask, limbs.shape)
    if use_kernel:
        return mask_add_kernel(limbs, mask, q_limbs=q_limbs,
                               subtract=subtract, interpret=interpret)
    op = _field.sub_mod if subtract else _field.add_mod
    return op(limbs, mask, jnp.asarray(q_limbs, dtype=jnp.uint32), xp=jnp)


def _core_mask(mask_material, mode: str, n: int, n_limbs: int):
    from ..crypto import field as _field
    if mode == "stream":
        # mask_material = (8,) uint32 PRF seed words; SHA runs in-trace
        return _field.stream_mask_traced(mask_material, n, n_limbs)
    return mask_material                       # paper: (L,) psi limbs


@functools.partial(jax.jit, static_argnames=(
    "q", "frac_bits", "mode", "codec", "use_kernel", "interpret", "n_limbs"))
def mea_encrypt_core(data, mask_material, *, q: int, frac_bits: int,
                     mode: str, codec: str, use_kernel: bool,
                     interpret: bool, n_limbs: int):
    """One-dispatch MEA-ECC encrypt: codec embed + mask PRF + limb add.

    ``data`` is (n,) float32 (codec="fixed") or (n,) uint32 raw words
    (codec="bits"); returns the (n, L) uint32 payload limbs.  The whole
    direction is a single elementwise XLA program (the limb add optionally
    through the Pallas ``mask_add`` kernel) — this is what makes encrypted
    rounds wire-speed instead of modeled.
    """
    from ..crypto import field as _field
    if codec == "fixed":
        limbs = _field.fixed_encode_traced(data, q, frac_bits, n_limbs)
    else:
        word = jnp.asarray(data, jnp.uint32)
        zero = jnp.zeros_like(word)
        limbs = jnp.stack([word] + [zero] * (n_limbs - 1), axis=-1)
    mask = _core_mask(mask_material, mode, limbs.shape[0], n_limbs)
    return _limb_ready(limbs, mask, q, use_kernel, interpret, subtract=False)


@functools.partial(jax.jit, static_argnames=(
    "q", "frac_bits", "mode", "codec", "use_kernel", "interpret"))
def mea_decrypt_core(payload, mask_material, *, q: int, frac_bits: int,
                     mode: str, codec: str, use_kernel: bool,
                     interpret: bool):
    """One-dispatch MEA-ECC decrypt: limb subtract + codec extract.

    Returns (n,) float32 (codec="fixed") or (n,) uint32 raw words
    (codec="bits").
    """
    from ..crypto import field as _field
    payload = jnp.asarray(payload, jnp.uint32)
    n, n_limbs = payload.shape
    mask = _core_mask(mask_material, mode, n, n_limbs)
    unmasked = _limb_ready(payload, mask, q, use_kernel, interpret,
                           subtract=True)
    if codec == "fixed":
        return _field.fixed_decode_traced(unmasked, q, frac_bits)
    return unmasked[:, 0]


def encrypted_coded_matmul(weights, blocks, rhs, material_out, material_back,
                           *, q: int, mode: str,
                           force_kernel: bool | None = None,
                           return_wire: bool = False):
    """One-dispatch encrypted round with kernel dispatch.

    encode -> MEA-ECC wire-out -> batched worker matmul -> MEA-ECC
    wire-back, one traceable program (see ``kernels.encrypted_round``).
    ``force_kernel`` is the usual tri-state for the matmuls: None = kernel
    on TPU only, True = the two halves of the Pallas ``coded_matmul``
    kernel (interpret mode off-TPU), False = pure XLA.  The wires are the
    specialized bits-codec wires either way.
    ``return_wire`` also returns the (N, W, L) out/back ciphertext limb
    planes (parity tests against ``mea_encrypt_core``).

    Per-round state (straggler mask is downstream; stream nonces arrive as
    fresh seed words in ``material_*``) is runtime data, so churn never
    retraces; shape classes cache like the plain fused round.  Standalone
    host-side wires should go through :func:`fused_wire`, which pads to
    the same pow2 buckets as the cipher cores.
    """
    from .encrypted_round import encrypted_coded_matmul as _impl
    use_kernel = _on_tpu() if force_kernel is None else bool(force_kernel)
    return _impl(weights, blocks, rhs, material_out, material_back, q=q,
                 mode=mode, use_kernel=use_kernel, interpret=not _on_tpu(),
                 return_wire=return_wire)


@functools.partial(jax.jit, static_argnames=("q", "mode", "use_kernel",
                                             "interpret"))
def _fused_wire_core(words, material, *, q, mode, use_kernel, interpret):
    from .encrypted_round import wire_roundtrip
    x = jax.lax.bitcast_convert_type(words, jnp.float32)
    out = wire_roundtrip(x, material, q=q, mode=mode, use_kernel=use_kernel,
                         interpret=interpret)
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


def fused_wire(words, material, *, q: int, mode: str,
               force_kernel: bool | None = None):
    """Standalone wire round trip (encrypt + pinned ciphertext + decrypt)
    over (N, W) uint32 payload words, jitted per pow2 bucket.

    The word axis pads to the same ``_bucket`` sizes as
    ``mea_encrypt_core`` — the counter PRF is prefix-stable and the pad
    lanes mask zeros, so pad-then-slice is bit-identical — which keeps
    host-side callers (timing probes, staged-path upgrades) at one
    compiled program per bucket instead of one per shape.
    """
    from ..crypto.mea_ecc import _bucket
    words = jnp.asarray(words, jnp.uint32)
    n, w = words.shape
    wb = _bucket(w)
    padded = jnp.pad(words, ((0, 0), (0, wb - w)))
    out = _fused_wire_core(padded, jnp.asarray(material, jnp.uint32), q=q,
                           mode=mode,
                           use_kernel=_on_tpu() if force_kernel is None
                           else bool(force_kernel),
                           interpret=not _on_tpu())
    return out[:, :w]


def flash_attention(q, k, v, *, causal=True, softcap=0.0,
                    force_kernel: bool | None = None):
    """Full-sequence attention with kernel dispatch (positions implicit)."""
    use_kernel = _on_tpu() if force_kernel is None else force_kernel
    if use_kernel:
        return flash_attention_kernel(q, k, v, causal=causal, softcap=softcap,
                                      interpret=not _on_tpu())
    b, sq = q.shape[:2]
    from ..models.attention import flash_attention as xla_flash
    pos_q = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
    pos_k = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
    return xla_flash(q, k, v, q_positions=pos_q, kv_positions=pos_k,
                     causal=causal, softcap=softcap)
