"""Plain reference of the ``spacdc-fig3-ffn`` rounds: the coded product
that a master decoding from a given responder set computes, in float64 on
the host, and the control, the same pipeline computed in float32 at
``high`` precision (three bf16 passes) on the device.

A coded round is approximate by design (Berrut's interpolant does not
reproduce the blocks), so the reference is the coded computation itself,
not A @ B.  It is linear: decoded block k = sum_j M[k, j] X_j B, where
X_0..X_{K-1} are A's row blocks, X_K.. the noise blocks, and M = decode
(responders) @ encode (responders).  The reference multiplies every block
by B once and mixes the products per round.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spacdc_ref  # noqa: E402


def _blocks(a, k: int):
    m, d = a.shape
    blk = -(-m // k)
    pad = np.zeros((k * blk - m, d), a.dtype)
    return np.concatenate([a, pad]).reshape(k, blk, d), blk


def reference(a, b, rounds, code: dict, seed: int):
    """float64 products for each round's responder set in ``rounds``.
    ``a``, ``b`` are host or device arrays; ``code`` holds n_workers,
    k_blocks, t_colluding and noise_scale; ``seed`` is the cluster seed."""
    n, k, t = code["n_workers"], code["k_blocks"], code["t_colluding"]
    a64 = np.asarray(a, np.float64)
    m, d = a64.shape
    blocks, blk = _blocks(a64, k)
    noise = np.asarray(spacdc_ref.noise_blocks(seed, t, blk, d,
                                               code["noise_scale"]),
                       np.float64)
    xs = np.concatenate([blocks, noise]).reshape(-1, d)
    xb = (xs @ np.asarray(b, np.float64)).reshape(k + t, blk, -1)
    del a64, blocks, xs
    for resp in rounds:
        mix = spacdc_ref.decode_through_encode(resp, n, k, t)
        yield np.einsum("kj,jbn->kbn", mix, xb).reshape(k * blk, -1)[:m]


def control(a, b, rounds, code: dict, seed: int):
    """The reference in the program's place one precision lower: encode,
    N worker products and masked decode, each a float32 matmul at ``high``
    precision, on the default device."""
    import jax
    import jax.numpy as jnp
    n, k, t = code["n_workers"], code["k_blocks"], code["t_colluding"]
    m, d = a.shape
    blk = -(-m // k)
    noise = spacdc_ref.noise_blocks(seed, t, blk, d, code["noise_scale"])
    enc = jnp.asarray(spacdc_ref.encoder(n, k, t), jnp.float32)

    @jax.jit
    def products(a, b, noise):
        xs = jnp.concatenate([jnp.pad(a, ((0, k * blk - m), (0, 0))),
                              noise.reshape(t * blk, d)])
        shards = spacdc_ref.bf16x3_dot(enc, xs.reshape(k + t, -1))
        shards = shards.reshape(n, blk, d)
        return jax.vmap(spacdc_ref.bf16x3_dot, (0, None))(shards, b)

    @jax.jit
    def decode(prods, dec):
        out = spacdc_ref.bf16x3_dot(dec, prods.reshape(n, -1))
        return out.reshape(k * blk, -1)[:m]

    prods = products(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
                     noise)
    for resp in rounds:
        dec = jnp.asarray(spacdc_ref.decoder(resp, n, k, t), jnp.float32)
        yield np.asarray(decode(prods, dec), np.float64)


def widest_gap(outputs, refs) -> float:
    """The largest over rounds of max |out - ref| / max |ref|."""
    gap = 0.0
    for out, ref in zip(outputs, refs):
        g = float(np.abs(np.asarray(out, np.float64) - ref).max()
                  / np.abs(ref).max())
        if not np.isfinite(g):          # a NaN must fail, not vanish in max
            return float("inf")
        gap = max(gap, g)
    return gap
