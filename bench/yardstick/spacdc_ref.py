"""Plain SPACDC coding math in float64 (paper section V, Eqs. 17-18), for the
references that decide ``correct``.  Written from the paper, not from the
program: nothing here imports it.

Nodes: the K data blocks and T noise blocks sit at the K + T Chebyshev
points of the first kind on [-1, 1] (the betas); worker i sits at the i-th
of N Chebyshev points of the second kind on [-1.05, 1.05] (the alphas),
moved off any beta it would meet.  Worker i's shard is Berrut's rational
interpolant through the blocks, evaluated at alpha_i; the master decodes
block k as Berrut's interpolant through the responders' results, evaluated
at beta_k.  Berrut's signs alternate over the nodes in sorted order.
"""

from __future__ import annotations

import numpy as np


def chebyshev(n: int, kind: int, lo: float = -1.0, hi: float = 1.0):
    k = np.arange(n, dtype=np.float64)
    if kind == 1:
        pts = np.cos((2 * k + 1) * np.pi / (2 * n))
    else:
        pts = np.cos(k * np.pi / max(n - 1, 1)) if n > 1 else np.zeros(1)
    return (lo + hi) / 2 + (hi - lo) / 2 * pts


def nodes(n_workers: int, k_blocks: int, t_noise: int):
    """(alphas (N,), betas (K + T,)) float64."""
    betas = chebyshev(k_blocks + t_noise, 1)
    alphas = chebyshev(n_workers, 2, -1.05, 1.05)
    for i in range(n_workers):
        while np.any(np.abs(alphas[i] - betas) < 1e-9):
            alphas[i] += 1e-3
    return alphas, betas


def berrut(x, pts):
    """(len(x), len(pts)) Berrut weights: row q interpolates at x[q] from
    values at ``pts``; rows sum to 1."""
    x = np.asarray(x, np.float64)[:, None]
    pts = np.asarray(pts, np.float64)
    rank = np.argsort(np.argsort(pts))
    signs = np.where(rank % 2 == 0, 1.0, -1.0)
    terms = signs / (x - pts[None, :])
    return terms / terms.sum(axis=1, keepdims=True)


def decode_through_encode(responders, n_workers: int, k_blocks: int,
                          t_noise: int):
    """(K, K + T) float64: decoded block k = sum_j M[k, j] f(block j), for a
    linear f, when the master decodes from ``responders``."""
    alphas, betas = nodes(n_workers, k_blocks, t_noise)
    resp = np.sort(np.asarray(responders, np.int64))
    enc = berrut(alphas[resp], betas)                  # (|F|, K + T)
    dec = berrut(betas[:k_blocks], alphas[resp])        # (K, |F|)
    return dec @ enc


def encoder(n_workers: int, k_blocks: int, t_noise: int):
    """(N, K + T) float64 encode weights."""
    alphas, betas = nodes(n_workers, k_blocks, t_noise)
    return berrut(alphas, betas)


def decoder(responders, n_workers: int, k_blocks: int, t_noise: int):
    """(K, N) float64 decode weights, zero for workers that did not respond."""
    alphas, betas = nodes(n_workers, k_blocks, t_noise)
    resp = np.sort(np.asarray(responders, np.int64))
    out = np.zeros((k_blocks, n_workers))
    out[:, resp] = berrut(betas[:k_blocks], alphas[resp])
    return out


def noise_blocks(seed: int, t_noise: int, blk: int, d: int, scale: float):
    """The T privacy noise blocks the deployment draws: ``scale`` times
    standard normal float32 from ``jax.random.PRNGKey(seed)``, the cluster
    seed (one draw for a block shape, whatever the job)."""
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(jax.random.PRNGKey(seed), (t_noise, blk, d))
    return (scale * z).astype(jnp.float32)


def bf16x3_dot(a, b):
    """float32 matmul at JAX's ``high`` precision: three bf16 passes.  On a
    TPU that is the chip's own ``Precision.HIGH``; elsewhere, where ``high``
    means full float32, it is spelled out: each operand split into a bf16
    head and a bf16 tail, and the three products that ``high`` keeps (the
    barrier keeps the compiler from merging them back into one dot)."""
    import jax
    import jax.numpy as jnp
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    hi = jax.lax.Precision.HIGHEST

    def split(x):
        h = x.astype(jnp.bfloat16).astype(jnp.float32)
        return h, (x - h).astype(jnp.bfloat16).astype(jnp.float32)

    ah, al = split(a)
    bh, bl = split(b)
    dot = lambda x, y: jnp.matmul(x, y, precision=hi)
    hh, hl, lh = jax.lax.optimization_barrier((dot(ah, bh), dot(ah, bl),
                                               dot(al, bh)))
    return hh + (hl + lh)
