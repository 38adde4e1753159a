"""Pallas TPU kernel: the SPACDC Berrut encode/decode contraction.

out[q, m] = Σ_j W[q, j] · B[j, m]
  W: (Q, J) coding matrix (Q = N workers on encode, K blocks on decode)
  B: (J, M) stacked block payloads, M = flattened m/K·d (large)

TPU adaptation of the paper's encoder (which the CPU/mpi4py original runs as
a dense BLAS call): Q is tiny (≤ ~64) while M is huge, so the natural TPU
layout streams M through VMEM in 512-lane tiles.  J is usually tiny too but
the gradient-coding path can push it into the hundreds, so the grid is 2-D
with the J axis innermost (sequential) and an f32 accumulator scratch
carried across J tiles:

  grid = (M // bm, Jp // bj)
  W tile:  (Qp, bj)    — one J-slab of the coding matrix
  B tile:  (bj, bm)    — one payload stripe per grid step
  acc:     (Qp, bm)    — f32 scratch, flushed at the last J step

Short axes (Q, J) are always padded to (8, 128)-multiples (cheap — the
coding matrix is tiny); the M payload axis is padded *only when misaligned*
with the tile size, via ``jnp.pad``, so the aligned common case moves no
payload bytes at all.  f32 accumulate regardless of payload dtype.
Validated in interpret mode against ``ref.berrut_combine`` over shape/dtype
sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import pad_to as _pad_to, tile as _tile

DEFAULT_BM = 512
DEFAULT_BJ = 512


def _kernel(w_ref, b_ref, o_ref, acc_ref, *, n_j_steps: int):
    j_i = pl.program_id(1)

    @pl.when(j_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)          # (Qp, bj)
    b = b_ref[...].astype(jnp.float32)          # (bj, bm)
    acc_ref[...] += jax.lax.dot_general(
        w, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,      # f32, as ref.berrut_combine
        preferred_element_type=jnp.float32)

    @pl.when(j_i == n_j_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bj", "interpret"))
def berrut_encode_kernel(weights: jnp.ndarray, blocks: jnp.ndarray,
                         *, bm: int = DEFAULT_BM, bj: int = DEFAULT_BJ,
                         interpret: bool = True):
    """weights (Q, J) f32; blocks (J, M) any float dtype -> (Q, M) blocks.dtype.

    ``interpret=True`` executes the kernel body in Python (CPU validation);
    on a TPU backend pass interpret=False for the compiled kernel.
    """
    q, j = weights.shape
    j2, m = blocks.shape
    assert j == j2, (weights.shape, blocks.shape)
    qp = _pad_to(max(q, 8), 8)
    bj, jp = _tile(max(j, 8), 8, bj)
    bm, mp = _tile(m, 128, bm)

    wp = jnp.pad(weights.astype(jnp.float32), ((0, qp - q), (0, jp - j)))
    if (jp, mp) != blocks.shape:                # aligned case: zero copies
        blocks = jnp.pad(blocks, ((0, jp - j), (0, mp - m)))

    n_j = jp // bj
    out = pl.pallas_call(
        functools.partial(_kernel, n_j_steps=n_j),
        grid=(mp // bm, n_j),
        in_specs=[
            pl.BlockSpec((qp, bj), lambda i, jk: (0, jk)),   # coding slab
            pl.BlockSpec((bj, bm), lambda i, jk: (jk, i)),   # payload stripe
        ],
        out_specs=pl.BlockSpec((qp, bm), lambda i, jk: (0, i)),
        out_shape=jax.ShapeDtypeStruct((qp, mp), blocks.dtype),
        scratch_shapes=[pltpu.VMEM((qp, bm), jnp.float32)],
        interpret=interpret,
    )(wp, blocks)
    return out[:q, :m]
