"""Pallas TPU kernel: the fused coded matmul — encode and worker compute in
one pass.

  out[n] = (W @ blocks)[n] @ B
    W:      (N, J)       coding matrix (J = K data blocks [+ T noise blocks])
    blocks: (J, blk, d)  stacked input blocks (one round's A, block-split)
    B:      (d, n_out)   the shared right factor
    out:    (N, blk, n_out)  per-worker results, ready for masked decode

This is the round hot path of every linear data-coded scheme (SPACDC / BACC
/ MDS / LCC / CONV): encode is a linear contraction, the worker task is a
matmul, so the coded shards (N, blk, d) never need to exist in HBM.  Tiling:

  grid = (blk // bi, n_out // bj, d // bd)       (d innermost — sequential)
  W tile:   (Np, Jp)      entire coding matrix, VMEM-resident every step
  A stripe: (Jp, bi, bd)  one (row-tile, d-step) stripe of all J blocks
  B tile:   (bd, bj)
  acc:      (Np, bi, bj)  f32 scratch, accumulated over the d axis

Per step the kernel forms the coded stripe  W @ A  -> (Np, bi, bd) *in
VMEM only*, contracts it with the B tile on the MXU and accumulates in f32;
the output block is flushed once per (i, j) tile at the last d step.  All
dims are padded to (8, 128) multiples — short axes (N, J) always, payload
axes only when misaligned.  Validated in interpret mode against
``ref.coded_matmul`` (tests/test_coded_matmul.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import pad_to as _pad_to, tile as _tile

DEFAULT_BI = 128    # row tile of each block (upper bound; see _row_tile)
DEFAULT_BD = 256    # contraction (d) tile
DEFAULT_BJ = 128    # n_out tile
# Budget for the kernel's VMEM working set.  Mosaic's default scoped-VMEM
# limit is 16 MiB; the rest is headroom for its own temporaries (a v5e
# compile at N=30, J=27 with bi=128 needed 19.8 MiB and was refused).
VMEM_BUDGET = 12 * 2 ** 20
# f32 contractions at full f32 precision, as the XLA twin (``ref``) runs
# them: without it Mosaic may multiply f32 operands in one bf16 pass
_F32 = jax.lax.Precision.HIGHEST


def _vmem_bytes(np_: int, jp: int, bi: int, bd: int, bj: int) -> int:
    """f32 working set of one grid step: double-buffered W, A stripe, B
    tile and out block, the acc scratch and the in-VMEM coded stripe."""
    return 4 * (2 * np_ * jp + 2 * jp * bi * bd + 2 * bd * bj
                + 3 * np_ * bi * bj + np_ * bi * bd)


def _row_tile(blk: int, np_: int, jp: int, bd: int, bj: int, cap: int):
    """(bi, padded blk): the largest row tile ≤ ``cap`` whose working set
    fits ``VMEM_BUDGET`` — the A stripe and the acc both grow with N and
    J, so wide codes get shorter row tiles instead of a refused compile."""
    while cap > 8 and _vmem_bytes(np_, jp, min(cap, _pad_to(blk, 8)), bd,
                                  bj) > VMEM_BUDGET:
        cap //= 2
    return _tile(blk, 8, cap)


def _tiles(n: int, j: int, blk: int, d: int, n_out: int, bi: int, bd: int,
           bj: int):
    """The shared tiling plan: (np_, jp, bi, blkp, bd, dp, bj, njp)."""
    np_ = _pad_to(max(n, 8), 8)
    jp = _pad_to(max(j, 8), 8)
    bd, dp = _tile(d, 128, bd)
    bj, njp = _tile(n_out, 128, bj)
    bi, blkp = _row_tile(blk, np_, jp, bd, bj, bi)
    return np_, jp, bi, blkp, bd, dp, bj, njp


def _encode(w_ref, a_ref):
    """The coded stripe W @ A of one (row-tile, d-step): (Np, bi, bd)."""
    w = w_ref[...].astype(jnp.float32)                      # (Np, Jp)
    a = a_ref[...].astype(jnp.float32)                      # (Jp, bi, bd)
    jp, bi, bd = a.shape
    return jax.lax.dot_general(
        w, a.reshape(jp, bi * bd), (((1,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32
    ).reshape(w.shape[0], bi, bd)


def _accumulate(coded, b_ref, o_ref, acc_ref, n_d_steps: int):
    """Worker compute: per-worker (bi, bd) @ (bd, bj) batched over N,
    accumulated over the d axis and flushed at its last step."""
    d_i = pl.program_id(2)

    @pl.when(d_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        coded, b_ref[...].astype(jnp.float32), (((2,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)

    @pl.when(d_i == n_d_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel(w_ref, a_ref, b_ref, o_ref, acc_ref, *, n_d_steps: int):
    # encode: the coded stripe lives only in VMEM/registers, never in HBM
    _accumulate(_encode(w_ref, a_ref), b_ref, o_ref, acc_ref, n_d_steps)


def _encode_kernel(w_ref, a_ref, o_ref):
    # only the N real rows leave the kernel (no padded rows to slice off)
    o_ref[...] = _encode(w_ref, a_ref)[:o_ref.shape[0]].astype(o_ref.dtype)


def _worker_kernel(c_ref, b_ref, o_ref, acc_ref, *, n_d_steps: int):
    _accumulate(c_ref[...].astype(jnp.float32), b_ref, o_ref, acc_ref,
                n_d_steps)


def _pad3(x, shape):
    if tuple(shape) == x.shape:                 # aligned case: zero copies
        return x
    return jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, shape)])


@functools.partial(jax.jit,
                   static_argnames=("bi", "bd", "bj", "interpret"))
def coded_matmul_kernel(weights: jnp.ndarray, blocks: jnp.ndarray,
                        rhs: jnp.ndarray, *, bi: int = DEFAULT_BI,
                        bd: int = DEFAULT_BD, bj: int = DEFAULT_BJ,
                        interpret: bool = True):
    """weights (N, J) f32; blocks (J, blk, d); rhs (d, n_out)
    -> (N, blk, n_out) in blocks.dtype.

    ``interpret=True`` executes the kernel body in Python (CPU validation);
    on a TPU backend pass interpret=False for the compiled kernel.
    """
    n, j = weights.shape
    j2, blk, d = blocks.shape
    d2, n_out = rhs.shape
    assert j == j2 and d == d2, (weights.shape, blocks.shape, rhs.shape)
    np_, jp, bi, blkp, bd, dp, bj, njp = _tiles(n, j, blk, d, n_out, bi, bd,
                                                bj)
    wp = _pad3(weights.astype(jnp.float32), (np_, jp))
    n_d = dp // bd
    out = pl.pallas_call(
        functools.partial(_kernel, n_d_steps=n_d),
        grid=(blkp // bi, njp // bj, n_d),
        in_specs=[
            pl.BlockSpec((np_, jp), lambda i, jo, k: (0, 0)),   # W resident
            pl.BlockSpec((jp, bi, bd), lambda i, jo, k: (0, i, k)),
            pl.BlockSpec((bd, bj), lambda i, jo, k: (k, jo)),
        ],
        out_specs=pl.BlockSpec((np_, bi, bj), lambda i, jo, k: (0, i, jo)),
        out_shape=jax.ShapeDtypeStruct((np_, blkp, njp), blocks.dtype),
        scratch_shapes=[pltpu.VMEM((np_, bi, bj), jnp.float32)],
        interpret=interpret,
    )(wp, _pad3(blocks, (jp, blkp, dp)), _pad3(rhs, (dp, njp)))
    return out[:n, :blk, :n_out]


@functools.partial(jax.jit, static_argnames=("n_out", "bi", "bd", "bj",
                                             "interpret"))
def coded_encode_kernel(weights: jnp.ndarray, blocks: jnp.ndarray, *,
                        n_out: int, bi: int = DEFAULT_BI,
                        bd: int = DEFAULT_BD, bj: int = DEFAULT_BJ,
                        interpret: bool = True):
    """The encode half of :func:`coded_matmul_kernel`, materialized:
    weights (N, J), blocks (J, blk, d) -> coded shards (N, blk, d) f32.

    Tiled exactly as the fused kernel would tile a round with ``n_out``
    columns, so every shard element is the same contraction the fused
    kernel forms in VMEM — bit for bit.  The encrypted round uses it to
    put real shards on the wire and stay bit-identical to the plain round.
    """
    n, j = weights.shape
    _, blk, d = blocks.shape
    np_, jp, bi, blkp, bd, dp, _, _ = _tiles(n, j, blk, d, n_out, bi, bd, bj)
    out = pl.pallas_call(
        _encode_kernel,
        grid=(blkp // bi, dp // bd),
        in_specs=[
            pl.BlockSpec((np_, jp), lambda i, k: (0, 0)),
            pl.BlockSpec((jp, bi, bd), lambda i, k: (0, i, k)),
        ],
        out_specs=pl.BlockSpec((n, bi, bd), lambda i, k: (0, i, k)),
        out_shape=jax.ShapeDtypeStruct((n, blkp, dp), jnp.float32),
        interpret=interpret,
    )(_pad3(weights.astype(jnp.float32), (np_, jp)),
      _pad3(blocks, (jp, blkp, dp)))
    return out if (blkp, dp) == (blk, d) else out[:, :blk, :d]


@functools.partial(jax.jit, static_argnames=("n_blocks", "bi", "bd", "bj",
                                             "interpret"))
def coded_worker_kernel(shards: jnp.ndarray, rhs: jnp.ndarray, *,
                        n_blocks: int, bi: int = DEFAULT_BI,
                        bd: int = DEFAULT_BD, bj: int = DEFAULT_BJ,
                        interpret: bool = True):
    """The worker half of :func:`coded_matmul_kernel`: shards (N, blk, d)
    @ rhs (d, n_out) -> (N, blk, n_out) f32, with the fused kernel's d
    and n_out tiling (for a code over ``n_blocks`` input blocks), so the
    accumulation order — and every output bit — matches it."""
    n, blk, d = shards.shape
    n_out = rhs.shape[1]
    np_, _, bi, blkp, bd, dp, bj, njp = _tiles(n, n_blocks, blk, d, n_out,
                                               bi, bd, bj)
    n_d = dp // bd
    out = pl.pallas_call(
        functools.partial(_worker_kernel, n_d_steps=n_d),
        grid=(blkp // bi, njp // bj, n_d),
        in_specs=[
            pl.BlockSpec((np_, bi, bd), lambda i, jo, k: (0, i, k)),
            pl.BlockSpec((bd, bj), lambda i, jo, k: (k, jo)),
        ],
        out_specs=pl.BlockSpec((np_, bi, bj), lambda i, jo, k: (0, i, jo)),
        out_shape=jax.ShapeDtypeStruct((np_, blkp, njp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((np_, bi, bj), jnp.float32)],
        interpret=interpret,
    )(_pad3(shards, (np_, blkp, dp)), _pad3(rhs, (dp, njp)))
    return out[:n, :blk, :n_out]
