"""Pallas TPU kernel: one decode step of the routed experts a chip holds.

  out = sum_e gates[:, e] * (silu(x @ W_g[e]) * (x @ W_u[e])) @ W_d[e]
    x:      (B, d)          the step's rows, in the compute dtype
    gates:  (B, E)          each row's routed weight of held expert e
                            (zero where the row did not choose it)
    W_g/u:  (E, d, f)       the held experts' gate and up projections
    W_d:    (E, f, d)       their down projections
    out:    (B, d) float32

Routing is data dependent, so there is no fixed ``x @ W`` to pre-encode:
the held experts run uncoded on the master (``models.coded``).  A decode
step routes few rows to each expert, so the work is bound by reading the
weights, and the kernel streams each held expert's weights through VMEM
exactly once per call:

  grid = (E, f // tf)                 (both axes accumulate into out)
  x, out:      (Bp, d)  resident for the whole call
  gate column: (Bp, 1)  expert e's
  W_g, W_u:    (d, tf)  one f-tile of expert e
  W_d:         (tf, d)  the matching rows

Each step multiplies in the compute dtype with float32 accumulation — the
weights are cast in VMEM, so they leave HBM in the dtype they are stored
in — scales the hidden tile by the gate column and adds its down
projection to ``out``.  Rows pad to the dtype's sublane tile with zero
gates.  Validated in interpret mode against ``ref.held_experts``
(tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiling import pad_to, tile

DEFAULT_TF = 512    # f tile (upper bound; a divisor of f is taken)


def _kernel(x_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    cd = x.dtype
    gate = jnp.dot(x, wg_ref[0].astype(cd),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[0].astype(cd), preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up * g_ref[0]).astype(cd)
    o_ref[...] += jnp.dot(h, wd_ref[0].astype(cd),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def held_experts_kernel(x, gates, w_gate, w_up, w_down, *,
                        tf: int = DEFAULT_TF, interpret: bool = True):
    """x (B, d); gates (B, E); w_gate, w_up (E, d, f); w_down (E, f, d)
    -> (B, d) float32.  ``interpret=True`` runs the body in Python (CPU
    validation); on a TPU pass ``interpret=False``."""
    b, d = x.shape
    e, _, f = w_gate.shape
    bp = pad_to(b, 16 if x.dtype.itemsize == 2 else 8)
    tf = tile(f, 128, tf)[0] if f % 128 == 0 else f
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    gp = jnp.pad(gates.astype(jnp.float32).T, ((0, 0), (0, bp - b)))[..., None]
    out = pl.pallas_call(
        _kernel,
        grid=(e, f // tf),
        in_specs=[
            pl.BlockSpec((bp, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, bp, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, tf), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, d, tf), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, tf, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bp, d), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        name="held_experts_kernel",
        interpret=interpret,
    )(xp, gp, w_gate, w_up, w_down)
    return out[:b]
