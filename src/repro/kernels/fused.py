"""Fused residual-add + RMSNorm Pallas kernel.

The pre-norm transformer repeats ``y = x + delta; h = rmsnorm(y)`` at
every sub-block boundary.  Unfused, XLA materializes ``y`` to HBM and the
norm reads it straight back: three HBM passes over the hidden stream
(write y, read y, write h) on top of the two operand reads.  This kernel
emits both outputs from one pass — read x and delta once, keep the sum in
VMEM, reduce/normalize there, write ``y`` and ``h`` — saving one full HBM
read of the hidden state per fusion site.

Same tiling discipline as ``rmsnorm.py``: rows x d tiles, scale hoisted
into VMEM scratch on the first grid step, ragged row counts handled by an
exact-remainder second call instead of dead padded tiles.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, r_ref, s_ref, y_ref, o_ref, scale_ref, *, eps: float):
    @pl.when(pl.program_id(0) == 0)
    def _hoist():
        scale_ref[...] = s_ref[...].astype(jnp.float32)

    y = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    o_ref[...] = ((y * jax.lax.rsqrt(var + eps))
                  * scale_ref[...]).astype(o_ref.dtype)


def _fused_rows(xf, rf, scale, eps: float, br: int, interpret: bool):
    rows, d = xf.shape
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xf.shape, xf.dtype),
            jax.ShapeDtypeStruct(xf.shape, xf.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d,), jnp.float32)],
        interpret=interpret,
    )(xf, rf, scale)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def fused_add_rmsnorm(x: jax.Array, res: jax.Array, scale: jax.Array, *,
                      eps: float = 1e-5, block_rows: int = 256,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """x, res: (..., d); scale: (d,).  Returns (normed, x + res)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    rf = res.reshape(rows, d)
    br = min(block_rows, rows)
    full = rows - rows % br
    ys: List[jax.Array] = []
    os: List[jax.Array] = []
    if full:
        y, o = _fused_rows(xf[:full], rf[:full], scale, eps, br, interpret)
        ys.append(y)
        os.append(o)
    if rows - full:
        y, o = _fused_rows(xf[full:], rf[full:], scale, eps, rows - full,
                           interpret)
        ys.append(y)
        os.append(o)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    o = os[0] if len(os) == 1 else jnp.concatenate(os)
    return o.reshape(orig_shape), y.reshape(orig_shape)
