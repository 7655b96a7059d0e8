"""Fused RMSNorm Pallas kernel.

One pass over each (rows x d) tile: mean-of-squares reduction (VPU),
rsqrt, scale — fused so x is read from HBM exactly once (XLA emits a
separate reduce + multiply without fusion guarantees across the rsqrt).
Rows tile defaults to 256 (autotunable), d kept whole (d <= ~8k fits VMEM
at 4 bytes: 8 MB tile).

Two refinements over the naive tiling:

  * ``scale`` is staged into VMEM scratch on the first grid step and read
    from there afterwards — the constant-index ``(d,)`` BlockSpec would
    otherwise re-fetch (and re-cast) it every step of the row sweep.
  * ragged row counts never compute dead tiles: the row range is split
    into a full-block sweep plus one exact-remainder call, instead of
    zero-padding the tail up to ``block_rows`` and normalizing garbage.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, s_ref, o_ref, scale_ref, *, eps: float):
    @pl.when(pl.program_id(0) == 0)
    def _hoist():                          # cast + stage scale once
        scale_ref[...] = s_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = ((x * jax.lax.rsqrt(var + eps))
                  * scale_ref[...]).astype(o_ref.dtype)


def _norm_rows(xf: jax.Array, scale: jax.Array, eps: float, br: int,
               interpret: bool) -> jax.Array:
    """xf: (rows, d) with rows % br == 0 — no padded tiles."""
    rows, d = xf.shape
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, xf.dtype),
        scratch_shapes=[pltpu.VMEM((d,), jnp.float32)],
        interpret=interpret,
    )(xf, scale)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., d); scale: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    br = min(block_rows, rows)
    full = rows - rows % br
    parts: List[jax.Array] = []
    if full:
        parts.append(_norm_rows(xf[:full], scale, eps, br, interpret))
    if rows - full:
        parts.append(_norm_rows(xf[full:], scale, eps, rows - full,
                                interpret))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(orig_shape)
