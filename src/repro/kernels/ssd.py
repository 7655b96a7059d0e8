"""Mamba-2 SSD chunked-scan Pallas kernel (TPU target).

State-space duality (arXiv:2405.21060): within a chunk the output is an
"attention-like" quadratic form; across chunks a recurrent state (P x N)
flows.  TPU mapping:

  * grid = (batch, heads, n_chunks); chunks are the minor (sequential)
    dimension, so the running state lives in VMEM scratch across the chunk
    sweep for one (batch, head) — the recurrence never touches HBM.
  * per grid step the kernel stages (chunk x P) inputs and (chunk x N)
    B/C projections into VMEM; the two einsums (scores C·B^T and the
    state update x^T·B) are MXU matmuls; decay weights are VPU elementwise.
  * chunk length defaults to 128 (MXU-aligned); P=64..128, N=64..128 fit
    VMEM comfortably: working set ~ chunk*(P+2N)*4B + P*N*4B ≈ 200 KB.

Outputs y(chunk x P) plus the final state per (b, h).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, st_out_ref, state_ref,
            *, chunk: int, n_chunks: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (Q, 1) column
    a = a_ref[hi]                                 # scalar decay rate (<0)
    b = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)           # (Q, N)

    # cumulative decay as a triangular matmul (Mosaic has no cumsum), kept
    # 2-D: cum as a column (Q, 1) and as a row (1, Q)
    da = dt * a                                   # (Q, 1)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    tril = causal.astype(jnp.float32)
    cum = jax.lax.dot_general(
        tril, da, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Q, 1)
    cum_row = jax.lax.dot_general(
        da, tril, (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (1, Q)
    total = jnp.sum(da, axis=0, keepdims=True)     # (1, 1) = cum_last
    # within-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    ldec = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Q, Q)
    # weight on x_j is scores_ij * L_ij * dt_j: fold dt_j into x's rows
    y_diag = jax.lax.dot_general(
        scores * ldec, x * dt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Q, P)

    # cross-chunk: y_off = (C decayed) @ state^T  (state: (P, N))
    st = state_ref[...]
    c_dec = c * jnp.exp(cum)
    y_off = jax.lax.dot_general(
        c_dec, st, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Q, P)
    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state = exp(cum_last) * state + sum_j w_j x_j b_j^T
    xw = x * (jnp.exp(total - cum) * dt)           # (Q, P)
    upd = jax.lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (P, N)
    state_ref[...] = jnp.exp(total) * st + upd

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        st_out_ref[0, 0] = state_ref[...].astype(st_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); a: (H,); b, c: (B, S, N).

    Returns (y (B,S,H,P), final_state (B,H,P,N)).  S need not divide
    ``chunk``: the tail is zero-padded, and padded steps are exact
    no-ops on the recurrence (dt = 0 -> decay exp(0) = 1, update 0), so
    the final state is unaffected and padded y rows are sliced off."""
    bs, s_orig, h, p = x.shape
    n = b.shape[-1]
    chunk = max(1, min(chunk, s_orig))
    pad = (-s_orig) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    s = s_orig + pad
    nc = s // chunk
    # layout: (B, H, nc, Q, ...) so (b, h) are grid-major, chunks minor;
    # dt travels as a (Q, 1) column and ``a`` as an SMEM scalar table, so
    # every block's trailing dims meet the TPU tiling rule
    xr = x.transpose(0, 2, 1, 3).reshape(bs, h, nc, chunk, p)
    dtr = dt.transpose(0, 2, 1).reshape(bs, h, nc, chunk, 1)
    br = b.reshape(bs, nc, chunk, n)
    cr = c.reshape(bs, nc, chunk, n)

    kern = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, st = pl.pallas_call(
        kern,
        grid=(bs, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, h, nc, chunk, p), x.dtype),
            jax.ShapeDtypeStruct((bs, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), xr, dtr, br, cr)
    y = y.reshape(bs, h, s, p).transpose(0, 2, 1, 3)
    return (y[:, :s_orig] if pad else y), st
