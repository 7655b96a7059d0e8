"""Flash attention Pallas kernels (TPU target, interpret-validated on CPU).

Blockwise online-softmax attention (Flash-Attention-2 recurrence) tiled for
the TPU memory hierarchy:

  * grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is minor
    (sequential on a TensorCore), so the fp32 accumulators for one q block
    live in VMEM scratch across the kv sweep.
  * BlockSpecs stage (block_q x head_dim) / (block_k x head_dim) tiles of
    Q/K/V from HBM into VMEM; head_dim (64/80/128 here) stays unsplit so
    the MXU sees full contraction dims; block sizes default to 128 —
    MXU-aligned (128x128 systolic array) — and are overridable per shape
    by the autotuner (``kernels/autotune.py``).
  * causal masking is done with iota comparisons inside the block; blocks
    entirely above the diagonal are skipped via ``pl.when`` (the FLOP
    saving XLA's dense attention cannot express).
  * non-divisible ``sq``/``sk`` are handled by internal zero-padding to
    the block grid plus an in-kernel ``k_pos >= kv_len`` mask (padded KV
    columns contribute nothing; padded Q rows are sliced off).  Blocks
    entirely past ``kv_len`` are skipped like above-diagonal ones.

The training kernel computes one (q_block, head) tile per grid step:
    m_new = max(m, rowmax(S));  l = l*corr + rowsum(P);  acc = acc*corr + P V
with S = Q K^T / sqrt(d) in fp32.

``flash_attention_decode`` is the serving-shaped variant: q_len == 1
against a long KV cache with a *dynamic* valid length.  The q row stays
resident in VMEM while the grid sweeps KV blocks.  The length is a
scalar-prefetch operand (SMEM): blocks past it are skipped at runtime, and
their index map clamps to the last live block, so no DMA is issued for
them either — decode cost tracks the actual cache fill, not the allocated
ring size.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_q: int, block_k: int, causal: bool, scale: float,
            n_kv_blocks: int, kv_len: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(k_pos > q_pos, NEG_INF, s)
        if kv_len is not None:                      # padded KV tail
            s = jnp.where(k_pos >= kv_len, NEG_INF, s)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    # skip blocks strictly above the diagonal and blocks entirely inside
    # the padded KV tail; block 0 always holds a live column, so m/l are
    # finite before any fully-masked block can contribute exp(0) garbage.
    live = True
    if causal:
        live = ki * block_k <= qi * block_q + block_q - 1
    if kv_len is not None:
        live = jnp.logical_and(live, ki * block_k < kv_len)
    if live is True:
        _body()
    else:
        pl.when(live)(_body)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _pad_axis1(x: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jax.Array:
    """q, k, v: (BH, S, D) with equal head counts (GQA handled in ops.py).

    ``sq``/``sk`` need not divide the block sizes: inputs are padded to
    the block grid and the pad is masked inside the kernel.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = max(1, min(block_q, sq))
    block_k = max(1, min(block_k, sk))
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    q = _pad_axis1(q, pad_q)
    k = _pad_axis1(k, pad_k)
    v = _pad_axis1(v, pad_k)
    nq, nk = (sq + pad_q) // block_q, (sk + pad_k) // block_k
    scale = 1.0 / math.sqrt(d)

    kern = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, n_kv_blocks=nk, kv_len=sk if pad_k else None)
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
        pltpu.VMEM((block_q,), jnp.float32),
    ]
    out = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pad_q, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq] if pad_q else out


# --- decode variant (q_len == 1, long KV, dynamic fill) -----------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, block_k: int, n_kv_blocks: int):
    ki = pl.program_id(1)
    kv_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)            # (1, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (1, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, k.shape[0]), 1)
        s = jnp.where(k_pos >= kv_len, NEG_INF, s)
        m_prev = m_ref[...]                          # (1, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    # blocks entirely past the cache fill are skipped at runtime
    pl.when(ki * block_k < kv_len)(_body)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_attention_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                           kv_len: jax.Array, *, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, D); k, v: (BH, S, D); kv_len: scalar int32 valid prefix.

    The kernel scales the Q row by 1/sqrt(d) once up front (cheaper than
    rescaling every score block).  S is padded to the block grid; both the
    pad and positions >= ``kv_len`` are masked via the same comparison.
    Q and the output travel as (BH, 1, D) so each block's trailing dims
    are the array's own, as the TPU tiling rule requires.
    """
    bh, d = q.shape
    sk = k.shape[1]
    block_k = max(1, min(block_k, sk))
    pad_k = (-sk) % block_k
    k = _pad_axis1(k, pad_k)
    v = _pad_axis1(v, pad_k)
    nk = (sk + pad_k) // block_k
    q = (q.astype(jnp.float32) / math.sqrt(d)).astype(q.dtype)[:, None]
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    def kv_block(b, j, len_ref):
        # clamp to the last live block: a repeated block index issues no DMA
        last = jnp.maximum(len_ref[0] - 1, 0) // block_k
        return (b, jnp.minimum(j, last), 0)

    kern = functools.partial(_decode_kernel, block_k=block_k, n_kv_blocks=nk)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda b, j, len_ref: (b, 0, 0)),
                pl.BlockSpec((1, block_k, d), kv_block),
                pl.BlockSpec((1, block_k, d), kv_block),
            ],
            out_specs=pl.BlockSpec((1, 1, d),
                                   lambda b, j, len_ref: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        interpret=interpret,
    )(kv_len, q, k, v)
    return out[:, 0]
