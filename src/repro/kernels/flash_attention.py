"""Flash attention Pallas kernels (TPU target, interpret-validated on CPU).

Blockwise online-softmax attention (Flash-Attention-2 recurrence) tiled for
the TPU memory hierarchy, forward and backward:

  * forward grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is
    minor (sequential on a TensorCore), so the fp32 accumulators for one q
    block live in VMEM scratch across the kv sweep.  Besides the output it
    writes each row's log-sum-exp ``m + log l`` (fp32), which is all the
    backward needs to rebuild the probabilities.
  * backward, two kernels over the same tiles: dK/dV on grid (batch*heads,
    kv_blocks, q_blocks) with the q sweep minor, and dQ on grid
    (batch*heads, q_blocks, kv_blocks) with the kv sweep minor; each keeps
    its accumulators in VMEM scratch and recomputes P from Q, K and the
    log-sum-exp.  ``di = rowsum(dO * O)`` is computed in jnp before them.
  * Q, K, V, P, dO and dS enter the MXU in the input dtype (bfloat16 in
    training) with fp32 accumulation; softmax statistics stay fp32.  The
    1/sqrt(d) scale is folded into Q once, so no kernel rescales a score
    block.
  * BlockSpecs stage (block x head_dim) tiles from HBM into VMEM; head_dim
    stays unsplit so the MXU sees full contraction dims.  The three
    kernels share one (block_q, block_k); :func:`default_blocks` is the
    rule the callers use, overridable per shape by the autotuner
    (``kernels/autotune.py``).
  * causal masking is done with iota comparisons, and only in blocks that
    straddle the diagonal; blocks entirely above it are skipped via
    ``pl.when`` (the FLOP saving XLA's dense attention cannot express) and
    their index maps repeat the previous live block, so no DMA is issued
    for them either.
  * non-divisible ``sq``/``sk`` are handled by internal zero-padding to
    the block grid plus an in-kernel ``k_pos >= kv_len`` mask on the last
    kv block (padded KV columns contribute nothing; padded Q rows are
    sliced off, so their dO is zero in the backward).
  * the log-sum-exp and ``di`` travel as (batch*heads, 1, S) rows, so a
    (1, block) tile of them meets the TPU tiling rule; the dK/dV kernel,
    which computes transposed (kv x q) score blocks, uses them as rows.
    Per-row statistics of the forward and dQ kernels live in VMEM as
    (block, 128) tiles whose lanes repeat one value, so subtracting them
    from a score block repeats whole vregs (on a v5e this made the forward
    a third faster at 512-row blocks than (block, 1) columns).

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
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
_LANES = 128                            # a vreg's minor dim
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def default_blocks(seq_len: int, head_dim: int) -> Tuple[int, int]:
    """(block_q, block_k) of all three kernels, a rule on the shape:
    512-row blocks (a shorter sequence is one block).  Measured on a v5e
    at head dim 64, S 512, 1024 and 2048, over 128-1024 blocks of each
    kernel (PERF.md, section 6)."""
    del head_dim
    b = min(seq_len, 512)
    return b, b


def _pad_axis1(x: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


class _Grid(NamedTuple):
    """One kernel's tiling of padded (sq, sk): static facts its body and
    index maps need."""
    bq: int
    bk: int
    nq: int
    nk: int
    causal: bool
    kv_len: Optional[int]          # set when the kv axis is padded

    @classmethod
    def make(cls, sq: int, sk: int, bq: int, bk: int, causal: bool):
        bq, bk = max(1, min(bq, sq)), max(1, min(bk, sk))
        return cls(bq, bk, -(-sq // bq), -(-sk // bk), causal,
                   sk if sk % bk else None)

    @property
    def pad_q(self) -> int:
        return self.nq * self.bq

    @property
    def pad_k(self) -> int:
        return self.nk * self.bk

    def live(self, qi, ki):
        """Block (qi, ki) holds some unmasked score."""
        if not self.causal:
            return True
        return ki * self.bk <= qi * self.bq + self.bq - 1

    def needs_mask(self, qi, ki):
        """Block (qi, ki) holds some masked score."""
        m = False
        if self.causal:
            m = ki * self.bk + self.bk - 1 > qi * self.bq
        if self.kv_len is not None:
            m = jnp.logical_or(m, ki == self.nk - 1)
        return m

    def last_k(self, qi):
        """Last live kv block of q block ``qi``."""
        if not self.causal:
            return self.nk - 1
        return jnp.minimum((qi * self.bq + self.bq - 1) // self.bk,
                           self.nk - 1)

    def first_q(self, ki):
        """First live q block of kv block ``ki``."""
        if not self.causal:
            return 0
        return jnp.minimum((ki * self.bk) // self.bq, self.nq - 1)

    def mask(self, s, qi, ki, transposed: bool = False):
        """``s`` with masked scores at NEG_INF; (bq, bk), or (bk, bq)
        when ``transposed``."""
        shape = s.shape
        qd, kd = (1, 0) if transposed else (0, 1)
        k_pos = ki * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, kd)
        dead = False
        if self.causal:
            q_pos = qi * self.bq + jax.lax.broadcasted_iota(
                jnp.int32, shape, qd)
            dead = k_pos > q_pos
        if self.kv_len is not None:
            dead = jnp.logical_or(dead, k_pos >= self.kv_len)
        return jnp.where(dead, NEG_INF, s)


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A (rows, 128) tile whose lanes all hold one column's value, as
    (rows, n): whole vregs repeated, not a per-element lane broadcast."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _run_blocks(g: _Grid, qi, ki, body):
    """``body(masked)`` on live blocks, masking only where needed."""
    live, masked = g.live(qi, ki), g.needs_mask(qi, ki)
    if masked is False:            # not causal, not padded: all blocks plain
        body(False)
        return
    pl.when(jnp.logical_and(live, masked))(lambda: body(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(
        lambda: body(False))


# --- forward -------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, g: _Grid):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked: bool):
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = g.mask(s, qi, ki)
        m_prev = m_ref[...]                               # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, g.bk))
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(corr, v.shape[1]) + \
            jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # kv block 0 always holds a live column of every row, so m and l are
    # finite before a masked block can contribute
    _run_blocks(g, qi, ki, body)

    @pl.when(ki == g.nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])
                    ).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l))[:, :1].T      # (1, bq)


def _scale_q(q: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * (1.0 / math.sqrt(q.shape[-1]))
            ).astype(q.dtype)


def _fwd(qs, k, v, g: _Grid, interpret: bool):
    """Padded, pre-scaled operands -> (o, lse (BH, 1, S) fp32)."""
    bh, _, d = qs.shape
    kv = lambda b, i, j: (b, jnp.minimum(j, g.last_k(i)), 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(bh, g.nq, g.nk),
        in_specs=[
            pl.BlockSpec((1, g.bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, g.bk, d), kv),
            pl.BlockSpec((1, g.bk, d), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, g.bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, g.bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, g.pad_q, d), qs.dtype),
                   jax.ShapeDtypeStruct((bh, 1, g.pad_q), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g.bq, d), jnp.float32),
                        pltpu.VMEM((g.bq, _LANES), jnp.float32),
                        pltpu.VMEM((g.bq, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qs, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """q: (BH, Sq, D); k, v: (BH, Sk, D) -> (out (BH, Sq, D), log-sum-exp
    of each row's scaled scores (BH, Sq) fp32)."""
    bh, sq, _ = q.shape
    sk = k.shape[1]
    g = _Grid.make(sq, sk, block_q, block_k, causal)
    o, lse = _fwd(_pad_axis1(_scale_q(q), g.pad_q - sq),
                  _pad_axis1(k, g.pad_k - sk), _pad_axis1(v, g.pad_k - sk),
                  g, interpret)
    return o[:, :sq], lse[:, 0, :sq]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jax.Array:
    """q, k, v: (BH, S, D) with equal head counts (GQA handled in ops.py).

    ``sq``/``sk`` need not divide the block sizes: inputs are padded to
    the block grid and the pad is masked inside the kernel.
    """
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)[0]


# --- backward ------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, g: _Grid):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked: bool):
        q, do = q_ref[0], do_ref[0]
        # transposed blocks (kv rows x q columns): lse and di are rows
        st = jax.lax.dot_general(k_ref[0], q, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = g.mask(st, qi, ki, transposed=True)
        pt = jnp.exp(st - lse_ref[0])
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0])
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _run_blocks(g, qi, ki, body)

    @pl.when(qi == g.nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_acc, lse_col, di_col, *, g: _Grid, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for row, col in ((lse_ref, lse_col), (di_ref, di_col)):
            col[...] = jnp.broadcast_to(row[0], (_LANES, g.bq)).T

    def body(masked: bool):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = g.mask(s, qi, ki)
        p = jnp.exp(s - _lanes(lse_col[...], g.bk))
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_col[...], g.bk))
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _run_blocks(g, qi, ki, body)

    @pl.when(ki == g.nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_operands(q, k, v, o, lse, do, g: _Grid):
    """Padded, pre-scaled operands of both backward kernels."""
    sq, sk = q.shape[1], k.shape[1]
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    row = lambda x: jnp.pad(x, ((0, 0), (0, g.pad_q - sq)))[:, None]
    return (_pad_axis1(_scale_q(q), g.pad_q - sq),
            _pad_axis1(k, g.pad_k - sk), _pad_axis1(v, g.pad_k - sk),
            _pad_axis1(do, g.pad_q - sq), row(lse), row(di))


def _dkv(ops, g: _Grid, interpret: bool):
    qs, k, v, do, lse, di = ops
    bh, _, d = qs.shape
    q_blk = lambda b, j, i: (b, jnp.maximum(i, g.first_q(j)), 0)
    row_blk = lambda b, j, i: (b, 0, jnp.maximum(i, g.first_q(j)))
    kv_blk = lambda b, j, i: (b, j, 0)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, g=g),
        grid=(bh, g.nk, g.nq),
        in_specs=[
            pl.BlockSpec((1, g.bq, d), q_blk),
            pl.BlockSpec((1, g.bk, d), kv_blk),
            pl.BlockSpec((1, g.bk, d), kv_blk),
            pl.BlockSpec((1, g.bq, d), q_blk),
            pl.BlockSpec((1, 1, g.bq), row_blk),
            pl.BlockSpec((1, 1, g.bq), row_blk),
        ],
        out_specs=[pl.BlockSpec((1, g.bk, d), kv_blk),
                   pl.BlockSpec((1, g.bk, d), kv_blk)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((g.bk, d), jnp.float32),
                        pltpu.VMEM((g.bk, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qs, k, v, do, lse, di)


def _dq(ops, g: _Grid, scale: float, interpret: bool):
    qs, k, v, do, lse, di = ops
    bh, _, d = qs.shape
    q_blk = lambda b, i, j: (b, i, 0)
    row_blk = lambda b, i, j: (b, 0, i)
    kv_blk = lambda b, i, j: (b, jnp.minimum(j, g.last_k(i)), 0)
    return pl.pallas_call(
        functools.partial(_dq_kernel, g=g, scale=scale),
        grid=(bh, g.nq, g.nk),
        in_specs=[
            pl.BlockSpec((1, g.bq, d), q_blk),
            pl.BlockSpec((1, g.bk, d), kv_blk),
            pl.BlockSpec((1, g.bk, d), kv_blk),
            pl.BlockSpec((1, g.bq, d), q_blk),
            pl.BlockSpec((1, 1, g.bq), row_blk),
            pl.BlockSpec((1, 1, g.bq), row_blk),
        ],
        out_specs=pl.BlockSpec((1, g.bq, d), q_blk),
        out_shape=jax.ShapeDtypeStruct(qs.shape, qs.dtype),
        scratch_shapes=[pltpu.VMEM((g.bq, d), jnp.float32),
                        pltpu.VMEM((g.bq, _LANES), jnp.float32),
                        pltpu.VMEM((g.bq, _LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qs, k, v, do, lse, di)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, lse: jax.Array, do: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gradients (dq, dk, dv) of :func:`flash_attention` at (q, k, v),
    given its output ``o``, the log-sum-exp ``lse`` that
    :func:`flash_attention_fwd` returned with it, and the output's
    cotangent ``do``."""
    sq, sk, d = q.shape[1], k.shape[1], q.shape[2]
    g = _Grid.make(sq, sk, block_q, block_k, causal)
    opr = _bwd_operands(q, k, v, o, lse, do, g)
    dk, dv = _dkv(opr, g, interpret)
    dq = _dq(opr, g, 1.0 / math.sqrt(d), interpret)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


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
