"""Shared neural building blocks (pure JAX, mesh-agnostic).

Attention comes in three selectable implementations:

  naive    materializes the full (Sq, Sk) score matrix — fine for short seqs
  chunked  blockwise online-softmax over KV chunks (flash-attention recurrence
           in pure jnp): O(Sq * block) live memory, the default for >=8k.
  window   sliding-window attention that is *linear* in sequence length: a
           scan over query blocks each attending to a dynamic KV slice of
           window+block tokens (mixtral SWA / long-context prefill).
  pallas   the TPU kernel in repro.kernels, forward only (no backward pass).

All softmax statistics are computed in float32 regardless of input dtype.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def rms_norm_residual(res: jax.Array, delta: jax.Array, scale: jax.Array,
                      eps: float = 1e-5, impl: str = "jnp"
                      ) -> Tuple[jax.Array, jax.Array]:
    """``y = res + delta; h = rms_norm(y)`` -> (h, y).

    The pre-norm residual seam every transformer block repeats.  With
    ``impl="pallas"`` both outputs come from the fused Pallas kernel
    (one HBM pass, see kernels/fused.py); otherwise plain jnp, which XLA
    fuses less aggressively across the rsqrt.
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        h, y = kops.fused_add_rmsnorm(res, delta, scale, eps=eps)
        return h, y
    y = res + delta
    return rms_norm(y, scale, eps), y


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # (S, half)
        ang = ang[None, :, None, :]                                     # (1,S,1,half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs          # (B,S,half)
        ang = ang[:, :, None, :]                                        # (B,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = x @ w_gate
    u = x @ w_up
    return (jax.nn.silu(g) * u) @ w_down


# --- attention -----------------------------------------------------------------

def _split_gqa(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,H,hd) -> (B,S,K,G,hd) grouping query heads over KV heads."""
    b, s, h, hd = q.shape
    assert h % n_kv == 0, (h, n_kv)
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: int, kv_len: Optional[jax.Array]) -> jax.Array:
    """(Sq, Sk) additive bias in f32."""
    m = jnp.zeros((q_pos.shape[0], k_pos.shape[0]), jnp.float32)
    if causal:
        m = jnp.where(k_pos[None, :] > q_pos[:, None], NEG_INF, m)
    if window > 0:
        m = jnp.where(q_pos[:, None] - k_pos[None, :] >= window, NEG_INF, m)
    if kv_len is not None:
        m = jnp.where(k_pos[None, :] >= kv_len, NEG_INF, m)
    return m


def attn_naive(q: jax.Array, k: jax.Array, v: jax.Array, *,
               q_pos: jax.Array, k_pos: jax.Array, causal: bool = True,
               window: int = 0, kv_len: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,Sq,H,hd), k/v: (B,Sk,K,hd) -> (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    n_kv = k.shape[2]
    qg = _split_gqa(q, n_kv)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    s = s + _mask_bias(q_pos, k_pos, causal, window, kv_len)[None, None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, hd)


def attn_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 q_pos: jax.Array, k_pos: jax.Array, causal: bool = True,
                 window: int = 0, kv_len: Optional[jax.Array] = None,
                 block: int = 1024, block_remat: bool = False) -> jax.Array:
    """Online-softmax over KV chunks; numerically identical to attn_naive."""
    b, sq, h, hd = q.shape
    sk, n_kv = k.shape[1], k.shape[2]
    block = min(block, sk)
    if sk % block != 0:       # pad KV to a multiple of block (masked out)
        pad = block - sk % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
        sk += pad
    qg = _split_gqa(q, n_kv)
    scale = 1.0 / math.sqrt(hd)
    n_blocks = sk // block
    k_b = k.reshape(b, n_blocks, block, n_kv, hd).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(b, n_blocks, block, n_kv, hd).transpose(1, 0, 2, 3, 4)
    kp_b = k_pos.reshape(n_blocks, block)

    def step(carry, xs):
        o, m, l = carry
        kc, vc, kpc = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kc).astype(jnp.float32) * scale
        s = s + _mask_bias(q_pos, kpc, causal, window, kv_len)[None, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vc.dtype), vc)
        o = o * corr[..., None] + pv.astype(jnp.float32)
        return (o, m_new, l), None

    g = h // n_kv
    o0 = jnp.zeros((b, n_kv, g, sq, hd), jnp.float32)
    m0 = jnp.full((b, n_kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), jnp.float32)
    # block_remat: recompute the score/probability blocks in the backward
    # pass instead of storing them (flash-attention-bwd memory shape; the
    # Pallas kernel does this natively on TPU)
    body = jax.checkpoint(step) if block_remat else step
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), (k_b, v_b, kp_b))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def attn_window_linear(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       window: int, q_block: int = 512) -> jax.Array:
    """Causal sliding-window attention, linear in seq length.

    Scans over query blocks; each block attends to a dynamic KV slice of
    ``window + q_block`` positions ending at the block's last token.  Used
    for SWA prefill (mixtral) where full chunked attention would waste
    O(S^2) work.
    """
    b, s, h, hd = q.shape
    n_kv = k.shape[2]
    q_block = min(q_block, s)
    assert s % q_block == 0, (s, q_block)
    span = window + q_block
    # pad KV at the front so every slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (span, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, 0), (0, 0), (0, 0)))
    n_blocks = s // q_block
    qg = _split_gqa(q, n_kv).reshape(b, n_blocks, q_block, n_kv, h // n_kv, hd)
    qg = qg.transpose(1, 0, 2, 3, 4, 5)   # (nb, b, qb, k, g, hd)
    scale = 1.0 / math.sqrt(hd)

    def step(_, xs):
        qc, i = xs
        # q block covers [i*qb, (i+1)*qb); it sees KV [(i+1)*qb - span, (i+1)*qb)
        start = (i + 1) * q_block                      # slice start in padded kv
        kc = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        q_pos = i * q_block + jnp.arange(q_block)
        k_pos = start - span + jnp.arange(span)        # unpadded positions
        sc = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc).astype(jnp.float32) * scale
        bias = _mask_bias(q_pos, k_pos, True, window, None)
        bias = jnp.where(k_pos[None, :] < 0, NEG_INF, bias)
        sc = sc + bias[None, None, None]
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(vc.dtype), vc)
        return None, o

    _, o = jax.lax.scan(step, None,
                        (qg, jnp.arange(n_blocks)))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
    return o


def attn_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                cache_len: jax.Array, window: int = 0,
                impl: str = "naive") -> jax.Array:
    """Single-token decode. q: (B,1,H,hd); caches: (B,S,K,hd).

    ``cache_len`` may be a scalar (lockstep batch, all rows at the same
    position) or a (B,) vector (continuous batching: rows joined at
    different times, each masks its own context).
    """
    b, _, h, hd = q.shape
    if impl == "pallas" and window == 0 and jnp.ndim(cache_len) == 0:
        from repro.kernels import ops as kops
        return kops.flash_attention_decode(q, k_cache, v_cache,
                                           cache_len=cache_len)
    n_kv = k_cache.shape[2]
    qg = _split_gqa(q, n_kv)[:, 0]                      # (B,K,G,hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32) * scale
    k_pos = jnp.arange(k_cache.shape[1])
    lens = jnp.reshape(cache_len, (-1, 1))               # (1,1) or (B,1)
    mask = k_pos[None] >= lens                           # (1,S) or (B,S)
    if window > 0:
        # ring buffer: valid positions are the last `window` written slots
        mask = mask | (k_pos[None] < lens - window)
    s = jnp.where(mask[:, None, None, :], NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def attention(q, k, v, *, impl: str = "chunked", causal: bool = True,
              window: int = 0, q_pos=None, k_pos=None,
              kv_len=None, block: int = 1024,
              block_remat: bool = False) -> jax.Array:
    """Dispatch over implementations; q_pos/k_pos default to arange."""
    if q_pos is None:
        q_pos = jnp.arange(q.shape[1])
    if k_pos is None:
        k_pos = jnp.arange(k.shape[1])
    if impl == "pallas":
        from repro.kernels import ops as kops
        # the kernel handles causal/non-causal and non-divisible (even
        # unequal) sequence lengths via internal pad+mask; only window
        # and explicit kv_len masking still route to the jnp fallback
        if window == 0 and kv_len is None and (
                not causal or q.shape[1] == k.shape[1]):
            return kops.flash_attention(q, k, v, causal=causal)
        impl = "chunked"
    if impl == "window" or (window > 0 and causal and q.shape[1] > window
                            and impl != "naive" and kv_len is None):
        return attn_window_linear(q, k, v, window=window)
    if impl == "naive":
        return attn_naive(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                          window=window, kv_len=kv_len)
    return attn_chunked(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                        window=window, kv_len=kv_len, block=block,
                        block_remat=block_remat)


def pick_attn_impl(cfg_impl: str, seq_len: int, differentiated: bool,
                   backend: Optional[str] = None) -> str:
    """Resolve the attention implementation for one call site.

    The Pallas kernels have no backward pass, so a differentiated caller
    (the training loss, a pipeline stage under ``jax.vjp``) always runs
    XLA attention, and an explicit ``"pallas"`` there is an error.
    Forward-only callers (prefill, serving) resolve ``"auto"`` to the
    kernel wherever it compiles to Mosaic (TPU).  Otherwise ``"auto"`` is
    naive for short sequences and the chunked online-softmax beyond (full
    scores don't fit)."""
    if differentiated and cfg_impl == "pallas":
        raise ValueError(
            "attn_impl='pallas' on a differentiated path: the Pallas "
            "attention kernel has no backward pass; train with 'auto', "
            "'naive' or 'chunked'")
    if cfg_impl != "auto":
        return cfg_impl
    if not differentiated and (backend or jax.default_backend()) == "tpu":
        return "pallas"
    return "naive" if seq_len <= 2048 else "chunked"
