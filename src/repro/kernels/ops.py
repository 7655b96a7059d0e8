"""Jit'd public wrappers around the Pallas kernels.

Handles layout adaptation (model tensors are (B, S, H, D); kernels take
flattened (B*H, S, D)), GQA head replication, and where a kernel runs:
``on_platform`` compiles it to Mosaic when the program is lowered for a
TPU and runs the same body under the Pallas interpreter elsewhere (CPU
tests).  The choice follows the platform the program is lowered for, not
the process's default backend, so a kernel is never interpreted on a TPU.

Block sizes: passing explicit ints pins the tiling; ``None`` (default)
uses the MXU-aligned defaults, or — when autotuning is on (the
``REPRO_KERNEL_AUTOTUNE=1`` env switch or ``block=\"auto\"``) — the
per-(op, shape, dtype, chip) winner from ``autotune.py``'s persistent
cache.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import autotune as at
from repro.kernels import flash_attention as fa
from repro.kernels import fused as fused_mod
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd as ssd_mod

BlockArg = Union[int, str, None]          # int | "auto" | None


def on_platform(kernel: Callable, *args, **static):
    """``kernel(*args, **static)`` as Mosaic on a TPU, interpreted
    elsewhere; ``kernel`` takes an ``interpret`` keyword."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


def _tune(block: BlockArg) -> bool:
    return block == "auto" or (block is None and at.enabled())


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: BlockArg = None,
                    block_k: BlockArg = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, K, D) with H % K == 0 -> (B, S, H, D)."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    kheads = k.shape[2]
    if kheads != h:                       # GQA: replicate KV heads
        rep = h // kheads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    if _tune(block_q) or _tune(block_k):
        cfg = at.tune_flash_attention(qt, kt, vt, causal=causal)
        block_q, block_k = cfg["block_q"], cfg["block_k"]
    bq = block_q if isinstance(block_q, int) else 128
    bk = block_k if isinstance(block_k, int) else 128
    o = on_platform(fa.flash_attention, qt, kt, vt, causal=causal,
                    block_q=bq, block_k=bk)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           cache_len: jax.Array,
                           block_k: BlockArg = None) -> jax.Array:
    """Decode-shaped attention: q: (B, 1, H, D); k, v: (B, S, K, D) caches;
    ``cache_len`` the (dynamic) valid prefix. -> (B, 1, H, D)."""
    b, one, h, d = q.shape
    assert one == 1, q.shape
    s = k.shape[1]
    kheads = k.shape[2]
    if kheads != h:
        rep = h // kheads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.reshape(b, h, d).reshape(b * h, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    bk = block_k if isinstance(block_k, int) else 128
    o = on_platform(fa.flash_attention_decode, qt, kt, vt, cache_len,
                    block_k=bk)
    return o.reshape(b, h, d)[:, None].reshape(b, 1, h, d)


def ssd_scan(x, dt, a, b, c, *, chunk: BlockArg = None):
    if _tune(chunk):
        chunk = at.tune_ssd_scan(x, dt, a, b, c)["chunk"]
    ck = chunk if isinstance(chunk, int) else 128
    return on_platform(ssd_mod.ssd_scan, x, dt, a, b, c, chunk=ck)


def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: BlockArg = None):
    if _tune(block_rows):
        block_rows = at.tune_rmsnorm(x, scale, eps=eps)["block_rows"]
    br = block_rows if isinstance(block_rows, int) else 256
    return on_platform(rn.rmsnorm, x, scale, eps=eps, block_rows=br)


def fused_add_rmsnorm(x, res, scale, *, eps: float = 1e-5,
                      block_rows: BlockArg = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Returns (rmsnorm(x + res) * scale, x + res) in one HBM pass."""
    if _tune(block_rows):
        block_rows = at.tune_fused_add_rmsnorm(
            x, res, scale, eps=eps)["block_rows"]
    br = block_rows if isinstance(block_rows, int) else 256
    return on_platform(fused_mod.fused_add_rmsnorm, x, res, scale, eps=eps,
                       block_rows=br)
