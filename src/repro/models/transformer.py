"""Decoder-only transformer: dense, MoE, and VLM families.

Design notes
------------
* **Scan-over-layers** with stacked parameters (leading ``layers`` dim):
  keeps HLO size O(1) in depth — required to compile 52/56-layer archs for
  512 host devices on this container, and standard TPU practice (MaxText).
* **Remat** (``cfg.remat``): the scanned layer body is wrapped in
  ``jax.checkpoint`` so only layer-boundary activations live through the
  backward pass; ``dots`` additionally saves matmul outputs.
* Every parameter is declared once with logical axes (see
  ``dist/sharding.py``); GQA heads that don't divide the 16-way model axis
  fall back to replication automatically.
* The same ``forward`` serves train (full seq, causal) and prefill (returns
  the KV cache); ``decode`` runs one token against the cache.  On a TPU
  both run the Pallas flash-attention kernels (``layers.pick_attn_impl``),
  whose custom VJP gives training the flash backward; training passes
  ``differentiated=True``, which keeps the forward-only fused
  residual-norm kernel out of the graph (``decoder_block``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.sharding import Decl, batch_spec, constrain
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig
from repro.telemetry import trace


# --- declarations ---------------------------------------------------------------

def layer_decls(cfg: ModelConfig, stacked: bool = True) -> Dict[str, Decl]:
    """One decoder layer; ``stacked`` prepends the layers dim."""
    d, hd = cfg.d_model, cfg.hd
    h, kv = cfg.n_heads, cfg.n_kv_heads
    pre = (cfg.n_layers,) if stacked else ()
    pax = ("layers",) if stacked else ()

    def decl(shape, axes, **kw):
        return Decl(pre + tuple(shape), pax + tuple(axes), **kw)

    out: Dict[str, Decl] = {
        "ln1": decl((d,), ("embed",), init="ones"),
        "ln2": decl((d,), ("embed",), init="ones"),
        "wq": decl((d, h, hd), ("embed", "heads", None), scale_dim=-3),
        "wk": decl((d, kv, hd), ("embed", "kv_heads", None), scale_dim=-3),
        "wv": decl((d, kv, hd), ("embed", "kv_heads", None), scale_dim=-3),
        "wo": decl((h, hd, d), ("heads", None, "embed"), scale_dim=-2),
    }
    if cfg.qkv_bias:
        out["bq"] = decl((h, hd), ("heads", None), init="zeros")
        out["bk"] = decl((kv, hd), ("kv_heads", None), init="zeros")
        out["bv"] = decl((kv, hd), ("kv_heads", None), init="zeros")
    if cfg.family == "moe":
        out.update(moe_mod.moe_decls(cfg, pre, pax))
    elif cfg.ffn_act == "swiglu":
        out.update({
            "w_gate": decl((d, cfg.d_ff), ("embed", "ff"), scale_dim=-2),
            "w_up": decl((d, cfg.d_ff), ("embed", "ff"), scale_dim=-2),
            "w_down": decl((cfg.d_ff, d), ("ff", "embed"), scale_dim=-2),
        })
    else:
        out.update({
            "w_up": decl((d, cfg.d_ff), ("embed", "ff"), scale_dim=-2),
            "w_down": decl((cfg.d_ff, d), ("ff", "embed"), scale_dim=-2),
        })
    return out


def decls(cfg: ModelConfig) -> Dict[str, Any]:
    d = {
        "embed": Decl((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                      init="embed"),
        "ln_f": Decl((cfg.d_model,), ("embed",), init="ones"),
        "layers": layer_decls(cfg),
    }
    if not cfg.tie_embeddings:
        d["lm_head"] = Decl((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                            scale_dim=-2)
    if cfg.family == "vlm":
        d["vision_proj"] = Decl((cfg.d_model, cfg.d_model), ("embed", None),
                                scale_dim=-2)
    return d


# --- layer forward ---------------------------------------------------------------

def _qkv(cfg: ModelConfig, p, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_delta(cfg: ModelConfig, p, x, positions, impl: str,
               mesh: Optional[Mesh]):
    """The attention sub-block's residual delta (un-added)."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, h, positions)
    if mesh is not None:
        q = constrain(q, batch_spec(mesh, q.shape[0], None, "model", None))
    with jax.named_scope(trace.ATTENTION):
        o = L.attention(q, k, v, impl=impl, causal=True, window=cfg.window,
                        q_pos=positions, k_pos=positions,
                        block_remat=cfg.attn_block_remat, mesh=mesh)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (k, v)


def attn_block(cfg: ModelConfig, p, x, positions, impl: str,
               mesh: Optional[Mesh]):
    delta, kv = attn_delta(cfg, p, x, positions, impl, mesh)
    return x + delta, kv


def _ffn(cfg: ModelConfig, p, h, mesh: Optional[Mesh]):
    """FFN applied to an already-normed hidden state."""
    with jax.named_scope(trace.MLP):
        if cfg.family == "moe":
            return moe_mod.moe_ffn(cfg, p, h, mesh)
        if cfg.ffn_act == "swiglu":
            return L.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        act = (jax.nn.gelu if cfg.ffn_act == "gelu"
               else lambda u: jnp.square(jax.nn.relu(u)))
        return act(h @ p["w_up"]) @ p["w_down"]


def ffn_block(cfg: ModelConfig, p, x, mesh: Optional[Mesh]):
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + _ffn(cfg, p, h, mesh)


def decoder_block(cfg: ModelConfig, p, x, positions, impl: str,
                  mesh: Optional[Mesh], differentiated: bool = False):
    """attn_block + ffn_block with the residual seam between them fused:
    the post-attention add and the FFN's pre-norm run as one Pallas pass
    when ``impl == "pallas"`` (see kernels/fused.py) and nothing
    differentiates the block (that kernel has no backward); identical math
    on the jnp path."""
    delta, kv = attn_delta(cfg, p, x, positions, impl, mesh)
    h, x = L.rms_norm_residual(
        x, delta, p["ln2"], cfg.norm_eps,
        impl="pallas" if impl == "pallas" and not differentiated else "jnp")
    return x + _ffn(cfg, p, h, mesh), kv


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# --- full-sequence forward (train / prefill) --------------------------------------

def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            mesh: Optional[Mesh] = None, return_cache: bool = False,
            attn_impl: Optional[str] = None, return_hidden: bool = False,
            differentiated: bool = False):
    """Returns logits (B,S,V) and optionally the KV cache (ring for SWA)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(cfg.dtype) @ params["vision_proj"]
        x = jnp.concatenate([patches.astype(cfg.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)
    impl = L.pick_attn_impl(attn_impl or cfg.attn_impl, s, differentiated)
    if mesh is not None:
        x = constrain(x, batch_spec(mesh, b, None, None))

    def body(x, lp):
        x, (k, v) = decoder_block(cfg, lp, x, positions, impl, mesh,
                                  differentiated)
        if mesh is not None:
            x = constrain(x, batch_spec(mesh, x.shape[0], None, None))
        if return_cache:
            if cfg.window and s > cfg.window:
                k, v = k[:, -cfg.window:], v[:, -cfg.window:]
            return x, (k, v)
        return x, None

    x, caches = jax.lax.scan(_remat(body, cfg.remat), x, params["layers"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    if return_hidden:
        return x, head
    with jax.named_scope(trace.HEAD):
        logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    if mesh is not None:
        logits = constrain(logits, batch_spec(mesh, b, None, "model"))
    if return_cache:
        k_all, v_all = caches
        cache = {"k": k_all, "v": v_all,
                 "len": jnp.asarray(s, jnp.int32)}
        return logits, cache
    return logits


# --- decode ----------------------------------------------------------------------

def cache_decls(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Decl]:
    """KV cache stand-ins (SWA archs cap the cache at the window)."""
    s = min(max_len, cfg.window) if cfg.window else max_len
    kv, hd = cfg.n_kv_heads, cfg.hd
    shp = (cfg.n_layers, batch, s, kv, hd)
    axes = ("layers", None, "kv_seq", "kv_heads", None)
    return {"k": Decl(shp, axes, init="zeros"),
            "v": Decl(shp, axes, init="zeros"),
            "len": Decl((), (), init="zeros")}


def decode(cfg: ModelConfig, params, cache, tokens: jax.Array, *,
           mesh: Optional[Mesh] = None):
    """One decode step. tokens: (B, 1). Returns (logits, new_cache)."""
    b = tokens.shape[0]
    pos = cache["len"]
    # per-row lengths (B,) support continuous batching: rows admitted at
    # different times decode in one batch, each at its own position.  A
    # scalar ``len`` keeps the original lockstep semantics (and the
    # single-compile property callers rely on).
    per_row = jnp.ndim(pos) == 1
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = pos[:, None] if per_row \
        else jnp.asarray(pos)[None]             # absolute position for RoPE
    cache_size = cache["k"].shape[2]
    # SWA: ring buffer — slot p%window holds position p; all written slots
    # are within the window by construction, so only unwritten slots are
    # masked (cache_len below) and no extra window mask is needed.
    slot = pos % cache_size if cfg.window else pos
    valid = jnp.minimum(pos + 1, cache_size)
    if per_row:
        hot = jnp.arange(cache_size)[None, :] == slot[:, None]   # (B,S)
        hot = hot[:, :, None, None]

    def body(x, lp_and_cache):
        lp, kc, vc = lp_and_cache
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, lp, h, positions)
        if per_row:
            kc = jnp.where(hot, k.astype(kc.dtype), kc)
            vc = jnp.where(hot, v.astype(vc.dtype), vc)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), slot, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), slot, 1)
        o = L.attn_decode(q, kc, vc, cache_len=valid, window=0)
        delta = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), lp["wo"])
        h, x = L.rms_norm_residual(x, delta, lp["ln2"], cfg.norm_eps)
        x = x + _ffn(cfg, lp, h, mesh)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    new_cache = {"k": k_new, "v": v_new, "len": pos + 1}
    return logits, new_cache
