"""Whisper-style encoder-decoder transformer (audio backbone only).

Per the assignment the conv frontend is a STUB: ``input_specs()`` provides
precomputed frame embeddings (B, n_frames, d_model).  The encoder is
bidirectional over frames with a learned positional embedding; the decoder
is a causal LM with cross-attention into the encoder output.

Simplification vs. released Whisper (documented): decoder positions use
RoPE instead of a learned table so decode_32k does not require a 32k-row
learned position table; FFN is GELU (faithful), norms are RMSNorm (shared
substrate).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import Decl, batch_spec, constrain
from repro.models import layers as L
from repro.models.config import ModelConfig


def _attn_decls(cfg: ModelConfig, pre, pax, prefix=""):
    d, hd, h, kv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads

    def decl(shape, axes, **kw):
        return Decl(pre + tuple(shape), pax + tuple(axes), **kw)

    return {
        prefix + "wq": decl((d, h, hd), ("embed", "heads", None), scale_dim=-3),
        prefix + "wk": decl((d, kv, hd), ("embed", "kv_heads", None), scale_dim=-3),
        prefix + "wv": decl((d, kv, hd), ("embed", "kv_heads", None), scale_dim=-3),
        prefix + "wo": decl((h, hd, d), ("heads", None, "embed"), scale_dim=-2),
    }


def _ffn_decls(cfg: ModelConfig, pre, pax):
    d, f = cfg.d_model, cfg.d_ff

    def decl(shape, axes, **kw):
        return Decl(pre + tuple(shape), pax + tuple(axes), **kw)

    return {
        "w_in": decl((d, f), ("embed", "ff"), scale_dim=-2),
        "w_out": decl((f, d), ("ff", "embed"), scale_dim=-2),
    }


def decls(cfg: ModelConfig) -> Dict:
    ne, nd = cfg.n_encoder_layers, cfg.n_layers
    enc = {"ln1": Decl((ne, cfg.d_model), ("layers", "embed"), init="ones"),
           "ln2": Decl((ne, cfg.d_model), ("layers", "embed"), init="ones")}
    enc.update(_attn_decls(cfg, (ne,), ("layers",)))
    enc.update(_ffn_decls(cfg, (ne,), ("layers",)))
    dec = {"ln1": Decl((nd, cfg.d_model), ("layers", "embed"), init="ones"),
           "lnc": Decl((nd, cfg.d_model), ("layers", "embed"), init="ones"),
           "ln2": Decl((nd, cfg.d_model), ("layers", "embed"), init="ones")}
    dec.update(_attn_decls(cfg, (nd,), ("layers",)))
    dec.update(_attn_decls(cfg, (nd,), ("layers",), prefix="c_"))
    dec.update(_ffn_decls(cfg, (nd,), ("layers",)))
    return {
        "embed": Decl((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                      init="embed"),
        "enc_pos": Decl((cfg.n_frames, cfg.d_model), (None, "embed"),
                        init="embed"),
        "frame_proj": Decl((cfg.d_model, cfg.d_model), ("embed", None),
                           scale_dim=-2),
        "ln_enc": Decl((cfg.d_model,), ("embed",), init="ones"),
        "ln_f": Decl((cfg.d_model,), ("embed",), init="ones"),
        "encoder": enc,
        "decoder": dec,
    }


def _mha(cfg, p, xq, xkv, *, causal, positions_q=None, positions_k=None,
         prefix="", rope_on=True):
    q = jnp.einsum("bsd,dhk->bshk", xq, p[prefix + "wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p[prefix + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p[prefix + "wv"])
    if rope_on:
        q = L.rope(q, positions_q, cfg.rope_theta)
        k = L.rope(k, positions_k, cfg.rope_theta)
    o = L.attention(q, k, v, impl="naive" if xq.shape[1] <= 2048 else "chunked",
                    causal=causal, q_pos=positions_q, k_pos=positions_k)
    return jnp.einsum("bshk,hkd->bsd", o, p[prefix + "wo"]), (k, v)


def encode(cfg: ModelConfig, params, frames: jax.Array,
           mesh: Optional[Mesh] = None) -> jax.Array:
    """frames: (B, F, d_model) stub embeddings -> encoder states."""
    x = frames.astype(cfg.dtype) @ params["frame_proj"]
    x = x + params["enc_pos"][None].astype(cfg.dtype)
    fpos = jnp.arange(x.shape[1])

    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        o, _ = _mha(cfg, lp, h, h, causal=False, positions_q=fpos,
                    positions_k=fpos, rope_on=False)
        x = x + o
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + jax.nn.gelu(h @ lp["w_in"]) @ lp["w_out"]
        return x, None

    body = body if cfg.remat == "none" else jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["ln_enc"], cfg.norm_eps)


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            mesh: Optional[Mesh] = None, return_cache: bool = False,
            attn_impl: Optional[str] = None, differentiated: bool = False):
    enc = encode(cfg, params, batch["frames"], mesh)
    tokens = batch["tokens"]
    bs, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    if mesh is not None:
        x = constrain(x, batch_spec(mesh, bs, None, None))
    tpos = jnp.arange(s)
    fpos = jnp.arange(enc.shape[1])

    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        o, (k, v) = _mha(cfg, lp, h, h, causal=True, positions_q=tpos,
                         positions_k=tpos)
        x = x + o
        h = L.rms_norm(x, lp["lnc"], cfg.norm_eps)
        o, (ck, cv) = _mha(cfg, lp, h, enc, causal=False, positions_q=tpos,
                           positions_k=fpos, prefix="c_", rope_on=False)
        x = x + o
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + jax.nn.gelu(h @ lp["w_in"]) @ lp["w_out"]
        return x, (k, v, ck, cv) if return_cache else None

    body = body if cfg.remat == "none" else jax.checkpoint(body)
    x, caches = jax.lax.scan(body, x, params["decoder"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["embed"].T.astype(cfg.dtype)).astype(jnp.float32)
    if return_cache:
        k, v, ck, cv = caches
        return logits, {"k": k, "v": v, "ck": ck, "cv": cv,
                        "len": jnp.asarray(s, jnp.int32)}
    return logits


def cache_decls(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Decl]:
    kv, hd, nd = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    return {
        "k": Decl((nd, batch, max_len, kv, hd),
                  ("layers", None, "kv_seq", "kv_heads", None), init="zeros"),
        "v": Decl((nd, batch, max_len, kv, hd),
                  ("layers", None, "kv_seq", "kv_heads", None), init="zeros"),
        "ck": Decl((nd, batch, cfg.n_frames, kv, hd),
                   ("layers", None, None, "kv_heads", None), init="zeros"),
        "cv": Decl((nd, batch, cfg.n_frames, kv, hd),
                   ("layers", None, None, "kv_heads", None), init="zeros"),
        "len": Decl((), (), init="zeros"),
    }


def decode(cfg: ModelConfig, params, cache, tokens: jax.Array, *,
           mesh: Optional[Mesh] = None):
    bs = tokens.shape[0]
    pos = cache["len"]
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.asarray(pos)[None]

    def body(x, lp_cache):
        lp, kc, vc, ck, cv = lp_cache
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos, 1)
        o = L.attn_decode(q, kc, vc, cache_len=pos + 1)
        x = x + jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), lp["wo"])
        h = L.rms_norm(x, lp["lnc"], cfg.norm_eps)
        cq = jnp.einsum("bsd,dhk->bshk", h, lp["c_wq"])
        o = L.attn_decode(cq, ck, cv, cache_len=jnp.asarray(ck.shape[1]))
        x = x + jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), lp["c_wo"])
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + jax.nn.gelu(h @ lp["w_in"]) @ lp["w_out"]
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["decoder"], cache["k"], cache["v"],
                  cache["ck"], cache["cv"]))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["embed"].T.astype(cfg.dtype)).astype(jnp.float32)
    return logits, {"k": k_new, "v": v_new, "ck": cache["ck"],
                    "cv": cache["cv"], "len": pos + 1}
