"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

The backbone is ``n_layers`` mamba2 layers; a single shared
(attention + FFN) block — one parameter set — is applied before every
``attn_every``-th group of backbone layers (arXiv:2411.15242; the released
model's LoRA projectors on the shared block are omitted, see config
docstring).

Structure: the layer stack is reshaped into ``n_groups = n_layers //
attn_every`` groups.  Each group = shared-attn application + a scanned
6-layer mamba segment, so the HLO holds one attention block + one scan body
per group (n_groups is small), while SSM params stay stacked.

Decode state = per-layer SSM states + per-*application* KV caches
(n_groups of them — the shared block has distinct activations per
application even though weights are shared).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import Decl, batch_spec, constrain
from repro.models import layers as L
from repro.models import mamba2, transformer
from repro.models.config import ModelConfig


def n_groups(cfg: ModelConfig) -> int:
    assert cfg.n_layers % cfg.attn_every == 0, (cfg.n_layers, cfg.attn_every)
    return cfg.n_layers // cfg.attn_every


def decls(cfg: ModelConfig) -> Dict:
    d = {
        "embed": Decl((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                      init="embed"),
        "ln_f": Decl((cfg.d_model,), ("embed",), init="ones"),
        "layers": mamba2.ssm_layer_decls(cfg),
        "shared_attn": transformer.layer_decls(
            _dense_view(cfg), stacked=False),
    }
    if not cfg.tie_embeddings:
        d["lm_head"] = Decl((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                            scale_dim=-2)
    return d


def _dense_view(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, family="dense")


def _shared_block(cfg: ModelConfig, params, x, positions, impl, mesh,
                  cache_kv=None, pos=None):
    """Shared attn+FFN application. Returns (x, (k, v)) full-seq, or decode."""
    dv = _dense_view(cfg)
    p = params["shared_attn"]
    if cache_kv is None:
        x, (k, v) = transformer.attn_block(dv, p, x, positions, impl, mesh)
        x = transformer.ffn_block(dv, p, x, mesh)
        return x, (k, v)
    kc, vc = cache_kv
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = transformer._qkv(dv, p, h, positions)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos, 1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos, 1)
    o = L.attn_decode(q, kc, vc, cache_len=pos + 1)
    x = x + jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    x = transformer.ffn_block(dv, p, x, mesh)
    return x, (kc, vc)


def _group_params(params, g: int, size: int):
    return jax.tree_util.tree_map(lambda a: a[g * size:(g + 1) * size],
                                  params["layers"])


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            mesh: Optional[Mesh] = None, return_cache: bool = False,
            attn_impl: Optional[str] = None, differentiated: bool = False):
    tokens = batch["tokens"]
    bs, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    if mesh is not None:
        x = constrain(x, batch_spec(mesh, bs, None, None))
    positions = jnp.arange(s)
    impl = L.pick_attn_impl(attn_impl or cfg.attn_impl, s, differentiated)
    ng, ae = n_groups(cfg), cfg.attn_every

    attn_caches = []
    ssm_states = []
    conv_states = []
    for g in range(ng):
        x, (k, v) = _shared_block(cfg, params, x, positions, impl, mesh)
        if return_cache:
            attn_caches.append((k, v))

        def body(x, lp):
            out, st = mamba2.mamba_block(cfg, lp, x, mesh=mesh,
                                         return_state=return_cache)
            return out, st

        body = body if cfg.remat == "none" else jax.checkpoint(body)
        x, st = jax.lax.scan(body, x, _group_params(params, g, ae))
        if return_cache:
            ssm_states.append(st["ssm"])
            conv_states.append(st["conv"])

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    if return_cache:
        cache = {
            "k": jnp.stack([k for k, _ in attn_caches]),
            "v": jnp.stack([v for _, v in attn_caches]),
            "ssm": jnp.concatenate(ssm_states, axis=0),
            "conv": jnp.concatenate(conv_states, axis=0),
            "len": jnp.asarray(s, jnp.int32),
        }
        return logits, cache
    return logits


def cache_decls(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Decl]:
    ng = n_groups(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd
    st = mamba2.state_decls(cfg, batch)
    return {
        "k": Decl((ng, batch, max_len, kv, hd),
                  (None, None, "kv_seq", "kv_heads", None), init="zeros"),
        "v": Decl((ng, batch, max_len, kv, hd),
                  (None, None, "kv_seq", "kv_heads", None), init="zeros"),
        "ssm": st["ssm"],
        "conv": st["conv"],
        "len": Decl((), (), init="zeros"),
    }


def decode(cfg: ModelConfig, params, cache, tokens: jax.Array, *,
           mesh: Optional[Mesh] = None):
    bs = tokens.shape[0]
    pos = cache["len"]
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.asarray(pos)[None]
    ng, ae = n_groups(cfg), cfg.attn_every

    ks, vs, ssms, convs = [], [], [], []
    for g in range(ng):
        x, (kc, vc) = _shared_block(
            cfg, params, x, positions, "naive", mesh,
            cache_kv=(cache["k"][g], cache["v"][g]), pos=pos)
        ks.append(kc)
        vs.append(vc)

        def body(x, lp_state):
            lp, ssm, conv = lp_state
            out, ns = mamba2.mamba_decode_block(
                cfg, lp, x, {"ssm": ssm, "conv": conv})
            return out, (ns["ssm"], ns["conv"])

        sl = slice(g * ae, (g + 1) * ae)
        x, (ssm_n, conv_n) = jax.lax.scan(
            body, x, (_group_params(params, g, ae),
                      cache["ssm"][sl], cache["conv"][sl]))
        ssms.append(ssm_n)
        convs.append(conv_n)

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    new_cache = {"k": jnp.stack(ks), "v": jnp.stack(vs),
                 "ssm": jnp.concatenate(ssms, axis=0),
                 "conv": jnp.concatenate(convs, axis=0),
                 "len": pos + 1}
    return logits, new_cache
