"""Unified model API: family dispatch + init + loss.

Every family module exposes the same surface:
    decls(cfg) -> pytree of Decl
    forward(cfg, params, batch, *, mesh, return_cache, attn_impl,
            differentiated)
    decode(cfg, params, cache, tokens, *, mesh)
    cache_decls(cfg, batch, max_len)   (or state_decls for ssm)
This module is the single entry point used by the trainer, server,
dry-run, and tests.
"""
from __future__ import annotations

import functools
from types import ModuleType
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist import sharding as shd
from repro.models import encdec, hybrid, mamba2, transformer
from repro.models.config import ModelConfig

IGNORE_LABEL = -100


def masked_ce_sums(logits: jax.Array, labels: jax.Array):
    """Masked next-token CE as sums: (nll_sum, n_tokens, n_correct).

    The single source of the loss math — shared by ``loss_fn``, the
    chunked-loss scan body, and the MPMD pipeline's last-stage program,
    so they stay numerically identical (fp32 log-softmax, IGNORE_LABEL
    masking).  Sum form so callers can accumulate before normalizing.
    """
    mask = labels != IGNORE_LABEL
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return (jnp.where(mask, nll, 0.0).sum(), mask.sum(),
            jnp.where(mask, logits.argmax(-1) == labels, False).sum())


def get_module(cfg: ModelConfig) -> ModuleType:
    return {
        "dense": transformer,
        "moe": transformer,
        "vlm": transformer,
        "ssm": mamba2,
        "hybrid": hybrid,
        "encdec": encdec,
    }[cfg.family]


def decls(cfg: ModelConfig):
    return get_module(cfg).decls(cfg)


def init(cfg: ModelConfig, key: jax.Array):
    return shd.init_from_decls(decls(cfg), key, cfg.param_dtype)


def cache_decls(cfg: ModelConfig, batch: int, max_len: int):
    mod = get_module(cfg)
    if cfg.family == "ssm":
        return mamba2.state_decls(cfg, batch, max_len)
    return mod.cache_decls(cfg, batch, max_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               start_len: int = 0):
    c = shd.init_from_decls(cache_decls(cfg, batch, max_len),
                            jax.random.PRNGKey(0), cfg.dtype)
    c["len"] = jnp.asarray(start_len, jnp.int32)
    return c


def forward(cfg: ModelConfig, params, batch, *, mesh: Optional[Mesh] = None,
            return_cache: bool = False, attn_impl: Optional[str] = None,
            return_hidden: bool = False, differentiated: bool = False):
    """``differentiated=True`` for callers that take gradients through
    the forward (``loss_fn``): it keeps the Pallas kernels, which have no
    backward pass, out of the graph."""
    kw = {}
    if return_hidden:        # transformer families only (chunked loss)
        kw["return_hidden"] = True
    return get_module(cfg).forward(cfg, params, batch, mesh=mesh,
                                   return_cache=return_cache,
                                   attn_impl=attn_impl,
                                   differentiated=differentiated, **kw)


def decode(cfg: ModelConfig, params, cache, tokens, *,
           mesh: Optional[Mesh] = None):
    return get_module(cfg).decode(cfg, params, cache, tokens, mesh=mesh)


def loss_fn(cfg: ModelConfig, params, batch, *,
            mesh: Optional[Mesh] = None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Next-token cross-entropy; labels == IGNORE_LABEL are masked.

    ``cfg.logits_chunk > 0`` (transformer families): the (B, S, V) fp32
    logits tensor is never materialized — the head projection + softmax
    run in sequence chunks inside a scan.  §Perf: cuts the dominant
    activation term for big-vocab train cells (granite/minitron/internvl).
    """
    if cfg.logits_chunk and cfg.family in ("dense", "moe", "vlm"):
        return _chunked_loss(cfg, params, batch, mesh=mesh)
    logits = forward(cfg, params, batch, mesh=mesh, differentiated=True)
    nll_sum, n_tok, n_corr = masked_ce_sums(logits, batch["labels"])
    denom = jnp.maximum(n_tok, 1)
    loss = nll_sum / denom
    metrics = {"loss": loss, "tokens": n_tok, "accuracy": n_corr / denom}
    return loss, metrics


def _chunked_loss(cfg: ModelConfig, params, batch, *,
                  mesh: Optional[Mesh] = None):
    x, head = forward(cfg, params, batch, mesh=mesh, return_hidden=True,
                      differentiated=True)
    labels = batch["labels"]
    b, s, d = x.shape
    c = min(cfg.logits_chunk, s)
    if s % c:
        pad = c - s % c
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=IGNORE_LABEL)
        s += pad
    nc = s // c
    xc = x.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)

    def body(carry, xs):
        nll_sum, n_tok, n_correct = carry
        xi, li = xs
        logits = (xi @ head.astype(xi.dtype)).astype(jnp.float32)
        s_nll, s_tok, s_corr = masked_ce_sums(logits, li)
        return (nll_sum + s_nll, n_tok + s_tok, n_correct + s_corr), None

    (nll_sum, n_tok, n_corr), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0), jnp.int32(0)), (xc, lc))
    denom = jnp.maximum(n_tok, 1)
    loss = nll_sum / denom
    return loss, {"loss": loss, "tokens": n_tok,
                  "accuracy": n_corr / denom}


def param_count(cfg: ModelConfig) -> int:
    """Exact parameter count from declarations (validates cfg.total_params)."""
    total = 0
    for d in jax.tree_util.tree_leaves(
            decls(cfg), is_leaf=lambda x: isinstance(x, shd.Decl)):
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total
