"""Plain float32 reference of the dense decoder of the Qwen2 architecture
and its AdamW step.

A configuration names its reference in its own file (``"reference"``);
this one is named by the configurations of that architecture
(``configs/qwen1.5-0.5b.json``).  Another architecture brings a module of
its own with the same four functions (``param_shapes``, ``seed_key``,
``init_params``, ``run_steps``), and the benchmark runs whichever module
the configuration names.

Written from the configuration file's equations, in straightforward
``jax.numpy``: no kernel, no cache, no sharding rule of the program.  It
imports nothing of the program and is given no weights that the program
made: the benchmark's own ``init_params`` makes them from the seed, and the
same function is called again here.

The layer, as the configuration states it (``model`` group of the file),
with ``n = rms(x) * ln1`` and ``m = rms(h) * ln2``:

    q, k, v = rope(Wq n + bq), rope(Wk n + bk), Wv n + bv
    h       = x + Wo . attn(q, k, v)
    out     = h + W_down . (silu(W_gate m) * (W_up m))

with causal softmax attention scaled by ``1/sqrt(head_dim)`` (each key and
value head shared by ``n_heads / n_kv_heads`` query heads), rotary
positions (half-split, ``rope_theta``), RMSNorm with a learned scale, a
final RMSNorm, and the output head tied to the embedding.  The loss is the
mean next-token cross-entropy over every token of the batch.

Weights are stored in the configuration's ``param_dtype``: every update is
computed in float32 from them and rounded back, as the configuration
states (no float32 master copy).  ``precision`` selects the arithmetic:

- ``"highest"``: float32 throughout, every matrix product at full
  precision: the reference;
- ``"fp8"``: every matrix product on float8 (e4m3) operands, each scaled
  by its largest magnitude as fp8 training does, with float32 sums; all
  else as the reference: the control for a bfloat16 configuration.

The optimizer is AdamW as the configuration's ``training`` group states:
bias-corrected moments, decoupled weight decay on every parameter, the
global gradient norm clipped to ``grad_clip``, and a learning rate that
warms up linearly over ``warmup_steps`` and then follows a cosine to a
tenth of its peak over ``total_steps``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# --- weights ------------------------------------------------------------------------

def param_shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """Name -> shape of every parameter; per-layer weights are stacked on a
    leading layer axis."""
    d, h, hd, ff = (model["d_model"], model["n_heads"], model["head_dim"],
                    model["d_ff"])
    kv, n = model["n_kv_heads"], model["n_layers"]
    layers = {
        "ln1": (n, d), "ln2": (n, d),
        "wq": (n, d, h, hd), "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
        "wo": (n, h, hd, d),
        "w_gate": (n, d, ff), "w_up": (n, d, ff), "w_down": (n, ff, d),
    }
    if model["qkv_bias"]:
        layers.update(bq=(n, h, hd), bk=(n, kv, hd), bv=(n, kv, hd))
    return {"embed": (model["vocab_size"], d), "ln_f": (d,), "layers": layers}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def init_params(model: Dict[str, Any], key: jax.Array):
    """The weights, drawn from ``key`` (``seed_key(seed)``) in the
    configuration's ``param_dtype``: norm scales at one, biases at zero,
    every matrix normal with standard deviation ``init_std`` (the published
    ``initializer_range``).  Pure: call it under ``jax.jit``, with the key
    an argument, so that one program serves every seed."""
    shapes = param_shapes(model)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    std, dtype = float(model["init_std"]), jnp.dtype(model["param_dtype"])
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key
        if name.startswith("ln"):
            leaves.append(jnp.ones(shape, dtype))
        elif name.startswith("b"):
            leaves.append(jnp.zeros(shape, dtype))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32) * std)
                          .astype(dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


# --- forward ------------------------------------------------------------------------

E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(a: jax.Array) -> jax.Array:
    """``a`` through float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model, precision, x, p):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps, hd = model["norm_eps"], model["head_dim"]
    group = model["n_heads"] // model["n_kv_heads"]
    h = _rms(x, p["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, p["wq"], precision)
    k = _mm("bsd,dhk->bshk", h, p["wk"], precision)
    v = _mm("bsd,dhk->bshk", h, p["wv"], precision)
    if model["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = _mm("bqhk,bshk->bhqs", q, k, precision) / math.sqrt(hd)
    n = x.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqs,bshk->bqhk", a, v, precision)
    x = x + _mm("bqhk,hkd->bqd", o, p["wo"], precision)
    h = _rms(x, p["ln2"], eps)
    u = (jax.nn.silu(_mm("bsd,df->bsf", h, p["w_gate"], precision))
         * _mm("bsd,df->bsf", h, p["w_up"], precision))
    return x + _mm("bsf,fd->bsd", u, p["w_down"], precision)


def row_nll(model: Dict[str, Any], params, tokens, labels,
            precision: str = "highest") -> jax.Array:
    """Next-token negative log-likelihood summed over each row of a block,
    shape (rows,).  Each layer is recomputed in the backward pass, so a
    block's activations are one layer deep."""
    x = params["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(lambda x, p: (_layer(model, precision, x, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["ln_f"].astype(jnp.float32), model["norm_eps"])
    logits = _mm("bsd,vd->bsv", x, params["embed"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].sum(-1)


# --- optimizer ----------------------------------------------------------------------

def lr_at(train: Dict[str, Any], step: int) -> float:
    """Learning rate of update ``step`` (1 for the first update)."""
    warm = min(step / max(train["warmup_steps"], 1), 1.0)
    if train["schedule"] == "constant":
        return train["lr"] * warm
    frac = min(max((step - train["warmup_steps"])
                   / max(train["total_steps"] - train["warmup_steps"], 1),
                   0.0), 1.0)
    return train["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


def adamw(train: Dict[str, Any], step: int, params, grads, m, v):
    """One AdamW update; returns (params, m, v, the clipped gradients)."""
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = train["grad_clip"]
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-12)) if clip > 0 else 1.0
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = train["beta1"], train["beta2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2, lr = 1 - b1 ** step, 1 - b2 ** step, lr_at(train, step)
    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        return (p32 - lr * ((m / c1) / (jnp.sqrt(v / c2) + train["eps"])
                            + train["weight_decay"] * p32)).astype(p.dtype)

    params = jax.tree.map(upd, params, m, v)
    return params, m, v, g


# --- three steps --------------------------------------------------------------------

def run_steps(model: Dict[str, Any], train: Dict[str, Any], seed: int,
              batches: List[Dict[str, np.ndarray]], *,
              precision: str = "highest", block_rows: int = 1,
              sharding: Optional[Any] = None, row_sharding: Optional[Any] = None,
              rows: Optional[Sequence[int]] = None,
              loss_rows: Optional[Sequence[int]] = None) -> Dict[str, Any]:
    """Train the reference from ``seed`` on ``batches`` (one per step, each
    ``tokens``/``labels`` shaped (num_micro, micro_batch, seq)).

    ``rows`` restricts the gradient to those rows of the flattened batch,
    and ``loss_rows`` the reported loss (by default the gradient's rows):
    a fault planted in the reference.  ``sharding`` places the weights and
    optimizer state (a pytree of shardings, or one for every leaf);
    ``row_sharding`` places each block of ``block_rows`` rows.

    Returns the loss of each step, the first step's clipped gradients and
    the final parameters, the last two as device arrays."""
    def block_loss(p, t, l, gw, lw):
        nll = row_nll(model, p, t, l, precision)
        return jnp.sum(gw * nll), jnp.sum(lw * nll)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_params(model, k),
                         out_shardings=sharding)(seed_key(seed))
        zeros = jax.jit(lambda p: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), p),
            out_shardings=sharding)
        m, v = zeros(params), zeros(params)
        grad_fn = jax.jit(jax.value_and_grad(block_loss, has_aux=True))
        add = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x.astype(jnp.float32) + y, a, b), donate_argnums=0)
        scale = jax.jit(lambda a, c: jax.tree.map(
            lambda x: x.astype(jnp.float32) * c, a), donate_argnums=0)
        step_fn = jax.jit(lambda s, p, g, m, v: adamw(train, s, p, g, m, v),
                          static_argnums=0, donate_argnums=(1, 3, 4))
        losses, first_grads = [], None
        for i, batch in enumerate(batches):
            seq = batch["tokens"].shape[-1]
            toks = np.asarray(batch["tokens"]).reshape(-1, seq)
            labs = np.asarray(batch["labels"]).reshape(-1, seq)
            n_rows = toks.shape[0]
            g_w = np.zeros(n_rows, np.float32)
            g_w[list(range(n_rows) if rows is None else rows)] = 1.0
            l_w = g_w.copy() if loss_rows is None else np.zeros_like(g_w)
            if loss_rows is not None:
                l_w[list(loss_rows)] = 1.0
            grads, loss_sum = None, 0.0
            for r0 in range(0, n_rows, block_rows):
                blk = slice(r0, r0 + block_rows)
                if not (g_w[blk].any() or l_w[blk].any()):
                    continue
                args = [toks[blk], labs[blk], g_w[blk], l_w[blk]]
                if row_sharding is not None:
                    args = [jax.device_put(a, row_sharding) for a in args]
                (_, lv), g = grad_fn(params, *args)
                grads = g if grads is None else add(grads, g)
                loss_sum += float(lv)
                del g
            grads = scale(grads, 1.0 / float(g_w.sum() * seq))
            losses.append(loss_sum / float(l_w.sum() * seq))
            params, m, v, g = step_fn(i + 1, params, grads, m, v)
            del grads
            if i == 0:
                first_grads = g
            else:
                del g
    return {"losses": losses, "grads": first_grads, "params": params}
