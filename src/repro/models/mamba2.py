"""Mamba-2 (SSD, state-space duality) in pure JAX.

Chunked SSD algorithm (arXiv:2405.21060 §6): the sequence is split into
chunks of length Q; within-chunk terms use the quadratic "attention-like"
form, cross-chunk terms flow through a recurrent state scanned over chunks.
This is O(S*Q) instead of O(S^2) and maps 1:1 onto the Pallas kernel in
``repro/kernels/ssd.py`` (this function is its oracle).

Decode carries a constant-size state (B, H, P, N) — no KV cache — which is
what makes long_500k feasible for the ssm/hybrid archs.

Simplifications vs. the reference implementation: ngroups=1 for B/C, no
bias terms, RMSNorm gate (as in mamba2), depthwise conv k=4.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import Decl, batch_spec, constrain
from repro.models import layers as L
from repro.models.config import ModelConfig

CONV_K = 4


# --- declarations ----------------------------------------------------------------

def ssm_layer_decls(cfg: ModelConfig, stacked: bool = True,
                    n_layers: Optional[int] = None) -> Dict[str, Decl]:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    h = cfg.ssm_nheads
    nl = n_layers if n_layers is not None else cfg.n_layers
    pre = (nl,) if stacked else ()
    pax = ("layers",) if stacked else ()

    def decl(shape, axes, **kw):
        return Decl(pre + tuple(shape), pax + tuple(axes), **kw)

    conv_dim = di + 2 * n
    return {
        "ln": decl((d,), ("embed",), init="ones"),
        # in_proj -> [z(di), x(di), B(n), C(n), dt(h)]
        "w_in": decl((d, 2 * di + 2 * n + h), ("embed", "ssm_inner"),
                     scale_dim=-2),
        "conv_w": decl((CONV_K, conv_dim), (None, "ssm_inner"), init="normal",
                       scale_dim=0),
        "conv_b": decl((conv_dim,), ("ssm_inner",), init="zeros"),
        "a_log": decl((h,), (None,), init="a_log"),
        "dt_bias": decl((h,), (None,), init="dt_bias"),
        "d_skip": decl((h,), (None,), init="ones"),
        "gate_ln": decl((di,), ("ssm_inner",), init="ones"),
        "w_out": decl((di, d), ("ssm_inner", "embed"), scale_dim=-2),
    }


def decls(cfg: ModelConfig) -> Dict:
    d = {
        "embed": Decl((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                      init="embed"),
        "ln_f": Decl((cfg.d_model,), ("embed",), init="ones"),
        "layers": ssm_layer_decls(cfg),
    }
    if not cfg.tie_embeddings:
        d["lm_head"] = Decl((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                            scale_dim=-2)
    return d


# --- SSD core ----------------------------------------------------------------------

def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  (B, S, H, P)   per-head inputs
    dt: (B, S, H)      positive step sizes (softplus applied by caller)
    a:  (H,)           negative decay rates (A = -exp(a_log))
    b:  (B, S, N)      input projections  (ngroups=1, shared across heads)
    c:  (B, S, N)      output projections
    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    bs, s, h, p = x.shape
    n = b.shape[-1]
    s_orig = s
    if s % chunk != 0:
        # pad with dt=0 steps: decay=exp(0)=1 and update=0, so padding is
        # state-neutral and the padded outputs are simply discarded.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // chunk
    f32 = jnp.float32

    xr = x.reshape(bs, nc, chunk, h, p).astype(f32)
    dtr = dt.reshape(bs, nc, chunk, h).astype(f32)
    br = b.reshape(bs, nc, chunk, n).astype(f32)
    cr = c.reshape(bs, nc, chunk, n).astype(f32)

    # log-decay within chunk: cum[i] = sum_{j<=i} dt_j * a
    da = dtr * a.astype(f32)                                # (B,nc,Q,H)
    cum = jnp.cumsum(da, axis=2)
    # within-chunk "attention" L[i,j] = exp(cum_i - cum_j) for i>=j
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    ldec = jnp.where(mask[None, None, :, :, None], jnp.exp(li), 0.0)
    scores = jnp.einsum("bcin,bcjn->bcij", cr, br)          # (B,nc,Q,Q)
    y_diag = jnp.einsum("bcij,bcijh,bcjh,bcjhp->bcihp",
                        scores, ldec, dtr, xr)

    # chunk-local end states: sum_j exp(cum_last - cum_j) dt_j x_j b_j^T
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum)              # (B,nc,Q,H)
    states = jnp.einsum("bcjh,bcjh,bcjhp,bcjn->bchpn",
                        dec_end, dtr, xr, br)               # (B,nc,H,P,N)
    chunk_dec = jnp.exp(cum[:, :, -1, :])                   # (B,nc,H)

    # recurrence over chunks: running state BEFORE each chunk
    s0 = (jnp.zeros((bs, h, p, n), f32) if init_state is None
          else init_state.astype(f32))

    def step(carry, xs):
        st_in = carry
        st_c, dec_c = xs
        st_out = dec_c[..., None, None] * st_in + st_c
        return st_out, st_in

    st_fin, st_before = jax.lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_dec.transpose(1, 0, 2)))
    st_before = st_before.transpose(1, 0, 2, 3, 4)          # (B,nc,H,P,N)

    # cross-chunk output: C_i · (exp(cum_i) * state_before_chunk)
    y_off = jnp.einsum("bcin,bcih,bchpn->bcihp",
                       cr, jnp.exp(cum), st_before)
    y = (y_diag + y_off).reshape(bs, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), st_fin


def ssd_ref_sequential(x, dt, a, b, c, init_state=None):
    """O(S) sequential oracle (used by tests to validate ssd_chunked)."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    st = (jnp.zeros((bs, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    ys = []
    for t in range(s):
        dec = jnp.exp(dt[:, t].astype(jnp.float32) * a)     # (B,H)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dt[:, t].astype(jnp.float32),
                         x[:, t].astype(jnp.float32),
                         b[:, t].astype(jnp.float32))
        st = dec[..., None, None] * st + upd
        ys.append(jnp.einsum("bn,bhpn->bhp", c[:, t].astype(jnp.float32), st))
    return jnp.stack(ys, axis=1).astype(x.dtype), st


# --- layer forward -------------------------------------------------------------------

def _conv1d_causal(x: jax.Array, w: jax.Array, bias: jax.Array,
                   state: Optional[jax.Array] = None):
    """Depthwise causal conv, k=CONV_K. x: (B,S,C); w: (K,C).

    Returns (y, new_state) where state is the last K-1 inputs."""
    k = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    windows = [xp[:, i:i + x.shape[1]] for i in range(k)]
    y = sum(wi * w[i] for i, wi in enumerate(windows)) + bias
    new_state = xp[:, -(k - 1):]
    return jax.nn.silu(y), new_state


def mamba_block(cfg: ModelConfig, p, x: jax.Array, *,
                mesh: Optional[Mesh] = None,
                state: Optional[Dict] = None,
                return_state: bool = False):
    """One mamba2 layer. x: (B,S,D). state: {'ssm','conv'} for decode/prefill
    continuation."""
    bs, s, d = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    hp = cfg.ssm_headdim
    res = x
    xn = L.rms_norm(x, p["ln"], cfg.norm_eps)
    proj = xn @ p["w_in"]
    z, xin, bb, cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xin, bb, cc], axis=-1)
    conv_state = None if state is None else state["conv"]
    conv_out, new_conv = _conv1d_causal(conv_in, p["conv_w"], p["conv_b"],
                                        conv_state)
    xin, bb, cc = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xin.reshape(bs, s, h, hp)
    chunk = min(cfg.ssm_chunk, s)
    ssm_state = None if state is None else state["ssm"]
    y, st_fin = ssd_chunked(xh, dt, a, bb, cc, chunk, ssm_state)
    y = y + xh * p["d_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(bs, s, di)
    y = L.rms_norm(y * jax.nn.silu(z), p["gate_ln"], cfg.norm_eps)
    out = res + (y @ p["w_out"]).astype(x.dtype)
    if mesh is not None:
        out = constrain(out, batch_spec(mesh, bs, None, None))
    if return_state:
        return out, {"ssm": st_fin, "conv": new_conv}
    return out, None


def mamba_decode_block(cfg: ModelConfig, p, x: jax.Array, state: Dict):
    """Single-token recurrent update. x: (B,1,D)."""
    out, new_state = mamba_block(cfg, p, x, state=state, return_state=True)
    return out, new_state


# --- full model ------------------------------------------------------------------------

def state_decls(cfg: ModelConfig, batch: int, max_len: int = 0) -> Dict[str, Decl]:
    h, hp, n = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * n
    return {
        "ssm": Decl((cfg.n_layers, batch, h, hp, n),
                    ("layers", None, "ssm_inner", None, None), init="zeros"),
        "conv": Decl((cfg.n_layers, batch, CONV_K - 1, conv_dim),
                     ("layers", None, None, "ssm_inner"), init="zeros"),
        "len": Decl((), (), init="zeros"),
    }


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            mesh: Optional[Mesh] = None, return_cache: bool = False,
            attn_impl: Optional[str] = None, differentiated: bool = False):
    tokens = batch["tokens"]
    bs = tokens.shape[0]
    x = params["embed"][tokens].astype(cfg.dtype)
    if mesh is not None:
        x = constrain(x, batch_spec(mesh, bs, None, None))

    def body(x, lp):
        out, st = mamba_block(cfg, lp, x, mesh=mesh, return_state=return_cache)
        return out, st

    body = body if cfg.remat == "none" else jax.checkpoint(body)
    x, states = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    if return_cache:
        cache = {"ssm": states["ssm"], "conv": states["conv"],
                 "len": jnp.asarray(tokens.shape[1], jnp.int32)}
        return logits, cache
    return logits


def decode(cfg: ModelConfig, params, cache, tokens: jax.Array, *,
           mesh: Optional[Mesh] = None):
    bs = tokens.shape[0]
    x = params["embed"][tokens].astype(cfg.dtype)

    def body(x, lp_state):
        lp, ssm, conv = lp_state
        out, ns = mamba_decode_block(cfg, lp, x, {"ssm": ssm, "conv": conv})
        return out, (ns["ssm"], ns["conv"])

    x, (ssm_new, conv_new) = jax.lax.scan(
        body, x, (params["layers"], cache["ssm"], cache["conv"]))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype)).astype(jnp.float32)
    return logits, {"ssm": ssm_new, "conv": conv_new, "len": cache["len"] + 1}
