"""Train step factory: microbatched grad accumulation + AdamW update.

The step consumes a batch shaped ``(num_micro, micro_batch, seq)`` and scans
over the leading dim accumulating fp32 gradients (1F1B's memory motivation —
only one microbatch of activations is live at a time; remat inside the layer
scan bounds it further).  Under pjit the gradient all-reduce over the dp axes
is inserted by XLA from the sharding propagation — there is no explicit
psum, which lets XLA overlap it with the backward pass where profitable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.telemetry import trace
from repro.train import optimizer as opt_lib


def microbatch_fields(cfg: ModelConfig) -> Tuple[str, ...]:
    fields = ["tokens", "labels"]
    if cfg.family == "encdec":
        fields.append("frames")
    if cfg.family == "vlm":
        fields.append("patches")
    return tuple(fields)


def loss_and_grads(cfg: ModelConfig, params, batch, mesh: Optional[Mesh],
                   micro_weights=None):
    """Scan over microbatches, accumulating fp32 grads and mean loss.

    ``micro_weights`` (shape ``(num_micro,)``, summing to 1) weights each
    microbatch's gradient and loss instead of the uniform ``1/num_micro``
    — the single-mesh form of the adaptive-batching gradient weights
    (``plan.grad_weights``), which keep the accumulated gradient an
    unbiased full-batch mean when microbatches carry unequal sample
    counts.  ``None`` is the exact uniform path."""

    def micro(params, mb):
        return model_lib.loss_fn(cfg, params, mb, mesh=mesh)

    grad_fn = jax.value_and_grad(lambda p, mb: micro(p, mb)[0])
    n_micro = batch["tokens"].shape[0]

    g0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if micro_weights is None:
        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = grad_fn(params, mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (loss_acc + loss, g_acc), None

        (loss_sum, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), g0), batch)
        inv = 1.0 / n_micro
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        return loss_sum * inv, grads

    w = jnp.asarray(micro_weights, jnp.float32)
    if w.shape != (n_micro,):
        raise ValueError(f"micro_weights shape {w.shape} != ({n_micro},)")

    def wbody(carry, xs):
        mb, wi = xs
        loss_acc, g_acc = carry
        loss, g = grad_fn(params, mb)
        g_acc = jax.tree_util.tree_map(
            lambda a, b: a + wi * b.astype(jnp.float32), g_acc, g)
        return (loss_acc + wi * loss, g_acc), None

    (loss_sum, grads), _ = jax.lax.scan(
        wbody, (jnp.float32(0.0), g0), (batch, w))
    return loss_sum, grads


def make_train_step(cfg: ModelConfig, opt_cfg: opt_lib.OptimizerConfig,
                    mesh: Optional[Mesh] = None,
                    micro_weights=None) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        loss, grads = loss_and_grads(cfg, params, batch, mesh,
                                     micro_weights=micro_weights)
        with jax.named_scope(trace.OPTIMIZER):
            params, opt_state, om = opt_lib.apply_updates(
                params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics

    return train_step


def batch_shardings(cfg: ModelConfig, mesh: Mesh, num_micro: int,
                    micro_batch: int) -> Dict[str, NamedSharding]:
    """Shardings for the (num_micro, micro_batch, ...) input batch."""
    spec2 = shd.batch_spec(mesh, micro_batch)
    out = {
        "tokens": NamedSharding(mesh, P(None, spec2[0], None)),
        "labels": NamedSharding(mesh, P(None, spec2[0], None)),
    }
    if cfg.family == "encdec":
        out["frames"] = NamedSharding(mesh, P(None, spec2[0], None, None))
    if cfg.family == "vlm":
        out["patches"] = NamedSharding(mesh, P(None, spec2[0], None, None))
    return out


def jit_train_step(cfg: ModelConfig, opt_cfg: opt_lib.OptimizerConfig,
                   mesh: Mesh, num_micro: int, micro_batch: int,
                   donate: bool = True, micro_weights=None):
    """Fully-sharded jitted train step for a concrete mesh.

    ``micro_weights`` are baked into the traced program (they change only
    on a manager-initiated rebalance, which re-jits)."""
    pspecs = shd.param_specs(model_lib.decls(cfg), cfg.sharding, mesh)
    pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                    is_leaf=lambda x: isinstance(x, P))
    opt_shard = {"m": pshard, "v": pshard,
                 "step": NamedSharding(mesh, P())}
    bshard = batch_shardings(cfg, mesh, num_micro, micro_batch)
    step = make_train_step(cfg, opt_cfg, mesh, micro_weights=micro_weights)
    metr_shard = NamedSharding(mesh, P())
    return jax.jit(
        step,
        in_shardings=(pshard, opt_shard, bshard),
        out_shardings=(pshard, opt_shard,
                       {"loss": metr_shard, "grad_norm": metr_shard,
                        "lr": metr_shard}),
        donate_argnums=(0, 1) if donate else (),
    )
