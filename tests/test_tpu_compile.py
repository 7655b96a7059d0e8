"""Compile-only checks of every Pallas kernel for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at the widths the main path uses
(OPT-350M for attention and the norms, mamba2-130m for the SSD scan, and
Qwen1.5-0.5B's training shapes for the flash forward and backward).
This catches what interpret mode cannot — block shapes that break the
TPU tiling rule, scalars outside SMEM, VMEM overuse — at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers each
import this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import fused, rmsnorm, ssd

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# OPT-350M: 16 heads of 64, d_model 1024; train micro-batch 4 at seq 2048
OPT_BH, OPT_S, OPT_HD, OPT_D = 4 * 16, 2048, 64, 1024
# mamba2-130m: 24 SSD heads of P=64, state N=128, chunk 128
SSD_B, SSD_S, SSD_H, SSD_P, SSD_N = 4, 2048, 24, 64, 128
# Qwen1.5-0.5B training microbatches: 2 x 16 heads at seq 2048, 8 x 16 at 512
QWEN_ATTN = {"s2048": (32, 2048, 64), "s512": (128, 512, 64)}


def _flash(kernel, bh, s, d):
    bq, bk = fa.default_blocks(s, d)
    return functools.partial(kernel, causal=True, block_q=bq, block_k=bk)


def _flash_fwd(bh, s, d):
    return _flash(fa.flash_attention_fwd, bh, s, d), [((bh, s, d), BF16)] * 3


def _flash_bwd(bh, s, d):
    return (_flash(fa.flash_attention_bwd, bh, s, d),
            [((bh, s, d), BF16)] * 4 + [((bh, s), F32), ((bh, s, d), BF16)])

CASES = {
    "flash_attention": (
        functools.partial(fa.flash_attention, causal=True),
        [((OPT_BH, OPT_S, OPT_HD), BF16)] * 3),
    "flash_attention_decode": (
        fa.flash_attention_decode,
        [((8 * 16, OPT_HD), BF16), ((8 * 16, OPT_S, OPT_HD), BF16),
         ((8 * 16, OPT_S, OPT_HD), BF16), ((), I32)]),
    "rmsnorm": (
        rmsnorm.rmsnorm,
        [((4 * OPT_S, OPT_D), BF16), ((OPT_D,), BF16)]),
    "fused_add_rmsnorm": (
        fused.fused_add_rmsnorm,
        [((4 * OPT_S, OPT_D), BF16), ((4 * OPT_S, OPT_D), BF16),
         ((OPT_D,), BF16)]),
    "ssd_scan": (
        ssd.ssd_scan,
        [((SSD_B, SSD_S, SSD_H, SSD_P), BF16), ((SSD_B, SSD_S, SSD_H), F32),
         ((SSD_H,), F32), ((SSD_B, SSD_S, SSD_N), BF16),
         ((SSD_B, SSD_S, SSD_N), BF16)]),
    **{f"flash_attention_fwd_lse.{n}": _flash_fwd(*shape)
       for n, shape in QWEN_ATTN.items()},
    **{f"flash_attention_bwd.{n}": _flash_bwd(*shape)
       for n, shape in QWEN_ATTN.items()},
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"


def _kernel_op_names(mesh, batch_sharding, params_sharding):
    """op_name of every Mosaic kernel in ``jax.grad`` of a reduced
    Qwen-architecture loss, compiled for the described chip(s)."""
    from repro.configs import get_config
    from repro.models import model as model_lib
    # "pallas" is what "auto" resolves to on a TPU (test_pick_attn_impl);
    # this process's backend is the CPU
    cfg = dataclasses.replace(
        get_config("qwen1_5_0_5b").reduced(), d_model=256, n_heads=4,
        n_kv_heads=4, head_dim=64, dtype="bfloat16", param_dtype="bfloat16",
        remat="full", attn_impl="pallas", sharding="fsdp_tp")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=params_sharding),
        jax.eval_shape(lambda: model_lib.init(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((4, 1024), I32, sharding=batch_sharding)
    batch = {"tokens": tokens, "labels": tokens}
    grad = jax.jit(jax.grad(
        lambda p, b: model_lib.loss_fn(cfg, p, b, mesh=mesh)[0]))
    text = grad.lower(params, batch).compile().as_text()
    return [m.group(1) for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line
            for m in [re.search(r'op_name="([^"]+)"', line)] if m]


def test_training_step_runs_the_flash_kernels_for_v5e(one_chip):
    """``jax.grad`` of a reduced Qwen-architecture loss, compiled for the
    chip, holds the flash forward and both backward kernels, each under
    the attention scope the benchmark's trace reduction reads."""
    from repro.telemetry import trace
    names = _kernel_op_names(None, one_chip, one_chip)
    kernels = {re.search(r"jit\((flash_attention_\w+)\)", n).group(1)
               for n in names if trace.ATTENTION in n}
    assert kernels == {"flash_attention_fwd", "flash_attention_bwd"}, names
    assert sum("flash_attention_bwd" in n for n in names) == 2, names
    assert all(trace.ATTENTION in n for n in names), names


def test_sharded_training_step_runs_the_kernels_per_shard(topo):
    """On a 2x2 ``fsdp_tp`` mesh XLA cannot partition a Mosaic kernel: the
    step compiles because each kernel runs on its shard (shard_map)."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    with jax.set_mesh(mesh):
        names = _kernel_op_names(mesh, NamedSharding(mesh, PartitionSpec(
            "data")), NamedSharding(mesh, PartitionSpec()))
    assert sum("flash_attention_bwd" in n for n in names) == 2, names
    assert all("shard_map" in n for n in names), names
