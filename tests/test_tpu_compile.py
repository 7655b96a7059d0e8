"""Compile-only checks of every Pallas kernel for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at the widths the main path uses
(OPT-350M for attention and the norms, mamba2-130m for the SSD scan).
This catches what interpret mode cannot — block shapes that break the
TPU tiling rule, scalars outside SMEM, VMEM overuse — at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers each
import this file.
"""
import functools
import os

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
