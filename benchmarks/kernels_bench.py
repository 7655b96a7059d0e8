"""Kernel microbenchmarks + the measured-kernel cost-table gate.

All numbers are real wall-clock of the kernels as they execute on this
rig (Pallas interpret mode on the CPU backend; the identical harness
times Mosaic-compiled kernels on a TPU) — NOT roofline estimates.  The
roofline appears here only as the baseline the measured tables must beat.

Sections:
  1. attention-impl comparison (naive / chunked / SWA-linear / SSD)
  2. autotuned Pallas kernels: per-shape block-size winners from the
     persistent cache vs the 128-everywhere defaults
  3. fused residual+RMSNorm vs unfused add-then-norm (gated: the fused
     kernel must not lose)
  4. ``calibrate_kernels`` cost-table accuracy on held-out shapes:
     per-op and block-kernel-suite relative error of the interpolated
     table vs the roofline-only guess, against measured truth
     (``KERNELS_GATE=1`` enforces benchmarks/accuracy_budget.json)
"""
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.kernels import autotune as at
from repro.kernels import ops as kops
from repro.core.profiler import kernel_costs, measured
from repro.core.profiler.hw_specs import get_accelerator
from benchmarks.common import emit

BUDGET = json.loads(
    (pathlib.Path(__file__).parent / "accuracy_budget.json").read_text())

# held-out shapes: inside the calibration grids' work range, absent from
# the tables -> exercises the log-space interpolation path, not exact hits
_HELDOUT_ATTN = ((4, 192, 64), (4, 384, 64))
_HELDOUT_NORM = ((1024, 256), (4096, 256))
_HELDOUT_DECODE = ((4, 512, 64),)


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _attn_impls(rng):
    b, s, h, d = 1, 1024, 4, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    flops = 4 * b * h * s * s * d * 0.5
    naive = jax.jit(lambda q, k, v: L.attention(q, k, v, impl="naive"))
    chunk = jax.jit(lambda q, k, v: L.attention(q, k, v, impl="chunked"))
    tn = _time(naive, q, k, v)
    tc = _time(chunk, q, k, v)
    emit("kern/attn_naive_1k", tn * 1e6, f"{flops/tn/1e9:.1f}GFLOP/s")
    emit("kern/attn_chunked_1k", tc * 1e6, f"{flops/tc/1e9:.1f}GFLOP/s")
    # SWA linear vs chunked full at long seq
    s2 = 4096
    q2 = jnp.asarray(rng.standard_normal((1, s2, 2, 64)), jnp.float32)
    k2 = jnp.asarray(rng.standard_normal((1, s2, 2, 64)), jnp.float32)
    v2 = jnp.asarray(rng.standard_normal((1, s2, 2, 64)), jnp.float32)
    win = jax.jit(lambda q, k, v: L.attn_window_linear(q, k, v, window=512))
    full = jax.jit(lambda q, k, v: L.attention(q, k, v, impl="chunked"))
    tw = _time(win, q2, k2, v2)
    tf = _time(full, q2, k2, v2)
    emit("kern/swa_linear_4k_w512", tw * 1e6, f"speedup={tf/tw:.2f}x")
    emit("kern/attn_chunked_4k", tf * 1e6, "")
    # mamba2 chunked SSD vs sequential-scan reference
    from repro.models.mamba2 import ssd_chunked
    from repro.kernels.ref import ssd_ref
    x = jnp.asarray(rng.standard_normal((1, 2048, 4, 32)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (1, 2048, 4)), jnp.float32)
    a = -jnp.ones((4,), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((1, 2048, 32)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((1, 2048, 32)), jnp.float32)
    f_chunk = jax.jit(lambda *a_: ssd_chunked(*a_, chunk=128)[0])
    f_seq = jax.jit(lambda *a_: ssd_ref(*a_)[0])
    t1 = _time(f_chunk, x, dt, a, bb, cc)
    t2 = _time(f_seq, x, dt, a, bb, cc)
    emit("kern/ssd_chunked_2k", t1 * 1e6, f"vs_sequential={t2/t1:.1f}x")


def _autotune(rng):
    """Tuned vs default block sizes (winners persisted on disk)."""
    x = jnp.asarray(rng.standard_normal((3000, 256)), jnp.float32)
    sc = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
    cfg = at.tune_rmsnorm(x, sc, eps=1e-5)
    t_def = _time(lambda: kops.rmsnorm(x, sc, block_rows=256))
    t_tuned = _time(lambda: kops.rmsnorm(x, sc,
                                         block_rows=cfg["block_rows"]))
    emit("kern/rmsnorm_tuned_3000x256", t_tuned * 1e6,
         f"block_rows={cfg['block_rows']} vs_default={t_def/t_tuned:.2f}x")
    q = jnp.asarray(rng.standard_normal((4, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((4, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 512, 64)), jnp.float32)
    fcfg = at.tune_flash_attention(q, k, v, causal=True)
    from repro.kernels import flash_attention as fa
    t_def = at.bench_time(lambda: fa.flash_attention(
        q, k, v, causal=True, interpret=True), iters=5)
    t_tuned = at.bench_time(lambda: fa.flash_attention(
        q, k, v, causal=True, interpret=True, **fcfg), iters=5)
    emit("kern/flash_tuned_512", t_tuned * 1e6,
         f"bq={fcfg['block_q']} bk={fcfg['block_k']} "
         f"vs_default={t_def/t_tuned:.2f}x")


def _pallas_add(x, r, br):
    """The materialize-y pass of the unfused pipeline, same executor as
    the kernels it is compared against (an XLA eager add would measure
    interpreter overhead vs compiled XLA, not the traffic the fusion
    removes)."""
    from jax.experimental import pallas as pl
    rows, d = x.shape
    return pl.pallas_call(
        lambda x_ref, r_ref, y_ref: y_ref.__setitem__(
            ..., x_ref[...] + r_ref[...]),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x, r)


def _fused(rng):
    """Fused residual+RMSNorm vs unfused add-then-norm (gated).

    Both pipelines produce both outputs (y = x + r and rmsnorm(y)) and
    both run through Pallas: unfused = add kernel (write y) + norm kernel
    (read y back) — three passes over the hidden stream; fused = one.
    """
    br = 256
    x = jnp.asarray(rng.standard_normal((4096, 512)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((4096, 512)), jnp.float32)
    sc = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
    add = jax.jit(_pallas_add, static_argnames=("br",))

    def unfused():
        y = add(x, r, br=br)
        return kops.rmsnorm(y, sc, block_rows=br), y

    t_un = at.bench_time(unfused, iters=5)
    t_fu = at.bench_time(
        lambda: kops.fused_add_rmsnorm(x, r, sc, block_rows=br), iters=5)
    speedup = t_un / t_fu
    emit("kern/fused_add_rmsnorm_4096x512", t_fu * 1e6,
         f"vs_unfused={speedup:.2f}x")
    return speedup


def _op_actual(rng, op, shape, dtype="float32"):
    """Measured truth for one held-out (op, shape)."""
    dt_ = jnp.float32
    if op == "flash_attention":
        bh, s, s2, d, _ = shape
        q = jnp.asarray(rng.standard_normal((1, s, bh, d)), dt_)
        k = jnp.asarray(rng.standard_normal((1, s2, bh, d)), dt_)
        v = jnp.asarray(rng.standard_normal((1, s2, bh, d)), dt_)
        return at.bench_time(lambda: kops.flash_attention(q, k, v,
                                                          causal=True),
                             iters=5)
    if op == "flash_decode":
        bh, sk, d = shape
        q = jnp.asarray(rng.standard_normal((1, 1, bh, d)), dt_)
        k = jnp.asarray(rng.standard_normal((1, sk, bh, d)), dt_)
        v = jnp.asarray(rng.standard_normal((1, sk, bh, d)), dt_)
        n = jnp.asarray(sk, jnp.int32)
        return at.bench_time(lambda: kops.flash_attention_decode(
            q, k, v, cache_len=n), iters=5)
    if op in ("rmsnorm", "fused_add_rmsnorm"):
        rows, d = shape
        x = jnp.asarray(rng.standard_normal((rows, d)), dt_)
        sc = jnp.asarray(rng.standard_normal((d,)), dt_)
        if op == "rmsnorm":
            return at.bench_time(lambda: kops.rmsnorm(x, sc), iters=5)
        r = jnp.asarray(rng.standard_normal((rows, d)), dt_)
        return at.bench_time(lambda: kops.fused_add_rmsnorm(x, r, sc),
                             iters=5)
    raise ValueError(op)


def _cost_table(rng):
    """Calibrate, then score table-vs-roofline on held-out shapes."""
    chip = at.default_chip()
    acc = get_accelerator(chip)
    cal = measured.calibrate_kernels(chip, iters=5)
    table = cal.table
    errs_t, errs_r = [], []
    suite_actual = suite_table = suite_roof = 0.0
    held = ([("flash_attention", (bh, s, s, d, 1))
             for bh, s, d in _HELDOUT_ATTN]
            + [("rmsnorm", sh) for sh in _HELDOUT_NORM]
            + [("flash_decode", sh) for sh in _HELDOUT_DECODE])
    for op, shape in held:
        actual = _op_actual(rng, op, shape)
        pred_t = table.lookup(op, shape, "float32")
        assert pred_t is not None, (op, shape)   # inside calibration range
        pred_r = kernel_costs.roofline_time(op, shape, "float32", acc)
        e_t = abs(pred_t - actual) / actual
        e_r = abs(pred_r - actual) / actual
        errs_t.append(e_t)
        errs_r.append(e_r)
        suite_actual += actual
        suite_table += pred_t
        suite_roof += pred_r
        emit(f"kern/cost_{op}_{'x'.join(map(str, shape))}", actual * 1e6,
             f"table_err={e_t:.3f} roofline_err={e_r:.3f}")
    med_t = float(np.median(errs_t))
    med_r = float(np.median(errs_r))
    emit("kern/cost_table_median_err", med_t * 1e6,
         f"roofline_median_err={med_r:.3f} gain={med_r/max(med_t,1e-9):.1f}x")
    # block-kernel-suite "layer cost": the summed kernel time of one
    # block's custom ops — what JobProfile's measured delta corrects
    layer_t = abs(suite_table - suite_actual) / suite_actual
    layer_r = abs(suite_roof - suite_actual) / suite_actual
    emit("kern/layer_err_measured", suite_actual * 1e6,
         f"err={layer_t:.3f}")
    emit("kern/layer_err_roofline", suite_roof * 1e6, f"err={layer_r:.3f}")
    kernel_costs.clear_kernel_tables()     # leave no global state behind
    return med_t, med_r, layer_t, layer_r


def run():
    rng = np.random.default_rng(0)
    _attn_impls(rng)
    _autotune(rng)
    fused_speedup = _fused(rng)
    med_t, med_r, layer_t, layer_r = _cost_table(rng)
    if os.environ.get("KERNELS_GATE", "0") not in ("", "0"):
        fails = []
        if fused_speedup < BUDGET["fused_speedup_min"]:
            fails.append(f"fused speedup {fused_speedup:.2f}x < "
                         f"{BUDGET['fused_speedup_min']}x")
        if med_t > BUDGET["kern_median_err_max"]:
            fails.append(f"table median err {med_t:.3f} > "
                         f"{BUDGET['kern_median_err_max']}")
        if med_t * BUDGET["kern_vs_roofline_gain_min"] > med_r:
            fails.append(
                f"table err {med_t:.3f} not "
                f"{BUDGET['kern_vs_roofline_gain_min']}x better than "
                f"roofline err {med_r:.3f}")
        if layer_t > layer_r:
            fails.append(f"layer-cost err {layer_t:.3f} worse than "
                         f"roofline-only {layer_r:.3f}")
        if fails:
            raise SystemExit("kernels gate FAILED: " + "; ".join(fails))
        print("# kernels gate OK", flush=True)
