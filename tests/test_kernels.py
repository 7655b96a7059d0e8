"""Pallas kernel sweeps vs. pure-jnp oracles (interpret mode on CPU).

Per the assignment: sweep shapes/dtypes and assert_allclose against the
ref.py oracle for every kernel.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,kh,d", [
    (1, 128, 2, 2, 64),
    (2, 256, 4, 2, 64),
    (1, 256, 3, 1, 80),        # MQA, odd head count, zamba head_dim
    (2, 128, 8, 8, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kh, d, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, kh, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, kh, d)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    rep = h // kh
    kr = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), kr, vr, causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("blocks", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    q = jnp.asarray(RNG.standard_normal((2, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 128, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3),
                                   causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 32, 16, 32),
    (2, 256, 3, 64, 64, 64),
    (1, 256, 4, 64, 128, 128),   # mamba2-130m geometry
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(b, s, h, p, n, chunk, dtype):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, dtype)
    cc = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, dtype)
    y, st = ops.ssd_scan(x, dt, a, bb, cc, chunk=chunk)
    yw, stw = ref.ssd_ref(x, dt, a, bb, cc)
    tol = dict(rtol=4e-2, atol=4e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yw, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("shape", [(4, 100, 512), (1, 7, 64), (16, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jnp.asarray(RNG.standard_normal(shape), dtype)
    sc = jnp.asarray(RNG.standard_normal(shape[-1:]), dtype)
    out = ops.rmsnorm(x, sc)
    want = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_layer():
    """Kernel path == model's chunked attention for a full-attention case."""
    from repro.models import layers as L
    q = jnp.asarray(RNG.standard_normal((2, 128, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 128, 2, 64)), jnp.float32)
    a = L.attention(q, k, v, impl="chunked", causal=True)
    b = L.attention(q, k, v, impl="pallas", causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


# --- non-divisible sequences (internal pad + mask) ---------------------------

@pytest.mark.parametrize("sq,sk,causal", [
    (100, 100, True),          # ragged vs any block size
    (192, 192, False),         # divisible by 64, ragged vs default 128
    (130, 70, False),          # unequal lengths (cross-attention shaped)
    (257, 300, False),         # both ragged vs default blocks
])
def test_flash_attention_non_divisible(sq, sk, causal):
    q = jnp.asarray(RNG.standard_normal((2, sq, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, sk, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, sk, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3),
                                   causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- backward: dQ and dK/dV kernels through the custom VJP ----------------------

def _attention_loss(fn, ct):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32)])
@pytest.mark.parametrize("sq,sk,h,kh", [
    (128, 128, 4, 2),          # GQA, divisible
    (100, 100, 4, 4),          # ragged vs every block size
    (130, 70, 4, 2),           # unequal and ragged, GQA
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads(blocks, sq, sk, h, kh, causal, dtype):
    """dQ, dK, dV of ops.flash_attention == jax.grad of the fp32 oracle
    (GQA's repeat summed back onto the KV heads by jnp's own VJP)."""
    bq, bk = blocks
    q = jnp.asarray(RNG.standard_normal((2, sq, h, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((2, sk, kh, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((2, sk, kh, 64)), dtype)
    ct = jnp.asarray(RNG.standard_normal((2, sq, h, 64)), jnp.float32)

    def oracle(q, k, v):
        bhsd = lambda x: jnp.repeat(x.astype(jnp.float32), h // x.shape[2],
                                    axis=2).transpose(0, 2, 1, 3)
        return ref.flash_attention_ref(bhsd(q), bhsd(k), bhsd(v),
                                       causal=causal).transpose(0, 2, 1, 3)

    got = jax.grad(_attention_loss(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                            block_q=bq, block_k=bk), ct),
        (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(_attention_loss(oracle, ct), (0, 1, 2))(q, k, v)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert err <= tol, f"d{name}: relative error {err}"


def test_ssd_non_divisible_seq():
    b, s, h, p, n = 1, 200, 2, 32, 16
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b, s, n)) * 0.5, jnp.float32)
    y, st = ops.ssd_scan(x, dt, a, bb, cc, chunk=64)
    yw, stw = ref.ssd_ref(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stw),
                               rtol=1e-4, atol=1e-4)


# --- decode-shaped attention (q_len=1, long KV, dynamic length) --------------

@pytest.mark.parametrize("cache_len", [1, 137, 300])
def test_flash_attention_decode(cache_len):
    b, s, h, kh, d = 2, 300, 4, 2, 64
    q = jnp.asarray(RNG.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kh, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kh, d)), jnp.float32)
    n = jnp.asarray(cache_len, jnp.int32)
    out = ops.flash_attention_decode(q, k, v, cache_len=n)
    from repro.models import layers as L
    want = L.attn_decode(q, k, v, cache_len=n, impl="naive")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_ref_oracle():
    bh, s, d = 4, 256, 64
    q = jnp.asarray(RNG.standard_normal((bh, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((bh, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((bh, s, d)), jnp.float32)
    from repro.kernels import flash_attention as fa
    out = fa.flash_attention_decode(q, k, v, jnp.asarray(100, jnp.int32),
                                    block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, 100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- fused residual-add + RMSNorm --------------------------------------------

@pytest.mark.parametrize("shape", [(4, 100, 512), (3, 87, 128), (16, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_add_rmsnorm_sweep(shape, dtype):
    x = jnp.asarray(RNG.standard_normal(shape), dtype)
    r = jnp.asarray(RNG.standard_normal(shape), dtype)
    sc = jnp.asarray(RNG.standard_normal(shape[-1:]), dtype)
    normed, summed = ops.fused_add_rmsnorm(x, r, sc)
    want_n, want_y = ref.fused_add_rmsnorm_ref(x, r, sc)
    np.testing.assert_allclose(np.asarray(normed, np.float32),
                               np.asarray(want_n, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(summed, np.float32),
                               np.asarray(want_y, np.float32), **_tol(dtype))


def test_rms_norm_residual_seam():
    from repro.models import layers as L
    x = jnp.asarray(RNG.standard_normal((2, 100, 256)), jnp.float32)
    d = jnp.asarray(RNG.standard_normal((2, 100, 256)), jnp.float32)
    sc = jnp.asarray(RNG.standard_normal((256,)), jnp.float32)
    h1, y1 = L.rms_norm_residual(x, d, sc, impl="jnp")
    h2, y2 = L.rms_norm_residual(x, d, sc, impl="pallas")
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-5, atol=2e-5)


# --- autotuner ----------------------------------------------------------------

def test_autotune_deterministic_and_persistent(tmp_path):
    from repro.kernels import autotune as at
    calls = []
    times = {16: 3e-3, 32: 1e-3, 64: 2e-3}

    def bench(c):
        calls.append(c["block"])
        return times[c["block"]]

    cands = [{"block": b} for b in (16, 32, 64)]
    cache = at.AutotuneCache(tmp_path / "tune.json")
    win = at.autotune("op", (128,), "float32", cands, bench,
                      chip="testchip", cache=cache)
    assert win == {"block": 32}
    assert calls == [16, 32, 64]
    # second call: cache hit, no re-benching
    win2 = at.autotune("op", (128,), "float32", cands, bench,
                       chip="testchip", cache=cache)
    assert win2 == win and calls == [16, 32, 64]
    # fresh cache instance on the same file = a new process
    cache2 = at.AutotuneCache(tmp_path / "tune.json")
    win3 = at.autotune("op", (128,), "float32", cands,
                       lambda c: 1 / 0, chip="testchip", cache=cache2)
    assert win3 == win
    # different candidate grid -> different key -> re-tunes (and a bench
    # that fails on every candidate is a hard error, not a silent winner)
    with pytest.raises(RuntimeError, match="no feasible"):
        at.autotune("op", (128,), "float32", cands[:2],
                    lambda c: 1 / 0, chip="testchip", cache=cache2)


def test_autotune_skips_infeasible_and_breaks_ties(tmp_path):
    from repro.kernels import autotune as at
    cache = at.AutotuneCache(tmp_path / "tune.json")

    def bench(c):
        if c["block"] == 16:
            raise ValueError("infeasible tiling")
        return 1e-3                      # tie between 32 and 64

    cands = [{"block": b} for b in (16, 32, 64)]
    win = at.autotune("op", (64,), "float32", cands, bench,
                      chip="testchip", cache=cache)
    assert win == {"block": 32}          # first of the tied candidates


def test_autotune_tuned_blocks_match_defaults(tmp_path):
    """blocks="auto" output is numerically identical to default blocks."""
    from repro.kernels import autotune as at
    import unittest.mock as mock
    x = jnp.asarray(RNG.standard_normal((4, 100, 128)), jnp.float32)
    sc = jnp.asarray(RNG.standard_normal((128,)), jnp.float32)
    with mock.patch.object(at, "_shared_cache",
                           lambda p: at.AutotuneCache(tmp_path / "t.json")):
        out = ops.rmsnorm(x, sc, block_rows="auto")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ops.rmsnorm(x, sc)),
                               rtol=2e-5, atol=2e-5)


def test_device_kind_maps_to_catalog(monkeypatch):
    """The autotuner files tables under the catalog key the profiler asks
    for; an unknown device kind is an error, never a default peak."""
    from repro.core.profiler import hw_specs
    from repro.kernels import autotune as at
    assert hw_specs.accelerator_for_kind("TPU v5 lite") == "tpu-v5e"
    assert set(hw_specs.DEVICE_KINDS.values()) <= set(hw_specs.ACCELERATORS)
    with pytest.raises(KeyError, match="unknown device_kind"):
        hw_specs.accelerator_for_kind("TPU v99")

    class FakeDevice:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [FakeDevice()])
    assert at.default_chip() == "tpu-v5e"
    FakeDevice.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="unknown device_kind"):
        at.default_chip()


# --- models/layers.py pallas dispatch path -----------------------------------

@pytest.mark.parametrize("s", [128, 100])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kh", [4, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_pallas_dispatch_parity(s, causal, kh, dtype):
    from repro.models import layers as L
    q = jnp.asarray(RNG.standard_normal((2, s, 4, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((2, s, kh, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((2, s, kh, 64)), dtype)
    got = L.attention(q, k, v, impl="pallas", causal=causal)
    for other in ("naive", "chunked"):
        want = L.attention(q, k, v, impl=other, causal=causal)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))


def test_attention_pallas_window_falls_back():
    """window > 0 routes off the kernel; result still matches naive."""
    from repro.models import layers as L
    q = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.float32)
    got = L.attention(q, k, v, impl="pallas", causal=True, window=32)
    want = L.attention(q, k, v, impl="naive", causal=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pick_attn_impl():
    from repro.models import layers as L
    assert L.pick_attn_impl("chunked", 128, False) == "chunked"
    assert L.pick_attn_impl("auto", 128, False, backend="tpu") == "pallas"
    assert L.pick_attn_impl("auto", 128, False, backend="cpu") == "naive"
    assert L.pick_attn_impl("auto", 8192, False, backend="cpu") == "chunked"
    # differentiated callers train through the kernel's custom VJP on a
    # TPU, from the measured threshold up; below it XLA attention is faster
    assert L.pick_attn_impl("auto", 2048, True, backend="tpu") == "pallas"
    assert L.pick_attn_impl("auto", 8192, True, backend="tpu") == "pallas"
    assert L.TRAIN_KERNEL_MIN_SEQ == 1024
    assert L.pick_attn_impl("auto", 1024, True, backend="tpu") == "pallas"
    assert L.pick_attn_impl("auto", 512, True, backend="tpu") == "naive"
    assert L.pick_attn_impl("auto", 512, False, backend="tpu") == "pallas"
    assert L.pick_attn_impl("auto", 2048, True, backend="cpu") == "naive"
    assert L.pick_attn_impl("pallas", 128, True) == "pallas"


def _qwen_like(**kw):
    """A reduced Qwen-architecture config (QKV bias, SwiGLU, RMSNorm,
    rotary, tied head) at head dim 64."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen1_5_0_5b").reduced(),
                               d_model=128, n_heads=2, n_kv_heads=2,
                               head_dim=64, **kw)


def _batch(cfg, b, s):
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, s + 1)),
                       jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_loss_fn_rejects_pallas_attention():
    """An explicit Pallas attention on the training path (once refused,
    for want of a backward) runs: the step's loss and gradients are
    finite, and its forward still matches the prefill's, which runs the
    same kernel."""
    from repro.models import model as model_lib
    cfg = _qwen_like(attn_impl="pallas")
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 40)
    (loss, _), grads = jax.value_and_grad(
        lambda p: model_lib.loss_fn(cfg, p, batch), has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    train_logits = model_lib.forward(cfg, params, batch, differentiated=True)
    prefill_logits = model_lib.forward(cfg, params, batch)
    np.testing.assert_allclose(np.asarray(train_logits),
                               np.asarray(prefill_logits),
                               rtol=2e-5, atol=2e-5)


def test_loss_fn_grads_pallas_match_naive():
    """The training loss of a Qwen-architecture model has the same value
    and gradients through the Pallas kernels as through naive attention."""
    from repro.models import model as model_lib
    cfg = _qwen_like(remat="full")
    params = model_lib.init(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg, 2, 96)
    out = {}
    for impl in ("pallas", "naive"):
        c = dataclasses.replace(cfg, attn_impl=impl)
        out[impl] = jax.value_and_grad(
            lambda p: model_lib.loss_fn(c, p, batch)[0])(params)
    (lp, gp), (ln, gn) = out["pallas"], out["naive"]
    np.testing.assert_allclose(float(lp), float(ln), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_attn_decode_pallas_impl():
    from repro.models import layers as L
    q = jnp.asarray(RNG.standard_normal((1, 1, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 256, 4, 64)), jnp.float32)
    n = jnp.asarray(200, jnp.int32)
    got = L.attn_decode(q, k, v, cache_len=n, impl="pallas")
    want = L.attn_decode(q, k, v, cache_len=n, impl="naive")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decoder_block_matches_unfused_blocks():
    """The fused residual seam composes exactly like attn_block+ffn_block
    (the path dist/pipeline.py still runs)."""
    from repro.models import model as model_lib
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=64,
                      dtype="float32", param_dtype="float32")
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(RNG.standard_normal((2, 16, 64)), jnp.float32)
    pos = jnp.arange(16)
    want, _ = T.attn_block(cfg, lp, x, pos, "naive", None)
    want = T.ffn_block(cfg, lp, want, None)
    got, _ = T.decoder_block(cfg, lp, x, pos, "naive", None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
