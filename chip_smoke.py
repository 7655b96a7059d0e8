#!/usr/bin/env python3
"""Bring-up smoke test: OPT-350M training and serving on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py                # one chip: train, then serve
    python3 chip_smoke.py --four-chips   # four chips: sharded step with a
                                         # resize, then the MPMD pipeline

Every phase drives the library the entry points use (``launch/train.py``'s
``build_trainer`` and ``ElasticTrainer``, ``ContinuousBatchingServer``,
``MPMDPipeline``) at OPT-350M's full published width, with random weights
from a fixed seed, and checks what comes out against a reference.  A
failed check raises, so the script exits non-zero.  The lines before the
last are bring-up observations, not benchmark results.  The last line is
one JSON object naming the device, printed only when every phase passed
on a TPU.  Everything runs in this one process, which holds the chips.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.profiler.hw_specs import (  # noqa: E402
    accelerator_for_kind, get_accelerator)
from repro.dist.pipeline import MPMDPipeline, even_stages  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serve.scheduler import ContinuousBatchingServer  # noqa: E402
from repro.serve.serve_step import Request, make_prefill  # noqa: E402
from repro.train import data as data_lib  # noqa: E402
from repro.train import optimizer as opt_lib  # noqa: E402
from repro.train.elastic import RuntimePlan  # noqa: E402

SEED = 0
# AdamW peak LR.  bf16 parameters carry no fp32 master copy, so an update
# below half an ulp of a weight is lost; 1e-3 keeps the warmup's first
# steps above that for weights of the init's scale.
LR = 1e-3
# bf16 training/serving against an fp32 or XLA reference.  At init the loss
# sits near ln(vocab) ~ 10.8 and bf16 carries ~3 significant digits, so 1%
# of the loss is well above rounding and well below any real defect.
LOSS_RTOL = 1e-2
# Pallas prefill vs XLA-attention prefill, both bf16 through 24 layers:
# max |logit difference| over the largest |logit| of the reference.
PREFILL_RTOL = 5e-2
CHECKPOINT_DIR = ROOT / ".chip_smoke"
OBS = "[bring-up observation, not a benchmark]"


def observe(phase: str, **facts) -> None:
    print(f"{OBS} {phase}: "
          + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def peak_bytes() -> int | str:
    stats = jax.devices()[0].memory_stats()
    return stats["peak_bytes_in_use"] if stats else "not reported"


def uses_kernel(lowered) -> bool:
    """Whether a lowered program contains a Pallas (Mosaic) kernel."""
    return "tpu_custom_call" in lowered.as_text()


def reference_loss(cfg, params, batch) -> float:
    """``model.loss_fn`` in fp32 with naive attention, averaged over the
    (num_micro, micro_batch, seq) batch's microbatches as the train step
    averages them."""
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                                attn_impl="naive")
    loss = jax.jit(lambda p, mb: model_lib.loss_fn(cfg32, p, mb)[0])
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n_micro = batch["tokens"].shape[0]
    losses = [loss(p32, {k: v[i] for k, v in batch.items()})
              for i in range(n_micro)]
    return float(np.mean(jax.device_get(losses)))


def _trainer(cfg, *, seq_len, micro_batch, num_micro, steps, workdir,
             plan_fn):
    return build_trainer(
        cfg, seq_len=seq_len, global_batch=micro_batch * num_micro,
        num_micro=num_micro, lr=LR, steps=steps, workdir=str(workdir),
        checkpoint_every=10**9, plan_fn=plan_fn)


def _param_devices(params) -> set:
    return {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}


# --- one chip -------------------------------------------------------------------

def train_phase(cfg, *, seq_len: int = 2048, micro_batch: int = 4,
                num_micro: int = 2, warmup: int = 2, steps: int = 5,
                workdir: Path = CHECKPOINT_DIR / "train"):
    """``ElasticTrainer`` as ``launch/train.py`` builds it, on a 1x1 mesh.
    Returns the trained parameters."""
    tr = _trainer(cfg, seq_len=seq_len, micro_batch=micro_batch,
                  num_micro=num_micro, steps=warmup + steps, workdir=workdir,
                  plan_fn=lambda n: RuntimePlan(
                      n_devices=1, dp=1, tp=1, num_microbatches=num_micro))
    tr.build(1, init_key=jax.random.PRNGKey(SEED))
    batch0 = tr.data.batch(0)
    ref = reference_loss(cfg, tr.params, batch0)
    with jax.set_mesh(tr.mesh):
        kernel = uses_kernel(tr.step_fn.lower(tr.params, tr.opt_state,
                                              batch0))
    attention = L.pick_attn_impl(cfg.attn_impl, seq_len, True)
    check(kernel == (attention == "pallas" and jax.default_backend() == "tpu"),
          f"attention resolves to {attention!r} but the compiled train step "
          f"{'contains' if kernel else 'lacks'} a Pallas kernel")
    log = tr.train(warmup + steps)
    losses = [r["loss"] for r in log]
    step_s = float(np.median([r["time_s"] for r in log[warmup:]]))
    tokens = micro_batch * num_micro * seq_len
    observe("train", model=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
            vocab=cfg.vocab_size, dtype=cfg.dtype, sharding=cfg.sharding,
            mesh="x".join(map(str, tr.plan.mesh_shape())),
            seq=seq_len, global_batch=micro_batch * num_micro,
            microbatches=num_micro,
            attention=attention, pallas_in_step=kernel,
            first_step_s_incl_compile=log[0]["time_s"],
            median_step_s=step_s, tokens_per_s=tokens / step_s,
            peak_bytes_in_use=peak_bytes())
    observe("train", losses=[round(x, 4) for x in losses],
            reference_loss_fp32_naive=ref, loss_rtol=LOSS_RTOL)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(abs(losses[0] - ref) <= LOSS_RTOL * abs(ref),
          f"first-step loss {losses[0]} vs fp32 naive reference {ref}")
    return tr.params


def serve_phase(cfg, params, *, n_requests: int = 8, min_prompt: int = 128,
                max_prompt: int = 1024, max_new: int = 32) -> bool:
    """``ContinuousBatchingServer`` answers ``n_requests`` greedy requests;
    the prefill's last-position logits are checked against XLA attention.
    Returns whether the compiled prefill contains a Pallas kernel."""
    rng = np.random.default_rng(SEED)
    lens = np.linspace(min_prompt, max_prompt, n_requests).astype(int)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, int(n),
                                               dtype=np.int32),
                    max_new_tokens=max_new) for i, n in enumerate(lens)]
    max_ctx = max_prompt + max_new
    server = ContinuousBatchingServer(cfg, params, max_slots=n_requests,
                                      max_ctx=max_ctx)
    t0 = time.perf_counter()
    server.run(reqs)
    wall = time.perf_counter() - t0
    check(all(r.done and len(r.output) == max_new for r in reqs),
          "a request did not complete")
    check(all(0 <= t < cfg.vocab_size for r in reqs for t in r.output),
          "a generated token is outside the vocabulary")

    toks = jnp.asarray(reqs[-1].prompt[None, :])       # the longest prompt
    batch = {"tokens": toks}
    prefill = jax.jit(make_prefill(cfg))
    kernel = uses_kernel(prefill.lower(params, batch))
    got, _ = prefill(params, batch)
    want = jax.jit(lambda p, b: model_lib.forward(
        cfg, p, b, attn_impl="chunked")[:, -1])(params, batch)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    observe("serve", requests=n_requests,
            prompt_lens=f"{int(lens[0])}-{int(lens[-1])}",
            new_tokens_each=max_new,
            prefill_attention=L.pick_attn_impl(cfg.attn_impl,
                                               int(lens[-1]), False),
            pallas_in_prefill=kernel,
            decode_attention="naive (per-row cache lengths)",
            prefill_calls=server.stats.prefill_calls,
            decode_steps=server.stats.decode_steps,
            wall_s_incl_compile=wall,
            new_tokens_per_s_incl_compile=n_requests * max_new / wall,
            peak_bytes_in_use=peak_bytes())
    observe("serve", prefill_vs_xla_max_rel_diff=rel,
            prefill_rtol=PREFILL_RTOL,
            same_greedy_token=bool(got.argmax() == want.argmax()))
    check(bool(np.all(np.isfinite(got))), "non-finite prefill logits")
    check(rel <= PREFILL_RTOL, f"prefill logits differ from XLA attention "
                               f"by {rel} of their scale")
    return kernel


# --- four chips -------------------------------------------------------------------

def sharded_phase(cfg, *, seq_len: int = 2048, micro_batch: int = 4,
                  num_micro: int = 2, steps: int = 3, after_resize: int = 2,
                  workdir: Path = CHECKPOINT_DIR / "sharded") -> None:
    """The train step on a dp=2 x tp=2 ``fsdp_tp`` mesh against the same
    step on one chip, then a kill-free resize to two chips."""
    kw = dict(seq_len=seq_len, micro_batch=micro_batch, num_micro=num_micro,
              steps=steps + after_resize)
    one = _trainer(cfg, workdir=workdir / "one", plan_fn=lambda n:
                   RuntimePlan(1, 1, 1, num_microbatches=num_micro), **kw)
    one.build(1, init_key=jax.random.PRNGKey(SEED))
    ref = [r["loss"] for r in one.train(steps)]
    del one

    def plan(n: int) -> RuntimePlan:
        tp = min(n, 2)
        return RuntimePlan(n, dp=n // tp, tp=tp, num_microbatches=num_micro)

    tr = _trainer(cfg, workdir=workdir / "four", plan_fn=plan, **kw)
    tr.build(4, init_key=jax.random.PRNGKey(SEED))
    check(len(_param_devices(tr.params)) == 4,
          "the 2x2 train state is not spread over four devices")
    got = [r["loss"] for r in tr.train(steps)]
    diff = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    observe("sharded", mesh="2x2 (data x model)", sharding=cfg.sharding,
            losses=[round(x, 4) for x in got],
            one_chip_losses=[round(x, 4) for x in ref],
            max_rel_diff=diff, loss_rtol=LOSS_RTOL)
    check(diff <= LOSS_RTOL, f"2x2 losses {got} vs one chip {ref}")

    tr.on_availability_change(2)
    check(len(_param_devices(tr.params)) == 2,
          "after the resize the train state is not on two devices")
    more = [r["loss"] for r in tr.train(after_resize)[-after_resize:]]
    observe("resize", kind=tr.reconfigs[-1]["kind"],
            mesh="x".join(map(str, tr.plan.mesh_shape())),
            reconfig_s=tr.reconfigs[-1]["reconfig_s"],
            losses=[round(x, 4) for x in more])
    check(tr.reconfigs[-1]["kind"] == "kill-free", "resize was not kill-free")
    check(all(np.isfinite(more)), f"non-finite loss after resize: {more}")


def pipeline_phase(cfg, *, seq_len: int = 2048, micro_batch: int = 4,
                   num_micro: int = 2) -> None:
    """``MPMDPipeline``, 2 stages x tp=2, against the single-program
    ``loss_fn`` on the same full parameters."""
    cfg = dataclasses.replace(cfg, tie_embeddings=False)
    stages = even_stages(cfg, tps=[2, 2], dp=1)
    pipe = MPMDPipeline(cfg, stages, opt_lib.OptimizerConfig(lr=LR))
    full = jax.jit(lambda k: model_lib.init(cfg, k))(
        jax.random.PRNGKey(SEED))
    pipe.full_params_like(full)
    devs = [_param_devices(p) for p in pipe.params]
    for st, mesh, d in zip(stages, pipe.meshes, devs):
        check(d == set(mesh.devices.flat) and len(d) == st.n_devices,
              f"stage {st.index} params on {d}, mesh {mesh.devices}")
    check(not devs[0] & devs[1], "the two stages share a device")
    data = data_lib.SyntheticDataset(cfg, data_lib.DataConfig(
        seq_len=seq_len, global_batch=micro_batch * num_micro,
        num_microbatches=num_micro))
    batch = data.batch(0)
    ref = reference_loss(cfg, full, batch)
    del full
    loss = pipe.train_step(batch)
    loss2 = pipe.train_step(data.batch(1))
    observe("pipeline", stages=len(stages),
            layers=[f"{s.start}-{s.stop}" for s in stages],
            tp=[s.tp for s in stages],
            devices=[sorted(d.id for d in ds) for ds in devs],
            tie_embeddings="False (MPMDPipeline needs untied embeddings)",
            loss=loss, reference_loss_fp32_naive=ref, second_loss=loss2,
            rel_diff=abs(loss - ref) / abs(ref), loss_rtol=LOSS_RTOL)
    check(abs(loss - ref) <= LOSS_RTOL * abs(ref),
          f"pipeline loss {loss} vs single-program reference {ref}")
    check(bool(np.isfinite(loss2)), "non-finite second pipeline loss")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases: the sharded train "
                         "step with a resize, and the MPMD pipeline")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    os.environ["REPRO_KERNEL_AUTOTUNE"] = "0"   # fixed tiles, no ~/.cache
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform!r}, "
              f"{dev.device_kind!r})", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    acc = get_accelerator(accelerator_for_kind(dev.device_kind))
    observe("device", platform=dev.platform, kind=repr(dev.device_kind),
            count=len(devices), catalog=acc.name,
            peak_bf16_flops=acc.peak_flops, hbm_bytes_per_s=acc.mem_bw)

    cfg = get_config("opt_350m")
    if args.four_chips:
        sharded_phase(cfg)
        pipeline_phase(cfg)
    else:
        params = train_phase(cfg)
        check(serve_phase(cfg, params),
              "the forward-only prefill ran no Pallas kernel on the TPU")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
