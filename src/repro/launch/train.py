"""End-to-end training driver.

Plans (Sailor planner against a cluster spec, or an explicit dp/tp), builds
the mesh over local devices, and trains with the elastic runtime —
checkpointing, straggler telemetry and kill-free reconfiguration included.

Examples:
  # ~100M-param model, a few hundred steps on CPU (reduced smoke: --reduced)
  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m --reduced \
      --steps 200 --seq-len 128 --global-batch 8

  # plan first against a simulated cluster, then execute the plan's dp/tp
  PYTHONPATH=src python -m repro.launch.train --arch opt-350m --plan \
      --cluster a100:8 --steps 50 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Callable

import jax

from repro.configs import get_config
from repro.core.cluster import heterogeneous_zone
from repro.core.planner.objectives import MAX_THROUGHPUT, Objective
from repro.core.planner.search import plan_for
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.train import data as data_lib
from repro.train import optimizer as opt_lib
from repro.train.elastic import ElasticTrainer, RuntimePlan


def parse_cluster(spec: str):
    """'a100:8,v100:16' -> heterogeneous single-zone ClusterSpec."""
    names = {"a100": "A100-40", "v100": "V100-16", "v5e": "tpu-v5e",
             "gh200": "GH200", "cpu": "cpu-host"}
    cap = {}
    for part in spec.split(","):
        t, n = part.split(":")
        cap[names.get(t.lower(), t)] = int(n)
    return heterogeneous_zone(cap)


def build_trainer(cfg: ModelConfig, *, seq_len: int, global_batch: int,
                  num_micro: int, lr: float, steps: int, workdir: str,
                  checkpoint_every: int,
                  plan_fn: Callable[[int], RuntimePlan]) -> ElasticTrainer:
    """The trainer ``main`` runs, before ``build``: synthetic data,
    AdamW with a 10-step warmup, and ``plan_fn`` mapping a device count
    to the (dp, tp) layout."""
    data_cfg = data_lib.DataConfig(
        seq_len=seq_len, global_batch=global_batch,
        num_microbatches=num_micro)
    opt_cfg = opt_lib.OptimizerConfig(lr=lr, warmup_steps=10,
                                      total_steps=steps)
    return ElasticTrainer(cfg, opt_cfg, data_cfg, workdir=workdir,
                          checkpoint_every=checkpoint_every,
                          plan_fn=plan_fn)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--num-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dp", type=int, default=0, help="0 = all devices")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--plan", action="store_true",
                    help="run the Sailor planner first and print its plan")
    ap.add_argument("--cluster", default="a100:8")
    ap.add_argument("--workdir", default="artifacts/train")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.plan:
        cluster = parse_cluster(args.cluster)
        res = plan_for(cfg, cluster, Objective(MAX_THROUGHPUT),
                       seq_len=args.seq_len, global_batch=args.global_batch)
        if res.best is None:
            raise SystemExit("planner found no valid plan")
        print(f"[planner] search={res.search_time_s:.2f}s "
              f"t_iter={res.best.t_iter:.3f}s "
              f"cost=${res.best.cost_per_iter:.4f}/iter")
        print(res.best.plan.describe())

    n_dev = len(jax.devices())
    dp = args.dp or max(1, n_dev // args.tp)
    trainer = build_trainer(
        cfg, seq_len=args.seq_len, global_batch=args.global_batch,
        num_micro=args.num_micro, lr=args.lr, steps=args.steps,
        workdir=args.workdir, checkpoint_every=args.checkpoint_every,
        plan_fn=lambda n: RuntimePlan(
            n_devices=dp * args.tp, dp=dp, tp=args.tp,
            num_microbatches=args.num_micro))
    trainer.build(dp * args.tp)
    t0 = time.time()
    log = trainer.train(args.steps)
    dt = time.time() - t0
    toks = args.steps * args.global_batch * args.seq_len
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({toks / dt:.0f} tok/s) loss {log[0]['loss']:.3f} -> "
          f"{log[-1]['loss']:.3f}")
    if trainer.detector.events:
        print(f"[train] straggler events at steps {trainer.detector.events}")


if __name__ == "__main__":
    main()
