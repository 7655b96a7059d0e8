"""Serving driver: batched greedy decoding against a (reduced) model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_0_5b --reduced \
      --requests 16 --prompt-len 32 --max-new 16

A warmup batch runs (and times) jit compilation of the prefill + decode
programs separately, so the reported tok/s is steady-state throughput —
the old single timer lumped XLA compile time into the serving window and
underreported throughput by an order of magnitude on short runs.
``--continuous`` serves through the paged continuous-batching scheduler
instead of the static lockstep batch.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serve.serve_step import BatchedServer, Request


def make_requests(cfg, n: int, prompt_len: int, max_new: int,
                  seed: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the paged continuous-batching scheduler")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.max_new + 8

    def build_server():
        if args.continuous:
            from repro.serve.scheduler import ContinuousBatchingServer
            return ContinuousBatchingServer(cfg, params,
                                            max_slots=args.batch_size,
                                            max_ctx=max_len)
        return BatchedServer(cfg, params, max_len=max_len,
                             batch_size=args.batch_size)

    server = build_server()
    # warmup: one full batch through prefill + decode compiles every shape
    # the timed run will hit; time it separately.
    warm = make_requests(cfg, args.batch_size, args.prompt_len,
                         args.max_new, seed=1)
    t0 = time.time()
    server.run(warm)
    t_compile = time.time() - t0

    reqs = make_requests(cfg, args.requests, args.prompt_len, args.max_new)
    t0 = time.time()
    server.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.output) for r in reqs)
    mode = "continuous" if args.continuous else "static"
    print(f"[serve:{mode}] compile+warmup {t_compile:.2f}s")
    print(f"[serve:{mode}] {len(reqs)} requests, {n_tok} tokens in "
          f"{dt:.2f}s steady-state ({n_tok / dt:.1f} tok/s)")
    assert all(r.done for r in reqs)
    print("sample output:", reqs[0].output[:8])


if __name__ == "__main__":
    main()
