#!/usr/bin/env python3
"""Readings from which a cell's limits are set.  Not run by the benchmark.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out calib.jsonl]

For each seed of ``--seeds``: the program's first three steps, as a run of
the benchmark makes them, against the reference (the lower readings).  For
each seed of ``--control-seeds`` also, each against the same reference:

- ``control``: the reference computed in float8 (``reference.py``);
- ``half_batch``: the reference with half of the batch left out, its mean
  taken over the rest;
- ``no_exchange``: the reference with the gradient of the first data
  shard's rows only, as a step without the exchange between chips would
  take it; on four chips only.

A step that returns its state unchanged reads 1 on ``update_norm_gap`` by
construction and needs no run.  One JSON line per seed and kind goes to
standard output and to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]

    spec = bench.resolve(args.workload)
    w = spec["workload"]
    os.environ["REPRO_KERNEL_AUTOTUNE"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    import compare
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench.device_check(w["chips"], spec["peaks"])
    out = open(args.out, "a") if args.out else None

    def emit(seed, kind, values, seconds):
        line = json.dumps({"workload": w["name"], "seed": seed, "kind": kind,
                           "seconds": seconds, **values})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    kinds = ["control", "half_batch"] + (["no_exchange"]
                                         if w["chips"] > 1 else [])
    for seed in sorted(set(seeds) | set(ctrl)):
        r = spec["kind"].Run(spec["config"], spec["traffic"], spec["cell"],
                               w["chips"], seed)
        t = time.perf_counter()
        r.setup()
        t_prog = time.perf_counter() - t
        r.free()
        t = time.perf_counter()
        ref = r.reference()
        t_ref = time.perf_counter() - t
        # off the device while the control and the faults run
        ref["grads"] = jax.device_get(ref["grads"])
        if seed in seeds:
            emit(seed, "program", r.readings(ref), [t_prog, t_ref])
        if seed not in ctrl:
            continue
        for kind in kinds:
            t = time.perf_counter()
            got = (r.reference(precision="fp8") if kind == "control"
                   else r.reference(fault=kind))
            emit(seed, kind, compare.readings(got, ref),
                 time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
