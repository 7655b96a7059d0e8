"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding configuration is coherent (no partitioner errors),
  * the per-device memory fits the target chip's HBM (``memory_analysis``
    against the ``--chip`` catalog entry, default tpu-v5e),
  * and it extracts the §Roofline terms: per-device FLOPs/bytes from
    ``cost_analysis`` + collective traffic parsed from the post-SPMD HLO.

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and are
consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both [--out artifacts/dryrun]
"""
import argparse
import dataclasses
import functools
import json
import os
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch import hlo as hlo_mod
from repro.launch import hlo_cost
from repro.launch import shapes as shapes_mod
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.config import SHAPES
from repro.core.profiler.hw_specs import AcceleratorSpec, get_accelerator
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts_lib

# Dry-runs price against the reproduction target by default; --chip swaps
# the whole roofline to any catalog entry (hw_specs.ACCELERATORS).
DEFAULT_CHIP = "tpu-v5e"


def step_fn_for(cell: shapes_mod.Cell, mesh):
    cfg = cell.cfg
    if cell.kind == "train":
        opt_cfg = opt_lib.OptimizerConfig()
        return ts_lib.make_train_step(cfg, opt_cfg, mesh)
    if cell.kind == "prefill":
        def prefill(params, batch):
            logits, cache = model_lib.forward(cfg, params, batch, mesh=mesh,
                                              return_cache=True)
            return logits[:, -1], cache
        return prefill

    def decode(params, cache, tokens):
        logits, cache = model_lib.decode(cfg, params, cache, tokens,
                                         mesh=mesh)
        return logits[:, -1], cache
    return decode


def _audit_cell(cfg, cell, mesh, hlo_text: str, tag: str) -> Dict:
    """Collective audit of one compiled train cell: diff the HLO's
    trip-weighted collective volumes against the simulator's predicted
    comm terms (``analysis.audit.predicted_comm``).  Advisory — the
    report rides on the artifact; ``repro.analysis.demo`` is the CI
    pass/fail gate."""
    from repro.analysis import audit as audit_mod
    from repro.analysis import collectives as coll_mod
    from repro.core.profiler.analytic import JobProfile, TrainJob
    sizes = dict(mesh.shape)
    tp = int(sizes.get("model", 1))
    dp = 1
    for a in ("pod", "data"):
        dp *= int(sizes.get(a, 1))
    n_micro = max(1, int(cell.num_microbatches or 1))
    mbs = max(1, cell.shape.global_batch // (dp * n_micro))
    job = TrainJob(cfg=cfg, seq_len=cell.shape.seq_len,
                   global_batch=cell.shape.global_batch)
    predicted = audit_mod.predicted_comm(JobProfile(job), tp=tp, dp=dp,
                                         mbs=mbs, n_micro=n_micro)
    topo = coll_mod.DeviceTopology.from_mesh(mesh, zone_axes=("pod",))
    return audit_mod.audit_hlo(hlo_text, topo, predicted,
                               tag=tag).to_dict()


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, mesh=None, overrides: Optional[Dict] = None,
             tag: str = "", chip: str = DEFAULT_CHIP,
             audit: bool = False) -> Dict:
    acc: AcceleratorSpec = get_accelerator(chip)
    cfg = get_config(arch)
    nm_override = 0
    if overrides:
        overrides = dict(overrides)
        nm_override = overrides.pop("num_microbatches", 0)
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "chip": chip,
                 "mesh_shape": dict(mesh.shape), "ok": False, "tag": tag,
                 "overrides": dict(overrides or {},
                                   **({"num_microbatches": nm_override}
                                      if nm_override else {}))}
    t0 = time.perf_counter()
    try:
        cell = shapes_mod.build_cell(cfg, shape_name, mesh,
                                     nm_override=nm_override)
        if cell.skip_reason:
            rec.update(ok=True, skipped=True, skip_reason=cell.skip_reason)
            return _save(rec, out_dir)
        rec["num_microbatches"] = cell.num_microbatches
        step = step_fn_for(cell, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step).lower(*cell.args)
            t_lower = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter()
        from repro.core.profiler.measured import xla_peak_bytes
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):     # older jax: list of dicts
            cost = cost[0] if cost else {}
        txt = compiled.as_text()
        # trip-count-aware accounting (XLA's cost_analysis counts while
        # bodies once — useless for scan-over-layers programs; see hlo_cost)
        scaled = hlo_cost.analyze(txt)
        colls = hlo_mod.collective_bytes(txt)      # raw, body-once (kept)
        n_chips = int(len(mesh.devices.reshape(-1)))
        flops_dev = scaled.flops
        bytes_dev = scaled.bytes_accessed
        per_dev_mem = xla_peak_bytes(compiled)
        # roofline terms (per device == per chip; see DESIGN.md §8),
        # priced from the accelerator catalog entry for ``chip``
        t_comp = flops_dev / acc.peak_flops
        t_mem = bytes_dev / acc.mem_bw
        t_coll = scaled.collective_traffic / acc.collective_link_bw
        tokens = cell.shape.global_batch * (
            cell.shape.seq_len if cell.kind != "decode" else 1)
        model_flops = 6 * cfg.active_params() * tokens if cell.kind == "train" \
            else 2 * cfg.active_params() * tokens
        rec.update(
            ok=True, skipped=False,
            lower_s=t_lower - t0, compile_s=t_compile - t_lower,
            n_chips=n_chips,
            per_device={
                "flops": flops_dev,
                "bytes_accessed": bytes_dev,
                "flops_xla_body_once": float(cost.get("flops", 0.0)),
                "bytes_xla_body_once": float(cost.get("bytes accessed", 0.0)),
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_bytes": per_dev_mem,
            },
            fits_hbm=bool(per_dev_mem <= acc.mem_bytes),
            collectives={k: {"traffic": v} for k, v in
                         scaled.collective_by_kind.items()},
            collectives_raw={k: {"count": v[0], "bytes": v[1],
                                 "traffic": v[2]}
                             for k, v in colls.by_kind.items()},
            roofline={
                "compute_s": t_comp,
                "memory_s": t_mem,
                "collective_s": t_coll,
                # multi-pod upper bound: all collective traffic priced at
                # DCN bandwidth (pod-axis attribution is in EXPERIMENTS.md)
                "collective_dcn_s": (scaled.collective_traffic
                                     / acc.cross_pod_bw
                                     if multi_pod else None),
                "dominant": max(
                    [("compute", t_comp), ("memory", t_mem),
                     ("collective", t_coll)], key=lambda kv: kv[1])[0],
            },
            model_flops_total=model_flops,
            hlo_flops_total=flops_dev * n_chips,
            useful_flops_ratio=(model_flops / (flops_dev * n_chips)
                                if flops_dev else None),
        )
        if audit and cell.kind == "train":
            rec["audit"] = _audit_cell(
                cfg, cell, mesh, txt,
                tag=f"{arch}__{shape_name}__{mesh_name}")
    except Exception as e:     # a failing cell is a bug — record it loudly
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return _save(rec, out_dir)


def _save(rec: Dict, out_dir: str) -> Dict:
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        out_dir,
        f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    # 512 host devices; takes effect only before JAX's first backend use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides k=v (int/float/str), e.g. "
                         "moe_dispatch=per_seq logits_chunk=512")
    ap.add_argument("--tag", default="",
                    help="artifact suffix for variant runs")
    ap.add_argument("--chip", default=DEFAULT_CHIP,
                    help="accelerator catalog entry to price the roofline "
                         "against (hw_specs.ACCELERATORS)")
    ap.add_argument("--audit", action="store_true",
                    help="run the collective auditor (repro.analysis) on "
                         "each train cell and record the report in the "
                         "artifact (advisory; the CI gate is "
                         "repro.analysis.demo)")
    args = ap.parse_args()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v
    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    # build each mesh once (512 host devices exist either way)
    mesh_cache = {mp: make_production_mesh(multi_pod=mp) for mp in meshes}
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, mp, args.out,
                               mesh=mesh_cache[mp], overrides=overrides,
                               tag=args.tag, chip=args.chip,
                               audit=args.audit)
                dt = time.perf_counter() - t0
                if rec.get("skipped"):
                    status = "SKIP"
                elif rec["ok"]:
                    status = ("OK  " if rec.get("fits_hbm") else "OK!M")
                else:
                    status = "FAIL"
                    failures += 1
                dom = rec.get("roofline", {}).get("dominant", "-")
                mem_gb = rec.get("per_device", {}).get("peak_bytes", 0) / 1e9
                print(f"[{status}] {arch:15s} {shape:12s} "
                      f"{'multi' if mp else 'single':6s} {dt:7.1f}s "
                      f"mem={mem_gb:6.2f}GB dom={dom}", flush=True)
                if status == "FAIL":
                    print("   ", rec.get("error"), flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells FAILED")
    print("all requested dry-run cells passed")


if __name__ == "__main__":
    main()
