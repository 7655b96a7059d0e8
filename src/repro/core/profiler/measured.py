"""Measured profiling + calibration on the actual host (paper §4.1).

The paper's profiler measures one node per GPU type with CUDA events.  The
only real device here is the CPU host, so this module:

  1. measures fwd/bwd wall-clock of a single transformer block (repeated
     layers reduced to one instance, exactly the paper's trick) for a grid
     of microbatch sizes,
  2. fits the ``cpu-host`` AcceleratorSpec's effective FLOP/s to those
     measurements (least squares over the grid),
  3. returns a calibrated AcceleratorSpec to drop into the catalog, after
     which the analytic profile *is* a measured profile for cpu-host.

benchmarks/simulator_accuracy.py uses this to validate the simulator's
iteration-time estimates against real measured multi-device step times
(Fig. 5b analog).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.profiler.hw_specs import ACCELERATORS, AcceleratorSpec
from repro.models import model as model_lib
from repro.models.config import ModelConfig


def _time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def measure_block(cfg: ModelConfig, seq_len: int, mbs_grid=(1, 2, 4),
                  ) -> List[Tuple[int, float, float]]:
    """Measure (mbs, fwd_s, fwd+bwd_s) for ONE decoder block of ``cfg``."""
    import dataclasses as dc
    one = dc.replace(cfg, n_layers=1, vocab_size=min(cfg.vocab_size, 1024),
                     remat="none", dtype="float32", param_dtype="float32")
    params = model_lib.init(one, jax.random.PRNGKey(0))
    out = []
    for mbs in mbs_grid:
        batch = {"tokens": jnp.zeros((mbs, seq_len), jnp.int32),
                 "labels": jnp.zeros((mbs, seq_len), jnp.int32)}
        if one.family == "encdec":
            batch["frames"] = jnp.zeros((mbs, one.n_frames, one.d_model))
        if one.family == "vlm":
            batch["patches"] = jnp.zeros((mbs, one.n_patches, one.d_model))
        fwd = jax.jit(lambda p, b: model_lib.forward(one, p, b))
        both = jax.jit(jax.grad(
            lambda p, b: model_lib.loss_fn(one, p, b)[0]))
        t_f = _time_fn(fwd, params, batch)
        t_fb = _time_fn(both, params, batch)
        out.append((mbs, t_f, t_fb))
    return out


def calibrate_cpu_host(cfg: ModelConfig, seq_len: int = 128) -> AcceleratorSpec:
    """Fit cpu-host effective FLOP/s from measured block times."""
    meas = measure_block(cfg, seq_len)
    flops_per_tok = 2 * cfg.layer_params()
    effs = []
    for mbs, t_f, t_fb in meas:
        fl = flops_per_tok * mbs * seq_len
        effs.append(fl / max(t_f, 1e-9))
        effs.append(3 * fl / max(t_fb, 1e-9))
    eff_flops = float(np.median(effs))
    base = ACCELERATORS["cpu-host"]
    return dataclasses.replace(base, peak_flops=eff_flops, efficiency=1.0)


def register_calibrated(spec: AcceleratorSpec, name: str = "cpu-host") -> None:
    ACCELERATORS[name] = dataclasses.replace(spec, name=name)


# --- event-engine calibration (paper §4.1 + §4.3) -----------------------------

@dataclasses.dataclass
class EngineCalibration:
    """Calibrated cpu-host profile + engine overhead coefficients.

    ``engine_cfg`` carries the fitted ``fixed_overhead_s`` (per-iteration
    dispatch/driver cost) and ``per_task_overhead_s`` (per jitted-program
    call / per ``device_put``), the engine's overlap/efficiency knobs the
    ISSUE's calibration loop fits against real ``MPMDPipeline`` wall-clock.
    """

    accelerator: AcceleratorSpec
    engine_cfg: "EngineConfig"
    points: List[Dict]              # measured grid: pp/mbs/n_micro/t rows


def _pipeline_ops(pp: int, n_micro: int) -> int:
    """Dispatched programs per MPMDPipeline.train_step: fwd+bwd per stage
    per microbatch, two transfers per boundary per microbatch, one update
    per stage."""
    return n_micro * pp * 2 + 2 * (pp - 1) * n_micro + pp


def measure_pipeline_step(cfg: ModelConfig, pp: int, n_micro: int, mbs: int,
                          seq_len: int, iters: int = 3) -> float:
    """Wall-clock seconds of one MPMDPipeline train step on host devices."""
    from repro.dist.pipeline import MPMDPipeline, even_stages
    from repro.train import optimizer as opt_lib

    pipe = MPMDPipeline(cfg, even_stages(cfg, tps=[1] * pp, dp=1),
                        opt_lib.OptimizerConfig(lr=1e-3))
    pipe.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size,
                        (n_micro, mbs, seq_len)).astype(np.int32)
    batch = {"tokens": toks, "labels": toks}
    return _time_fn(pipe.train_step, batch, iters=iters)


# --- memory calibration (paper §4.3 / Fig. 3) ---------------------------------

@dataclasses.dataclass
class MemoryCalibration:
    """Fitted memory-model coefficients + the measured grid behind them.

    ``mem_cfg`` carries the fitted ``fragmentation`` (XLA workspace /
    allocator multiplier) and ``runtime_overhead`` (fixed bytes) on top of
    a base config matching the measured runtime's dtypes.  ``points`` rows
    hold the per-program raw prediction vs XLA ``memory_analysis()`` truth.
    """

    mem_cfg: "MemoryModelConfig"
    points: List[Dict]


def xla_peak_bytes(compiled) -> int:
    """XLA's live peak for one compiled program: arguments + outputs +
    temporaries, minus donated aliases (the dry-run formula)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _host_mem_base() -> "MemoryModelConfig":
    """Memory config matching the fp32 host runtime, with the calibratable
    coefficients zeroed so the kernel returns the *raw* structural bytes."""
    from repro.core.simulator.memory import MemoryModelConfig
    return MemoryModelConfig(param_bytes=4, grad_bytes=4, opt_bytes=8,
                             act_bytes=4, fragmentation=1.0,
                             act_fragmentation=1.0,
                             runtime_overhead=0.0, dp_bucket_frac=0.0)


def _train_memory_points(cfg: ModelConfig, seq_len: int,
                         mbs_grid) -> List[Dict]:
    """Compiled single-device train-step programs (grad accumulation over
    microbatches, like the runtime): raw model prediction vs XLA truth."""
    from repro.core.profiler.analytic import JobProfile, TrainJob
    from repro.core.simulator import memory as mem_mod
    from repro.train.train_step import make_train_step

    from repro.train import optimizer as opt_lib

    base = _host_mem_base()
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
    opt_state = opt_lib.init_state(params)
    rows = []
    for mbs in mbs_grid:
        n_micro = 2
        gbs = n_micro * mbs
        job = TrainJob(cfg=cfg, seq_len=seq_len, global_batch=gbs,
                       remat=cfg.remat)
        profile = JobProfile(job)
        batch = {"tokens": jnp.zeros((n_micro, mbs, seq_len), jnp.int32),
                 "labels": jnp.zeros((n_micro, mbs, seq_len), jnp.int32)}
        step = jax.jit(make_train_step(cfg, opt_cfg))
        compiled = step.lower(params, opt_state, batch).compile()
        actual = xla_peak_bytes(compiled)
        comp = mem_mod.stage_memory_components(
            profile, 0, profile.n_partition_units, mbs, 1,
            in_flight=1.0, mem_cfg=base)   # grad accumulation: 1 in flight
        rows.append({"kind": "train", "arch": cfg.name, "mbs": mbs,
                     "static": comp["static"], "act": comp["act"],
                     "raw_pred": comp["static"] + comp["act"],
                     "actual": actual})
    return rows


def _stage_memory_points(cfg: ModelConfig, seq_len: int, mbs: int,
                         pp: int = 2) -> List[Dict]:
    """Compiled pipeline-stage programs (the exact slices ``MPMDPipeline``
    jits per stage: fwd+vjp+optimizer update in one program), one point per
    stage — this is what grounds the per-stage accounting the planner's
    feasibility check runs on."""
    import functools

    from repro.dist.pipeline import (_stage_apply, _stage_loss,
                                     even_stages, stage_decls)
    from repro.dist import sharding as shd
    from repro.core.profiler.analytic import JobProfile, TrainJob
    from repro.core.simulator import memory as mem_mod
    from repro.train import optimizer as opt_lib

    base = _host_mem_base()
    job = TrainJob(cfg=cfg, seq_len=seq_len, global_batch=mbs,
                   remat=cfg.remat)
    profile = JobProfile(job)
    stages = even_stages(cfg, tps=[1] * pp, dp=1)
    rows = []
    for st in stages:
        p = shd.init_from_decls(stage_decls(cfg, st), jax.random.PRNGKey(0),
                                cfg.param_dtype)
        o = opt_lib.init_state(p)
        opt_cfg = opt_lib.OptimizerConfig(lr=1e-3)
        x = (jnp.zeros((mbs, seq_len), jnp.int32) if st.first
             else jnp.zeros((mbs, seq_len, cfg.d_model), jnp.float32))
        gy = jnp.zeros((mbs, seq_len, cfg.d_model), jnp.float32)
        labels = jnp.zeros((mbs, seq_len), jnp.int32)
        apply_ = functools.partial(_stage_apply, cfg, st)

        if st.last:
            def step(p, o, x, labels, st=st):
                loss, gp = jax.value_and_grad(
                    functools.partial(_stage_loss, cfg, st))(p, x, labels)
                p2, o2, _ = opt_lib.apply_updates(p, gp, o, opt_cfg)
                return loss, p2, o2
            args = (p, o, x, labels)
        else:
            def step(p, o, x, gy, apply_=apply_):
                _, vjp = jax.vjp(lambda pp_: apply_(pp_, x), p)
                (gp,) = vjp(gy)
                p2, o2, _ = opt_lib.apply_updates(p, gp, o, opt_cfg)
                return p2, o2
            args = (p, o, x, gy)
        compiled = jax.jit(step).lower(*args).compile()
        actual = xla_peak_bytes(compiled)
        # profile-layer range of this stage: embed rides with stage 0,
        # the head with the last stage (MPMDPipeline's ownership rule)
        lo = 0 if st.first else st.start + 1
        hi = profile.n_partition_units if st.last else st.stop + 1
        comp = mem_mod.stage_memory_components(profile, lo, hi, mbs, 1,
                                               in_flight=1.0, mem_cfg=base)
        rows.append({"kind": "stage", "arch": cfg.name, "mbs": mbs,
                     "stage": st.index, "pp": pp,
                     "static": comp["static"], "act": comp["act"],
                     "raw_pred": comp["static"] + comp["act"],
                     "actual": actual})
    return rows


def calibrate_memory(cfgs, seq_len: int = 64,
                     mbs_grid=(1, 2, 4)) -> MemoryCalibration:
    """Fit the memory model's ``fragmentation`` / ``runtime_overhead``
    against real ``jax.jit(...).compile().memory_analysis()`` on host
    devices (the same hook ``launch/dryrun.py`` gates HBM fit with).

    Grid: single-device *training* programs (grad-accumulating train step)
    for every config x mbs, plus 2-stage *pipeline-stage* programs (the
    slices ``MPMDPipeline`` compiles per stage) for transformer configs.
    Least-squares fit of

        actual ~= frag * static + frag * act_frag * act + overhead

    — the static stream (params/grads/optimizer, exact dtype arithmetic)
    and the activation stream (where XLA's workspace and padding live) get
    separate multipliers, clamped to ``frag >= 1``, ``act_frag >= 1``,
    ``overhead >= 0`` (the structural terms lower-bound a real allocator).
    """
    rows: List[Dict] = []
    for cfg in cfgs:
        rows.extend(_train_memory_points(cfg, seq_len, mbs_grid))
        if cfg.family in ("dense", "moe") and not cfg.tie_embeddings:
            rows.extend(_stage_memory_points(cfg, seq_len, mbs_grid[-1]))
    A = np.asarray([[r["static"], r["act"], 1.0] for r in rows])
    y = np.asarray([r["actual"] for r in rows], dtype=float)
    # minimize RELATIVE residuals (the feasibility gate cares about
    # percent error, and absolute least squares would let the largest
    # programs dominate): divide each row by its ground truth.
    W = A / y[:, None]
    ones = np.ones_like(y)

    def _clamped(a, b, c):
        a = max(a, 1.0)
        return a, max(b, a), max(c, 0.0)

    candidates = []
    free, *_ = np.linalg.lstsq(W, ones, rcond=None)        # a, b, c free
    candidates.append(_clamped(*(float(v) for v in free)))
    noc, *_ = np.linalg.lstsq(W[:, :2], ones, rcond=None)  # c = 0
    candidates.append(_clamped(float(noc[0]), float(noc[1]), 0.0))
    tied = W[:, 0] + W[:, 1]                               # b = a
    eq, *_ = np.linalg.lstsq(np.stack([tied, W[:, 2]], 1), ones, rcond=None)
    candidates.append(_clamped(float(eq[0]), float(eq[0]), float(eq[1])))
    one = float((tied @ ones) / (tied @ tied))             # b = a, c = 0
    candidates.append(_clamped(one, one, 0.0))
    # small grids can make the unconstrained solution infeasible in a way
    # naive clamping turns into a systematic over-prediction — evaluate
    # every candidate AFTER clamping and keep the best actual fit.
    a, b, c = min(candidates,
                  key=lambda abc: float(np.sum((W @ abc - ones) ** 2)))
    mem_cfg = dataclasses.replace(
        _host_mem_base(), fragmentation=a, act_fragmentation=b / a,
        runtime_overhead=c)
    return MemoryCalibration(mem_cfg=mem_cfg, points=rows)


# --- kernel calibration (the third leg: per-op cost tables) -------------------

@dataclasses.dataclass
class KernelCalibration:
    """Measured kernel cost table + the raw grid behind it.

    ``table`` maps (op, shape, dtype) -> seconds on ``table.chip``;
    registering it (done by default) makes ``analytic.JobProfile.cost``
    consult the measurements before the roofline.  ``points`` rows keep
    the per-shape measured vs roofline times for reporting/gating.
    """

    table: "KernelCostTable"
    points: List[Dict]


# small-by-default grids: CPU interpret mode is the measured backend on
# this container, so a handful of shapes per op keeps calibration O(10 s)
# while spanning ~two decades of work for the log-space interpolation.
_ATTN_SHAPES = ((4, 128, 64), (4, 256, 64), (4, 512, 64))      # (bh, s, d)
_DECODE_SHAPES = ((4, 256, 64), (4, 1024, 64))                 # (bh, sk, d)
_NORM_SHAPES = ((512, 256), (2048, 256), (8192, 256))          # (rows, d)
_SSD_SHAPES = ((1, 128, 2, 32, 16), (1, 512, 2, 32, 16))       # (b,s,h,p,n)


def calibrate_kernels(chip: Optional[str] = None, *,
                      dtypes=("float32",),
                      attn_shapes=_ATTN_SHAPES,
                      decode_shapes=_DECODE_SHAPES,
                      norm_shapes=_NORM_SHAPES,
                      ssd_shapes=_SSD_SHAPES,
                      iters: int = 3, autotune_blocks: bool = False,
                      register: bool = True,
                      path: Optional[str] = None) -> KernelCalibration:
    """Benchmark the real Pallas kernels into a per-(op, shape, dtype,
    chip) cost table (interpret mode on this CPU container; Mosaic on a
    real TPU — same code path, ``ops.on_platform`` decides).

    With ``autotune_blocks`` the autotuner picks the tiling first (winner
    cached on disk), so the table prices the *tuned* kernels.  The table
    is registered into :mod:`kernel_costs` (``register=False`` to skip)
    and optionally saved to ``path`` (JSON, reloadable with
    ``KernelCostTable.load``).
    """
    from repro.core.profiler import kernel_costs
    from repro.core.profiler.hw_specs import get_accelerator
    from repro.kernels import autotune as at
    from repro.kernels import ops as kops

    chip = chip or at.default_chip()
    acc = get_accelerator(chip) if chip in ACCELERATORS else None
    table = kernel_costs.KernelCostTable(chip=chip)
    points: List[Dict] = []
    rng = np.random.default_rng(0)

    def _arr(shape, dtype):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def _add(op, shape, dtype, fn):
        t = at.bench_time(fn, iters=iters)
        table.add(op, shape, dtype, t)
        row = {"op": op, "shape": tuple(shape), "dtype": dtype,
               "time_s": t}
        if acc is not None:
            row["roofline_s"] = kernel_costs.roofline_time(
                op, shape, dtype, acc)
        points.append(row)

    blocks = "auto" if autotune_blocks else None
    for dtype in dtypes:
        for bh, s, d in attn_shapes:
            q = _arr((1, s, bh, d), dtype)
            k, v = _arr(q.shape, dtype), _arr(q.shape, dtype)
            _add("flash_attention", (bh, s, s, d, 1), dtype,
                 lambda q=q, k=k, v=v: kops.flash_attention(
                     q, k, v, causal=True, block_q=blocks, block_k=blocks))
        for bh, sk, d in decode_shapes:
            q = _arr((1, 1, bh, d), dtype)
            k, v = _arr((1, sk, bh, d), dtype), _arr((1, sk, bh, d), dtype)
            n = jnp.asarray(sk, jnp.int32)
            _add("flash_decode", (bh, sk, d), dtype,
                 lambda q=q, k=k, v=v, n=n: kops.flash_attention_decode(
                     q, k, v, cache_len=n))
        for rows, d in norm_shapes:
            x, sc = _arr((rows, d), dtype), _arr((d,), dtype)
            _add("rmsnorm", (rows, d), dtype,
                 lambda x=x, sc=sc: kops.rmsnorm(x, sc,
                                                 block_rows=blocks))
            r = _arr((rows, d), dtype)
            _add("fused_add_rmsnorm", (rows, d), dtype,
                 lambda x=x, r=r, sc=sc: kops.fused_add_rmsnorm(
                     x, r, sc, block_rows=blocks))
        for bs, s, h, p, n in ssd_shapes:
            x = _arr((bs, s, h, p), dtype)
            dt = jnp.asarray(rng.uniform(0.001, 0.1, (bs, s, h)),
                             jnp.float32)
            a = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
            bb = _arr((bs, s, n), dtype)
            cc = _arr((bs, s, n), dtype)
            _add("ssd_scan", (bs, s, h, p, n), dtype,
                 lambda x=x, dt=dt, a=a, bb=bb, cc=cc: kops.ssd_scan(
                     x, dt, a, bb, cc, chunk=blocks))
    if register:
        kernel_costs.register_kernel_table(table)
    if path:
        table.save(path)
    return KernelCalibration(table=table, points=points)


def calibrate_engine(cfg: ModelConfig, seq_len: int = 32, mbs: int = 2,
                     n_micro_grid=(1, 2, 4), max_pp: int = 2
                     ) -> EngineCalibration:
    """Fit the event engine's overhead coefficients on this host.

    1. Calibrate cpu-host effective FLOP/s from single-block wall-clock
       (:func:`calibrate_cpu_host`) so compute terms are measured, and
    2. run real ``MPMDPipeline`` steps over a (pp, n_micro) grid, fitting
       the residual against the raw engine prediction as
       ``a + b * n_dispatched_programs`` (least squares, clamped >= 0):
       ``a`` is per-iteration driver overhead, ``b`` per-task dispatch.

    Returns the calibrated AcceleratorSpec (already registered) and an
    ``EngineConfig`` carrying the fitted overheads.
    """
    from repro.core.cluster import single_zone
    from repro.core.planner.plan import homogeneous_plan
    from repro.core.profiler.analytic import JobProfile, TrainJob
    from repro.core.simulator import timing as timing_mod
    from repro.core.simulator.engine import EngineConfig

    cfg = dataclasses.replace(cfg, tie_embeddings=False)
    spec = calibrate_cpu_host(cfg, seq_len=seq_len)
    register_calibrated(spec, "cpu-host")

    n_dev = len(jax.devices())
    pps = [p for p in range(1, max_pp + 1) if p <= n_dev]
    cluster = single_zone("cpu-host", max(pps))
    zone = cluster.zones[0].name
    rows, A, y = [], [], []
    raw = EngineConfig()                        # zero overheads
    for pp in pps:
        for n_micro in n_micro_grid:
            gbs = n_micro * mbs
            job = TrainJob(cfg=cfg, seq_len=seq_len, global_batch=gbs)
            profile = JobProfile(job)
            plan = homogeneous_plan("cpu-host", zone, pp, 1, 1,
                                    profile.n_partition_units, mbs, gbs)
            pred = timing_mod.iteration_time(profile, plan, cluster,
                                             raw).t_iter
            meas = measure_pipeline_step(cfg, pp, n_micro, mbs, seq_len)
            ops = _pipeline_ops(pp, n_micro)
            rows.append({"pp": pp, "n_micro": n_micro, "mbs": mbs,
                         "t_measured": meas, "t_raw_pred": pred,
                         "n_ops": ops})
            A.append([1.0, float(ops)])
            y.append(max(meas - pred, 0.0))
    coef, *_ = np.linalg.lstsq(np.asarray(A), np.asarray(y), rcond=None)
    a, b = float(coef[0]), float(coef[1])
    if b < 0:
        b = 0.0
        a = float(np.mean(y))
    a = max(a, 0.0)
    return EngineCalibration(
        accelerator=spec,
        engine_cfg=EngineConfig(fixed_overhead_s=a, per_task_overhead_s=b),
        points=rows)
