"""MPMD pipeline runner with heterogeneous per-stage tensor parallelism.

This is the execution-layer piece that distinguishes Sailor (§4.4) from
same-TP-everywhere systems: each pipeline stage runs its *own* jitted
program on its *own* disjoint device set, with its own (dp, tp) mesh —
``even_stages(cfg, tps=[4, 2])`` gives stage 0 four-way TP and stage 1
two-way TP, matching plans where early stages land on better-connected
GPUs.  Activations and activation-gradients move between stage device
sets with ``jax.device_put`` (ICI/host transfer), parameters never move.

Schedule (DESIGN.md §5): microbatched 1F1B-style — at most ``n_stages``
microbatches are in flight, each backward is issued as soon as its
microbatch clears the last stage, so per-stage live activations are
bounded like 1F1B (backward recomputes the stage forward, so only the
stage *inputs* are retained).  The per-stage optimizer update runs where
the parameters live.

The pipeline numerically matches the single-program reference: scanning
layers [0..k) then [k..n) equals scanning [0..n), and the loss/update
math is shared with ``models/model.py`` and ``train/optimizer.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import mesh as mesh_lib
from repro.dist import sharding as shd
from repro.launch.compile_cache import persistent_cache_disabled
from repro.models import layers as L
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.model import masked_ce_sums
from repro.train import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage: layers [start, stop) at (dp, tp)."""
    index: int
    start: int
    stop: int
    tp: int
    dp: int = 1
    first: bool = False
    last: bool = False

    @property
    def n_layers(self) -> int:
        return self.stop - self.start

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp


def even_stages(cfg: ModelConfig, tps: Sequence[int],
                dp: int = 1) -> List[Stage]:
    """Split ``cfg.n_layers`` as evenly as possible over ``len(tps)`` stages.

    Remainder layers go to the earliest stages (they also hold the larger
    TP degrees in descending-tps plans).  Device-agnostic: meshes are built
    by :class:`MPMDPipeline`, so this is callable from the planner.
    """
    n_stages = len(tps)
    if not 1 <= n_stages <= cfg.n_layers:
        raise ValueError(f"{n_stages} stages for {cfg.n_layers} layers")
    base, rem = divmod(cfg.n_layers, n_stages)
    stages, start = [], 0
    for i, tp in enumerate(tps):
        stop = start + base + (1 if i < rem else 0)
        stages.append(Stage(index=i, start=start, stop=stop, tp=int(tp),
                            dp=int(dp), first=(i == 0),
                            last=(i == n_stages - 1)))
        start = stop
    return stages


def stage_decls(cfg: ModelConfig, stage: Stage) -> Dict[str, Any]:
    """Parameter declarations owned by one stage."""
    sub = dataclasses.replace(cfg, n_layers=stage.n_layers)
    d: Dict[str, Any] = {"layers": transformer.layer_decls(sub)}
    if stage.first:
        d["embed"] = shd.Decl((cfg.vocab_size, cfg.d_model),
                              ("vocab", "embed"), init="embed")
    if stage.last:
        d["ln_f"] = shd.Decl((cfg.d_model,), ("embed",), init="ones")
        d["lm_head"] = shd.Decl((cfg.d_model, cfg.vocab_size),
                                ("embed", "vocab"), scale_dim=-2)
    return d


def _slice_full_params(full: Any, stage: Stage) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "layers": jax.tree_util.tree_map(
            lambda a: a[stage.start:stage.stop], full["layers"])}
    if stage.first:
        out["embed"] = full["embed"]
    if stage.last:
        out["ln_f"] = full["ln_f"]
        out["lm_head"] = full["lm_head"]
    return out


def _stage_apply(cfg: ModelConfig, stage: Stage, params, x):
    """Stage forward: tokens (first) or hidden states -> hidden states.

    Differentiated: the backward programs re-run it under ``jax.vjp``, and
    the forward program must match them numerically."""
    if stage.first:
        x = params["embed"][x].astype(cfg.dtype)
    s = x.shape[1]
    positions = jnp.arange(s)
    impl = L.pick_attn_impl(cfg.attn_impl, s, differentiated=True)

    def body(h, lp):
        h, _ = transformer.attn_block(cfg, lp, h, positions, impl, None)
        h = transformer.ffn_block(cfg, lp, h, None)
        return h, None

    x, _ = jax.lax.scan(transformer._remat(body, cfg.remat), x,
                        params["layers"])
    if stage.last:
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x


def _stage_loss(cfg: ModelConfig, stage: Stage, params, x, labels):
    """Last-stage tail: layers + final norm + head + masked CE.

    The CE is ``models/model.py::masked_ce_sums`` — the same program as
    the single-model ``loss_fn``, so pipeline and reference losses agree
    to float32 reduction order.
    """
    h = _stage_apply(cfg, stage, params, x)
    logits = (h @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    nll_sum, n_tok, _ = masked_ce_sums(logits, labels)
    return nll_sum / jnp.maximum(n_tok, 1)


def _uncached(fn):
    """``fn``, compiled and run outside the persistent compile cache.

    On TPU (JAX 0.9.0, libtpu 0.0.34) a multi-chip executable whose devices
    do not start at device 0 halts the chips when it is loaded back from
    the cache: the runtime's launch barrier fails (``Invalid logical z``).
    Compiled afresh, the same program runs.  Every stage after the first
    has such a device set, so no stage program goes through the cache.
    """
    @functools.wraps(fn)
    def call(*args):
        with persistent_cache_disabled():
            return fn(*args)
    return call


class MPMDPipeline:
    """Multi-program multi-data pipeline over disjoint per-stage meshes.

    Supports the scan-transformer families ('dense', 'moe') with untied
    embeddings; stage 0 owns the embedding table, the last stage owns the
    final norm + LM head.
    """

    def __init__(self, cfg: ModelConfig, stages: Sequence[Stage],
                 opt_cfg: opt_lib.OptimizerConfig,
                 devices: Optional[Sequence] = None,
                 policy: str = "fsdp_tp"):
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"MPMD pipeline supports scan-transformer families, "
                f"not {cfg.family!r}")
        if cfg.tie_embeddings:
            raise NotImplementedError(
                "tied embeddings span first+last stage; untie for MPMD")
        if stages[0].start != 0 or stages[-1].stop != cfg.n_layers:
            raise ValueError(f"stages do not cover [0, {cfg.n_layers})")
        for a, b in zip(stages, stages[1:]):
            if a.stop != b.start:
                raise ValueError(f"stages not contiguous: [{a.start},{a.stop})"
                                 f" then [{b.start},{b.stop})")
        if (not stages[0].first or not stages[-1].last
                or any(s.first for s in stages[1:])
                or any(s.last for s in stages[:-1])):
            raise ValueError("stage first/last flags inconsistent with order")
        self.cfg = cfg
        self.stages = list(stages)
        self.opt_cfg = opt_cfg
        devices = list(jax.devices()) if devices is None else list(devices)
        need = sum(st.n_devices for st in self.stages)
        if need > len(devices):
            raise ValueError(f"plan needs {need} devices, "
                             f"have {len(devices)}")
        self.meshes: List[Mesh] = []
        off = 0
        for st in self.stages:
            self.meshes.append(mesh_lib.data_model_mesh(
                st.dp, st.tp, devices[off:off + st.n_devices]))
            off += st.n_devices
        self._pshards = []
        self._oshards = []
        for st, mesh in zip(self.stages, self.meshes):
            specs = shd.param_specs(stage_decls(cfg, st), policy, mesh)
            ps = jax.tree_util.tree_map(
                lambda s, m=mesh: NamedSharding(m, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            self._pshards.append(ps)
            self._oshards.append({"m": ps, "v": ps,
                                  "step": NamedSharding(mesh, P())})
        self.params: Optional[List[Any]] = None
        self.opt_states: Optional[List[Any]] = None
        self._programs = [self._build_programs(st) for st in self.stages]
        self._telemetry = None          # TelemetryBus (attach_telemetry)
        self._injector = None           # telemetry.FaultInjector
        self._tel_zones: List[str] = []
        self._tel_step = 0

    # --- telemetry (opt-in; zero overhead when detached) -----------------------

    def attach_telemetry(self, bus, injector=None,
                         zones: Optional[Sequence[str]] = None) -> None:
        """Stream per-microbatch timings onto a ``telemetry.TelemetryBus``.

        When attached, ``train_step`` times every per-stage forward /
        backward program and inter-stage transfer (``block_until_ready``,
        so timings are real, not dispatch) and emits the shared sample
        schema — ``fwd_time``/``bwd_time`` keyed ``(stage, 0)``,
        ``p2p_time`` keyed ``(stage, stage+1, 0, 0)``, per-stage
        heartbeats, and ``step_time`` — then closes the step with
        ``bus.end_step``.  ``zones`` labels each stage's pool in the
        sample meta (defaults to ``stage<i>``) so detectors and the RCA
        layer can map streams to cluster coordinates.  ``injector``
        (a ``telemetry.FaultInjector``) perturbs the *real* pipeline:
        active compute-delay/link-degrade faults matching a stage's zone
        sleep the corresponding extra seconds, and hung stages stop
        heartbeating — the chaos suite's hardware-free fault rig.
        """
        self._telemetry = bus
        self._injector = injector
        self._tel_zones = list(zones) if zones is not None else \
            [f"stage{i}" for i in range(len(self.stages))]
        self._tel_step = 0

    def _emit(self, metric: str, key, value: float, **meta) -> None:
        from repro.telemetry.bus import Sample, wall_clock
        self._telemetry.emit(Sample(metric, key, wall_clock(),
                                    self._tel_step, value, meta))

    def _timed(self, fn, metric: str, key, zone: str, acc: str = "host",
               **meta):
        """Run ``fn``, block, emit its wall seconds; inject fault delay."""
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if self._injector is not None:
            if metric in ("fwd_time", "bwd_time"):
                extra = self._injector.compute_delay_s(
                    self._tel_step, zone, acc, dt)
            elif metric == "p2p_time":
                extra = dt * (self._injector.link_factor(
                    self._tel_step, zone, meta.get("zone_b", "")) - 1.0)
            else:
                extra = 0.0
            if extra > 0:
                time.sleep(extra)
                dt += extra
        self._emit(metric, key, dt, zone=zone, acc_type=acc, **meta)
        return out

    # --- per-stage jitted programs ---------------------------------------------

    def _build_programs(self, stage: Stage) -> Dict[str, Any]:
        cfg, opt_cfg = self.cfg, self.opt_cfg
        apply_ = functools.partial(_stage_apply, cfg, stage)
        loss_ = functools.partial(_stage_loss, cfg, stage)

        def fwd(p, x):
            return apply_(p, x)

        def bwd_last(p, x, labels):
            if stage.first:    # single-stage pipeline: x is integer tokens
                loss, gp = jax.value_and_grad(loss_)(p, x, labels)
                return loss, gp, None
            loss, (gp, gx) = jax.value_and_grad(loss_, argnums=(0, 1))(
                p, x, labels)
            return loss, gp, gx

        def bwd_mid(p, x, gy):
            _, vjp = jax.vjp(apply_, p, x)
            gp, gx = vjp(gy)
            return gp, gx

        def bwd_first(p, x, gy):
            # x is integer tokens: no input gradient to propagate
            _, vjp = jax.vjp(lambda pp: apply_(pp, x), p)
            (gp,) = vjp(gy)
            return gp

        def update(p, o, g):
            return opt_lib.apply_updates(p, g, o, opt_cfg)

        # old params/opt state are dead after the update: donate them so the
        # optimizer step doesn't transiently double the stage's footprint
        prog = {"fwd": jax.jit(fwd),
                "update": jax.jit(update, donate_argnums=(0, 1))}
        if stage.last:
            prog["bwd"] = jax.jit(bwd_last)
        elif stage.first:
            prog["bwd"] = jax.jit(bwd_first)
        else:
            prog["bwd"] = jax.jit(bwd_mid)
        return {name: _uncached(fn) for name, fn in prog.items()}

    # --- parameter loading -----------------------------------------------------

    def full_params_like(self, full: Any) -> Any:
        """Load a full single-program parameter tree into the pipeline.

        Each stage receives its slice, placed on its mesh under the stage
        sharding; optimizer state is initialized alongside.  Returns
        ``full`` unchanged so callers can run a single-program reference
        against the exact same weights.
        """
        self.params = []
        self.opt_states = []
        for st, mesh, ps, os_ in zip(self.stages, self.meshes,
                                     self._pshards, self._oshards):
            sliced = _slice_full_params(full, st)
            p = jax.device_put(sliced, ps)
            self.params.append(p)
            self.opt_states.append(
                _uncached(jax.jit(opt_lib.init_state, out_shardings=os_))(p))
        return full

    def init_params(self, key: jax.Array) -> None:
        """Initialize per-stage parameters in place (no full copy)."""
        self.params = []
        self.opt_states = []
        keys = jax.random.split(key, len(self.stages))
        for st, k, ps, os_ in zip(self.stages, keys, self._pshards,
                                  self._oshards):
            p = _uncached(jax.jit(
                lambda kk, st=st: shd.init_from_decls(
                    stage_decls(self.cfg, st), kk, self.cfg.param_dtype),
                out_shardings=ps))(k)
            self.params.append(p)
            self.opt_states.append(
                _uncached(jax.jit(opt_lib.init_state, out_shardings=os_))(p))

    # --- transfers -------------------------------------------------------------

    def _to_stage(self, idx: int, arr, *rest_axes):
        mesh = self.meshes[idx]
        spec = shd.batch_spec(mesh, arr.shape[0], *rest_axes)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    # --- the step --------------------------------------------------------------

    def _forward_micro(self, tokens) -> Dict[str, Any]:
        """Run one microbatch through every stage; keep per-stage inputs
        (backward recomputes the stage forward from them)."""
        inputs = []
        tel = self._telemetry
        x = self._to_stage(0, tokens, None)
        for i, st in enumerate(self.stages):
            if i > 0:
                if tel is not None:
                    x = self._timed(
                        lambda x=x, i=i: self._to_stage(i, x, None, None),
                        "p2p_time", (i - 1, i, 0, 0),
                        self._tel_zones[i - 1],
                        zone_b=self._tel_zones[i])
                else:
                    x = self._to_stage(i, x, None, None)
            inputs.append(x)
            if tel is not None:
                x = self._timed(
                    lambda i=i, x=x: self._programs[i]["fwd"](
                        self.params[i], x),
                    "fwd_time", (i, 0), self._tel_zones[i])
            else:
                x = self._programs[i]["fwd"](self.params[i], x)
        return {"inputs": inputs}

    def _backward_micro(self, ctx: Dict[str, Any], labels):
        """Reverse sweep; returns (loss, per-stage grads)."""
        n = len(self.stages)
        tel = self._telemetry
        grads: List[Any] = [None] * n
        labels = self._to_stage(n - 1, labels, None)
        if tel is not None:
            loss, grads[n - 1], gx = self._timed(
                lambda: self._programs[n - 1]["bwd"](
                    self.params[n - 1], ctx["inputs"][n - 1], labels),
                "bwd_time", (n - 1, 0), self._tel_zones[n - 1])
        else:
            loss, grads[n - 1], gx = self._programs[n - 1]["bwd"](
                self.params[n - 1], ctx["inputs"][n - 1], labels)
        for i in range(n - 2, 0, -1):
            if tel is not None:
                gx = self._timed(
                    lambda gx=gx, i=i: self._to_stage(i, gx, None, None),
                    "p2p_time", (i, i + 1, 0, 0), self._tel_zones[i],
                    zone_b=self._tel_zones[i + 1])
                grads[i], gx = self._timed(
                    lambda i=i, gx=gx: self._programs[i]["bwd"](
                        self.params[i], ctx["inputs"][i], gx),
                    "bwd_time", (i, 0), self._tel_zones[i])
            else:
                gx = self._to_stage(i, gx, None, None)
                grads[i], gx = self._programs[i]["bwd"](
                    self.params[i], ctx["inputs"][i], gx)
        if n > 1:
            if tel is not None:
                gx = self._timed(
                    lambda: self._to_stage(0, gx, None, None),
                    "p2p_time", (0, 1, 0, 0), self._tel_zones[0],
                    zone_b=self._tel_zones[1])
                grads[0] = self._timed(
                    lambda gx=gx: self._programs[0]["bwd"](
                        self.params[0], ctx["inputs"][0], gx),
                    "bwd_time", (0, 0), self._tel_zones[0])
            else:
                gx = self._to_stage(0, gx, None, None)
                grads[0] = self._programs[0]["bwd"](
                    self.params[0], ctx["inputs"][0], gx)
        return loss, grads

    def grad_step(self, batch: Dict[str, Any],
                  weights: Optional[Sequence[float]] = None):
        """Forward/backward over a (num_micro, batch, seq) token batch
        WITHOUT applying the optimizer update.

        Returns ``(loss, grads)`` with ``grads`` the per-stage combined
        gradient trees.  ``weights=None`` averages microbatches uniformly
        (``g = (1/M) sum_m g_m`` — the classic path, unchanged).  With
        ``weights`` given, microbatch ``m`` contributes ``weights[m] *
        g_m`` and the loss is the same weighted sum — the unbiased
        adaptive-microbatching combine where microbatch ``m`` of ``b_m``
        samples carries ``w_m = b_m / B``.  Weights may sum to less than 1
        when a DP group (:class:`AdaptiveDPGroup`) normalizes across its
        replicas; loss normalization is then completed by the group sum.
        """
        if self.params is None:
            raise RuntimeError("load parameters first (full_params_like / "
                               "init_params)")
        tokens, labels = batch["tokens"], batch["labels"]
        num_micro = tokens.shape[0]
        n = len(self.stages)
        w = None
        if weights is not None:
            w = np.asarray(weights, dtype=np.float32)
            if w.shape != (num_micro,):
                raise ValueError(f"weights shape {w.shape} does not match "
                                 f"{num_micro} microbatches")
        acc: List[Any] = [None] * n
        losses: List[Any] = []

        # 1F1B-style: bound in-flight microbatches by the stage count; each
        # backward drains the oldest pending forward.
        pending: collections.deque = collections.deque()
        next_mb = 0
        while next_mb < num_micro or pending:
            if next_mb < num_micro and len(pending) < n:
                pending.append(
                    (next_mb, self._forward_micro(tokens[next_mb])))
                next_mb += 1
            else:
                mb, ctx = pending.popleft()
                loss, grads = self._backward_micro(ctx, labels[mb])
                losses.append(loss)      # device scalar; no sync here
                if w is not None:
                    wm = float(w[mb])
                    grads = [jax.tree_util.tree_map(
                        lambda a, _w=wm: a * _w, g) for g in grads]
                for i in range(n):
                    acc[i] = grads[i] if acc[i] is None else \
                        jax.tree_util.tree_map(jnp.add, acc[i], grads[i])

        if w is None:
            inv = 1.0 / num_micro
            out_grads = [jax.tree_util.tree_map(lambda a: a * inv, acc[i])
                         for i in range(n)]
            loss = float(np.sum(jax.device_get(losses)) * inv)
        else:
            out_grads = acc              # already weighted at add time
            loss = float(np.sum(np.asarray(jax.device_get(losses),
                                           dtype=np.float64)
                                * w.astype(np.float64)))
        return loss, out_grads

    def apply_grads(self, grads: Sequence[Any]) -> None:
        """Apply per-stage gradient trees through the stage optimizers —
        the update half of :meth:`train_step`.  ``AdaptiveDPGroup`` routes
        DP-combined (possibly staleness-delayed) gradients through here."""
        for i in range(len(self.stages)):
            self.params[i], self.opt_states[i], _ = \
                self._programs[i]["update"](self.params[i],
                                            self.opt_states[i], grads[i])

    def train_step(self, batch: Dict[str, Any],
                   weights: Optional[Sequence[float]] = None) -> float:
        """One optimizer step over a (num_micro, batch, seq) token batch.

        Returns the mean over microbatches of the per-microbatch masked
        mean loss, at the pre-update parameters — the same normalization
        as the single-program ``train_step.loss_and_grads`` (and equal to
        the flat-batch loss when valid-token counts are even across
        microbatches, e.g. whenever no label is IGNORE_LABEL).  With
        ``weights``, gradient accumulation and the loss use the given
        per-microbatch weights instead (see :meth:`grad_step`).
        """
        t_start = time.perf_counter()
        out, grads = self.grad_step(batch, weights)
        n = len(self.stages)
        self.apply_grads(grads)
        if self._telemetry is not None:
            from repro.telemetry.bus import wall_clock
            for i in range(n):
                zone = self._tel_zones[i]
                if self._injector is None or \
                        not self._injector.hung(self._tel_step, zone, "host"):
                    self._emit("heartbeat", (i, 0), 1.0, zone=zone,
                               acc_type="host",
                               chips=self.stages[i].n_devices)
            self._emit("step_time", (),
                       time.perf_counter() - t_start)
            self._telemetry.end_step(self._tel_step, wall_clock())
            self._tel_step += 1
        return out


class AdaptiveDPGroup:
    """Data-parallel group of :class:`MPMDPipeline` replicas under an
    adaptive per-replica batch assignment.

    Replica ``r`` runs its OWN microbatch stack (``n_r`` microbatches of
    ``b_r`` sequences); gradients combine host-side with the unbiased
    weights ``w_r = b_r * n_r / B`` — inside a replica each microbatch
    carries ``w_r / n_r = b_r / B``, so the group total equals the
    full-batch mean gradient exactly (up to float association), which is
    why adaptive batching is convergence-neutral.

    ``staleness=k`` opts into bounded-staleness sync: the combined
    gradient of step ``t`` is applied at step ``t + k`` (the first ``k``
    steps apply nothing), letting a high-latency DP edge overlap its
    all-reduce with ``k`` iterations of compute.  ``k=0`` applies the
    current combined gradient immediately — the synchronous path.
    """

    def __init__(self, replicas: Sequence[MPMDPipeline],
                 weights: Optional[Sequence[float]] = None,
                 staleness: int = 0):
        if not replicas:
            raise ValueError("empty DP group")
        self.replicas = list(replicas)
        r = len(self.replicas)
        self.weights = [1.0 / r] * r if weights is None \
            else [float(x) for x in weights]
        if len(self.weights) != r:
            raise ValueError(f"{len(self.weights)} weights for {r} replicas")
        if staleness < 0:
            raise ValueError(f"staleness={staleness} (must be >= 0)")
        self.staleness = int(staleness)
        self._pending: collections.deque = collections.deque()

    @classmethod
    def from_assignment(cls, replicas: Sequence[MPMDPipeline], assignment,
                        staleness: int = 0) -> "AdaptiveDPGroup":
        """Group with weights from a planner
        :class:`~repro.core.planner.plan.BatchAssignment`."""
        return cls(replicas, weights=list(assignment.weights()),
                   staleness=staleness)

    def train_step(self, batches: Sequence[Dict[str, Any]]) -> float:
        """One DP step: per-replica weighted grad accumulation over each
        replica's own (n_r, b_r, seq) stack, host-side weighted combine,
        delayed apply under bounded staleness.  Returns the group loss
        (the ``w_r``-weighted mean microbatch loss — the full-batch masked
        mean when valid-token counts are even)."""
        if len(batches) != len(self.replicas):
            raise ValueError(f"{len(batches)} batches for "
                             f"{len(self.replicas)} replicas")
        loss = 0.0
        grads_per_rep: List[Sequence[Any]] = []
        for r, (rep, batch) in enumerate(zip(self.replicas, batches)):
            n_micro = batch["tokens"].shape[0]
            w_micro = [self.weights[r] / n_micro] * n_micro
            l_r, g_r = rep.grad_step(batch, weights=w_micro)
            loss += l_r
            grads_per_rep.append(g_r)
        self._pending.append(self._combine(grads_per_rep))
        if len(self._pending) > self.staleness:
            self._apply(self._pending.popleft())
        return loss

    def flush(self) -> int:
        """Apply every still-buffered combined gradient (end-of-training
        drain under ``staleness > 0``).  Returns how many were applied."""
        n = 0
        while self._pending:
            self._apply(self._pending.popleft())
            n += 1
        return n

    def _combine(self, grads_per_rep: Sequence[Sequence[Any]]) -> List[Any]:
        """Host-side sum of the replicas' already-weighted per-stage
        gradient trees (every replica holds a full model copy, so the
        stage pytrees are congruent)."""
        n_stages = len(grads_per_rep[0])
        out: List[Any] = []
        for i in range(n_stages):
            acc = jax.device_get(grads_per_rep[0][i])
            for g_r in grads_per_rep[1:]:
                acc = jax.tree_util.tree_map(np.add, acc,
                                             jax.device_get(g_r[i]))
            out.append(acc)
        return out

    def _apply(self, combined: List[Any]) -> None:
        for rep in self.replicas:
            rep.apply_grads(combined)


def shard_batch_by_assignment(batch: Dict[str, Any], assignment
                              ) -> List[Dict[str, Any]]:
    """Split a flat (B, seq) batch into per-replica (n_r, b_r, seq)
    microbatch stacks following a
    :class:`~repro.core.planner.plan.BatchAssignment` (contiguous split;
    exact conservation guarantees the slices tile the batch)."""
    out: List[Dict[str, Any]] = []
    off = 0
    for rb in assignment.replicas:
        take = rb.samples
        rep_batch = {}
        for k, v in batch.items():
            sl = v[off:off + take]
            rep_batch[k] = sl.reshape((rb.n_micro, rb.mbs) + sl.shape[1:])
        out.append(rep_batch)
        off += take
    return out
