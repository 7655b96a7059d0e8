"""Elastic training loop: controller + kill-free reconfiguration (§4.4).

The paper's framework keeps workers alive across availability changes: they
tear down communicators, repartition the model, and continue.  JAX's
functional model makes the equivalent operation a *reshard*: live state
arrays are ``device_put`` onto the new mesh's shardings and the step is
re-jitted — no process restart, no rollback (rollback to the latest async
checkpoint only happens when devices are *lost* with state on them, i.e. a
failure rather than a planned change).

The controller here is in-process and drives meshes built over subsets of
``jax.devices()`` — on a real multi-host deployment the same logic runs in
the coordinator with device sets arriving from the cluster manager; the
decision logic (replan on change, kill-free vs. rollback) is identical.

Straggler mitigation: per-step wall times feed an EWMA detector; a step
slower than ``straggler_factor``x the running median flags the event to the
controller, which (like Sailor) re-invokes the planner — here recorded and
surfaced in metrics so tests/examples can assert on it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import mesh as mesh_lib
from repro.dist import sharding as shd
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.train import checkpoint as ckpt_lib
from repro.train import data as data_lib
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts_lib


@dataclasses.dataclass(frozen=True)
class RuntimePlan:
    """What the launcher needs from a planner decision for one jit program."""
    n_devices: int
    dp: int
    tp: int
    num_microbatches: int = 1
    # per-microbatch gradient weights (len == num_microbatches, summing
    # to 1) from an adaptive plan's BatchAssignment; None = uniform
    micro_weights: Optional[Tuple[float, ...]] = None

    def mesh_shape(self) -> Tuple[int, int]:
        assert self.dp * self.tp == self.n_devices, self
        return (self.dp, self.tp)


class StragglerDetector:
    def __init__(self, factor: float = 3.0, window: int = 20,
                 warmup: int = 5):
        self.factor = factor
        self.times: List[float] = []
        self.window = window
        self.warmup = warmup
        self.events: List[int] = []

    def observe(self, step: int, dt: float) -> bool:
        """Flag ``step`` if ``dt`` exceeds ``factor``x the median of the
        last ``window`` completed steps (the history excludes ``dt``
        itself, else a slow step would drag its own baseline up)."""
        hist = self.times[-self.window:]
        self.times.append(dt)
        del self.times[:-self.window]        # bound memory for long runs
        if len(hist) >= self.warmup and \
                dt > self.factor * float(np.median(hist)):
            self.events.append(step)
            return True
        return False


class ElasticTrainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: opt_lib.OptimizerConfig,
                 data_cfg: data_lib.DataConfig, workdir: str,
                 checkpoint_every: int = 20,
                 plan_fn: Optional[Callable[[int], RuntimePlan]] = None,
                 telemetry=None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.data = data_lib.SyntheticDataset(cfg, data_cfg)
        self.ckpt = ckpt_lib.CheckpointManager(workdir)
        self.checkpoint_every = checkpoint_every
        self.plan_fn = plan_fn or self._default_plan
        self.detector = StragglerDetector()
        # optional telemetry.TelemetryBus: the step loop then emits
        # step_time / data_stall / heartbeat samples and closes each step
        # with end_step, feeding the control plane's online detectors
        # alongside (not instead of) the in-loop StragglerDetector.
        self.telemetry = telemetry
        # telemetry timestamps come from this clock; the manager's
        # controller pins it to its sim clock so bus events interleave
        # time-ordered with feed events (None = wall clock).
        self.clock: Optional[Callable[[], float]] = None
        self.log: List[Dict[str, Any]] = []
        self.reconfigs: List[Dict[str, Any]] = []

        self.mesh: Optional[Mesh] = None
        self.plan: Optional[RuntimePlan] = None
        self.step_fn = None
        self.params = None
        self.opt_state = None
        self.step = 0

    # --- planning ------------------------------------------------------------
    def _default_plan(self, n_devices: int) -> RuntimePlan:
        """Greedy: all devices data-parallel (planner integration replaces
        this in examples/elastic_reconfig.py)."""
        return RuntimePlan(n_devices=n_devices, dp=n_devices, tp=1,
                           num_microbatches=self.data_cfg.num_microbatches)

    # --- (re)build -------------------------------------------------------------
    def _shardings(self, mesh: Mesh):
        pspec = shd.param_specs(model_lib.decls(self.cfg), self.cfg.sharding,
                                mesh)
        pshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspec,
            is_leaf=lambda x: isinstance(x, P))
        oshard = {"m": pshard, "v": pshard,
                  "step": NamedSharding(mesh, P())}
        return pshard, oshard

    def build(self, n_devices: int, init_key: Optional[jax.Array] = None):
        """Initial build or kill-free rebuild onto ``n_devices`` devices."""
        plan = self.plan_fn(n_devices)
        mesh = mesh_lib.data_model_mesh(*plan.mesh_shape(),
                                        jax.devices()[:n_devices])
        pshard, oshard = self._shardings(mesh)
        live = self.params is not None
        with jax.set_mesh(mesh):
            if not live:
                key = init_key if init_key is not None else jax.random.PRNGKey(0)
                self.params = jax.jit(
                    lambda k: model_lib.init(self.cfg, k),
                    out_shardings=pshard)(key)
                self.opt_state = jax.jit(
                    opt_lib.init_state, out_shardings=oshard)(self.params)
            else:
                # kill-free: reshard live state onto the new mesh
                self.params = jax.device_put(self.params, pshard)
                self.opt_state = jax.device_put(self.opt_state, oshard)
        self.step_fn = ts_lib.jit_train_step(
            self.cfg, self.opt_cfg, mesh, plan.num_microbatches,
            self.data_cfg.micro_batch,
            micro_weights=plan.micro_weights)
        self.mesh, self.plan = mesh, plan

    # --- failure path -------------------------------------------------------------
    def restore_from_checkpoint(self, n_devices: int):
        """Failure recovery: rebuild mesh, load latest checkpoint."""
        self.params = None
        self.opt_state = None
        self.build(n_devices)
        template = {
            "params": jax.tree_util.tree_map(np.asarray,
                                             jax.device_get(self.params)),
            "opt": jax.tree_util.tree_map(np.asarray,
                                          jax.device_get(self.opt_state)),
        }
        pshard, oshard = self._shardings(self.mesh)
        try:
            state, step = self.ckpt.restore(
                template, shardings={"params": pshard, "opt": oshard})
            self.params, self.opt_state = state["params"], state["opt"]
            self.step = step
        except FileNotFoundError:
            self.step = 0          # cold start

    # --- events ----------------------------------------------------------------------
    def on_availability_change(self, n_devices: int, failure: bool = False):
        t0 = time.perf_counter()
        step_at_event = self.step
        if failure:
            self.restore_from_checkpoint(n_devices)
            kind = "rollback"
        else:
            self.build(n_devices)
            kind = "kill-free"
        # step times change scale with the device set; a stale median would
        # flag every post-reconfig (re-jit) step as a straggler.
        self.detector.times.clear()
        self.reconfigs.append({
            "step": step_at_event, "resumed_at": self.step,
            "n_devices": n_devices, "kind": kind,
            "reconfig_s": time.perf_counter() - t0})

    # --- telemetry -------------------------------------------------------------------
    def _emit_telemetry(self, step_s: float, data_s: float) -> None:
        """One step's samples onto the attached bus (no-op when detached)."""
        if self.telemetry is None:
            return
        from repro.telemetry.bus import Sample, wall_clock
        t = self.clock() if self.clock is not None else wall_clock()
        emit = self.telemetry.emit
        emit(Sample("step_time", (), t, self.step, step_s))
        emit(Sample("data_stall", (), t, self.step, data_s))
        emit(Sample("heartbeat", (0, 0), t, self.step, 1.0,
                    {"zone": "local", "acc_type": "host",
                     "chips": self.plan.n_devices if self.plan else 0}))
        self.telemetry.end_step(self.step, t)

    # --- training -------------------------------------------------------------------
    def train(self, num_steps: int,
              events: Sequence[Tuple[int, int, bool]] = ()) -> List[Dict]:
        """Run ``num_steps``; ``events`` = (at_step, new_n_devices, failure).

        Multiple events scheduled at the same step are applied in the order
        given (the old ``{step: event}`` dict silently kept only the last
        one — a coalesced capacity-up + failure pair lost the failure)."""
        ev: Dict[int, List[Tuple[int, bool]]] = {}
        for s, n, f in events:
            ev.setdefault(s, []).append((n, f))
        if self.mesh is None:
            self.build(len(jax.devices()))
        end = self.step + num_steps
        while self.step < end:
            if self.step in ev:
                for n, failure in ev.pop(self.step):
                    self.on_availability_change(n, failure)
            t_data = time.perf_counter()
            batch = self.data.batch(self.step)
            t_data = time.perf_counter() - t_data      # input-pipeline wait
            with jax.set_mesh(self.mesh):
                t0 = time.perf_counter()
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch)
                metrics = jax.device_get(metrics)
                dt = time.perf_counter() - t0
            straggler = self.detector.observe(self.step, dt)
            rec = {"step": self.step, "time_s": dt,
                   "loss": float(metrics["loss"]),
                   "n_devices": self.plan.n_devices,
                   "straggler_flag": straggler}
            self.log.append(rec)
            self._emit_telemetry(dt, t_data)
            self.step += 1
            if self.step % self.checkpoint_every == 0:
                self.ckpt.save(self.step, {
                    "params": self.params, "opt": self.opt_state})
        # saves stay in flight: joining here would put checkpoint I/O on
        # the critical path of callers stepping one step at a time (the
        # manager.Controller loop).  save()/restore() already serialize
        # against the in-flight write; call ckpt.wait() for durability.
        return self.log
