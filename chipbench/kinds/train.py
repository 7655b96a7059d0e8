"""The ``train`` traffic kind: the elastic trainer's step.

The system under test is built as the training entry point builds it
(``repro.launch.train.build_trainer`` and ``ElasticTrainer.build``), and
every step, the checked ones and the timed ones alike, goes through
``ElasticTrainer.train(1)``, which reads its batch from the feed below.

A traffic file of this kind gives ``seq_len``, ``global_batch``,
``num_micro`` (microbatches a step) and ``zipf_exponent``: tokens are
drawn from a Zipf law over the vocabulary, as words of text are, and
every row of every step differs.  The cell file gives the mesh
(``dp`` x ``tp``), the reference's ``block_rows`` and the limits.

The run is handed the configuration's own reference module (the one its
configuration file names), and imports none itself.  That module gives
``seed_key``, ``init_params``, ``param_shapes`` and ``run_steps``.

Set-up makes the weights on the device from the seed in one jitted call
(the reference's ``init_params``, in the trainer's own layout, which must
be the reference's), puts them in the trainer's place, and runs the first
three steps, which also compile the step.  The reference then follows
those three steps (``run_steps``) once the window has closed and the
trainer's state is freed.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import compare

CHECKED_STEPS = 3
SPAN = "chipbench.train_call"


class Feed:
    """``batch(step)``: tokens and next-token labels shaped
    (num_micro, global_batch / num_micro, seq_len), from (seed, step)."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        self.t, self.seed = traffic, int(seed)
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -traffic["zipf_exponent"]
        self.cdf = np.cumsum(w / w.sum())
        self.vocab = vocab

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        t = self.t
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, int(step)]))
        mb = t["global_batch"] // t["num_micro"]
        u = rng.random((t["num_micro"], mb, t["seq_len"] + 1))
        toks = np.minimum(np.searchsorted(self.cdf, u), self.vocab - 1)
        toks = toks.astype(np.int32)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


def _minus(a, b, shard=None):
    """a - b leaf by leaf in float32, ``b`` laid out as ``shard`` says (so
    that weights drawn inside a jitted call are made split, as ``a`` is)."""
    if shard is not None:
        b = jax.tree.map(jax.lax.with_sharding_constraint, b, shard)
    return jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


def slowest(window: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The window's compiles and Python collections, and the phases of its
    slowest step, from the trainer's records of its steps."""
    worst = max(window, key=lambda r: r["time_s"])
    return {"window_compiles": sum(r["compiles"] for r in window),
            "window_gc_collections": sum(r["gc_collections"] for r in window),
            "slowest_step": {k: worst[k] for k in (
                "step", "time_s", "data_s", "dispatch_s", "sync_s",
                "compiles", "compile_s", "gc_collections", "gc_s")}}


def _program_opt(opt_cfg) -> Dict[str, Any]:
    return {k: getattr(opt_cfg, k) for k in (
        "lr", "beta1", "beta2", "eps", "weight_decay", "warmup_steps",
        "total_steps", "grad_clip", "schedule")}


class Run:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 cell: Dict[str, Any], chips: int, seed: int, ref):
        self.model = dict(config["model"])
        self.train_cfg = dict(config["training"])
        self.ref_model = dict(self.model,
                              init_std=self.train_cfg["init_std"])
        self.traffic, self.cell = traffic, cell
        self.chips, self.seed = chips, int(seed)
        self.tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
        self.feed = Feed(traffic, self.model["vocab_size"], seed)
        self.workdir = tempfile.mkdtemp(prefix="chipbench-")
        self.ref = ref
        self.tr = None
        self.prog: Dict[str, Any] = {}

    # --- the system under test --------------------------------------------------
    def setup(self) -> None:
        from repro.dist.mesh import data_model_mesh
        from repro.launch.train import build_trainer
        from repro.models import model as model_lib
        from repro.models.config import ModelConfig
        from repro.train import optimizer as opt_lib
        from repro.train.elastic import RuntimePlan

        t, mesh = self.traffic, self.cell["mesh"]
        want_opt = {k: self.train_cfg[k] for k in (
            "lr", "beta1", "beta2", "eps", "weight_decay", "warmup_steps",
            "total_steps", "grad_clip", "schedule")}
        self.tr = tr = build_trainer(
            ModelConfig(**self.model), seq_len=t["seq_len"],
            global_batch=t["global_batch"], num_micro=t["num_micro"],
            lr=want_opt["lr"], steps=want_opt["total_steps"],
            workdir=self.workdir, checkpoint_every=10 ** 9,
            plan_fn=lambda n: RuntimePlan(n, dp=mesh["dp"], tp=mesh["tp"],
                                          num_microbatches=t["num_micro"]))
        if _program_opt(tr.opt_cfg) != want_opt:
            raise SystemExit(f"the trainer's optimizer {_program_opt(tr.opt_cfg)} "
                             f"is not the configuration's {want_opt}")
        # the benchmark's weights from the seed, drawn once, in the
        # trainer's own layout; ``build`` then finds them in place
        key = self.ref.seed_key(self.seed)
        draw = lambda k: self.ref.init_params(self.ref_model, k)  # noqa: E731
        layout = lambda f: jax.tree.map(  # noqa: E731
            lambda a: (a.shape, a.dtype), jax.eval_shape(f, key))
        if layout(lambda k: model_lib.init(tr.cfg, k)) != layout(draw):
            raise SystemExit("the trainer's parameters are not the "
                             "configuration's")
        plan = tr.plan_fn(self.chips)
        shard, opt_shard = tr._shardings(data_model_mesh(
            *plan.mesh_shape(), jax.devices()[:self.chips]))
        tr.params = jax.jit(draw, out_shardings=shard)(key)
        tr.opt_state = jax.jit(opt_lib.init_state,
                               out_shardings=opt_shard)(tr.params)
        tr.build(self.chips)
        tr.data = self.feed

        norms = jax.jit(compare.leaf_norms)
        change = jax.jit(lambda p, k: compare.leaf_norms(_minus(
            p, draw(k), shard)))
        b1 = self.train_cfg["beta1"]
        for i in range(CHECKED_STEPS):
            tr.train(1)
            if i == 0:      # m1 = (1 - beta1) g1, g1 as the optimizer got it
                self.prog["grad_norms"] = jax.tree.map(
                    lambda x: np.asarray(x) / (1.0 - b1),
                    jax.device_get(norms(tr.opt_state["m"])))
                # the gradient itself, kept on the host
                self.prog["grads"] = jax.tree.map(
                    lambda x: np.asarray(x) / (1.0 - b1),
                    jax.device_get(tr.opt_state["m"]))
        self.prog["update_norms"] = jax.device_get(change(tr.params, key))
        self.prog["losses"] = [r["loss"] for r in tr.log[:CHECKED_STEPS]]

    def window(self, seconds: float) -> Dict[str, Any]:
        """Steps the trainer until ``seconds`` have passed; the rate is over
        every whole step, from the first dispatch to the synchronised end
        of the last."""
        tr = self.tr
        n0 = len(tr.log)
        t0 = time.perf_counter()
        while True:
            tr.train(1)
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(tr.params)
        dt = time.perf_counter() - t0
        steps = len(tr.log) - n0
        losses = [r["loss"] for r in tr.log[n0:]]
        # the trainer's own time of each step (dispatch to its metrics on
        # the host), to tell a slow step from slow host work between steps
        step_s = [r["time_s"] for r in tr.log[n0:]]
        print(json.dumps(slowest(tr.log[n0:])), file=sys.stderr, flush=True)
        return {"steps": steps, "seconds": dt,
                "failed": sum(not math.isfinite(x) for x in losses),
                "train_tokens_per_s": steps * self.tokens_per_step / dt,
                "step_s_median": float(np.median(step_s)),
                "step_s_max": max(step_s),
                "between_steps_s": dt - sum(step_s)}

    def traced(self, min_seconds: float, min_steps: int) -> None:
        """Steps under the benchmark's host spans, for a profiler trace."""
        tr = self.tr
        t0, n = time.perf_counter(), 0
        with jax.profiler.TraceAnnotation("chipbench.window"):
            while n < min_steps or time.perf_counter() - t0 < min_seconds:
                with jax.profiler.TraceAnnotation(SPAN):
                    tr.train(1)
                n += 1
            jax.block_until_ready(tr.params)

    def step_hlo(self) -> str:
        """The compiled step's HLO text, whose op_name paths hold the
        program's scopes.  Lowered with the window's own shapes, so JAX's
        in-process cache hands back the executable that the window ran and
        nothing compiles.  Where something would, the persistent cache is
        off: its key leaves the scope metadata out, so it could hand back
        an executable whose HLO lacks them, or has older ones."""
        from repro.launch.compile_cache import persistent_cache_disabled
        tr = self.tr
        batch = self.feed.batch(tr.step)
        with jax.set_mesh(tr.mesh), persistent_cache_disabled():
            return tr.step_fn.lower(tr.params, tr.opt_state,
                                    batch).compile().as_text()

    def simulated(self, peak_bytes: Sequence[int], step_s: float) -> Dict:
        """What the planner's simulator predicts for this cell's plan."""
        from repro.core.cluster import heterogeneous_zone
        from repro.core.planner.plan import homogeneous_plan
        from repro.core.profiler.analytic import JobProfile, TrainJob
        from repro.core.simulator import memory as mem_lib
        from repro.core.simulator.simulate import simulate
        from repro.models.config import ModelConfig

        t, mesh = self.traffic, self.cell["mesh"]
        prof = JobProfile(TrainJob(ModelConfig(**self.model), t["seq_len"],
                                   t["global_batch"]))
        units = prof.n_partition_units
        mbs = t["global_batch"] // t["num_micro"] // mesh["dp"]
        plan = homogeneous_plan("tpu-v5e", "us-central1-a", 1, mesh["dp"],
                                mesh["tp"], units, mbs, t["global_batch"])
        res = simulate(prof, plan, heterogeneous_zone(
            {"tpu-v5e": self.chips}))
        peak = mem_lib.stage_peak_bytes(prof, 0, units, mbs, mesh["tp"], 1.0)
        return {"simulated_step_s": res.t_iter, "measured_step_s": step_s,
                "simulated_stage_peak_bytes": peak,
                "measured_peak_bytes_max": max(peak_bytes)}

    def free(self) -> None:
        tr, self.tr = self.tr, None
        tr.params = tr.opt_state = tr.step_fn = None
        del tr
        gc.collect()
        shutil.rmtree(self.workdir, ignore_errors=True)

    # --- the check ------------------------------------------------------------------
    def batches(self) -> List[Dict[str, np.ndarray]]:
        return [self.feed.batch(i) for i in range(CHECKED_STEPS)]

    def ref_layout(self):
        """The reference's own placement: on one chip as it comes; on
        several, every leaf split along its first non-layer axis that the
        chips divide, and each block of rows split over the chips."""
        devs = jax.devices()[:self.chips]
        if len(devs) == 1:
            return None, None, self.cell["block_rows"]
        mesh = Mesh(np.array(devs), ("x",))
        n = len(devs)

        def spec(shape):
            lo = 1 if len(shape) > 1 and shape[0] == self.model["n_layers"] \
                else 0
            for ax in range(lo, len(shape)):
                if shape[ax] % n == 0:
                    return NamedSharding(mesh, P(*([None] * ax + ["x"])))
            return NamedSharding(mesh, P())

        shapes = self.ref.param_shapes(self.model)
        shard = jax.tree.map(spec, shapes,
                             is_leaf=lambda s: isinstance(s, tuple))
        return shard, NamedSharding(mesh, P("x")), self.cell["block_rows"]

    def reference(self, precision: str = "highest",
                  fault: Optional[str] = None) -> Dict[str, Any]:
        """The reference's three steps, as readings.  ``fault`` plants one
        in it: ``half_batch`` (the gradient and the loss of the first half
        of the rows only) or ``no_exchange`` (the gradient of the first
        data shard's rows only, the loss of all)."""
        shard, rows_shard, block = self.ref_layout()
        t = self.traffic
        n_rows, mb = t["global_batch"], t["global_batch"] // t["num_micro"]
        rows = loss_rows = None
        if fault == "half_batch":
            rows = loss_rows = range(n_rows // 2)
        elif fault == "no_exchange":
            per = mb // self.cell["mesh"]["dp"]
            rows = [i * mb + r for i in range(t["num_micro"])
                    for r in range(per)]
            loss_rows = range(n_rows)
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        out = self.ref.run_steps(
            self.ref_model, self.train_cfg, self.seed, self.batches(),
            precision=precision, block_rows=block, sharding=shard,
            row_sharding=rows_shard, rows=rows, loss_rows=loss_rows)
        with jax.default_matmul_precision("highest"):
            norms = jax.jit(compare.leaf_norms)
            change = jax.jit(lambda p, k: compare.leaf_norms(_minus(
                p, self.ref.init_params(self.ref_model, k), shard)))
            res = {"losses": out["losses"], "grads": out["grads"],
                   "grad_norms": jax.device_get(norms(out["grads"])),
                   "update_norms": jax.device_get(change(
                       out["params"], self.ref.seed_key(self.seed)))}
        del out
        gc.collect()
        return res

    def readings(self, ref: Dict[str, Any]) -> Dict[str, float]:
        return compare.readings(self.prog, ref)
