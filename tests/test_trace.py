"""The program's own tracing: named scopes in the compiled train step, and
the phases and counters that each ElasticTrainer step records."""
import dataclasses
import gc
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.dist import mesh as mesh_lib
from repro.models import model as model_lib
from repro.telemetry import trace
from repro.telemetry.bus import TelemetryBus
from repro.train import data as data_lib
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts_lib
from repro.train.elastic import ElasticTrainer, RuntimePlan

# the matmuls under no scope: the q/k/v and the output projections
REST_DOTS = ("bsd,dhk->bshk", "bshk,hkd->bsd")


def _phase(path):
    if "rematted_computation" in path:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


@pytest.fixture(scope="module")
def step_hlo():
    """The compiled train step of a Qwen-architecture model at a CPU's
    size: q/k/v bias, SwiGLU, tied head, full remat, bfloat16."""
    cfg = dataclasses.replace(get_config("qwen1_5_0_5b").reduced(),
                              remat="full", dtype="bfloat16",
                              param_dtype="bfloat16")
    assert cfg.qkv_bias and cfg.ffn_act == "swiglu" and cfg.tie_embeddings
    mesh = mesh_lib.data_model_mesh(1, 1, jax.devices()[:1])
    step = ts_lib.jit_train_step(cfg, opt_lib.OptimizerConfig(), mesh, 2, 2)
    params = jax.eval_shape(lambda k: model_lib.init(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(opt_lib.init_state, params)
    tok = jax.ShapeDtypeStruct((2, 2, 32), jnp.int32)
    with jax.set_mesh(mesh):
        return step.lower(params, opt, {"tokens": tok, "labels": tok}
                          ).compile().as_text()


def _paths(hlo, op):
    out = []
    for line in hlo.splitlines():
        if f" {op}(" in line:
            m = re.search(r'op_name="([^"]*)"', line)
            out.append(m.group(1) if m else line)
    return out


def test_every_dot_is_under_a_scope_or_a_projection(step_hlo):
    dots = _paths(step_hlo, "dot")
    assert dots
    for p in dots:
        assert any(s in p for s in trace.SCOPES) or \
            any(r in p for r in REST_DOTS), p
    for s in (trace.ATTENTION, trace.MLP, trace.HEAD):
        assert any(s in p for p in dots), s
    paths = re.findall(r'op_name="([^"]*)"', step_hlo)
    for s in (trace.LOSS, trace.OPTIMIZER):
        assert any(s in p for p in paths), s


def test_attention_phases_are_told_apart(step_hlo):
    attn = [p for p in _paths(step_hlo, "dot") if trace.ATTENTION in p]
    assert {_phase(p) for p in attn} == {"forward", "recompute", "backward"}


def _trainer(tmp_path, telemetry=None):
    cfg = get_config("smollm_360m").reduced()
    data_cfg = data_lib.DataConfig(seq_len=16, global_batch=4)
    opt_cfg = opt_lib.OptimizerConfig(lr=1e-3, warmup_steps=2,
                                      total_steps=30)
    tr = ElasticTrainer(cfg, opt_cfg, data_cfg, workdir=str(tmp_path),
                        checkpoint_every=100, telemetry=telemetry,
                        plan_fn=lambda n: RuntimePlan(1, 1, 1, 1))
    tr.build(1)
    return tr


def test_each_step_records_its_phases_and_counters(tmp_path):
    bus = TelemetryBus()
    tr = _trainer(tmp_path, bus)
    log = tr.train(3)
    for r in log:
        assert r["time_s"] == r["dispatch_s"] + r["sync_s"]
        assert min(r["data_s"], r["dispatch_s"], r["sync_s"]) > 0
        assert r["gc_collections"] >= 0 and r["gc_s"] >= 0
    # the first step traces and compiles the step; the same shapes later
    # compile nothing
    assert log[0]["compiles"] > 0 and log[0]["compile_s"] > 0
    assert [(r["compiles"], r["compile_s"]) for r in log[1:]] == \
        [(0, 0.0), (0, 0.0)]
    # the bus and the record read the same clock reads
    assert bus.values("step_time") == [r["time_s"] for r in log]
    assert bus.values("data_stall") == [r["data_s"] for r in log]


def test_a_collection_inside_a_step_is_counted(tmp_path):
    tr = _trainer(tmp_path)
    tr.train(1)
    batch = tr.data.batch

    def collecting(step):
        gc.collect()
        return batch(step)
    tr.data.batch = collecting
    rec = tr.train(1)[-1]
    assert rec["gc_collections"] >= 1 and 0 < rec["gc_s"] <= rec["data_s"]


def test_a_collection_after_the_record_is_counted_in_its_step(tmp_path):
    tr = _trainer(tmp_path)
    tr.train(1)
    emit = tr._emit_telemetry

    def collecting(step_s, data_s):
        emit(step_s, data_s)
        gc.collect()
    tr._emit_telemetry = collecting
    rec = tr.train(1)[-1]
    assert rec["gc_collections"] >= 1 and rec["gc_s"] > 0


def test_reconfig_time_is_its_span(tmp_path):
    tr = _trainer(tmp_path)
    tr.train(2, events=[(1, 1, False)])
    (r,) = tr.reconfigs
    assert r["kind"] == "kill-free" and r["reconfig_s"] > 0
    # the rebuilt step compiles again on its first dispatch
    assert tr.log[1]["compiles"] > 0
