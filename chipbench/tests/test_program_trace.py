"""The program's names in a trace: the HLO scope map, the program's host
spans under the profiler on the CPU, and what the readers give on the
recorded traces."""
from __future__ import annotations

import glob
import re
from pathlib import Path

import pytest

import program_trace as pt
import trace_reduce as tr

BENCH = Path(pt.__file__).resolve().parent
OLD_TRACE = BENCH / "testdata" / "v5e_qwen1.5-0.5b_s2048.json.gz"


# --- the scope map -------------------------------------------------------------------

HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.3 (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %exponential.1 = bf16[8,8]{1,0} exponential(%param_0), metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/repro.attention/exp"}
  ROOT %bitcast.2 = bf16[8,8]{1,0} bitcast(%exponential.1)
}

ENTRY %main.9 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %fusion.591 = bf16[8,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.3
  %dot.4 = bf16[8,8]{1,0} dot(%fusion.591, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/transpose(jvp(repro.head))/dot_general"}
  ROOT %copy.5 = bf16[8,8]{1,0} copy(%dot.4)
}
"""


def test_hlo_scopes_follow_roots_and_operands():
    sc = pt.hlo_scopes(HLO)
    # a fusion without metadata takes its root's, which a bitcast takes
    # from its operand
    assert pt.scope(sc["fusion.591"]) == "repro.attention"
    assert pt.phase(sc["fusion.591"]) == "recompute"
    assert pt.scope(sc["dot.4"]) == "repro.head"
    assert pt.phase(sc["dot.4"]) == "backward"
    assert pt.scope(sc["copy.5"]) == "repro.head"
    assert "p0" not in sc
    assert pt.scope(None) == "rest" and pt.phase("jit(f)/jvp()/add") == \
        "forward"


# --- the program's spans under the profiler ---------------------------------------

def test_program_spans_reach_the_reduction_inside_the_benchmarks(tmp_path):
    import jax
    from repro.configs import get_config
    from repro.train import data as data_lib
    from repro.train import optimizer as opt_lib
    from repro.train.elastic import ElasticTrainer, RuntimePlan

    trainer = ElasticTrainer(
        get_config("smollm_360m").reduced(),
        opt_lib.OptimizerConfig(total_steps=10),
        data_lib.DataConfig(seq_len=16, global_batch=4),
        workdir=str(tmp_path / "w"), checkpoint_every=100,
        plan_fn=lambda n: RuntimePlan(1, 1, 1, 1))
    trainer.build(1)
    trainer.train(1)
    jax.profiler.start_trace(str(tmp_path / "t"))
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation(pt.TRAIN_CALL):
                    trainer.train(1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "t" / "**" / "*.xplane.pb"),
                        recursive=True)
    red = pt.load(path)
    # what trace_reduce keeps is as it was
    assert {k: red[k] for k in ("devices", "spans", "window")} == \
        tr.load(path)
    assert {s[0] for s in red["spans"]} == {"chipbench.window",
                                           pt.TRAIN_CALL}
    calls = [(s, s + d) for n, s, d in red["spans"] if n == pt.TRAIN_CALL]
    names = [n for n, _, _ in red["program_spans"]]
    for n in ("repro.train.step", "repro.train.data", "repro.train.dispatch",
              "repro.train.sync", "repro.train.log"):
        assert names.count(n) == 2, (n, names)
    for _, s, d in red["program_spans"]:
        assert any(lo <= s and s + d <= hi for lo, hi in calls)
    assert 0 < pt.span_s(red, pt.DATA_SPAN) < tr.window_s(red)
    assert 0 < pt.METRICS["data_wait_share.train"](red) < 100


def test_a_gap_between_steps_is_cut_at_the_programs_spans():
    # the device idles from the end of step A's work to the start of step
    # B's; the gap's middle falls between call B's start and its step's
    red = {"devices": {"TPU:0": [["op.a", 0, 100], ["op.b", 200, 100]]},
           "window": [0, 300],
           "spans": [["chipbench.window", 0, 300],
                     [pt.TRAIN_CALL, 0, 149], [pt.TRAIN_CALL, 149, 151]],
           "program_spans": [
               ["repro.train.step", 1, 147], ["repro.train.sync", 50, 70],
               ["repro.train.log", 122, 26], ["repro.train.step", 151, 149],
               ["repro.train.data", 152, 28],
               ["repro.train.dispatch", 182, 13]]}
    ((name, t),) = tr.idle_gaps(red)
    assert name == pt.TRAIN_CALL and t == pytest.approx(100e-9)
    gaps = pt.idle_gaps(red, n=10 ** 6)
    assert sum(t for _, t in gaps) == pytest.approx(100e-9)
    assert [n for n, _ in gaps[:4]] == [
        "repro.train.data", "repro.train.log", "repro.train.sync",
        "repro.train.dispatch"]
    assert [t for _, t in gaps[:4]] == pytest.approx([28e-9, 26e-9, 20e-9,
                                                      13e-9])


# --- a reduced trace from before the program named its work --------------------------

@pytest.fixture(scope="module")
def old():
    return tr.load_reduced(str(OLD_TRACE))


def test_an_unscoped_trace_reads_as_it_did(old):
    assert {m: f(old) for m, f in pt.METRICS.items()} == \
        dict.fromkeys(pt.METRICS)
    assert pt.idle_gaps(old) == tr.idle_gaps(old)
    assert pt.top_ops(old) == [[f"{n} rest", t] for n, t in tr.top_ops(old)]
    assert pt.steps(old) == 3


# --- a scoped trace: Qwen1.5-0.5B, seq 512, one v5e, five steps -----------------------

SCOPED_TRACE = BENCH / "testdata" / "v5e_qwen1.5-0.5b_s512_scoped.json.gz"
# what ``program_trace.py`` printed for the run that recorded it
PRINTED = {"attention_ms.train": 57.429545000000005,
           "mlp_ms.train": 141.39113840000002,
           "head_loss_ms.train": 121.8652944,
           "optimizer_ms.train": 18.7410792,
           "recompute_ms.train": 58.187105599999995,
           "data_wait_share.train": 0.3479252984092945}


@pytest.fixture(scope="module")
def scoped():
    return tr.load_reduced(str(SCOPED_TRACE))


def test_readers_give_what_the_run_printed(scoped):
    assert pt.steps(scoped) == 5
    assert {m: f(scoped) for m, f in pt.METRICS.items()} == \
        pytest.approx(PRINTED, rel=1e-12)


def test_scopes_and_rest_add_up_to_busy_time(scoped):
    paths = scoped["scopes"]
    assert all(sum(s in p for s in pt.SCOPES) == 1 or pt.scope(p) == "rest"
               for p in paths.values())
    ops = tr.top_ops(scoped, n=10 ** 6)
    rest = sum(t for n, t in ops if pt.scope(paths.get(n)) == "rest")
    five = sum(pt.scope_s(scoped, re.escape(s)) for s in pt.SCOPES)
    busy = tr.busy_s(scoped)["TPU:0"]
    assert five + rest == pytest.approx(busy, rel=1e-6)
    assert 0 < rest < five
    # the breakdown names each op's scope and phase
    top = pt.top_ops(scoped)
    assert top[0][0] == "fusion.517 repro.head/backward"
    assert [t for _, t in top] == [t for _, t in tr.top_ops(scoped)]


def test_an_unscoped_build_of_the_step_reads_nothing(scoped):
    # the same ops, mapped through the HLO of a build without the scopes
    bare = dict(scoped, scopes={
        n: re.sub(r"repro\.\w+", "", p) for n, p in scoped["scopes"].items()})
    assert not pt.scoped(bare) and pt.scoped(scoped)
    got = {m: f(bare) for m, f in pt.METRICS.items()}
    wait = got.pop("data_wait_share.train")
    assert got == dict.fromkeys(got)
    # the host spans need no scopes
    assert wait == pytest.approx(PRINTED["data_wait_share.train"], rel=1e-12)


def test_no_gap_inside_a_trainer_call_is_left_unnamed(scoped):
    gaps = pt.idle_gaps(scoped, n=10 ** 6)
    assert sum(t for _, t in gaps) == pytest.approx(
        tr.window_s(scoped) - tr.busy_s(scoped)["TPU:0"], rel=1e-6)
    long_ = [name for name, t in gaps if t > 1e-3]
    assert long_ and all(n.startswith(pt.PROGRAM_PREFIX) for n in long_)
