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
from run import load_module, resolve

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


# a step's flash kernels, as the v5e's compiler writes them (bodies cut)
KERNEL_HLO = """\
ENTRY %main.9 (p0: bf16[32,2048,64]) -> bf16[32,2048,64] {
  %p0 = bf16[32,2048,64]{2,1,0} parameter(0)
  %flash_attention_fwd.19 = (bf16[32,2048,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[32,1,2048]{2,1,0:T(1,128)S(1)}) custom-call(%p0, %p0, %p0), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32,2048,64]{2,1,0}, bf16[32,2048,64]{2,1,0}, bf16[32,2048,64]{2,1,0}}, metadata={op_name="jit(train_step)/jvp(repro.attention)/jit(flash_attention_fwd)/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  %flash_attention_bwd.20 = (bf16[32,2048,64]{2,1,0:T(8,128)(2,1)S(1)}, bf16[32,2048,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(%p0, %p0, %p0, %p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(repro.attention))/jit(flash_attention_bwd)/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  %flash_attention_bwd.21 = bf16[32,2048,64]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%p0, %p0, %p0, %p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(repro.attention))/jit(flash_attention_bwd)/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  %custom-call.1 = bf16[32,2048,64]{1,2,0} custom-call(%p0), custom_call_target="ConcatBitcast"
  ROOT %copy.5 = bf16[32,2048,64]{2,1,0} copy(%flash_attention_bwd.21)
}
"""


def test_hlo_kernels_give_each_mosaic_calls_outputs():
    got = pt.hlo_kernels(KERNEL_HLO)
    assert got == {"flash_attention_fwd.19": [[32, 2048, 64], [32, 1, 2048]],
                   "flash_attention_bwd.20": [[32, 2048, 64], [32, 2048, 64]],
                   "flash_attention_bwd.21": [[32, 2048, 64]]}
    sc = pt.hlo_scopes(KERNEL_HLO)
    assert {pt.scope(sc[n]) for n in got} == {"repro.attention"}
    assert [pt.phase(sc[n]) for n in sorted(got)] == [
        "backward", "backward", "forward"]


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
    assert set(red) == {"devices", "spans", "window", "program_spans",
                        "scopes", "kernels"}
    assert red["window"] == [red["spans"][0][1],
                             max(s + d for _, s, d in red["spans"])]
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
    assert 0 < pt.data_wait_share(red) < 100


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
    # uncut, the whole gap takes the name of the call its middle falls in
    ((name, t),) = pt.idle_gaps(dict(red, program_spans=[]))
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
    assert _read_six(old, "qwen1.5-0.5b.train.s2048") == dict.fromkeys(SIX)
    # with none of the program's spans, gaps are cut nowhere and named by
    # the benchmark's
    gaps = pt.idle_gaps(old, n=10 ** 6)
    assert {n for n, _ in gaps} <= {"chipbench.window", pt.TRAIN_CALL}
    assert sum(t for _, t in gaps) == pytest.approx(
        tr.window_s(old) - tr.busy_s(old)["TPU:0"], rel=1e-6)
    assert pt.top_ops(old) == [[f"{n} rest", t] for n, t in tr.top_ops(old)]
    assert pt.steps(old) == 3


# --- scoped traces: Qwen1.5-0.5B on one v5e, seq 512 and seq 2048 -----------------

SCOPED_TRACE = BENCH / "testdata" / "v5e_qwen1.5-0.5b_s512_scoped.json.gz"
KERNEL_TRACE = BENCH / "testdata" / "v5e_qwen1.5-0.5b_s2048_scoped.json.gz"
# what the runs that recorded them printed: s512, five steps on XLA
# attention; s2048, four steps on the flash kernels
PRINTED = {"attention_ms.train": 57.429545000000005,
           "mlp_ms.train": 141.39113840000002,
           "head_loss_ms.train": 121.8652944,
           "optimizer_ms.train": 18.7410792,
           "recompute_ms.train": 58.187105599999995,
           "data_wait_share.train": 0.3479252984092945}
PRINTED_S2048 = {"attention_ms.train": 178.3453495,
                 "mlp_ms.train": 144.1204025,
                 "head_loss_ms.train": 125.27886525000001,
                 "optimizer_ms.train": 18.7402595,
                 "recompute_ms.train": 84.863443,
                 "data_wait_share.train": 0.27346859813900865,
                 "flash_attention_roofline.train": 27.912040879185035}


@pytest.fixture(scope="module")
def scoped():
    return tr.load_reduced(str(SCOPED_TRACE))


@pytest.fixture(scope="module")
def kernel_trace():
    return tr.load_reduced(str(KERNEL_TRACE))


SIX = sorted(PRINTED)


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def _ctx(red, cell):
    spec = resolve(cell)
    return {"trace": red, "result": {}, "chips": 1, "config": spec["config"],
            "traffic": spec["traffic"], "cell": spec["cell"],
            "flops": spec["flops"],
            "peak": spec["peaks"]["devices"]["TPU v5 lite"]}


def _read_six(red, cell):
    ctx = _ctx(red, cell)
    return {m: _reader(m)(ctx) for m in SIX}


def test_readers_give_what_the_run_printed(scoped):
    assert pt.steps(scoped) == 5
    assert _read_six(scoped, "qwen1.5-0.5b.train.s512") == \
        pytest.approx(PRINTED, rel=1e-12)


@pytest.mark.parametrize("cell", ["s512", "s2048"])
@pytest.mark.parametrize("name", sorted(PRINTED))
def test_each_scope_reader_gives_what_the_run_printed(scoped, kernel_trace,
                                                      name, cell):
    red, printed = ((scoped, PRINTED) if cell == "s512"
                    else (kernel_trace, PRINTED_S2048))
    got = _reader(name)(_ctx(red, f"qwen1.5-0.5b.train.{cell}"))
    assert got == pytest.approx(printed[name], rel=1e-12)


def test_roofline_of_the_recorded_kernels(kernel_trace):
    got = _reader("flash_attention_roofline.train")(
        _ctx(kernel_trace, "qwen1.5-0.5b.train.s2048"))
    assert pt.steps(kernel_trace) == 4
    assert got["value"] == pytest.approx(
        PRINTED_S2048["flash_attention_roofline.train"], rel=1e-12)
    # head dim 64 fills half the MXU's 128 lanes: about 50% at most
    assert 0 < got["value"] < 50
    assert got["bound"] == "compute"
    k = got["kernels"]
    # 24 layers x 4 microbatches a step; the forward runs again in remat
    assert {n: v["calls_per_step"] for n, v in k.items()} == {
        "fwd": 192, "dq": 96, "dkv": 96}
    # the two-output backward kernel is dK/dV: it does 8/6 of dQ's work
    # and takes longer
    assert k["dkv"]["device_ms_per_step"] > 1.2 * k["dq"]["device_ms_per_step"]
    assert all(v["bound"] == "compute" and 0 < v["roofline_share"] < 50
               for v in k.values())


def _roofline(red):
    return _reader("flash_attention_roofline.train")(
        _ctx(red, "qwen1.5-0.5b.train.s2048"))


def _kernels(red, f):
    return dict(red, kernels={n: f(n, out)
                              for n, out in red["kernels"].items()})


@pytest.mark.parametrize("shape", [[64, 2048, 64], [32, 1024, 64],
                                   [32, 2048, 128]])
def test_roofline_refuses_a_kernel_call_of_another_size(kernel_trace, shape):
    red = _kernels(kernel_trace, lambda n, out: [shape, *out[1:]])
    got = _roofline(red)
    assert got["value"] is None
    assert "not the call of 32 rows x heads of 2048 positions of width 64" \
        in got["note"]


def test_roofline_takes_any_layout_of_the_same_call(kernel_trace):
    # (rows, S, heads, d) tiles hold the same call as (rows x heads, S, d)
    red = _kernels(kernel_trace, lambda n, out: [[2, 2048, 16, 64], *out[1:]])
    assert _roofline(red) == _roofline(kernel_trace)


def test_roofline_reads_nothing_for_a_kernel_it_does_not_know(kernel_trace):
    # a backward kernel with three outputs (dQ, dK and dV fused)
    red = _kernels(kernel_trace, lambda n, out: out + [out[0]] * (
        n.startswith("flash_attention_bwd") and len(out) == 2))
    got = _roofline(red)
    assert got["value"] is None and "(3 outputs)" in got["note"]
    # a renamed kernel under repro.attention
    (fwd, *_) = sorted(n for n in kernel_trace["kernels"] if "fwd" in n)
    renamed = dict(
        kernel_trace,
        kernels={("splash_fwd.1" if n == fwd else n): out
                 for n, out in kernel_trace["kernels"].items()},
        scopes={("splash_fwd.1" if n == fwd else n): p
                for n, p in kernel_trace["scopes"].items()})
    got = _roofline(renamed)
    assert got["value"] is None and "splash_fwd.1 (2 outputs)" in got["note"]


def test_no_kernel_no_roofline(scoped, old):
    # s512 trains on XLA attention: no kernel op ran
    read = _reader("flash_attention_roofline.train")
    assert not scoped.get("kernels")
    assert read(_ctx(scoped, "qwen1.5-0.5b.train.s512")) is None
    assert read(_ctx(old, "qwen1.5-0.5b.train.s2048")) is None


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
    got = _read_six(bare, "qwen1.5-0.5b.train.s512")
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
