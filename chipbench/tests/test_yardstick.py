"""The benchmark's own arithmetic on the CPU: FLOP counts, the trace
reduction on a recorded v5e trace, lookup by name, and refusal without a
chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import program_trace as pt
import run as bench
import tiny
import trace_reduce as tr

BENCH = Path(bench.__file__).resolve().parent
ROOT = BENCH.parent
TRACE = BENCH / "testdata" / "v5e_qwen1.5-0.5b_s2048.json.gz"


def _model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


# --- FLOP counts -----------------------------------------------------------------------

GPT_NEO_2_7B = dict(n_layers=32, d_model=2560, n_heads=20, n_kv_heads=20,
                    head_dim=128, d_ff=10240, vocab_size=50257,
                    ffn_act="gelu", qkv_bias=False, tie_embeddings=True)
QWEN = "qwen1.5-0.5b"


@pytest.mark.parametrize("model,seq,want_params", [
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816 + 3 x 1024 + 2 x 1024)
    #   + 151936 x 1024 + 1024
    (_model(QWEN), 2048, 463_987_712),
    (_model(QWEN), 512, 463_987_712),
    # 32 x (4 x 2560^2 + 2 x 2560 x 10240 + 2 x 2560) + 50257 x 2560 + 2560
    (GPT_NEO_2_7B, 2048, 2_645_406_720),
])
def test_dense_flops_against_hand_count(model, seq, want_params):
    flops = bench.load_module(BENCH / "flops" / "dense.py")
    d, n = model["d_model"], model["n_layers"]
    assert flops.params(model) == want_params
    assert flops.flops_per_token(model, seq) == \
        6 * want_params + 12 * n * d * seq


def test_flops_per_token_values():
    flops = bench.load_module(BENCH / "flops" / "dense.py")
    m = _model(QWEN)
    assert flops.flops_per_token(m, 2048) == pytest.approx(3.388e9, rel=1e-3)
    assert flops.flops_per_token(m, 512) == pytest.approx(2.935e9, rel=1e-3)
    assert flops.flops_per_token(GPT_NEO_2_7B, 2048) == \
        pytest.approx(17.89e9, rel=1e-3)


def test_attention_kernels_against_hand_count():
    # one microbatch of s2048: 2 rows x 16 heads, S 2048, d 64, bfloat16
    flops = bench.load_module(BENCH / "flops" / "dense.py")
    traffic = json.loads((BENCH / "traffic" / "train.s2048.json").read_text())
    got = flops.attention_kernels(_model(QWEN), traffic)
    bh, s, d = 2 * 16, 2048, 64
    tri = bh * s * s * d // 2                # half the scores, per S^2 d
    act, row = bh * s * d * 2, bh * s * 4    # a bf16 (BH, S, d), an f32 row
    assert tri == 4_294_967_296
    assert got == {
        "fwd": {"flops": 4 * tri, "bytes": 4 * act + row},
        "dq": {"flops": 6 * tri, "bytes": 5 * act + 2 * row},
        "dkv": {"flops": 8 * tri, "bytes": 6 * act + 2 * row}}
    assert got["fwd"]["flops"] == 17_179_869_184
    assert got["dkv"]["bytes"] == 50_855_936
    # 192 forward calls (forward and recompute), 96 dQ and 96 dK/dV a step
    step = (192 * got["fwd"]["flops"] + 96 * got["dq"]["flops"]
            + 96 * got["dkv"]["flops"])
    assert step == pytest.approx(9.07e12, rel=1e-3)


# --- trace reduction -------------------------------------------------------------------

def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.length([(0, 2), (1, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5), (8, 10)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_enclosing_events_are_left_out():
    evs = [["while.1", 0, 100], ["fusion.2", 10, 20], ["fusion.3", 40, 10],
           ["copy.4", 120, 5], ["empty", 130, 0]]
    assert tr.leaf_events(evs) == [["fusion.2", 10, 20], ["fusion.3", 40, 10],
                                   ["copy.4", 120, 5]]


def test_reduction_of_a_synthetic_trace():
    red = tr.reduced(
        {"TPU:0": [["fusion.1", 0, 40], ["all-gather-start.2", 40, 20],
                   ["fusion.1", 70, 10]],
         "TPU:1": [["fusion.1", 0, 50], ["all-reduce.3", 30, 40]]},
        [["chipbench.window", 0, 100], ["chipbench.train_call", 0, 60]])
    assert tr.window_s(red) == pytest.approx(100e-9)
    assert tr.busy_s(red) == pytest.approx({"TPU:0": 70e-9, "TPU:1": 70e-9})
    # device 1's all-reduce overlaps its fusion for 20 ns of its 40
    assert tr.collective_exposed_s(red) == pytest.approx(
        {"TPU:0": 20e-9, "TPU:1": 20e-9})
    top = dict(tr.top_ops(red))
    assert top["fusion.1"] == pytest.approx((50 + 50) / 2 * 1e-9)
    gaps = pt.idle_gaps(red)
    assert gaps[0] == ["chipbench.window", pytest.approx(30e-9)]
    assert {g[0] for g in gaps} <= {"chipbench.window",
                                    "chipbench.train_call"}


@pytest.fixture(scope="module")
def recorded():
    if not TRACE.exists():
        pytest.fail(f"{TRACE} is missing")
    return tr.load_reduced(str(TRACE))


def test_recorded_trace_reduction(recorded):
    red = recorded
    assert list(red["devices"]) == ["TPU:0"]
    assert {s[0] for s in red["spans"]} == {"chipbench.window",
                                           "chipbench.train_call"}
    win = tr.window_s(red)
    busy = tr.busy_s(red)["TPU:0"]
    assert 0 < busy <= win
    ops = [(n, s, s + d) for n, s, d in red["devices"]["TPU:0"]]
    lo, hi = red["window"]
    # busy time is the union of the op intervals, which never exceeds
    # their sum and is at least the longest op
    total = sum(min(e, hi) - max(s, lo) for _, s, e in ops
                if e > lo and s < hi) * 1e-9
    assert busy <= total * (1 + 1e-12)
    assert busy >= max(d for _, _, d in red["devices"]["TPU:0"]) * 1e-9 * 0.99
    top = tr.top_ops(red)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert sum(t for _, t in tr.top_ops(red, n=10 ** 6)) == \
        pytest.approx(total, rel=1e-9)
    # one chip: no collective runs, so nothing to read
    assert tr.collective_exposed_s(red) == {}
    gaps = pt.idle_gaps(red)
    assert all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in pt.idle_gaps(red, n=10 ** 6)) == \
        pytest.approx(win - busy, rel=1e-6)


def test_metric_readers_on_recorded_trace(recorded):
    spec = bench.resolve(QWEN + ".train.s512")
    ctx = {"trace": recorded, "chips": 1, "result": {"train_tokens_per_s": 1e4},
           "peak": {"bf16_flops_per_s": 197e12}, "config": spec["config"],
           "traffic": spec["traffic"], "flops": spec["flops"]}
    read = lambda n: bench.load_module(BENCH / "metrics" / f"{n}.py").read(ctx)
    idle = read("device_idle_share.train")
    assert 0 <= idle < 100
    busy = tr.busy_s(recorded)["TPU:0"]
    assert idle == pytest.approx(100 * (1 - busy / tr.window_s(recorded)))
    per_token = spec["flops"].flops_per_token(_model(QWEN), 512)
    assert per_token == pytest.approx(2.935e9, rel=1e-3)
    assert read("mfu.train") == pytest.approx(100 * per_token * 1e4 / 197e12)


# --- lookup by name ---------------------------------------------------------------------

def test_every_named_file_is_found():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        spec = bench.resolve(w["name"])
        assert spec["workload"] == w
        assert {m["name"] for m in spec["per_layer"]} <= \
            {m["name"] for m in b["per_layer"]}
        assert set(spec["cell"]["limits"]) == {
            "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
            "grad_norm_gap", "grad_diff_gap", "update_norm_gap"}


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(LookupError, match="no workload named"):
        bench.resolve("no-such-cell")
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["per_layer"].append(dict(b["per_layer"][0], name="no_such_metric"))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(LookupError, match="no_such_metric"):
        bench.resolve(b["workloads"][0]["name"], root=root)
    b["per_layer"].pop()
    b["workloads"][0]["traffic"] = "no_such_traffic"
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(LookupError, match="no_such_traffic"):
        bench.resolve(b["workloads"][0]["name"], root=root)


def test_resolve_gives_the_configurations_own_modules():
    spec = bench.resolve(QWEN + ".train.s2048")
    assert spec["config"]["reference"] == "chipbench/reference.py"
    assert spec["config"]["flops"] == "chipbench/flops/dense.py"
    assert Path(spec["reference"].__file__) == BENCH / "reference.py"
    assert Path(spec["flops"].__file__) == BENCH / "flops" / "dense.py"
    for f in ("seed_key", "init_params", "param_shapes", "run_steps"):
        assert callable(getattr(spec["reference"], f))
    assert spec["flops"].flops_per_token(spec["config"]["model"], 2048) == \
        pytest.approx(3.388e9, rel=1e-3)


def _checkout(tmp_path):
    """A copy of the benchmark's files, with the configuration's file at
    hand to change."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "chipbench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root, root / "chipbench" / "configs" / f"{QWEN}.json"


@pytest.mark.parametrize("key", ["reference", "flops"])
@pytest.mark.parametrize("how", ["no key", "no file"])
def test_a_missing_reference_or_flops_is_an_error(tmp_path, key, how):
    root, cfg_file = _checkout(tmp_path)
    cfg = json.loads(cfg_file.read_text())
    if how == "no key":
        del cfg[key]
    else:
        cfg[key] = "chipbench/no_such_module.py"
    cfg_file.write_text(json.dumps(cfg))
    with pytest.raises(LookupError, match=(
            repr(key) if how == "no key" else "no_such_module.py")):
        bench.resolve(QWEN + ".train.s512", root=root)


def test_a_second_configuration_needs_only_new_files(tmp_path):
    # a second architecture, as a later PR would bring it: its own
    # configuration file naming its own reference (here one with learned
    # positions, whose parameters the program does not have), its own cell,
    # and entries in BENCHMARK.json; no file that is there changes
    root, cfg_file = _checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    ref = (BENCH / "reference.py").read_text()
    assert ref.count('"ln_f": (d,)') == 1
    (root / "chipbench" / "reference_other.py").write_text(ref.replace(
        '"ln_f": (d,)', '"ln_f": (d,), "wpe": (2048, d)'))
    cfg = json.loads(cfg_file.read_text())
    cfg.update(name="other", reference="chipbench/reference_other.py")
    cfg["model"].update(tiny.TINY_MODEL)
    (root / "chipbench" / "configs" / "other.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "cells" / "other.train.s512.json").write_bytes(
        (BENCH / "cells" / f"{QWEN}.train.s512.json").read_bytes())
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append(dict(b["configs"][0], name="other",
                             file="chipbench/configs/other.json"))
    b["workloads"].append(dict(b["workloads"][1], name="other.train.s512",
                               config="other"))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == data for p, data in before.items())

    spec = bench.resolve("other.train.s512", root=root)
    assert Path(spec["reference"].__file__).name == "reference_other.py"
    assert "wpe" in spec["reference"].param_shapes(spec["config"]["model"])
    spec["traffic"] = dict(spec["traffic"], seq_len=tiny.TINY_SEQ)
    run = bench.make_run(spec, 4294967301)
    try:
        with pytest.raises(SystemExit, match="the trainer's parameters are "
                           "not the configuration's"):
            run.setup()
    finally:
        shutil.rmtree(run.workdir, ignore_errors=True)


def test_the_slowest_window_step_and_its_compiles():
    kind = bench.load_module(BENCH / "kinds" / "train.py")
    rec = lambda step, t, c, g: {  # noqa: E731
        "step": step, "time_s": t, "data_s": 0.001, "dispatch_s": 0.002,
        "sync_s": t - 0.002, "compiles": c, "compile_s": 0.1 * c,
        "gc_collections": g, "gc_s": 0.01 * g, "loss": 1.0}
    got = kind.slowest([rec(3, 0.5, 0, 1), rec(4, 2.3, 1, 0),
                        rec(5, 0.5, 0, 2)])
    assert got["window_compiles"] == 1
    assert got["window_gc_collections"] == 3
    assert got["slowest_step"] == {k: v for k, v in rec(4, 2.3, 1, 0).items()
                                   if k != "loss"}


# --- refusal without a chip -------------------------------------------------------------

def _run(cwd, env_extra=None, *args):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"))}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         QWEN + ".train.s2048", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_numbers():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_exits_nonzero_with_no_numbers(monkeypatch,
                                                           capsys):
    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    spec = bench.resolve(QWEN + ".train.s2048")
    with pytest.raises(SystemExit, match="not in peaks.json"):
        bench.device_check(1, spec["peaks"])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        Dev.device_kind = "TPU v5 lite"
        bench.device_check(4, spec["peaks"])
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
