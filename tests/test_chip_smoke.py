"""CPU rehearsals of ``chip_smoke.py`` at reduced width, its refusal to
report from a CPU, and the compile-cache helper the entry points share."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from helpers import REPO, run_py


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reduced(**kw):
    from repro.configs import get_config
    return dataclasses.replace(get_config("opt_350m").reduced(),
                               sharding="fsdp_tp", dtype="bfloat16",
                               param_dtype="bfloat16", **kw)


def test_train_phase_reduced(tmp_path):
    params = _smoke().train_phase(_reduced(), seq_len=64, micro_batch=2,
                                  num_micro=2, workdir=tmp_path)
    assert jax.tree.leaves(params)


def test_serve_phase_reduced():
    from repro.models import model as model_lib
    cfg = _reduced(attn_impl="pallas")     # interpreted kernel in prefill
    params = model_lib.init(cfg, jax.random.PRNGKey(0))
    kernel = _smoke().serve_phase(cfg, params, n_requests=8, min_prompt=8,
                                  max_prompt=64, max_new=4)
    assert kernel is False     # lowered for the CPU: the interpreter runs it


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_main_fails_without_tpu(tmp_path, where):
    """No accelerator, or no repository beside the script: non-zero exit
    and no result line."""
    cwd = REPO
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0, p.stdout
    assert '"ok"' not in p.stdout


def _cache_dir_in_subprocess(env_dir):
    code = textwrap.dedent("""
        import json, os
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                                enable_compile_cache)
        d = enable_compile_cache()
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
        print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir,
                          "checkout": str(CHECKOUT_CACHE_DIR)}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env.update(JAX_COMPILATION_CACHE_DIR=str(env_dir),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_honours_env(tmp_path):
    got = _cache_dir_in_subprocess(tmp_path / "cache")
    assert got["dir"] == got["config"] == str(tmp_path / "cache")
    assert os.listdir(tmp_path / "cache"), "nothing was cached there"


def test_compile_cache_defaults_to_fixed_checkout_dir():
    runs = [_cache_dir_in_subprocess(None) for _ in range(2)]
    want = os.path.join(REPO, ".jax_cache")
    assert all(r["dir"] == r["config"] == r["checkout"] == want
               for r in runs), runs


@pytest.mark.slow
def test_four_chip_phases_reduced(tmp_path):
    """The ``--four-chips`` phases on four host devices: 2x2 sharded step
    against one device, resize to two, and the 2-stage x tp=2 pipeline."""
    out = run_py(f"""
        import dataclasses, importlib.util, pathlib
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config("opt_350m").reduced(),
                                  sharding="fsdp_tp", dtype="bfloat16",
                                  param_dtype="bfloat16")
        smoke.sharded_phase(cfg, seq_len=32, micro_batch=4, num_micro=2,
                            workdir=pathlib.Path({str(tmp_path)!r}))
        smoke.pipeline_phase(cfg, seq_len=32, micro_batch=2, num_micro=2)
        print("FOUR-OK")
    """, devices=4, timeout=600)
    assert "FOUR-OK" in out
