"""Shared test utilities."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 600):
    """Run python code in a subprocess with N host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    if p.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}")
    return p.stdout


def tiny_batch(cfg, batch=2, seq=16, seed=0, with_labels=True):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    out = {"tokens": jnp.asarray(toks[:, :seq])}
    if with_labels:
        labels = toks[:, 1:seq + 1]
        if cfg.family == "vlm":
            ign = np.full((batch, cfg.n_patches), -100, np.int32)
            labels = np.concatenate([ign, labels], axis=1)
        out["labels"] = jnp.asarray(labels)
    if cfg.family == "encdec":
        out["frames"] = jnp.asarray(
            rng.standard_normal((batch, cfg.n_frames, cfg.d_model)) * 0.02,
            jnp.float32)
    if cfg.family == "vlm":
        out["patches"] = jnp.asarray(
            rng.standard_normal((batch, cfg.n_patches, cfg.d_model)) * 0.02,
            jnp.float32)
    return out
