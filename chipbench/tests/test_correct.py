"""``correct`` at a size the CPU holds: a sound run passes, and the control
and every fault a training cell can have fail, each against the cell's own
limits.

The control is the reference computed in float8 in the program's place
(the configuration states bfloat16).  The faults are planted in the
trainer's timed path underneath a whole run of the harness
(``tiny.plant``): a step that returns its state unchanged, and half of
the batch left out with the mean taken over the rest.  The exchange
between chips exists in no cell of one chip; it is planted on a 2x2 mesh
of host devices, which drives the benchmark's paths across chips.  A
token altered where it is produced is a serving fault; no cell serves.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import compare
import tiny

HERE = Path(__file__).resolve().parent
CELLS = ("qwen1.5-0.5b.train.s2048", "qwen1.5-0.5b.train.s512")


def run_tiny(fault, cell, mesh):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    dp, tp = (int(n) for n in mesh.split("x"))
    if dp * tp > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={dp * tp}"
    p = subprocess.run(
        [sys.executable, str(HERE / "tiny.py"), fault, cell, mesh,
         "--seed", "4294967301", "--seconds", "0.5", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    assert p.stderr.strip().splitlines()[-1].startswith("check ")
    return out


CASES = [(c, "1x1", f) for c in CELLS
         for f in ("none", "state_unchanged", "half_batch")] + [
    (CELLS[0], "2x2", f)
    for f in ("none", "half_batch", "no_exchange")]


@pytest.mark.parametrize("cell,mesh,fault", CASES)
def test_run_is_correct_only_without_a_fault(cell, mesh, fault):
    out = run_tiny(fault, cell, mesh)
    assert out["correct"] is (fault == "none"), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_cells_limits(cell):
    spec = tiny.tiny_spec(cell)
    r = spec["kind"].Run(spec["config"], spec["traffic"], spec["cell"],
                           1, 4294967301)
    ref = r.reference()
    values = compare.readings(r.reference(precision="fp8"), ref)
    checks = compare.check(values, spec["cell"]["limits"])
    assert not compare.passed(checks), checks
    assert compare.passed(compare.check(compare.readings(ref, ref),
                                        spec["cell"]["limits"]))
