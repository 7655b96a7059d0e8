"""A cell of the benchmark at a size the CPU runs in seconds, and a run of
the harness on it with the look for a chip skipped.

    python chipbench/tests/tiny.py <fault|none> <cell> <dp>x<tp> [run.py arguments]

runs one such run in this process and prints what ``run.main`` prints;
``fault`` plants one of ``FAULTS`` in the trainer's timed path first, and
the cell runs on a ``dp`` x ``tp`` mesh of host devices (its own mesh is
one chip; a larger one drives the benchmark's paths across chips).  The
tests start it in a subprocess, so that each mesh gets its own count of
host devices.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench  # noqa: E402

RESOLVE = bench.resolve

TINY_MODEL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
                  vocab_size=1024, head_dim=64)
TINY_SEQ = 128
FAULTS = ("none", "state_unchanged", "half_batch", "no_exchange")


def tiny_spec(workload: str, mesh: str = "1x1"):
    """The cell's own files, with the model's widths and the sequence cut
    to a CPU's size, on a ``dp`` x ``tp`` mesh; batch, limits and
    everything else as they are."""
    spec = RESOLVE(workload)
    spec["config"]["model"].update(TINY_MODEL)
    spec["traffic"] = dict(spec["traffic"], seq_len=TINY_SEQ)
    dp, tp = (int(n) for n in mesh.split("x"))
    spec["cell"] = dict(spec["cell"], mesh={"dp": dp, "tp": tp},
                        block_rows=max(spec["cell"]["block_rows"], dp * tp))
    spec["workload"] = dict(spec["workload"], chips=dp * tp)
    return spec


def cpu_devices(chips, peaks):
    import jax
    devs = jax.devices()
    assert len(devs) >= chips, (len(devs), chips)
    return devs, peaks["devices"]["TPU v5 lite"]


def plant(fault: str) -> None:
    """Break the trainer's timed path underneath the harness."""
    from repro.train import optimizer, train_step
    if fault == "state_unchanged":
        real = optimizer.apply_updates

        def unchanged(params, grads, state, cfg):
            _, _, metrics = real(params, grads, state, cfg)
            return params, state, metrics
        optimizer.apply_updates = unchanged
    elif fault == "half_batch":
        real = train_step.loss_and_grads

        def half(cfg, params, batch, mesh, micro_weights=None):
            n = batch["tokens"].shape[1] // 2
            return real(cfg, params, {k: v[:, :n] for k, v in batch.items()},
                        mesh, micro_weights)
        train_step.loss_and_grads = half
    elif fault == "no_exchange":
        real = train_step.loss_and_grads

        def local(cfg, params, batch, mesh, micro_weights=None):
            # each data shard keeps the gradient of its own rows: seen from
            # the first shard, the loss of all rows and the gradient of its
            # rows alone
            n = batch["tokens"].shape[1] // mesh.shape["data"]
            loss, _ = real(cfg, params, batch, mesh, micro_weights)
            _, grads = real(cfg, params,
                            {k: v[:, :n] for k, v in batch.items()},
                            mesh, micro_weights)
            return loss, grads
        train_step.loss_and_grads = local
    elif fault != "none":
        raise ValueError(fault)


def main(argv):
    fault, workload, mesh, rest = argv[0], argv[1], argv[2], argv[3:]
    bench.resolve = lambda name, root=bench.ROOT: tiny_spec(workload, mesh)
    plant(fault)
    return bench.main(["--workload", workload, *rest], chip_check=cpu_devices)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
