"""End-to-end collective-audit demo on a 2-zone mesh (CI's audit gate).

Compiles a small scan-over-layers training step on a (pod=2, data=2,
model=2) mesh — the 'pod' axis crosses zones — in two variants:

* **clean**: the layer activation carries its sharding constraint
  (``constrain(h, P(("pod","data"), "model"))``, sequence/activation
  parallel).  XLA emits exactly the collectives the closed-form
  prediction prices (per-layer TP all-reduces + TP-sharded DP gradient
  all-reduces) and the audit comes back empty, volumes within tolerance.
* **seeded**: that one ``constrain`` is dropped.  GSPMD then replicates
  the activation stream, the TP all-reduces vanish, and the gradient
  all-reduces grow to full (unsharded) weights across zones — the audit
  reports a ``VolumeMismatch`` on the all-reduce volume.

This is the ISSUE-8 acceptance scenario: one removed ``constrain()`` =>
nonzero findings; unmodified model => zero findings, volumes within 20%.

Usage::

    PYTHONPATH=src python -m repro.analysis.demo [--variant both]
        [--out artifacts/analysis]

Exit status is 0 iff the clean variant audits clean AND the seeded
variant produces at least one error finding.
"""
import argparse
import json
import os
import sys
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analysis import audit as audit_mod
from repro.analysis import collectives as coll_mod
from repro.analysis.findings import Report
from repro.dist import mesh as mesh_lib
from repro.dist import sharding as sh
from repro.launch.hlo import ring_traffic

BATCH, D_MODEL, D_FF, LAYERS = 16, 32, 64, 4
PODS, DP, TP = 2, 2, 2
MIN_BYTES = 64          # below the 1 KiB TP ARs, above the 4 B scalars


def _step_fn(constrained: bool):
    def loss_fn(params, x):
        def body(h, _):
            h = jax.nn.relu(h @ params["w1"]) @ params["w2"]
            if constrained:
                # activation/sequence-parallel sharding: this constraint
                # alone creates the model-axis sharding of the stream —
                # dropping it is the seeded mismatch.
                h = sh.constrain(h, P(("pod", "data"), "model"))
            return h, None
        out, _ = jax.lax.scan(body, x, None, length=LAYERS)
        return jnp.mean(out * out)

    def step(params, x):
        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                     params, grads)
        return loss, new
    return step


def compile_variant(constrained: bool) -> Tuple[str, object]:
    """(post-SPMD HLO text, mesh) of one variant of the demo step."""
    mesh = mesh_lib.pod_data_model_mesh(PODS, DP, TP)
    params = {"w1": jnp.zeros((D_MODEL, D_FF), jnp.float32),
              "w2": jnp.zeros((D_FF, D_MODEL), jnp.float32)}
    x = jnp.zeros((BATCH, D_MODEL), jnp.float32)
    repl = NamedSharding(mesh, P())
    x_shard = NamedSharding(mesh, P(("pod", "data"), None))
    step = _step_fn(constrained)
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            step,
            in_shardings=({"w1": repl, "w2": repl}, x_shard),
        ).lower(params, x).compile()
        txt = compiled.as_text()
    return txt, mesh


def predicted() -> Dict[str, float]:
    """Closed-form per-device comm of the *clean* program — the same
    Megatron accounting ``analytic.py``/``timing.py`` charge.

    With ``h`` model-sharded and ``w1`` row-sharded along the model axis,
    each layer's ``h @ w1`` produces partial sums of the *hidden*
    activation (local_batch x D_FF, f32) that one TP all-reduce combines,
    forward and again in backward.  The weight grads are scan-carried, so
    XLA syncs each layer's TP-sharded gradient contribution across the
    DP groups (pod x data) inside the loop body — LAYERS trips, not one
    step-end reduce."""
    local_hidden = (BATCH // (PODS * DP)) * D_FF * 4
    tp_traffic = 2 * LAYERS * ring_traffic("all-reduce", local_hidden, TP)
    grad_local = (D_MODEL * D_FF // TP) * 4
    dp_traffic = 2 * LAYERS * ring_traffic("all-reduce", grad_local,
                                           PODS * DP)
    return {"all-reduce": tp_traffic + dp_traffic}


def audit_variant(constrained: bool, out_dir: str) -> Report:
    txt, mesh = compile_variant(constrained)
    topo = coll_mod.DeviceTopology.from_mesh(mesh, zone_axes=("pod",),
                                             chips_per_node=4)
    tag = "demo_clean" if constrained else "demo_seeded"
    report = audit_mod.audit_hlo(txt, topo, predicted(),
                                 min_bytes=MIN_BYTES, tag=tag)
    path = report.save(out_dir)
    print(report.render())
    print(f"  -> {path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.demo",
        description="collective-audit demo: clean vs seeded-mismatch cell")
    ap.add_argument("--variant", default="both",
                    choices=["clean", "seeded", "both"])
    ap.add_argument("--out", default="artifacts/analysis")
    args = ap.parse_args(argv)
    # eight host devices; takes effect only before JAX's first backend use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    ok = True
    if args.variant in ("clean", "both"):
        clean = audit_variant(True, args.out)
        if not clean.ok or clean.findings:
            print("FAIL: clean variant should audit with zero findings")
            ok = False
        else:
            rel = clean.summary.get("rel_diff", {}).get("all-reduce")
            print(f"clean variant: 0 findings "
                  f"(all-reduce volume within {rel:.1%} of prediction)")
    if args.variant in ("seeded", "both"):
        seeded = audit_variant(False, args.out)
        kinds = seeded.by_kind()
        if not seeded.errors():
            print("FAIL: seeded variant should produce error findings")
            ok = False
        else:
            print(f"seeded variant: {json.dumps(kinds)} — the dropped "
                  f"constrain() was caught")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
