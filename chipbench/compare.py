"""The numbers that decide ``correct`` for a training cell, and their check.

Each is a gap between what the program's timed path produced in its first
three steps and what the plain reference (``reference.py``) produces from
the same seed and the same rows:

- ``loss_gap.stepN``: |program loss - reference loss| / reference loss, for
  each of the first three steps;
- ``grad_norm_gap``: the first step's gradient as the optimizer got it,
  worked out from the optimizer's first moment after one step
  (``m1 / (1 - beta1)``); by the worst leaf, |program norm - reference
  norm| over the larger of the reference's norm of that leaf and its
  median leaf's norm;
- ``update_norm_gap``: the same for the change of the parameters over the
  three steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's are left out: under Adam they move by rounding alone;
- ``grad_diff_gap``: the norm of the difference of the two first
  gradients, by the worst leaf, over the same denominator as
  ``grad_norm_gap``.

A leaf is one parameter of one layer: stacked per-layer weights are split
along their layer axis.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Leaves whose reference gradient norm is under this share of the median
# leaf's are left out of the update comparison.
NEGLIGIBLE_GRAD = 1e-3


def leaf_norms(tree: Any) -> Dict[str, jax.Array]:
    """Name -> norm of each leaf, a vector over layers for stacked leaves.
    Pure: call it under ``jax.jit``."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = x.astype(jnp.float32)
        if "layers" in name:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x),
                                         axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


@jax.jit
def diff_norms(a: Any, b: Any) -> Dict[str, jax.Array]:
    """Name -> norm of ``a - b`` of each leaf, per layer as ``leaf_norms``,
    on the device; either tree may come from the host."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def flat(norms: Dict[str, Any]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64).reshape(-1) for k, v in norms.items()}


def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
                   keep: Optional[Dict[str, np.ndarray]] = None) -> float:
    """max over leaves of |got - want| / max(want, median leaf of want)."""
    if set(got) != set(want):
        return math.inf
    w_all = np.concatenate([want[k] for k in sorted(want)])
    med = float(np.median(w_all))
    worst = 0.0
    for k in want:
        if got[k].shape != want[k].shape:
            return math.inf
        denom = np.maximum(want[k], med)
        gap = np.abs(got[k] - want[k]) / np.maximum(denom, 1e-30)
        if keep is not None:
            gap = gap[keep[k]]
        if gap.size:
            worst = max(worst, float(np.max(gap)))
    return worst if np.all([np.all(np.isfinite(g)) for g in got.values()]) \
        else math.inf


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (three floats),
    ``grad_norms`` and ``update_norms`` (name -> per-layer norms), and
    ``grads``, the first gradient whole."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_gap.step{i + 1}"] = (float(abs(a - b) / abs(b))
                                        if math.isfinite(a) else math.inf)
    g_ref = flat(ref["grad_norms"])
    med = float(np.median(np.concatenate(list(g_ref.values()))))
    out["grad_norm_gap"] = worst_leaf_gap(flat(prog["grad_norms"]), g_ref)
    diff = flat(diff_norms(prog["grads"], ref["grads"]))
    out["grad_diff_gap"] = max(
        float(np.max(diff[k] / np.maximum(g_ref[k], med)))
        for k in g_ref) if set(diff) == set(g_ref) else math.inf
    keep = {k: v >= NEGLIGIBLE_GRAD * med for k, v in g_ref.items()}
    out["update_norm_gap"] = worst_leaf_gap(flat(prog["update_norms"]),
                                            flat(ref["update_norms"]), keep)
    return out


def check(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each number the cell compares (those its limits name) beside its
    limit; a limit on a number that is not read is an error."""
    missing = set(limits) - set(values)
    if missing:
        raise KeyError(f"limits on numbers not read: {sorted(missing)}")
    return {k: {"value": float(values[k]), "limit": float(limits[k])}
            for k in sorted(limits)}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
