"""Mesh factory shared by the launcher, the elastic trainer, and tests.

All meshes in the repo use the same axis vocabulary (DESIGN.md §4):
  'pod'   slow/DCN domain (multi-pod only)
  'data'  data parallelism (+ parameter fsdp under the fsdp_tp policy)
  'model' tensor parallelism

Helpers take explicit sizes so planner output (dp, tp[, pods]) maps 1:1
onto a mesh; devices default to ``jax.devices()`` prefix order, which is
also the contract the MPMD pipeline uses to carve disjoint per-stage
device sets.  Every axis is ``AxisType.Auto``: the sharding code relies on
XLA's propagation, and ``jax.make_mesh`` would otherwise make the axes
Explicit.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def named_mesh(shape: Sequence[int], axes: Sequence[str],
               devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over the first ``prod(shape)`` devices (or the given ones).

    Built via ``jax.make_mesh`` so device assignment is topology-aware
    (the trailing 'model' axis lands on ICI-adjacent devices on real
    hardware) rather than a naive prefix reshape.
    """
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()[:n]
    devices = list(np.asarray(devices).reshape(-1))
    if len(devices) != n:
        raise ValueError(f"need {n} devices for mesh {tuple(shape)}, "
                         f"got {len(devices)}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def data_model_mesh(dp: int, tp: int,
                    devices: Optional[Sequence] = None) -> Mesh:
    """The workhorse 2-D ('data', 'model') mesh."""
    return named_mesh((dp, tp), ("data", "model"), devices)


def pod_data_model_mesh(pods: int, dp: int, tp: int,
                        devices: Optional[Sequence] = None) -> Mesh:
    """3-D multi-pod mesh; 'pod' is the DCN-crossing (slow) axis."""
    return named_mesh((pods, dp, tp), ("pod", "data", "model"), devices)
