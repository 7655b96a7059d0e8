"""Production mesh definition for the multi-pod dry-run.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before JAX's
first backend use and only then builds meshes.

Single pod:  (16, 16)    -> ("data", "model")     = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16) -> ("pod", "data", "model") = 512 chips, the 'pod'
axis crossing DCN.  Batch shards over ('pod','data') by default; the
pipeline hillclimb maps PP onto 'pod' instead (paper H5: PP across the slow
domain, DP within).

Mesh construction lives in ``repro.dist.mesh`` (shared with the elastic
trainer and the MPMD pipeline); this module only pins the production
shapes.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.dist import mesh as dist_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    if multi_pod:
        return dist_mesh.pod_data_model_mesh(2, 16, 16)
    return dist_mesh.data_model_mesh(16, 16)
