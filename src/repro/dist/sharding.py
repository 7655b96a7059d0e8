"""Logical-axis sharding engine (paper §4.4 'framework' layer).

Every tensor in the system is declared once as a :class:`Decl` — a shape
plus *logical* axis names ("embed", "heads", "ff", ...).  A sharding
*policy* maps logical axes to candidate mesh axes; :func:`logical_to_spec`
resolves a declaration against a concrete mesh into a ``PartitionSpec``
under two rules (see DESIGN.md §4):

  1. **Divisibility fallback** — a dim whose size does not divide the mesh
     axis is replicated instead (smollm's 15 heads on a 16-way model axis,
     granite's MQA kv=1).  No padding, no partial shards, no surprises in
     the memory model.
  2. **Each mesh axis is used at most once** per tensor, first dim wins
     (left to right) — a tensor cannot be sharded twice over 'model'.

Policies (``policy_rules``):
  replicated  everything replicated (reduced CPU configs)
  tp          megatron-style tensor parallelism over 'model'
  fsdp_tp     'tp' + parameter fsdp: 'embed' sharded over 'data'

Candidate lists are tried in order, which encodes preferences like MoE
expert-parallel-else-tensor-parallel ('experts' before 'e_ff', both over
'model'; see models/moe.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Axis = Optional[str]
Rules = Mapping[str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Decl:
    """Shape + logical axes + init recipe for one tensor.

    ``init``: scaled | normal | zeros | ones | embed | a_log | dt_bias
    ("scaled"/"normal": gaussian with std ``shape[scale_dim]**-0.5`` when
    ``scale_dim`` is set, else 0.02).
    """
    shape: Tuple[int, ...]
    axes: Tuple[Axis, ...]
    init: str = "scaled"
    scale_dim: Optional[int] = None

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(self.shape))
        object.__setattr__(self, "axes", tuple(self.axes))
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


_TP_RULES: Dict[str, Tuple[str, ...]] = {
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    # MoE: expert parallelism when n_experts divides 'model' (dbrx 16e/16),
    # else tensor parallelism inside each expert (mixtral 8e/16).
    "experts": ("model",),
    "e_ff": ("model",),
    "ssm_inner": ("model",),
}

POLICIES: Dict[str, Rules] = {
    "replicated": {},
    "tp": _TP_RULES,
    "fsdp_tp": {**_TP_RULES, "embed": ("data",)},
}


def policy_rules(name: str) -> Rules:
    try:
        return POLICIES[name]
    except KeyError:
        raise KeyError(f"unknown sharding policy {name!r}; "
                       f"known: {sorted(POLICIES)}") from None


def _mesh_sizes(mesh) -> Dict[str, int]:
    # works for Mesh, AbstractMesh, and the dict-shaped fakes in tests
    return dict(mesh.shape)


def logical_to_spec(shape: Sequence[int], axes: Sequence[Axis],
                    rules: Rules, mesh) -> P:
    """Resolve logical axes to a PartitionSpec on ``mesh``.

    Non-divisible dims replicate; each mesh axis is assigned at most once
    (first dim, left to right).
    """
    sizes = _mesh_sizes(mesh)
    used: set = set()
    parts = []
    for dim, ax in zip(shape, axes):
        pick = None
        for cand in (rules.get(ax, ()) if ax is not None else ()):
            if cand in sizes and cand not in used and dim % sizes[cand] == 0:
                pick = cand
                break
        if pick is not None:
            used.add(pick)
        parts.append(pick)
    return P(*parts)


def param_specs(decls: Any, policy: str, mesh) -> Any:
    """Tree of Decl -> tree of PartitionSpec under ``policy``."""
    rules = policy_rules(policy)
    return jax.tree_util.tree_map(
        lambda d: logical_to_spec(d.shape, d.axes, rules, mesh),
        decls, is_leaf=lambda x: isinstance(x, Decl))


# --- data-parallel batch dim -----------------------------------------------------

DP_AXIS_NAMES = ("pod", "data")


def dp_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the batch dim may shard over, in mesh order ('pod' first)."""
    return tuple(n for n in _mesh_sizes(mesh) if n in DP_AXIS_NAMES)


def batch_spec(mesh, batch: int, *rest: Axis) -> P:
    """Spec for a ``(batch, ...)`` tensor: batch over the flattened dp axes.

    Divisibility fallback drops the outermost (slowest, 'pod') axis first:
    e.g. on a (pod=2, data=16, model=16) mesh batch=256 -> ('pod','data'),
    batch=16 -> 'data', batch=1 -> replicated.  ``rest`` entries are passed
    through for the trailing dims (validated later by :func:`constrain`).
    """
    axes = dp_axes(mesh)
    sizes = _mesh_sizes(mesh)
    for i in range(len(axes)):
        group = axes[i:]
        if batch % math.prod(sizes[a] for a in group) == 0:
            return P(group if len(group) > 1 else group[0], *rest)
    return P(None, *rest)


# --- in-graph sharding hints -----------------------------------------------------

def _sanitize(shape: Sequence[int], spec: P, sizes: Dict[str, int]) -> P:
    used: set = set()
    parts = []
    for dim, part in zip(shape, tuple(spec)):
        names = (part,) if isinstance(part, str) else tuple(part or ())
        ok = (names
              and all(n in sizes and n not in used for n in names)
              and dim % math.prod(sizes[n] for n in names) == 0)
        if ok:
            used.update(names)
            parts.append(part)
        else:
            parts.append(None)
    return P(*parts)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` against the ``jax.set_mesh`` mesh, or
    no-op.

    The spec is sanitized with the same divisibility/axis-once rules as
    ``logical_to_spec`` so callers can pass optimistic hints (e.g. heads
    over 'model') that degrade to replication on meshes where they don't
    divide.  Outside a mesh context this is the identity, which keeps
    single-device paths free of partitioner machinery.  Under ``vmap`` the
    constraint sees the unbatched aval and JAX prepends the batch dim.
    """
    mesh = jax.sharding.get_abstract_mesh()     # what jit sees of set_mesh
    if mesh.empty:
        return x
    spec = _sanitize(x.shape, spec, _mesh_sizes(mesh))
    if all(s is None for s in tuple(spec)):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# --- initialization --------------------------------------------------------------

def _init_one(d: Decl, key: jax.Array, dtype) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "a_log":
        # mamba2: A ~ U[1, 16), stored as log A
        a = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(dtype)
    if d.init == "dt_bias":
        # mamba2: dt ~ logU[1e-3, 1e-1), stored as softplus^-1(dt)
        u = jax.random.uniform(key, d.shape, jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if d.init == "embed":
        std = 0.02
    elif d.init in ("scaled", "normal"):
        std = (d.shape[d.scale_dim] ** -0.5 if d.scale_dim is not None
               else 0.02)
    else:
        raise ValueError(f"unknown init {d.init!r} for {d}")
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)


def init_from_decls(decls: Any, key: jax.Array,
                    dtype: Union[str, jnp.dtype]) -> Any:
    """Initialize a pytree of Decl into arrays of ``dtype``.

    Each leaf gets an independent fold of ``key``, so the result is
    invariant to tree iteration order changes only up to leaf count —
    declarations are stable per config, which is all checkpointing needs.
    """
    dtype = jnp.dtype(dtype)
    leaves, treedef = jax.tree_util.tree_flatten(
        decls, is_leaf=lambda x: isinstance(x, Decl))
    keys = jax.random.split(key, max(len(leaves), 1))
    return jax.tree_util.tree_unflatten(
        treedef, [_init_one(d, k, dtype) for d, k in zip(leaves, keys)])
