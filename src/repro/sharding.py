"""Logical-axis sharding rules (MaxText-style) with auto-degradation.

Every parameter / activation dimension is named with a *logical* axis
("batch", "heads", "mlp", ...).  A rule table maps logical axes to mesh
axes.  ``resolve`` turns a tuple of logical names into a
``PartitionSpec`` for a concrete mesh, dropping any rule whose mesh axes
do not divide the dimension (auto-degradation to replication).  This is
what lets one model definition lower onto the 1-device CPU mesh, the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh without per-mesh
hand edits: e.g. gemma3's 8 query heads cannot shard over a 16-way
"model" axis, so "heads" degrades to replicated while "mlp" stays TP.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical->mesh rules.  Values are a mesh-axis name, a tuple of
# mesh-axis names, or None (replicate).
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    # activations
    "batch": ("pod", "data"),          # data parallel over pod x data
    "seq": None,                       # sequence replicated by default
    "seq_shard": ("data",),            # opt-in sequence parallelism (long ctx)
    "embed": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    # parameters
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    # KV-cache head_dim: falls back to "model" when kv_heads don't divide
    # the model axis (GQA kv < 16) -- contracting-dim TP for decode, keeps
    # 32k x batch caches on-chip (resolve()'s used-set makes this a no-op
    # when kv_heads already took the axis)
    "kv_dim": "model",
    "cache_seq": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ff": None,
    "inner": "model",                  # mamba d_inner / rwkv fused head dim
    "state": None,
    "conv": None,
    "lora": None,
    "stack": None,                     # scan-stacked leading layer dim
}


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def resolve(
    logical: Sequence[str | None],
    mesh: Mesh,
    dims: Sequence[int] | None = None,
    overrides: Mapping[str, tuple[str, ...] | str | None] | None = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec for ``mesh``.

    ``dims`` (optional) enables divisibility-based auto-degradation:
    a rule is dropped when the dimension is not divisible by the mesh
    axes' product.  Mesh axes absent from ``mesh`` are dropped, and a
    mesh axis is never used twice in one spec.
    """
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    out: list[tuple[str, ...] | None] = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            out.append(None)
            continue
        rule = rules.get(name)
        if rule is None:
            out.append(None)
            continue
        axes = (rule,) if isinstance(rule, str) else tuple(rule)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        if not axes:
            out.append(None)
            continue
        if dims is not None:
            dim = dims[i]
            if dim % _axis_size(mesh, axes) != 0:
                # try progressively shorter prefixes before replicating
                while axes and dim % _axis_size(mesh, axes) != 0:
                    axes = axes[:-1]
                if not axes:
                    out.append(None)
                    continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(
    mesh: Mesh,
    logical: Sequence[str | None],
    dims: Sequence[int] | None = None,
    overrides=None,
) -> NamedSharding:
    return NamedSharding(mesh, resolve(logical, mesh, dims, overrides))


def tree_specs(schema_tree, mesh: Mesh, overrides=None):
    """Map a pytree of ``ParamDef`` (see models.schema) to PartitionSpecs."""
    from repro.models.schema import ParamDef  # local import to avoid cycle

    def leaf(pd):
        if isinstance(pd, ParamDef):
            return resolve(pd.logical, mesh, pd.shape, overrides)
        return P()

    return jax.tree.map(leaf, schema_tree,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def param_shardings(cfg, mesh: Mesh, rules=None):
    """``NamedSharding`` pytree placing a model's parameters on ``mesh``."""
    from repro.models.schema import model_schema  # local: avoids a cycle

    specs = tree_specs(model_schema(cfg), mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def constrain(x, mesh: Mesh, logical: Sequence[str | None], overrides=None):
    """with_sharding_constraint via logical names (no-op off-mesh)."""
    if mesh is None or mesh.empty:
        return x
    spec = resolve(logical, mesh, x.shape, overrides)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
