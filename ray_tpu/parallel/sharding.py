"""Logical-axis sharding rules (t5x/GSPMD style).

Models annotate every parameter/activation dimension with a *logical* name
("embed", "heads", "batch", ...); a rule table maps logical names to mesh axes.
Swapping parallelism strategy = swapping the rule table, never the model code.

This replaces the reference's strategy-per-integration design (SURVEY.md §2.7:
DDP in `train/torch/config.py`, FSDP only via Lightning/Accelerate shims) with
one declarative mechanism.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# A rule maps a logical axis name -> mesh axis (str), tuple of mesh axes, or
# None (replicated). First matching rule wins.
LogicalRules = tuple[tuple[str, object], ...]

# Default rules: fsdp shards params along their largest ("embed"-ish) dim
# (ZeRO-3), tp shards heads/mlp/vocab (Megatron layout), sp shards the
# activation sequence axis (context parallel), dp+fsdp share the batch.
DEFAULT_RULES: LogicalRules = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    # the residual stream's rows where a layer splits its tp products
    # (parallel/tp_products.py): each chip of a tp group keeps its own
    ("rows", ("sp", "tp")),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("layers", "pp"),
    ("expert", "ep"),
    ("norm", None),
)


def logical_to_mesh_spec(
    logical_axes: tuple[str | None, ...],
    rules: LogicalRules = DEFAULT_RULES,
    mesh: Mesh | None = None,
) -> PartitionSpec:
    """Resolve a tuple of logical axis names into a PartitionSpec.

    If ``mesh`` is given, mesh axes of size 1 are dropped (cosmetic) and a
    mesh axis may be used at most once across the spec — later duplicate uses
    fall back to replication, which matches GSPMD validity rules.
    """
    table = dict()
    for name, target in rules:
        table.setdefault(name, target)
    used: set[str] = set()
    out = []
    for ax in logical_axes:
        target = table.get(ax) if ax is not None else None
        if target is None:
            out.append(None)
            continue
        axes = (target,) if isinstance(target, str) else tuple(target)
        if mesh is not None:
            axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def logical_tree_to_shardings(
    logical_tree,
    mesh: Mesh,
    rules: LogicalRules = DEFAULT_RULES,
):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, logical_to_mesh_spec(axes, rules, mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )


def shard_constraint(x, logical_axes, rules: LogicalRules = DEFAULT_RULES):
    """with_sharding_constraint by logical axis names (no-op outside jit/mesh)."""
    spec = logical_to_mesh_spec(logical_axes, rules)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except RuntimeError as e:
        # Only "no ambient mesh" (eager / single-device use) is benign; real
        # misconfigurations (unknown axis names etc.) must surface.
        if "mesh" in str(e).lower():
            return x
        raise
