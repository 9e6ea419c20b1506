"""Logical-axis partitioning (MaxText-style, self-contained).

Every parameter and activation in the model is annotated with a tuple of
*logical* axis names (e.g. ``("embed", "heads", "head_dim")``). A rule table
maps logical names to mesh axes. :func:`logical_to_mesh_spec` applies the
rules with a **divisibility fallback**: if a tensor dimension is not
divisible by the mesh-axis size (e.g. 2 KV heads over a 16-way model axis,
arctic's 56 heads over 16), that dimension is replicated instead of sharded.
This keeps one rule table valid across all ten assigned architectures.
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axis (or tuple of axes), in priority order.
# ``batch``-like axes shard over the data-parallel axes; ``model``-ish axes
# over the tensor-parallel axis.
LOGICAL_RULES: dict[str, Tuple[str, ...]] = {
    # data-parallel axes
    "batch": ("pod", "data"),
    "expert_batch": ("pod", "data"),
    # sequence: replicated for training activations (we shard batch), but KV
    # caches for long-context decode shard their length over `data`.
    "seq": (),
    "kv_seq": ("data",),
    # Megatron-style sequence parallelism: the residual stream at block
    # boundaries shards its seq dim over `model` — the remat-saved per-layer
    # activation stacks shrink 16×; GSPMD inserts the all-gather before
    # attention and the reduce-scatter after the block.
    "seq_sp": ("model",),
    # tensor-parallel axes
    "vocab": ("model",),
    # FSDP: the d_model dim of *weights* shards over `data` (472B arctic in
    # f32 would otherwise be 117 GB/device). Activations are unaffected —
    # their (pod, data) axes are already consumed by the batch dim, so the
    # same rule falls back to replicated there. Cross-pod stays pure DP.
    "embed": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "heads_group": ("model",),  # q-heads-per-kv-head dim of GQA logits
    # fallback when kv_heads doesn't divide the model axis (qwen's kv=2,
    # arctic's 56 heads): shard the head feature dim instead — keeps KV
    # caches and KV projections distributed (contracting-dim sharding;
    # GSPMD inserts the partial-sum all-reduce).
    "head_dim": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "ssm_inner": ("model",),
    "ssm_heads": ("model",),
    "ssm_state": (),
    "conv_width": (),
    "layers": (),          # scan-stacked leading layer axis: never sharded
    "group": (),
}


# Mesh axes that carry data parallelism, in nesting order. Single source of
# truth for batch placement (batch_spec), the deferred-psum train step, and
# the elastic DP subsystem (repro.distributed).
DATA_AXES: Tuple[str, ...] = ("pod", "data")


def mesh_data_axes(mesh) -> Tuple[str, ...]:
    """The subset of DATA_AXES present on ``mesh`` (possibly empty)."""
    if mesh is None:
        return ()
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def _mesh_axis_sizes(mesh) -> Mapping[str, int]:
    # works for both Mesh and AbstractMesh: .shape is a name→size mapping.
    # Axes in Manual mode (inside shard_map) are excluded: constraints may
    # only reference Auto axes — the manual axes are the caller's business.
    return {
        name: size
        for (name, size), t in zip(mesh.shape.items(), mesh.axis_types)
        if t != AxisType.Manual
    }


def logical_to_mesh_spec(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    shape: Optional[Sequence[int]] = None,
    rules: Optional[Mapping[str, Tuple[str, ...]]] = None,
) -> P:
    """Map logical axis names to a PartitionSpec for ``mesh``.

    If ``shape`` is given, any dimension not divisible by the product of its
    assigned mesh axes falls back to partial assignment (greedy prefix of
    the rule's axis list) or replication. Mesh axes are never assigned twice.
    """
    rules = dict(LOGICAL_RULES if rules is None else rules)
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    spec: list[Any] = []
    for i, ax in enumerate(logical_axes):
        if ax is None:
            spec.append(None)
            continue
        cand = [a for a in rules.get(ax, ()) if a in sizes and a not in used]
        if not cand:
            spec.append(None)
            continue
        # greedy: take the longest prefix of candidate axes that divides dim
        assign: list[str] = []
        prod = 1
        dim = None if shape is None else int(shape[i])
        for a in cand:
            nprod = prod * sizes[a]
            if dim is not None and dim % nprod != 0:
                break
            assign.append(a)
            prod = nprod
        if not assign:
            spec.append(None)
            continue
        used.update(assign)
        spec.append(tuple(assign) if len(assign) > 1 else assign[0])
    return P(*spec)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_mesh_spec(logical_axes, mesh, shape))


def shard_tree(tree_axes, tree_vals, mesh: Mesh):
    """Build a NamedSharding tree from a matching tree of logical-axes tuples.

    ``tree_axes`` has the same structure as ``tree_vals`` with each leaf a
    tuple of logical axis names (length = rank of the value leaf).
    """
    return jax.tree.map(
        lambda axes, val: named_sharding(mesh, axes, np.shape(val)),
        tree_axes,
        tree_vals,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]], mesh=None):
    """``with_sharding_constraint`` by logical names; no-op outside a mesh.

    Unresolved dims are pinned replicated. (Hillclimb note: mapping them to
    P.UNCONSTRAINED instead was measured WORSE on deepseek-67b train_4k —
    collective 86.5 s → 102.4 s, memory 44.8 → 60.5 GB — GSPMD's propagation
    without the replication anchors produces more resharding, not less;
    hypothesis refuted, see EXPERIMENTS.md §Perf.)

    The mesh is the one given, else the abstract mesh in context
    (``jax.set_mesh``, or the manual region of a ``shard_map``)."""
    mesh = mesh or jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        return x
    spec = logical_to_mesh_spec(logical_axes, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(mesh: Mesh, extra_dims: int = 1, batch_size: Optional[int] = None) -> P:
    """PartitionSpec for a (batch, ...) input: batch over all data axes.

    With ``batch_size`` given, applies the divisibility fallback (greedy
    prefix of the data axes; batch=1 long-context decode → replicated)."""
    axes = list(mesh_data_axes(mesh))
    if batch_size is not None:
        sizes = _mesh_axis_sizes(mesh)
        keep, prod = [], 1
        for a in axes:
            if batch_size % (prod * sizes[a]) != 0:
                break
            keep.append(a)
            prod *= sizes[a]
        axes = keep
    return P(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None), *([None] * extra_dims))
