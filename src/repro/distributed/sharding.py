"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Every parameter/activation dimension carries a logical axis name (see
``models/module.py``). A rule table maps logical names to mesh axes; the
PartitionSpec for a tensor is derived per-dim, with a divisibility guard
that falls back to replication when a dim does not divide the mesh extent
(we design shapes so this never triggers for the production meshes — see
DESIGN.md §6 — but the guard keeps arbitrary smoke configs safe).

Also home of the ``shard_map`` (replication checks off) used by every
manual-SPMD path (the fog scan engine's device-sharded runner and the
production FedAvg round): the per-fog-device parameter stacks diverge
between aggregations, which replicated-pjit params cannot express.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = partial(jax.shard_map, check_vma=False)

# Default logical->mesh rules for the production meshes. "batch" maps to
# ("pod","data") — on the single-pod mesh "pod" is simply absent and drops
# out. Fused projection output dims ("heads_fused", "mlp", "experts",
# "ssm_inner", "vocab") carry the tensor-parallel sharding; q-head counts
# are padded to multiples of the model-axis extent at config time.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "vocab": ("model",),
    "embed": (),
    "heads": ("model",),        # padded q heads
    "kv_heads": (),             # kv replicated at train/prefill (small)
    "head_dim": (),
    "mlp": ("model",),
    "experts": ("model",),
    "expert_mlp": ("model",),   # mixtral-style: shard within-expert ffn
    "ssm_heads": ("model",),
    "ssm_inner": ("model",),
    "ssm_state": (),
    "conv_dim": ("model",),
    "cache_seq": ("model",),    # decode KV cache: sequence-sharded
    "seq": (),
    "layers": (),
    "groups": (),
    "frames": (),
    "stack": (),                # paper-scale per-fog-device axis (vmapped)
}


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for_axes(axes, shape, mesh: Mesh, rules=None) -> P:
    """Derive a PartitionSpec from logical axis names + shape."""
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    out = []
    for dim, name in zip(shape, axes):
        if name is None:
            out.append(None)
            continue
        mesh_axes = tuple(a for a in rules.get(name, ()) if a in sizes)
        if not mesh_axes:
            out.append(None)
            continue
        extent = int(np.prod([sizes[a] for a in mesh_axes]))
        if dim % extent != 0:
            # replication fallback (small smoke meshes / odd dims)
            out.append(None)
        else:
            out.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    # PartitionSpec forbids trailing Nones? (it allows them; keep as-is)
    return P(*out)


def tree_pspecs(axes_tree, shape_tree, mesh: Mesh, rules=None):
    """Map trees of logical axes + shapes to a tree of PartitionSpec."""
    return jax.tree_util.tree_map(
        lambda axes, shp: spec_for_axes(axes, shp.shape if hasattr(shp, "shape") else shp, mesh, rules),
        axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree, shape_tree, mesh: Mesh, rules=None):
    specs = tree_pspecs(axes_tree, shape_tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh, rules=None) -> P:
    """PartitionSpec for a (batch, ...) tensor's leading dim."""
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    axes = tuple(a for a in rules["batch"] if a in sizes)
    if not axes:
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def data_axis_size(mesh: Mesh, rules=None) -> int:
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    return int(np.prod([sizes[a] for a in rules["batch"] if a in sizes]) or 1)
