"""Logical-axis sharding rules — how ZeRO stages & TP become PartitionSpecs.

The reference implements ZeRO with an eager partitioning runtime: flat fp16
buffers split across DP ranks (stage 1/2, runtime/zero/stage_1_and_2.py:93) and
per-parameter shards with a fetch/prefetch coordinator (stage 3,
runtime/zero/stage3.py:66 + partitioned_param_coordinator.py:44). On TPU the
same *placement decisions* are expressed declaratively: every model parameter
carries a tuple of logical axis names; a rule table maps logical names to mesh
axes; XLA's SPMD partitioner then derives the all-gathers and reduce-scatters
the reference hand-schedules.

Stage → rule mapping (SURVEY.md §7):
  stage 0: params/grads/opt replicated (grads psum'd by pjit)
  stage 1: params replicated; optimizer state sharded over (data, fsdp)
  stage 2: + gradients reduce-scattered onto the same shards
  stage 3: params themselves sharded over fsdp (+data if fsdp axis is 1)
Tensor parallelism composes by mapping width logical axes ('heads', 'mlp',
'vocab') onto 'model' first; the ZeRO axis then takes a remaining dimension.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, tuple[str, ...]]
Rules = Sequence[tuple[str, MeshAxes]]

# Default logical-axis → mesh-axis table for transformer models.
# 'model' = tensor parallel; 'fsdp' = ZeRO-3 axis; None = replicated.
DEFAULT_TP_RULES: Rules = (
    ("stage", "pipe"),
    ("vocab", "model"),
    ("heads", "model"),
    ("kv", None),
    ("mlp", "model"),
    ("ffn_in", "model"),
    ("embed", None),
    ("layers", None),
    # EP rides the DP devices (reference: utils/groups.py:109 "expert parallel
    # group is a subset of data parallel group").
    ("expert", ("data", "fsdp")),
    ("context", "context"),
    ("batch", ("data", "fsdp")),
)


def _axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(mesh.shape)


def _mesh_axes_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape.get(a, 1) for a in axes]))


def spec_from_logical(
    logical_axes: Optional[tuple],
    shape: tuple[int, ...],
    rules: Rules,
    mesh: Mesh,
    zero_fallback: MeshAxes = None,
) -> PartitionSpec:
    """Map one parameter's logical axes to a PartitionSpec, skipping any mesh
    axis that does not divide the dimension (reference analogue: padding of
    the flat partition buffers, stage_1_and_2.py:562 — we instead replicate
    non-divisible dims, which XLA handles without padding).

    ``zero_fallback``: ZeRO axes that MUST land somewhere if possible. The
    reference's flat-buffer partitioning shards *every* tensor's optimizer
    state across DP ranks regardless of its shape (stage_1_and_2.py:93); the
    rule table alone can miss leaves whose logical axes carry no ZeRO rule
    (attention biases, per-head scales). When none of the fallback axes were
    placed by the rules, the largest still-unsharded divisible dim takes them.
    """
    if logical_axes is None:
        return PartitionSpec()
    assert len(logical_axes) == len(shape), f"{logical_axes} vs {shape}"
    table = dict(rules)
    used: set[str] = set()
    out = []
    for name, dim in zip(logical_axes, shape):
        axes = table.get(name)
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and size > 1 and dim % size == 0:
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    if zero_fallback is not None:
        fb = (zero_fallback,) if isinstance(zero_fallback, str) else tuple(zero_fallback)
        fb = tuple(a for a in fb if a in mesh.shape and a not in used)
        size = int(np.prod([mesh.shape[a] for a in fb])) if fb else 1
        if fb and size > 1:
            candidates = [
                (shape[i], i) for i in range(len(shape))
                if out[i] is None and shape[i] % size == 0 and shape[i] >= size
            ]
            if candidates:
                _, i = max(candidates)
                out[i] = fb if len(fb) > 1 else fb[0]
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def zero_stage_rules(stage: int, base: Rules = DEFAULT_TP_RULES) -> tuple[Rules, Rules]:
    """Return (param_rules, optstate_rules) for a ZeRO stage.

    Parameters follow ``param_rules``; optimizer state (fp32 master weights,
    Adam moments) follows ``optstate_rules``. For stages 1/2 the optimizer
    state additionally shards its 'embed'/widest free axis over (fsdp, data)
    while params stay replicated — exactly the reference's split of "model
    state" vs "optimizer state" placement (stage_1_and_2.py:93 docstring).
    """
    base = tuple(base)
    if stage == 0:
        return base, base
    # opt-state rules: put the ZeRO axis on 'embed' (every matrix/vector in a
    # transformer has an embed-like dim; it is rarely TP-sharded).
    zero_axes = ("fsdp", "data")
    opt = tuple((k, zero_axes) if k == "embed" else (k, v) for k, v in base)
    if stage < 3:
        return base, opt
    # stage 3: params themselves are sharded (FSDP).
    return opt, opt


def make_param_specs(logical_axes_tree, shapes_tree, rules: Rules, mesh: Mesh):
    """Tree-map ``spec_from_logical`` over a model's parameter pytree."""
    return jax.tree.map(
        lambda ax, shp: spec_from_logical(ax, tuple(shp), rules, mesh),
        logical_axes_tree,
        shapes_tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)),
    )


def tree_shardings(mesh: Mesh, specs_tree):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def batch_and_head_axes(mesh: Mesh, n_batch: int, num_heads: int):
    """Mesh axes for a batch-like dim and a heads dim, as PartitionSpec
    entries: the batch rides the ZeRO/data axes (``data``, ``fsdp``), heads
    ride the TP axis (``model``). Any axis that does not divide its dim is
    dropped (the dim stays whole), mirroring ``spec_from_logical``'s
    non-divisible rule."""
    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    size = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    if size <= 1 or n_batch % size:
        batch_axes = ()
    model_size = mesh.shape.get("model", 1)
    head_ax = "model" if (model_size > 1 and num_heads % model_size == 0) else None
    batch = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    return batch, head_ax


def kv_slot_cache_spec(mesh: Mesh, n_slots: int, num_heads: int) -> PartitionSpec:
    """PartitionSpec for the serving engine's persistent slot KV cache
    [L, n_slots, Smax, H, Dh]: slots ride the ZeRO/data axes (each device
    group owns a contiguous run of slots), heads ride the TP axis — XLA then
    keeps decode-attention reads local to the shard that owns the slot. Any
    mesh axis that does not divide its dim is dropped (replicated), mirroring
    ``spec_from_logical``'s non-divisible rule."""
    slot, head_ax = batch_and_head_axes(mesh, n_slots, num_heads)
    return PartitionSpec(None, slot, None, head_ax, None)


def kv_prefix_pool_spec(mesh: Mesh, n_prefix_slots: int, num_heads: int) -> PartitionSpec:
    """PartitionSpec for the serving engine's prefix-cache KV pool
    [L, n_prefix_slots, Pmax, H, Dh] — deliberately the SAME layout rule as
    ``kv_slot_cache_spec`` (pool slots over the ZeRO/data axes, heads over
    the TP axis): the prefix fetch/store programs are dynamic-slice copies
    between the pool and the slot cache, and matching layouts keep those
    copies shard-local on the head axis instead of resharding every reuse."""
    return kv_slot_cache_spec(mesh, n_prefix_slots, num_heads)


def constrain(tree, mesh: Mesh, specs_tree):
    """with_sharding_constraint over a pytree (inside jit)."""
    flat_x, treedef = jax.tree.flatten(tree)
    flat_s = treedef.flatten_up_to(specs_tree)
    out = [
        jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)) if isinstance(s, PartitionSpec) else x
        for x, s in zip(flat_x, flat_s)
    ]
    return jax.tree.unflatten(treedef, out)
